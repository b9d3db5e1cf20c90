"""The join's ``join.drain`` spans in the window's trees: what the two
``join_*`` metrics of ``metrics/`` read.

One span a side a run of a single-device hash join (``side`` ``left`` or
``right``), or one for both sides where the out-of-core controller stages
them (``side`` ``both``, ``mode`` what it returned): the child's batches
pulled to their end, and with them everything under the child (scans,
filter stages, uploads, an inner join). Args ``batches`` (the batches
drained, empty ones included) and ``rows``. A program from before the span
has none, and every reader here then returns nothing."""
from benchmark import spans

DRAIN = "join.drain"


def window(ctx):
    """(queries in the window, the window's drains) or None."""
    trees = spans.trees(ctx)
    drains = [r for tree in trees or () for r in tree if r.name == DRAIN]
    if not drains:
        return None
    return len(trees), drains


def outermost(ctx, drains):
    """The drains with no drain among their ancestors, by ``parent_id``:
    an inner join's drains lie inside the outer join's, and are not
    counted twice."""
    by_id = {r.span_id: r for tree in spans.trees(ctx) for r in tree}
    ids = {r.span_id for r in drains}
    out = []
    for r in drains:
        parent = by_id.get(r.parent_id)
        while parent is not None and parent.span_id not in ids:
            parent = by_id.get(parent.parent_id)
        if parent is None:
            out.append(r)
    return out
