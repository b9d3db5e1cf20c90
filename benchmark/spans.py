"""The window's span trees, from the ring the program keeps while its
``trace.enabled`` is set: what the ``program_span`` metrics of
``metrics/<name>.py`` read.

A run's queries each leave one ``query`` root in the ring, with the spans it
caused under it (``parent_id``). The window's are the last ``ctx["queries"]``
roots: warm-up's come before them and nothing comes after, since the
reference that judges ``correct`` runs none of the program. A closed loop is
read whole, not only its profiled slice.

Nothing to read gives None, never an error: a program from before these
spans has no ``query`` root (or no ``dropped`` counter), and the metric is
then left out of the line. None also where the ring overwrote part of the
window: its ``dropped`` counter, the sequence number of the oldest record it
still holds, then reaches past the root before the window's first."""
from benchmark.loadgen import percentile

ROOT = "query"
_KEY = "_span_trees"


def _ring():
    """(records oldest first, records lost) or None."""
    try:
        from spark_rapids_tpu.utils.tracing import TRACER
    except ImportError:
        return None
    dropped = getattr(TRACER, "dropped", None)
    if dropped is None:
        return None
    return TRACER.since(0), dropped


def trees_of(records, dropped, queries):
    """One list of records (root first) for each of the last ``queries``
    completed roots, or None."""
    roots = [r for r in records
             if r.name == ROOT and r.parent_id is None]
    if not queries or len(roots) < queries:
        return None
    before = roots[-queries - 1].seq if len(roots) > queries else -1
    if dropped > before + 1:
        return None
    children = {}
    for r in records:
        if r.seq > before and r.parent_id is not None:
            children.setdefault(r.parent_id, []).append(r)
    trees = []
    for root in roots[-queries:]:
        tree, i = [root], 0
        while i < len(tree):
            tree.extend(children.get(tree[i].span_id, ()))
            i += 1
        trees.append(tree)
    return trees


def trees(ctx):
    """The window's trees, read once per run."""
    if _KEY not in ctx:
        ring = _ring()
        ctx[_KEY] = ring and trees_of(*ring, ctx["queries"])
    return ctx[_KEY]


def _named(tree, names):
    """The tree's spans called one of ``names``; a name that ends in ``.``
    stands for every span whose name starts with it."""
    return [r for r in tree
            if any(r.name == n or (n.endswith(".") and r.name.startswith(n))
                   for n in names)]


def per_query(ctx, names, scale, field="dur_ns"):
    """Sum of the named spans' ``field`` (nanoseconds) over the window,
    over its queries, times ``scale``."""
    window = trees(ctx)
    if not window:
        return None
    total = sum(getattr(r, field) for t in window for r in _named(t, names))
    return scale * total / len(window)


def request_median(ctx, names, scale):
    """Each request's sum of the named spans' durations; the median."""
    window = trees(ctx)
    if not window:
        return None
    return scale * percentile(
        [sum(r.dur_ns for r in _named(t, names)) for t in window], 50)
