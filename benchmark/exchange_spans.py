"""The single-device exchange's spans in the window's trees: what the five
``exchange_*`` metrics of ``metrics/`` read.

``exchange.map`` is one run of an exchange's map side (args
``partitioning``, ``rows``, ``bytes``, ...), ``exchange.split`` one map-side
batch under it (args ``path``, ``widenings``, ...). An exchange whose
``partitioning`` is ``single`` moves nothing (the planner's gather of every
partition into one above an aggregate or a sort): it is left out of what
the exchange costs and moves. A program from before the spans has none, and
every reader here then returns nothing."""
from benchmark import spans

MAP, SPLIT = "exchange.map", "exchange.split"
#: the paths on which a split program reordered rows; ``single`` passes the
#: batch through
SPLIT_PATHS = ("kernel", "sort", "encoded")


def window(ctx):
    """(queries in the window, the window's spans) or None."""
    trees = spans.trees(ctx)
    if not trees:
        return None
    return len(trees), [r for tree in trees for r in tree]


def maps(records):
    """The ``exchange.map`` spans that repartitioned rows."""
    return [r for r in records if r.name == MAP and r.args
            and r.args.get("partitioning") != "single"]


def splits(records, under=None):
    """The ``exchange.split`` spans on a reordering path; ``under``: only
    the children of these ``exchange.map`` spans."""
    ids = None if under is None else {r.span_id for r in under}
    return [r for r in records if r.name == SPLIT and r.args
            and r.args.get("path") in SPLIT_PATHS
            and (ids is None or r.parent_id in ids)]
