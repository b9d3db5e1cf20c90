"""The leaf spans of PR 36 in the window's trees: the parquet scan's host
pipeline (``scan.*``, ``stage.*``), the action's own blocks (``query.*``,
``action.*``), ``batch.concat`` and ``exchange.fetch``; and the share of the
device's idle gaps that a span of the program names, not a container.

A program from before these spans has no ``query.prepare`` under any root:
every reader here then returns None and the metric is left out of the line.
A program that has them opens that span in every query, so where the window
holds none of the spans a metric names, its reading is 0."""
from benchmark import spans
from benchmark.reduce import ACTION_RANGE, BETWEEN

#: opened once by every query of a program that has these spans
MARK = "query.prepare"
#: the action's own blocks: children of ``query`` and of ``action`` (never
#: the ``query`` root or the ``action`` span themselves)
ACTION_BLOCKS = ("query.", "action.")
_KEY = "_host_span_records"


def records(ctx):
    """(queries in the window, the window's spans), or None where there is
    no window or the program is from before the spans."""
    if _KEY not in ctx:
        window = spans.trees(ctx)
        flat = [r for t in window or () for r in t]
        ctx[_KEY] = ((len(window), flat)
                     if any(r.name == MARK for r in flat) else None)
    return ctx[_KEY]


def per_query(ctx, names, scale):
    """Sum of the named spans' durations (nanoseconds) over the window,
    over its queries, times ``scale``."""
    if records(ctx) is None:
        return None
    return spans.per_query(ctx, names, scale)


def decoded_mb_per_query(ctx):
    """Bytes the scan's host decode produced: the decoded size of every
    column chunk the page reader kept (``scan.chunk_decode`` whose ``form``
    is not ``declined``) and what pyarrow's own read returned."""
    got = records(ctx)
    if got is None:
        return None
    queries, recs = got
    total = sum(r.args["decoded_bytes"] for r in recs
                if r.name == "scan.chunk_decode" and r.args
                and r.args.get("form") != "declined")
    total += sum(r.args["bytes"] for r in recs
                 if r.name == "scan.arrow_read" and r.args)
    return total / 1e6 / queries


def idle_named_share(ctx):
    """Of the traced slice's listed idle gaps, without the time between
    queries: the seconds in gaps named by a span of the program (a name
    that starts with a lower-case letter and is not the action's range), as
    a share of the seconds in all of them. An exec's pull range starts with
    a capital: a container."""
    trace = ctx.get("trace")
    gaps = [(name, s) for name, s in (trace or {}).get("idle_gaps", ())
            if name != BETWEEN]
    total = sum(s for _, s in gaps)
    if not total:
        return None
    named = sum(s for name, s in gaps
                if name[:1].islower() and name != ACTION_RANGE)
    return 100.0 * named / total
