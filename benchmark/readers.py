"""The general readers of per-layer metrics. ``metrics/<name>.json`` names
one of these with its arguments; a metric that needs another kind of reading
brings ``metrics/<name>.py`` with a ``read(ctx)`` of its own. A reader that
finds nothing to read returns None and the metric is left out of the line.

``ctx`` is what one run gathered: ``queries`` completed in the window,
``before``/``after`` (program counters at the window's two ends),
``requests`` (one record per served request), ``trace`` (reduce.py's numbers
for the traced slice, with ``queries`` and ``least_bytes`` in it), ``memory``
and the device's ``peaks``."""
import importlib.util
import os

from benchmark.loadgen import percentile

HERE = os.path.dirname(os.path.abspath(__file__))


def counter_delta(ctx, counters, scale=1.0):
    return scale * sum(ctx["after"][c] - ctx["before"][c] for c in counters)


def counter_delta_per_query(ctx, counters, scale=1.0):
    if not ctx["queries"]:
        return None
    return counter_delta(ctx, counters, scale) / ctx["queries"]


def counter_at_window_start(ctx, counter, scale=1.0):
    return scale * ctx["before"][counter]


def counter_ratio(ctx, numerator, denominator, scale=1.0):
    """One counter's rise over the window as a share of another's; None
    where the other did not rise."""
    below = counter_delta(ctx, [denominator])
    if not below:
        return None
    return scale * counter_delta(ctx, [numerator]) / below


def request_percentile(ctx, field, q, scale=1.0):
    values = [r[field] for r in ctx.get("requests", [])
              if r.get(field) is not None]
    return scale * percentile(values, q) if values else None


def trace_busy_per_query(ctx):
    t = ctx.get("trace")
    return t["busy_s"] / t["queries"] if t and t["queries"] else None


def trace_idle_share(ctx):
    t = ctx.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None


def trace_hbm_roofline(ctx):
    """Least time the chip could take for the slice's queries (their least
    bytes over the HBM peak) as a share of the time the device was busy.
    Over 100 % the count is wrong: the run fails rather than print it."""
    t = ctx.get("trace")
    if not t or not t["queries"] or not t["busy_s"]:
        return None
    share = 100.0 * t["least_bytes"] / ctx["peaks"]["hbm_bytes_per_s"] / t["busy_s"]
    if share > 100.0:
        raise RuntimeError(f"hbm roofline share {share:.1f} % > 100 %: the "
                           "least-bytes count or the busy time is wrong")
    return share


def memory_peak_share(ctx):
    m = ctx.get("memory")
    if not m or not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]


def read(name, spec, ctx):
    """The metric's value in this run, or None."""
    own = os.path.join(HERE, "metrics", f"{name}.py")
    if os.path.isfile(own):
        module_spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + name.replace(".", "_"), own)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module.read(ctx)
    return globals()[spec["reader"]](ctx, **spec.get("args", {}))
