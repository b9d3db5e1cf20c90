"""From a profiler trace (``.xplane.pb``) to numbers: device busy time as the
union of the intervals in which an operation ran, the idle share, the device
operations that took most time, and the longest idle gaps named by what the
host was doing in them. Read with ``jax.profiler.ProfileData`` alone."""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device line that holds one event per operation; the other device
#: lines ("XLA Modules", "Steps", ...) enclose these and would hide the gaps
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: the program's per-exec ranges: ``<Exec>#<plan_id>``, e.g.
#: ``PipelinedExec(depth=2)#3``
EXEC_RANGE = re.compile(r"^[A-Za-z_][\w()=,. \-]*#\d+$")
ACTION_RANGE = "tpu-sql-action"
BETWEEN = "between queries"
TOP = 10


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        there = [os.path.join(d, f) for d, _, fs in os.walk(log_dir)
                 for f in fs]
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}: {there}")
    return found[-1]


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def short_name(module, op):
    """``jit_fn/%fusion.12``: the trace names an op by its whole HLO text
    and a module by ``name(fingerprint)``; jitted programs have no names of
    their own yet (PERF.md, Open questions)."""
    name = op.split(" = ")[0].strip()
    if module:
        name = module.split("(")[0] + "/" + name
    return name[:80]


def _device_ops(plane):
    """[(name, start, end)] of the plane's operations, each named with the
    module that was running when it started."""
    lines = list(plane.lines)
    chosen = [ln for ln in lines if ln.name == OP_LINE] or lines
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for ln in lines if ln.name == MODULE_LINE
                     for e in ln.events)
    starts = [m[0] for m in modules]
    ops = []
    for ln in chosen:
        for e in ln.events:
            if e.duration_ns <= 0:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = (modules[i][2] if i >= 0 and e.start_ns < modules[i][1]
                      else "")
            ops.append((short_name(module, e.name), e.start_ns,
                        e.start_ns + e.duration_ns))
    return ops


def _host_ranges(planes):
    """[(name, start, end)] of the program's own ranges on the host threads."""
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == ACTION_RANGE or EXEC_RANGE.match(e.name):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def name_gap(start, end, ranges):
    """The innermost program range that covers the gap's middle: an exec's
    ``<Exec>#<plan_id>``, else the action range, else between queries."""
    mid = (start + end) / 2
    covering = [(e - s, name) for name, s, e in ranges if s <= mid <= e]
    execs = [c for c in covering if c[1] != ACTION_RANGE]
    if execs:
        return min(execs)[1]
    return ACTION_RANGE if covering else BETWEEN


def reduce_planes(planes, window_s=None):
    """The trace's numbers. ``window_s``: the traced slice by the host's
    clock; without it, the span from the first to the last device event."""
    device = [p for p in planes if DEVICE_PLANE.match(p.name)]
    per_chip, by_op, all_busy = [], {}, []
    for plane in device:
        ops = _device_ops(plane)
        busy = union((s, e) for _, s, e in ops)
        per_chip.append(sum(e - s for s, e in busy) / 1e9)
        for name, s, e in ops:
            by_op[name] = by_op.get(name, 0.0) + (e - s) / 1e9
        all_busy.append(busy)
    if not per_chip or not any(per_chip):
        return None
    busy_s = sum(per_chip) / len(per_chip)
    first = min(b[0][0] for b in all_busy if b)
    last = max(b[-1][1] for b in all_busy if b)
    if window_s is None:
        window_s = (last - first) / 1e9
    ranges = _host_ranges(planes)
    gaps = {}
    busy0 = all_busy[0]
    for (_, end), (start, _) in zip(busy0, busy0[1:]):
        name = name_gap(end, start, ranges)
        gaps[name] = gaps.get(name, 0.0) + (start - end) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_s, "window_s": window_s, "chips": len(device),
            "device_ops": top(by_op), "idle_gaps": top(gaps)}


def reduce_file(path, window_s=None):
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    return reduce_planes(list(data.planes), window_s)
