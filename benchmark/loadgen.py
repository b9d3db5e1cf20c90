"""The one traffic generator: reads a cell's file under ``workloads/`` and
makes the order of queries and (open loop) their due times. The sequence is
the file's own (its ``arrival_seed``), the same for every ``--seed``: the
run's seed makes the tables, and changes neither the work offered nor when
it arrives."""
import math
import random


def cycle(workload):
    """One closed-loop cycle: each query as often as its weight, in the
    file's order."""
    return [q["id"] for q in workload["queries"] for _ in range(q["weight"])]


def open_schedule(workload, seed, seconds):
    """[(due seconds from window start, query id)], sorted by due time.

    ``rate_per_s * seconds`` requests (rounded down to whole cycles, at
    least one). The
    gaps between arrivals are the quantiles of the exponential distribution
    with that rate (a Poisson stream's gaps, without the luck of a draw),
    scaled to end inside the window, and shuffled once by the file's
    ``arrival_seed``; the queries keep the file's weights exactly and are
    shuffled the same way. ``seed`` is not read: where a window holds a
    dozen requests of seconds each, a fresh shuffle (or a rotation) per seed
    makes the tail a matter of where the short gaps happened to meet, and
    runs with different seeds then differ far more than runs of one."""
    kinds = cycle(workload)
    n = max(int(workload["rate_per_s"] * seconds) // len(kinds), 1) * len(kinds)
    rng = random.Random(workload.get("arrival_seed", 0))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(gaps) * (n - 0.5) / n
    rng.shuffle(gaps)
    order = kinds * (n // len(kinds))
    rng.shuffle(order)
    due, t = [], 0.0
    for gap, qid in zip(gaps, order):
        t += gap * scale
        due.append((t, qid))
    return due


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as numpy's default."""
    if not values:
        return None
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
