"""The trace reduction: busy union, idle share, gap attribution. On planes
built by hand, and on a small trace recorded on a TPU v5e (PR 24)."""
import os
import types

import pytest

from benchmark import reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb")


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[_event(*e) for e in evs])
        for ln, evs in lines.items()])


def test_exec_ranges_are_recognised():
    for name in ("TpuSortExec#1", "PipelinedExec(depth=2)#3",
                 "HostToDeviceExec#12"):
        assert reduce.EXEC_RANGE.match(name)
    for name in ("tpu-sql-action", "PjitFunction(fn)", "DevicePut"):
        assert not reduce.EXEC_RANGE.match(name)


def test_union_merges_overlaps():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert reduce.union([]) == []
    assert reduce.union([(0, 10), (2, 3)]) == [(0, 10)]


def test_busy_is_the_union_of_op_intervals_and_gaps_are_named():
    s = 1_000_000_000
    device = _plane("/device:TPU:0", {
        # a module event covers the gaps between its ops: not read
        "XLA Modules": [("jit_agg(123)", 0, 6 * s)],
        "XLA Ops": [("fusion.1", 0, 1 * s), ("fusion.2", s // 2, 1 * s),
                    ("sort.3", 3 * s, 1 * s), ("fusion.1", 7 * s, 1 * s)],
    })
    host = _plane("/host:CPU", {"python": [
        ("tpu-sql-action", 0, int(4.5 * s)),
        ("TpuHashAggregateExec#2", int(1.4 * s), int(1.8 * s)),
        ("unrelated", 0, 10 * s),
    ]})
    got = reduce.reduce_planes([device, host], window_s=10.0)
    assert got["busy_s"] == pytest.approx(1.5 + 1.0 + 1.0)
    assert got["window_s"] == 10.0 and got["chips"] == 1
    # an op is named with the module running when it started, if any
    assert dict(map(tuple, got["device_ops"])) == {
        "jit_agg/fusion.1": pytest.approx(1.0),
        "jit_agg/fusion.2": pytest.approx(1.0),
        "jit_agg/sort.3": pytest.approx(1.0), "fusion.1": pytest.approx(1.0)}
    gaps = dict(map(tuple, got["idle_gaps"]))
    # 1.5..3 s lies in the aggregate's pull; 4..7 s starts in the action
    # but its middle (5.5 s) is past it: between queries
    assert gaps == {"TpuHashAggregateExec#2": pytest.approx(1.5),
                    "between queries": pytest.approx(3.0)}
    # without a host clock the window is first op to last op
    assert reduce.reduce_planes([device, host])["window_s"] == pytest.approx(8.0)


def test_two_chips_average_and_no_device_ops_reads_nothing():
    s = 1_000_000_000
    a = _plane("/device:TPU:0", {"XLA Ops": [("f", 0, 2 * s)]})
    b = _plane("/device:TPU:1", {"XLA Ops": [("f", 0, 4 * s)]})
    assert reduce.reduce_planes([a, b], 10.0)["busy_s"] == pytest.approx(3.0)
    host = _plane("/host:CPU", {"python": [("tpu-sql-action", 0, s)]})
    assert reduce.reduce_planes([host], 10.0) is None


@pytest.mark.skipif(not os.path.isfile(FIXTURE), reason="fixture not recorded")
def test_recorded_tpu_trace():
    assert os.path.getsize(FIXTURE) < 1_000_000
    """0.3 s of Q1/Q6 at SF0.01 on a TPU v5e, trimmed to the device's op and
    module lines and the host's python lines."""
    got = reduce.reduce_file(FIXTURE)
    assert got["chips"] == 1
    assert got["busy_s"] == pytest.approx(0.011329547)
    assert got["window_s"] == pytest.approx(0.296601382)
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert set(gaps) == {"tpu-sql-action", "between queries",
                         "FusedAggregateStageExec#2",
                         "FusedAggregateStageExec#1", "TpuSortExec#1",
                         "PipelinedExec(depth=2)#3"}
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    name, seconds = got["device_ops"][0]
    assert name == "jit_fn/%while.16" and seconds == pytest.approx(0.003691277)
    assert all(len(n) <= 80 for n, _ in got["device_ops"])
    # with the host's clock for the slice, the idle share is against it
    assert reduce.reduce_file(FIXTURE, 0.5)["window_s"] == 0.5
