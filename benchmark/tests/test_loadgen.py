"""The open-loop generator: schedule from the seed, latency from due time."""
import types

import pytest

from benchmark import harness, loadgen

WORKLOAD = {"queries": [{"id": "q1", "weight": 1}, {"id": "q6", "weight": 1}],
            "rate_per_s": 5.0, "loop": "open", "entry": "served"}


def test_the_schedule_is_the_files_for_every_seed():
    a = loadgen.open_schedule(WORKLOAD, 2**31 + 5, 10.0)
    assert a == loadgen.open_schedule(WORKLOAD, 2**31 + 5, 10.0)
    assert a == loadgen.open_schedule(WORKLOAD, 7, 10.0)
    other = loadgen.open_schedule(dict(WORKLOAD, arrival_seed=9), 7, 10.0)
    assert a != other and len(a) == len(other) == 50
    gaps = lambda s: sorted(round(y[0] - x[0], 9)
                            for x, y in zip([(0.0, None)] + s, s))
    assert gaps(a) == gaps(other)       # the same gaps, in another order
    assert [q for _, q in a].count("q1") == 25
    assert 0 < a[0][0] and a[-1][0] < 10.0
    assert all(x[0] <= y[0] for x, y in zip(a, a[1:]))
    # exponential gaps: the median gap is ln 2 of the mean
    mean = a[-1][0] / len(a)
    assert gaps(a)[25] == pytest.approx(0.693 * mean, rel=0.05)


def test_percentile_is_numpys_default():
    assert loadgen.percentile([1, 2, 3, 4], 50) == 2.5
    assert loadgen.percentile([5], 90) == 5
    assert loadgen.percentile(list(range(11)), 90) == 9
    assert loadgen.percentile([], 50) is None


def test_cycle_keeps_weights():
    assert loadgen.cycle({"queries": [{"id": "a", "weight": 2},
                                      {"id": "b", "weight": 1}]}) == ["a", "a", "b"]


def test_latency_counts_from_due_time_and_lateness_is_reported(monkeypatch):
    """A server that stalls 0.3 s per request, one at a time: the later
    requests queue, and their wait is in the latency because it is counted
    from when each was due, not from when it was sent."""
    import threading
    import time
    lock = threading.Lock()

    def slow(st, qid, record=None):
        with lock:
            time.sleep(0.3)
        record["queue_wait_s"] = 0.001
        return "answer"

    monkeypatch.setattr(harness, "run_query", slow)
    st = types.SimpleNamespace(
        workload=dict(WORKLOAD, rate_per_s=10.0), qids=["q1", "q6"],
        counters=types.SimpleNamespace(snapshot=lambda: {}))
    win = harness.open_window(st, 1.0, 3)
    assert win.attempted == 10 and win.failed == 0 and win.queries == 10
    assert sum(len(v) for v in win.answers.values()) == 10
    # ten requests of 0.3 s through one lock: the last waits ~2 s
    assert win.end_to_end["latency_p90_s"] > 1.5
    assert win.end_to_end["latency_p50_s"] > 0.6
    assert all(r["late_s"] is not None and r["late_s"] < 0.2
               for r in win.requests)
    assert all(r["sent"] >= r["due"] for r in win.requests)


def test_a_refused_request_is_failed_not_wrong(monkeypatch):
    def refuse(st, qid, record=None):
        raise RuntimeError("shed")
    monkeypatch.setattr(harness, "run_query", refuse)
    st = types.SimpleNamespace(
        workload=dict(WORKLOAD, rate_per_s=4.0), qids=["q1", "q6"],
        counters=types.SimpleNamespace(snapshot=lambda: {}))
    win = harness.open_window(st, 1.0, 3)
    assert win.attempted == 4 and win.failed == 4 and win.unanswered == 0
    assert win.end_to_end["latency_p50_s"] is None


def test_the_trace_closes_after_the_last_answer_and_a_late_one_counts(
        monkeypatch):
    """What the driver's check refused (PR 24): the trace was closed between
    two requests, in the generator's thread; the profiler took so long to
    write that the last request went out with no time left and was counted
    as never answered. Now the trace closes once, after every answer, and
    the grace runs from the window's close or the last send, whichever is
    later."""
    import time
    monkeypatch.setattr(harness, "GRACE_S", 1.0)

    def slow(st, qid, record=None):
        time.sleep(0.6)         # past the window's close, within the grace
        return "answer"

    class Tracer:
        running, stops, done = True, [], []

        def start(self):
            pass

        def query_done(self, qid):
            self.done.append(time.perf_counter())

        def stop(self):
            time.sleep(0.5)     # a profiler that is slow to write
            self.stops.append(time.perf_counter())

    monkeypatch.setattr(harness, "run_query", slow)
    st = types.SimpleNamespace(
        workload=dict(WORKLOAD, rate_per_s=8.0), qids=["q1", "q6"],
        counters=types.SimpleNamespace(snapshot=lambda: {}))
    tracer = Tracer()
    win = harness.open_window(st, 0.5, 3, tracer)
    assert win.attempted == 4 and win.failed == 0 and win.unanswered == 0
    assert len(tracer.stops) == 1 and len(tracer.done) == 4
    assert tracer.stops[0] > max(tracer.done)
    assert all(r["late_s"] < 0.2 for r in win.requests)


def test_an_answer_that_never_comes_is_unanswered(monkeypatch):
    import threading
    monkeypatch.setattr(harness, "GRACE_S", 0.3)
    release = threading.Event()

    def hang(st, qid, record=None):
        release.wait(5.0)
        return "answer"

    monkeypatch.setattr(harness, "run_query", hang)
    st = types.SimpleNamespace(
        workload=dict(WORKLOAD, rate_per_s=4.0), qids=["q1", "q6"],
        counters=types.SimpleNamespace(snapshot=lambda: {}))
    win = harness.open_window(st, 0.5, 3)
    release.set()
    assert win.attempted == 2 and win.unanswered == 2 and win.failed == 2
