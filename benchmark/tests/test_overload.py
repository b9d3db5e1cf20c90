"""The bounded open loop (``overload.py``) over a fake client: the schedule
and its tenants, the threads it holds, what ``completed_qps`` counts and what
``correct`` judges, a shed request; the cell's readers on hand-made records;
and the manifest with the cell."""
import collections
import copy
import itertools
import threading
import time
import types

import pyarrow as pa
import pyarrow.compute as pc
import pytest

from benchmark import correct, harness, manifest, overload, readers, run

CELL = "tpch_sf1_server.mixed_overload"
SQL = {"q1": "select 1", "q3": "select 3", "q6": "select 6"}


def workload(**changes):
    return dict(copy.deepcopy(manifest.workload_file(CELL)), **changes)


class FakeHandle:
    def __init__(self, client, qid, tenant):
        self.client, self.qid, self.tenant = client, qid, tenant
        self.metrics = {}

    def result(self):
        self.client.observe()
        self.client.wait(self)
        self.metrics = {"queue_wait_s": 0.001}
        return pa.table({"q": [self.qid]})


class FakeClient:
    """Answers every request at once, or when ``wait`` lets it; sheds the
    submissions whose index is in ``shed``; notes the most threads alive."""

    def __init__(self, shed=(), wait=None):
        self.shed, self.submitted, self.peak = set(shed), 0, 0
        self.wait = wait or (lambda handle: None)
        self._lock = threading.Lock()

    def observe(self):
        with self._lock:
            self.peak = max(self.peak, threading.active_count())

    def submit(self, sql, tenant="default", label=""):
        self.observe()
        index, self.submitted = self.submitted, self.submitted + 1
        if index in self.shed:
            raise RuntimeError("OverloadedError: tenant queue at its bound")
        return FakeHandle(self, label, tenant)


class Counters:
    def snapshot(self):
        return {"xla_compile_requests": 0, "program_misses": 0}


class Tracer:
    """SliceTracer's surface, without the profiler."""

    def __init__(self):
        self.starts = self.stops = 0
        self.running = False
        self.done_qids = []

    def start(self):
        self.starts += 1
        self.running = True

    def stop(self):
        self.stops += 1
        self.running = False

    def query_done(self, qid):
        if self.running:
            self.done_qids.append(qid)


def state(client, max_concurrent=4, **changes):
    """The loop's view of a run: the cell's file, a client, and the server's
    scheduler, of which it reads how many queries run at once."""
    scheduler = types.SimpleNamespace(max_concurrent=max_concurrent)
    return types.SimpleNamespace(
        cell=CELL, workload=workload(**changes), qids=sorted(SQL), sql=SQL,
        client=client, counters=Counters(),
        session=types.SimpleNamespace(scheduler=scheduler))


def exact(shares, n):
    """{key: count} of ``n`` in ``shares`` by largest remainder."""
    total = sum(shares.values())
    counts = {k: int(n * v / total) for k, v in shares.items()}
    by_remainder = sorted(shares, key=lambda k: counts[k] - n * shares[k]
                          / total)
    for k in by_remainder[:n - sum(counts.values())]:
        counts[k] += 1
    return counts


# ------------------------------------------------------------ the schedule
def test_the_schedule_keeps_the_weights_and_the_tenant_shares_exactly():
    w = workload()
    plan = overload.schedule(w, 48.0)
    n = len(plan)
    per_cycle = sum(q["weight"] for q in w["queries"])
    assert n == int(w["rate_per_s"] * 48) // per_cycle * per_cycle
    assert [d for d, _, _ in plan] == sorted(d for d, _, _ in plan)
    assert 0 < plan[0][0] and plan[-1][0] < 48.0
    kinds = collections.Counter(q for _, q, _ in plan)
    assert kinds == {q["id"]: n * q["weight"] // per_cycle
                     for q in w["queries"]}
    tenants = collections.Counter(t for _, _, t in plan)
    assert tenants == exact({t["id"]: t["share"] for t in w["tenants"]}, n)
    assert tenants.most_common()[0][1] > n / 2
    # each tenant sends a mix, not one kind
    assert all(len({q for _, q, t in plan if t == tenant}) == 3
               for tenant in tenants)


@pytest.mark.parametrize("shares", [(7, 2, 1), (1.0, 0.50348, 0.33702)],
                         ids=["whole", "zipf"])
@pytest.mark.parametrize("n", [1, 7, 10, 999])
def test_tenant_draw_keeps_the_shares_at_any_count(n, shares):
    tenants = [{"id": k, "share": v} for k, v in zip("abc", shares)]
    drawn = collections.Counter(overload.tenant_draw(tenants, n, 41))
    assert sum(drawn.values()) == n
    for t in tenants:
        assert abs(drawn[t["id"]] - n * t["share"] / sum(shares)) < 1


def test_the_schedule_is_the_same_for_two_seeds():
    runs = []
    for seed in (2**31 + 5, 7):
        win = harness.window(state(FakeClient(), rate_per_s=51.0), 0.4, seed)
        runs.append([(r["due"], r["qid"], r["tenant"]) for r in win.requests])
    assert runs[0] == runs[1] and len(runs[0]) == 18


# ------------------------------------------------------------- the threads
def test_a_thousand_requests_never_hold_more_than_sixteen_threads():
    client = FakeClient()
    before = threading.active_count()
    tracer = Tracer()
    five = [{"id": "q1", "weight": 2}, {"id": "q3", "weight": 1},
            {"id": "q6", "weight": 2}]      # whole cycles of 1,000
    st = state(client, rate_per_s=1000.0, queries=five, trace_offset_s=0.2,
               trace_slice_s=0.3)
    win = overload.window(st, 1.0, 5.0, tracer)
    assert win.attempted == 1000 and win.failed == 0
    assert sum(len(a) for a in win.answers.values()) == 1000
    # the caller sends; every other thread the loop holds is its own
    assert client.peak - before + 1 <= overload.MAX_THREADS
    assert win.loop_threads == 1 + 3 * 4 + 1 <= overload.MAX_THREADS
    assert tracer.starts == 1 and tracer.stops >= 1
    assert 0 < len(tracer.done_qids) < 1000
    assert threading.active_count() == before


@pytest.mark.parametrize("max_concurrent,threads", [(1, 4), (2, 7), (4, 13),
                                                    (5, 16)])
def test_the_collectors_follow_the_queries_the_server_runs_at_once(
        max_concurrent, threads):
    win = overload.window(state(FakeClient(), max_concurrent, rate_per_s=60.0),
                          0.2, 1.0)
    assert win.loop_threads == threads and win.failed == 0
    assert win.queries == win.attempted == 12


def test_a_server_that_runs_more_queries_than_the_loop_can_follow_is_refused():
    """Five or more tenants' collectors past sixteen threads: refused, and
    no thread started."""
    before = threading.active_count()
    with pytest.raises(ValueError, match="threads"):
        overload.window(state(FakeClient(), 6), 0.2, 1.0)
    assert threading.active_count() == before


def test_a_small_tenant_is_timed_when_answered_not_behind_the_backlog():
    """The large tenant's handles hold until released; the others answer at
    once and are timed so, as a collector per tenant waits on them."""
    release = threading.Event()
    large = workload()["tenants"][0]["id"]
    client = FakeClient(wait=lambda h: h.tenant == large and release.wait())
    threading.Timer(0.6, release.set).start()
    win = overload.window(state(client, rate_per_s=100.0), 0.4, 5.0)
    small = [r for r in win.requests if r["tenant"] != large]
    assert small and all(r["done"] < 0.5 for r in small)
    assert all(r["done"] >= 0.55 for r in win.requests
               if r["tenant"] == large)


# ------------------------------------------------ completed_qps and correct
def rec(due, done, answered=True, tenant="team_a", **extra):
    r = {"due": due, "qid": "q6", "tenant": tenant, "sent": due,
         "done": done, **extra}
    if answered:
        r["table"] = pa.table({"x": [1]})
    return r


def test_completed_qps_counts_what_was_answered_by_the_close():
    records = [rec(0.1, 0.5), rec(0.2, 1.9), rec(0.3, 2.0),
               rec(1.5, 2.5), rec(1.8, 7.0),
               rec(1.0, 1.2, answered=False, error="shed")]
    assert overload.completed_qps(records, 2.0) == 3 / 2.0


def test_answers_after_the_close_are_judged_but_not_counted():
    release = threading.Event()
    client = FakeClient(wait=lambda h: release.wait())
    threading.Timer(0.5, release.set).start()
    win = overload.window(state(client, rate_per_s=50.0), 0.3, 5.0)
    assert win.end_to_end == {"completed_qps": 0.0}
    assert win.queries == win.attempted == 15 and win.failed == 0
    assert sum(len(a) for a in win.answers.values()) == 15


def test_an_answer_that_never_comes_is_unanswered():
    never = threading.Event()
    large = workload()["tenants"][0]["id"]
    client = FakeClient(wait=lambda h: h.tenant == large and never.wait(3))
    win = overload.window(state(client, rate_per_s=51.0), 0.2, 0.3)
    hung = sum(r["tenant"] == large for r in win.requests)
    assert win.attempted == 9 and 4 <= hung < 9
    assert win.unanswered == hung and win.failed == hung
    assert win.queries == 9 - hung
    never.set()


def test_a_shed_request_counts_as_failed():
    win = overload.window(state(FakeClient(shed={3}), rate_per_s=51.0), 0.4,
                          5.0)
    assert win.attempted == 18 and win.failed == 1 and win.unanswered == 0
    (shed,) = [r for r in win.requests if "error" in r]
    assert "Overloaded" in shed["error"] and "table" not in shed
    assert sum(len(a) for a in win.answers.values()) == 17


# ------------------------------------------ a whole run of the cell, faulted
def _run():
    result, numbers, win = harness.run_cell(
        CELL, 2**31 + 41, 2.0, False, time.perf_counter(), scale=0.01,
        need_tpu=False)
    return result, {n["name"]: n for n in numbers}, win


def test_a_sound_run_of_the_cell_is_correct():
    result, numbers, win = _run()
    assert result["correct"] is True, numbers
    assert result["attempted"] == len(overload.schedule(workload(), 2.0))
    assert result["failed"] == 0 and numbers["failed"]["value"] == 0
    assert set(result["metrics"]) == {"setup_s", "completed_qps"}
    assert {r["tenant"] for r in win.requests} == {"team_a", "team_b",
                                                   "team_c"}
    assert all(numbers[f"{q}.answers"]["value"] >= 1
               for q in ("q1", "q3", "q6"))


def _altered(qid, alter):
    """A served answer of ``qid`` altered where the client hands it over."""
    from spark_rapids_tpu.serving.client import RemoteQueryHandle
    sound = RemoteQueryHandle.result

    def result(self):
        table = sound(self)
        return alter(table) if self.label == qid else table
    return RemoteQueryHandle, "result", result


def _times(column, factor):
    return lambda t: t.set_column(
        t.column_names.index(column), column,
        pc.multiply(t.column(column), pa.scalar(factor, pa.float64())))


@pytest.mark.parametrize("qid,alter,number", [
    ("q6", _times("revenue", 1 + 1e-8), "q6.rel_gap"),
    ("q3", lambda t: t.set_column(
        t.column_names.index("l_orderkey"), "l_orderkey",
        pc.add(t.column("l_orderkey"), 1)), "q3.exact_mismatch"),
    ("q1", lambda t: t.slice(1), "q1.exact_mismatch"),
], ids=["q6_float", "q3_key", "q1_row_dropped"])
def test_an_altered_served_answer_is_not_correct(monkeypatch, qid, alter,
                                                 number):
    monkeypatch.setattr(*_altered(qid, alter))
    result, numbers, _ = _run()
    assert result["correct"] is False
    assert not correct.holds(numbers[number]), numbers[number]


def test_half_of_the_rows_left_out_is_not_correct(monkeypatch):
    """The server's tables hold half of lineitem; the reference all of it."""
    from spark_rapids_tpu.api import TpuSession
    whole = TpuSession.createDataFrame

    def half(self, table, *args, **kwargs):
        if table.num_rows > 1000:
            table = table.slice(0, table.num_rows // 2)
        return whole(self, table, *args, **kwargs)

    monkeypatch.setattr(TpuSession, "createDataFrame", half)
    result, numbers, _ = _run()
    assert result["correct"] is False
    assert not correct.holds(numbers["q1.exact_mismatch"])   # count_order


def _fails_once(where):
    """One request of the run refused at submission (shed), or accepted and
    then raising from its result, as a query that errors on the server."""
    from spark_rapids_tpu.serving.client import (QueryServiceClient,
                                                 RemoteQueryHandle)
    cls, name = ((QueryServiceClient, "submit") if where == "submit"
                 else (RemoteQueryHandle, "result"))
    sound, calls = getattr(cls, name), itertools.count(1)

    def once(self, *args, **kwargs):
        if next(calls) == 5:      # one call in any thread sees 5
            raise RuntimeError(f"{where}: refused by the server")
        return sound(self, *args, **kwargs)
    return cls, name, once


@pytest.mark.parametrize("where", ["submit", "result"],
                         ids=["shed_at_submit", "raises_from_result"])
def test_a_failed_request_is_not_correct(monkeypatch, where):
    """The warm-up has run (set-up submits too): patch once the window is
    about to start, so the fifth request of the window is the one."""
    sound_window = overload.window

    def window(st, *args, **kwargs):
        monkeypatch.setattr(*_fails_once(where))
        return sound_window(st, *args, **kwargs)

    monkeypatch.setattr(overload, "window", window)
    result, numbers, win = _run()
    assert result["correct"] is False and result["failed"] == 1
    assert numbers["failed"]["value"] == 1 and numbers["unanswered"][
        "value"] == 0
    assert not correct.holds(numbers["failed"])
    assert all(correct.holds(n) for k, n in numbers.items() if k != "failed")


# --------------------------------------------------------------- the readers
def read(name, requests):
    return readers.read(name, manifest.metric_file(name),
                        {"requests": requests})


def test_the_readers_on_hand_made_records():
    requests = [
        rec(0.0, 1.0, tenant="team_a", queue_wait_s=0.8, latency_s=1.0),
        rec(0.0, 3.0, tenant="team_a", queue_wait_s=2.5, latency_s=3.0),
        rec(0.0, 5.0, tenant="team_a", queue_wait_s=4.9, latency_s=5.0),
        rec(0.0, 0.2, tenant="team_b", queue_wait_s=0.0, latency_s=0.2),
        rec(0.0, 0.4, tenant="team_c", queue_wait_s=0.1, latency_s=0.4),
        rec(0.0, 0.1, answered=False, tenant="team_a", error="shed"),
    ]
    # done - due - queue_wait: 0.2, 0.5, 0.1, 0.2, 0.3
    assert read("service_s_p50.overload", requests) == pytest.approx(0.2)
    assert read("small_tenant_latency_p90_s.overload",
                requests) == pytest.approx(0.38)
    assert read("latency_p90_s.overload", requests) == pytest.approx(4.2)
    assert read("queue_wait_p90_ms.overload",
                requests) == pytest.approx(3940.0)
    assert read("service_s_p50.overload", []) is None
    assert read("small_tenant_latency_p90_s.overload", requests[:3]) is None


# -------------------------------------------------------------- the manifest
def test_the_manifest_lists_the_cell_and_completed_qps(capsys):
    mf = manifest.load()
    assert run.main(["--validate"]) == 0
    assert (f"valid: {len(mf['workloads'])} cells, "
            f"{len(mf['end_to_end'])} end-to-end" in capsys.readouterr().out)
    assert len(mf["workloads"]) >= 9 and len(mf["end_to_end"]) >= 5
    entry = manifest.workload_entry(mf, CELL)
    assert entry["chips"] == 1 and entry["config"] == "tpch_sf1_server"
    assert [m["name"] for m in manifest.metrics_of(mf, CELL, "end_to_end")
            ] == ["setup_s", "completed_qps"]
    (qps,) = [m for m in mf["end_to_end"] if m["name"] == "completed_qps"]
    assert qps["better"] == "higher" and qps["workloads"] == [CELL]
    assert 0.01 <= qps["bound"] <= 0.25
    ours = manifest.metrics_of(mf, CELL, "per_layer")
    assert len(ours) == 13
    for m in ours:
        if m["moves"] == "setup_s":     # what set-up did, as in every cell
            assert m["name"] in ("first_call_s", "upload_mb_setup")
            assert len(m["workloads"]) == len(mf["workloads"])
            continue
        assert m["name"].endswith(".overload") and m["workloads"] == [CELL]
        assert m["moves"] == "completed_qps"
    assert manifest.tables_named(
        [q["id"] for q in manifest.workload_file(CELL)["queries"]],
        manifest.config_file(mf, "tpch_sf1_server")["schema"]) == [
        "customer", "orders", "lineitem"]
