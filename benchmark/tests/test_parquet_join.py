"""The cell ``tpch_sf1_parquet.join`` (PR 38): TPC-H Q3 over three parquet
tables read at every query. The two ``join_*`` readers on hand-made
``join.drain`` spans (outermost only; a program without the span or its
args: no reading), the manifest, and the cell at SF0.01 on the CPU: three
directories written, uploads at every query, ``correct``, both metrics in a
traced run."""
import os
import time

import pytest

from benchmark import correct, harness, manifest, readers, run, spans
from benchmark.tests.test_spans import MS, Ring

CELL = "tpch_sf1_parquet.join"
RESIDENT = "tpch_sf1_session.join"
DRAIN_S = "join_drain_s_per_query.collect"
BATCHES = "join_input_batches_per_query.collect"
TWO = (DRAIN_S, BATCHES)
SEED = 2**31 + 38


def _query(ring, outer_ms, inner_ms, **args):
    """A query of two joins: the inner one's drain inside the outer one's."""
    root = ring.add("query", 1000 * MS)
    top = ring.add("TpuShuffledHashJoinExec", 900 * MS, root)
    outer = ring.add("join.drain", outer_ms * MS, top)
    outer.args = dict(side="both", batches=7, **args) if args else None
    inner_exec = ring.add("TpuBroadcastHashJoinExec", inner_ms * MS, outer)
    inner = ring.add("join.drain", inner_ms * MS, inner_exec)
    inner.args = dict(side="both", batches=3, **args) if args else None


def _read(name, ring, queries, monkeypatch):
    records = ring.close()
    for r in records:
        r.args = getattr(r, "args", None)
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    return readers.read(name, manifest.metric_file(name),
                        {"queries": queries})


def test_the_drain_counts_the_outermost_spans_only(monkeypatch):
    ring = Ring()
    for _ in range(2):
        _query(ring, 600, 200, mode="inline", rows=10)
    assert _read(DRAIN_S, ring, 2, monkeypatch) == pytest.approx(0.6)
    assert _read(BATCHES, ring, 2, monkeypatch) == 10.0


def test_drains_on_both_sides_add_up(monkeypatch):
    """Without the out-of-core controller: one span a side, both
    outermost."""
    ring = Ring()
    root = ring.add("query", 1000 * MS)
    join = ring.add("TpuShuffledHashJoinExec", 900 * MS, root)
    for side, ms, n in (("left", 300, 1), ("right", 400, 6)):
        ring.add("join.drain", ms * MS, join).args = dict(
            side=side, batches=n, rows=n * 10)
    assert _read(DRAIN_S, ring, 1, monkeypatch) == pytest.approx(0.7)
    assert _read(BATCHES, ring, 1, monkeypatch) == 7.0


def test_without_the_span_or_its_args_there_is_no_reading(monkeypatch):
    ring = Ring()
    ring.add("query", 1000 * MS)         # a program from before the span
    assert _read(DRAIN_S, ring, 1, monkeypatch) is None
    assert _read(BATCHES, ring, 1, monkeypatch) is None
    ring = Ring()
    _query(ring, 600, 200)               # a partitioned drain notes no count
    assert _read(DRAIN_S, ring, 1, monkeypatch) == pytest.approx(0.6)
    assert _read(BATCHES, ring, 1, monkeypatch) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)   # no tracer at all
    for name in TWO:
        assert readers.read(name, manifest.metric_file(name),
                            {"queries": 1}) is None


def test_the_manifest_lists_the_cell_and_its_metrics(capsys):
    mf = manifest.load()
    assert run.main(["--validate"]) == 0
    assert (f"valid: {len(mf['workloads'])} cells, "
            f"{len(mf['end_to_end'])} end-to-end and "
            f"{len(mf['per_layer'])} per-layer metrics"
            in capsys.readouterr().out)
    entry = manifest.workload_entry(mf, CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch_sf1_parquet_q3", "join", 1)
    assert manifest.workload_file(CELL)["queries"] \
        == manifest.workload_file(RESIDENT)["queries"]
    ours = {m["name"] for m in manifest.metrics_of(mf, CELL, "per_layer")}
    scans = {m["name"] for m in manifest.metrics_of(
        mf, "tpch_sf1_parquet.scanagg", "per_layer")}
    joins = {m["name"] for m in manifest.metrics_of(mf, RESIDENT,
                                                    "per_layer")}
    assert ours == scans | joins
    two = [m for m in mf["per_layer"] if m["name"] in TWO]
    assert len(two) == len(TWO)
    for m in two:
        assert m["workloads"] == [CELL, RESIDENT]
        assert (m["layer"], m["moves"], m["source"]) == (
            "Operators (XLA)", "query_wall_s", "program_span")
    assert [m["name"] for m in manifest.metrics_of(mf, CELL, "end_to_end")] \
        == ["setup_s", "query_wall_s"]


def test_the_cell_reads_three_files_at_every_query(monkeypatch):
    made, real = [], harness.tempfile.mkdtemp

    def mkdtemp(*args, **kwargs):
        path = real(*args, **kwargs)
        if kwargs.get("prefix") == "benchmark-tables-":
            made.append(path)
        return path

    monkeypatch.setattr(harness.tempfile, "mkdtemp", mkdtemp)
    st = harness.setup(CELL, SEED, False, scale=0.01, need_tpu=False)
    try:
        (tmp,) = made
        assert sorted(os.listdir(tmp)) == ["customer", "lineitem", "orders"]
        uploaded, sound = [], harness.run_query

        def noted(st_, qid, record=None):
            table = sound(st_, qid, record)
            uploaded.append(st_.counters.snapshot()["upload_bytes"])
            plan = st_.session.last_plan.tree_string()
            assert plan.count("TpuParquetScanExec") == 3
            assert "HostToDeviceExec" not in plan
            return table

        monkeypatch.setattr(harness, "run_query", noted)
        win = harness.closed_window(st, 1.0)
    finally:
        harness.teardown(st)
    assert not os.path.exists(tmp)
    assert win.failed == 0 and win.queries >= 2
    rises = [b - a for a, b in zip([win.before["upload_bytes"]] + uploaded,
                                   uploaded)]
    assert all(r > 0 for r in rises), rises
    ok, numbers = correct.judge(win.answers, st.tables, len(st.cpu_execs))
    assert ok, numbers


def test_a_traced_run_is_correct_and_prints_both_metrics():
    result, numbers, _ = harness.run_cell(
        CELL, SEED + 1, 2.0, True, time.perf_counter(), scale=0.01,
        need_tpu=False)
    assert result["correct"] is True, numbers
    assert result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got[DRAIN_S] > 0
    # at SF0.01 every side is one batch: two a join
    assert got[BATCHES] == 4.0
    assert got["upload_mb_per_query.collect"] > 0
    assert {"scan_pull_s_per_query.collect", "scan_wait_s_per_query.collect",
            "scan_decode_s_per_query.collect"} <= set(got)
