"""The configuration's file says where its tables come from and at what
scale: ``tables_from`` (``memory`` | ``parquet``) and ``scale_factor``. The
parquet cell at SF0.01 on whatever backend jax finds: its plan, its uploads,
its directory; the default path's calls; what ``validate`` refuses; the two
metrics the cell brings."""
import os
import time

import pytest

from benchmark import correct, harness, manifest, readers, spans
from benchmark.tests.test_spans import MS, Ring

PARQUET = "tpch_sf1_parquet.scanagg"
SCANAGG = "tpch_sf1_session.scanagg"
SERVED = "tpch_sf1_server.short_openloop"
SEED = 2**31 + 27


@pytest.fixture
def tmp_dirs(monkeypatch):
    """The directories ``dataframes`` made, in order (the program makes a
    spill directory of its own through the same call)."""
    made, real = [], harness.tempfile.mkdtemp

    def mkdtemp(*args, **kwargs):
        path = real(*args, **kwargs)
        if kwargs.get("prefix") == "benchmark-tables-":
            made.append(path)
        return path

    monkeypatch.setattr(harness.tempfile, "mkdtemp", mkdtemp)
    return made


def test_a_parquet_cell_scans_its_files_at_every_query(monkeypatch, tmp_dirs):
    st = harness.setup(PARQUET, SEED, False, scale=0.01, need_tpu=False)
    try:
        (tmp,) = tmp_dirs
        assert st.tmp_dir == tmp
        assert os.listdir(tmp) == ["lineitem"]
        assert os.listdir(os.path.join(tmp, "lineitem")) == ["part-0.parquet"]
        # the reference's tables stay the Arrow tables from the seed
        assert st.tables["lineitem"].num_rows > 50_000
        uploaded, plans = [], []
        sound = harness.run_query

        def noted(st_, qid, record=None):
            table = sound(st_, qid, record)
            uploaded.append(st_.counters.snapshot()["upload_bytes"])
            plans.append(st_.session.last_plan.tree_string())
            return table

        monkeypatch.setattr(harness, "run_query", noted)
        win = harness.closed_window(st, 1.0)
    finally:
        harness.teardown(st)
    assert not os.path.exists(tmp) and st.tmp_dir is None
    assert win.failed == 0 and win.queries >= 2 and win.queries % 2 == 0
    for plan in plans:
        assert "TpuParquetScanExec" in plan
        assert "HostToDeviceExec" not in plan and "LocalScan" not in plan
    # nothing is cached: every query of the window uploads the file again
    rises = [b - a for a, b in zip([win.before["upload_bytes"]] + uploaded,
                                   uploaded)]
    assert all(r > 0 for r in rises), rises
    assert win.after["decoded_equivalent_bytes"] \
        > win.before["decoded_equivalent_bytes"]
    ok, numbers = correct.judge(win.answers, st.tables, len(st.cpu_execs))
    assert ok, numbers


def test_a_traced_parquet_run_is_correct_and_names_its_metrics():
    result, numbers, _ = harness.run_cell(
        PARQUET, SEED + 1, 2.0, True, time.perf_counter(), scale=0.01,
        need_tpu=False)
    assert result["correct"] is True, numbers
    assert result["failed"] == 0
    listed = {m["name"] for m in manifest.load()["per_layer"]
              if PARQUET in m["workloads"]}
    assert set(result["metrics"]) <= listed
    assert {"scan_pull_s_per_query.collect", "link_encoded_share.collect",
            "upload_mb_per_query.collect", "upload_stage_s_per_query.collect",
            "compiles_in_window.collect"} <= set(result["metrics"])
    assert result["metrics"]["upload_mb_per_query.collect"]["value"] > 0
    assert 0 < result["metrics"]["link_encoded_share.collect"]["value"] <= 100
    assert result["metrics"]["scan_pull_s_per_query.collect"]["value"] > 0


def test_the_served_entry_registers_views_over_the_files(monkeypatch, tmp_dirs):
    """No served cell reads files yet; the hook serves that entry the same
    way: the views are the readers' DataFrames."""
    real = manifest.config_file
    monkeypatch.setattr(manifest, "config_file", lambda mf, name: {
        **real(mf, name), "tables_from": "parquet"})
    result, numbers, win = harness.run_cell(
        SERVED, SEED + 2, 2.0, False, time.perf_counter(), scale=0.01,
        need_tpu=False)
    assert result["correct"] is True, numbers
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert win.after["upload_bytes"] > win.before["upload_bytes"]
    (tmp,) = tmp_dirs
    assert not os.path.exists(tmp)


def test_the_directory_goes_when_warm_up_raises(monkeypatch, tmp_dirs):
    def still_compiling(st):
        assert os.path.isdir(st.tmp_dir)
        raise RuntimeError("still compiling")

    monkeypatch.setattr(harness, "warm_up", still_compiling)
    with pytest.raises(RuntimeError, match="still compiling"):
        harness.setup(PARQUET, SEED, False, scale=0.01, need_tpu=False)
    (tmp,) = tmp_dirs
    assert not os.path.exists(tmp)


def test_the_directory_goes_when_a_table_cannot_be_written(monkeypatch,
                                                           tmp_dirs):
    import pyarrow.parquet as pq

    def full(*args, **kwargs):
        raise OSError("no space left on device")

    monkeypatch.setattr(pq, "write_table", full)
    with pytest.raises(OSError, match="no space"):
        harness.setup(PARQUET, SEED, False, scale=0.01, need_tpu=False)
    (tmp,) = tmp_dirs
    assert not os.path.exists(tmp)


@pytest.mark.parametrize("cell,entry,names", [
    (SCANAGG, "collect", ["lineitem"]),
    (SERVED, "served", ["lineitem"]),
    ("tpch_sf1_session.join", "collect", ["customer", "orders", "lineitem"]),
])
def test_without_tables_from_the_tables_are_created_as_before(
        monkeypatch, tmp_dirs, cell, entry, names):
    """The accepted cells' files state no ``tables_from``: one
    ``createDataFrame`` per table in the schema's order, no file, no reader."""
    from spark_rapids_tpu.api import TpuSession
    config = manifest.config_file(
        manifest.load(), manifest.workload_entry(manifest.load(),
                                                 cell)["config"])
    assert "tables_from" not in config and config["entry"] == entry
    calls, whole = [], TpuSession.createDataFrame

    def created(self, table, *args, **kwargs):
        calls.append((table, args, kwargs))
        return whole(self, table, *args, **kwargs)

    monkeypatch.setattr(TpuSession, "createDataFrame", created)
    monkeypatch.setattr(TpuSession, "read", property(
        lambda self: pytest.fail("a memory cell read a file")))
    monkeypatch.setattr(harness, "warm_up", lambda st: None)
    st = harness.setup(cell, SEED, False, scale=0.01, need_tpu=False)
    harness.teardown(st)
    assert list(st.tables) == names == list(st.dfs)
    assert [c[0] for c in calls] == [st.tables[n] for n in names]
    assert all(c[1:] == ((), {}) for c in calls)
    assert tmp_dirs == [] and st.tmp_dir is None


def test_a_run_takes_its_scale_from_the_configurations_file(monkeypatch):
    seen = []

    def gen(names, scale, seed):
        seen.append(scale)
        raise KeyboardInterrupt  # far enough

    monkeypatch.setattr(harness, "gen_tables", gen)
    real = manifest.config_file
    monkeypatch.setattr(manifest, "config_file", lambda mf, name: {
        **real(mf, name), "scale_factor": 0.25})
    for scale in (None, 0.01):
        with pytest.raises(KeyboardInterrupt):
            harness.run_cell(SCANAGG, SEED, 1.0, False, time.perf_counter(),
                             scale=scale, need_tpu=False)
    assert seen == [0.25, 0.01]


def test_every_committed_configuration_states_sf1():
    mf = manifest.load()
    assert {manifest.config_file(mf, c["name"])["scale_factor"]
            for c in mf["configs"]} == {1.0}


@pytest.mark.parametrize("stated,expected", [
    ({"tables_from": "csv"}, "tables_from 'csv'"),
    ({"tables_from": None}, "tables_from None"),
    ({"scale_factor": 0}, "scale_factor 0 "),
    ({"scale_factor": -1.0}, "scale_factor -1.0"),
    ({"scale_factor": "1"}, "scale_factor '1'"),
    ({"scale_factor": True}, "scale_factor True"),
])
def test_validate_refuses_what_the_harness_cannot_take(monkeypatch, stated,
                                                       expected):
    mf, real = manifest.load(), manifest.config_file

    def config_file(m, name):
        config = real(m, name)
        return {**config, **stated} if name == "tpch_sf1_parquet" else config

    monkeypatch.setattr(manifest, "config_file", config_file)
    bad = manifest.problems_of(mf)
    assert len(bad) == 1 and expected in bad[0] \
        and "'tpch_sf1_parquet'" in bad[0], bad
    with pytest.raises(manifest.ManifestError):
        manifest.validate(mf)


def test_validate_takes_both_sources_and_a_missing_key():
    assert manifest.config_problems("c", {"scale_factor": 10}) == []
    for source in manifest.TABLES_FROM:
        assert manifest.config_problems(
            "c", {"scale_factor": 0.5, "tables_from": source}) == []
    assert manifest.tables_from({}) == "memory"
    assert len(manifest.config_problems("c", {})) == 1     # no scale_factor


def parquet_query(ring, k):
    """One query of the parquet cell: the scan's pulls run under
    ``PipelinedExec`` under the fused aggregate."""
    root = ring.add("query", 5000 * MS * k)
    action = ring.add("action", 4900 * MS * k, root)
    agg = ring.add("FusedAggregateStageExec", 4800 * MS * k, action)
    pipe = ring.add("PipelinedExec(depth=2)", 4700 * MS * k, agg)
    scan = ring.add("TpuParquetScanExec", 4600 * MS * k, pipe)
    for _ in range(6):
        upload = ring.add("transfer.upload", 300 * MS * k, scan)
        ring.add("upload.stage", 250 * MS * k, upload)
    ring.add("program.agg", 4 * MS, agg)
    return root


def _read(name, ctx):
    return readers.read(name, manifest.metric_file(name), ctx)


def test_scan_pull_reads_the_scan_spans_of_the_window(monkeypatch):
    ring = Ring()
    parquet_query(ring, 9)                  # warm-up: never read
    parquet_query(ring, 1)
    parquet_query(ring, 2)
    records = ring.close()
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    name = "scan_pull_s_per_query.collect"
    assert _read(name, {"queries": 2}) == pytest.approx((4.6 + 9.2) / 2)
    assert _read(name, {"queries": 3}) == pytest.approx(
        (4.6 + 9.2 + 41.4) / 3)
    # nothing to read: no ring, a window the ring lost part of, no such span
    assert _read(name, {"queries": 4}) is None
    monkeypatch.setattr(spans, "_ring", lambda: (records, records[-1].seq))
    assert _read(name, {"queries": 2}) is None
    memory = Ring()
    root = memory.add("query", 50 * MS)
    memory.add("HostToDeviceExec", 10 * MS, root)
    monkeypatch.setattr(spans, "_ring", lambda: (memory.close(), 0))
    assert _read(name, {"queries": 1}) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)
    assert _read(name, {"queries": 1}) is None


def test_link_encoded_share_reads_the_two_counters_over_the_window():
    name = "link_encoded_share.collect"
    before = {"encoded_bytes": 1_000, "decoded_equivalent_bytes": 9_000}
    after = {"encoded_bytes": 1_000 + 50, "decoded_equivalent_bytes": 9_200}
    assert _read(name, {"before": before, "after": after}) == 25.0
    # set-up's bytes are not the window's; no file scan, no reading
    assert _read(name, {"before": after, "after": after}) is None
    assert readers.counter_ratio(
        {"before": before, "after": after}, "encoded_bytes",
        "decoded_equivalent_bytes") == 0.25


def test_the_counters_are_read_beside_upload_bytes():
    snap = harness.counters().snapshot()
    assert {"upload_bytes", "encoded_bytes", "decoded_equivalent_bytes",
            "program_hits", "program_misses", "first_call_s",
            "xla_compile_requests"} == set(snap)
