"""Structured query tracing (utils/tracing.py): the span ring, per-exec
spans, EXPLAIN ANALYZE, Chrome export, per-exec jax.profiler ranges, the
metric-registry coverage contract, and the per-action/per-query
recursion-depth attribution fix."""
import json
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.utils import metrics as um
from spark_rapids_tpu.utils import tracing

BASE_CONF = {
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    # single-threaded plan: per-node SELF times sum to the action wall
    # (producer threads are genuine concurrency and deliberately do not
    # subtract cross-thread)
    "spark.rapids.tpu.transfer.pipeline.enabled": "false",
}


def _table(rows: int = 4096) -> pa.Table:
    rng = np.random.default_rng(7)
    return pa.table({"k": rng.integers(0, 8, rows).astype("int64"),
                     "v": rng.random(rows)})


def _q(sess, table=None):
    df = sess.create_dataframe(table if table is not None else _table())
    return (df.filter(F.col("v") > 0.25)
            .groupBy("k").agg(F.sum("v").alias("s"),
                              F.count(F.lit(1)).alias("c")))


# ------------------------------------------------------------------ the ring
def test_ring_buffer_bounded_and_windowed():
    t = tracing.Tracer(capacity=16)
    with t.activate():
        for i in range(40):
            t.record(f"s{i}", "exec", i, 1)
        mark = t.mark()
        t.record("tail", "exec", 99, 1)
    assert len(t.since(0)) == 16          # bounded: oldest overwritten
    window = t.since(mark)
    assert [r.name for r in window] == ["tail"]


def test_disabled_mode_records_nothing():
    t = tracing.Tracer(capacity=32)
    assert t.span("x", "exec") is tracing._NULL_SPAN
    with t.span("x", "exec"):
        pass
    t.instant("y", "exec")
    t.record("z", "exec", 0, 1)
    assert t.since(0) == []
    assert not t.on


@pytest.mark.parametrize("site", ["parquet_prefetch", "parquet_serial",
                                  "exchange"])
def test_disabled_mode_records_nothing_at_the_leaf_sites(site, tpch,
                                                         lineitem_dir):
    """Untraced, the scan's host pipeline, the action's blocks, the
    concat, the fetches and the consolidation calls reach the ring with
    nothing: the same queries that leave those spans when traced."""
    assert not tracing.TRACER.on
    mark = tracing.TRACER.mark()
    out = _leaf_site_query(site, tpch, lineitem_dir, trace=False).collect()
    assert out.num_rows >= 1
    assert tracing.TRACER.mark() == mark


def test_span_records_on_exit():
    t = tracing.Tracer(capacity=32)
    with t.activate():
        with t.span("work", "transfer", {"bytes": 10}):
            pass
    (rec,) = t.since(0)
    assert rec.name == "work" and rec.cat == "transfer"
    assert rec.dur_ns >= 0 and rec.args == {"bytes": 10}
    ev = rec.to_event()
    assert ev["ph"] == "X" and ev["cat"] == "transfer"


# ------------------------------------------------------- traced action + EA
def test_explain_analyze_rows_and_wall_sum():
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.trace.enabled": "true"})
    out = _q(sess).collect()
    assert out.num_rows == 8
    text = sess.explain_analyze()
    assert "rows=8" in text                     # aggregate output observed
    assert "rows=4096" in text or "rows=" in text
    assert "wall=" in text and "self=" in text
    # per-node SELF times sum (within driver slack: planning, to_arrow,
    # admission live outside exec spans) to the action wall
    wall_ns = sess.last_action_wall_s * 1e9
    total_self = sum((tracing.observed_of(nd) or {}).get("self_ns", 0)
                    for nd in _iter_execs(sess.last_plan))
    assert 0 < total_self <= wall_ns * 1.1
    assert total_self >= wall_ns * 0.2


def _iter_execs(plan):
    yield plan
    for c in plan.children:
        yield from _iter_execs(c)


def test_untraced_action_renders_tree_without_stats():
    sess = TpuSession(BASE_CONF)
    _q(sess).collect()
    text = sess.explain_analyze()
    assert "TpuHashAggregateExec" in text or "FusedAggregate" in text \
        or "*(" in text
    assert "rows=" not in text


def test_chrome_export_valid_with_layers(tmp_path):
    path = str(tmp_path / "trace.json")
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.trace.enabled": "true",
                       "spark.rapids.tpu.trace.export.path": path,
                       # grace partitioning on: memory-layer spans
                       "spark.rapids.tpu.memory.outOfCore."
                       "forcePartitions": "2"})
    _q(sess).collect()
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert events, "no trace events exported"
    cats = {e["cat"] for e in events}
    # exec spans, transfer uploads, grace partitioning, admission wait
    assert {"exec", "transfer", "memory", "serving"} <= cats, cats
    for e in events:
        assert "name" in e and "ts" in e and e["ph"] in ("X", "i")
    assert doc["otherData"]["action_wall_s"] > 0
    counts = tracing.layer_counts(sess.last_trace)
    # the window is the whole tree now: root, plan and action with the
    # layers under them
    assert all(counts[c] >= 1 for c in
               ("query", "plan", "action", "exec", "program", "transfer",
                "memory", "serving")), counts
    # one root and one action; the action's own blocks share its layer
    assert counts["query"] == 1 and counts["action"] > 1, counts
    assert [r.name for r in sess.last_trace
            if r.name in ("query", "action")] == ["action", "query"]


def _fake_annotations(monkeypatch):
    """Patch the profiler's range class; the names it was given."""
    names = []

    class FakeAnnotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", FakeAnnotation)
    return names


def test_per_exec_profiler_ranges(monkeypatch):
    """TRACE_ENABLED's docstring promise (satellite): named profiler
    ranges PER OPERATOR, not just the one whole-action range."""
    names = _fake_annotations(monkeypatch)
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.trace.enabled": "true"})
    _q(sess).collect()
    per_exec = [n for n in names if "#" in n]
    assert per_exec, f"no per-exec ranges, saw {sorted(set(names))[:10]}"
    # range names are op#plan_id — one per operator, not one per action
    assert any(n.split("#")[0].endswith("Exec") for n in per_exec)


def test_query_handle_analyze_export_and_spans(tmp_path):
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.trace.enabled": "true"})
    handle = sess.submit(_q(sess))
    out = handle.result(timeout=300)
    assert out.num_rows == 8
    text = handle.explain_analyze()
    assert "rows=8" in text and "wall=" in text
    path = str(tmp_path / "query.json")
    n = handle.export_trace(path)
    assert n >= 1
    doc = json.load(open(path))
    qids = {e["args"]["query_id"] for e in doc["traceEvents"]
            if "args" in e and "query_id" in e["args"]}
    assert qids == {handle.query_id}
    # serving lifecycle instants rode the query's spans
    names = {e["name"] for e in doc["traceEvents"]}
    assert any(nm.startswith("serving.state.") for nm in names), names


def test_handle_analyze_requires_tracing():
    sess = TpuSession(BASE_CONF)
    handle = sess.submit(_q(sess))
    handle.result(timeout=300)
    with pytest.raises(RuntimeError, match="trace.enabled"):
        handle.explain_analyze()


# ----------------------------------------------- one span tree per query
TPCH_CONF = {**BASE_CONF, "spark.rapids.tpu.sql.hasNans": "false",
             "spark.rapids.tpu.trace.enabled": "true",
             # several chunks, so an upload has stage, wait and assemble
             "spark.rapids.tpu.transfer.chunkRows": "2048"}
#: every kind a cached program may be named by: the leading strings of the
#: program-cache keys ("-" -> "_") and the evaluator's "project"
KNOWN_KINDS = {
    "project", "filter", "agg", "sort", "stage", "window", "join_size",
    "join_gather", "slice_padded", "exchange", "exchange_enc",
    "exchange_enc_piece", "exchange_sketch", "exchange_fused",
    "exchange_pids", "exchange_keys", "grace_split", "grace_sample",
    "mesh_gather", "dist_agg", "ici_repart", "mproject", "mfilter",
    "mshrink", "mexpand", "mwindow", "mwindow_part", "magg",
    "magg_merge_ag", "magg_merge_part", "magg_part", "mjoin_size",
    "mjoin_gather", "mjoin_lpart", "mjoin_rpart", "msort", "msort_sample",
    "msort_part", "munion", "mexchange", "pconsol", "ree_expand"}
#: ring-only by their nature: the root, and windows whose two ends are only
#: known afterwards (tracing.record with explicit timestamps)
RING_ONLY = {"query", "serving.queue_wait", "serving.preempt_yield",
             "shuffle.fetch"}


@pytest.fixture(scope="module")
def tpch():
    from spark_rapids_tpu.benchmarks.tpch_data import gen_all
    return gen_all(0.002, seed=7)


def _tpch_query(qnum, sess, tables):
    from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
    return QUERIES[qnum]({k: sess.create_dataframe(tables[k])
                          for k in ("lineitem", "orders", "customer")})


@pytest.fixture(scope="module")
def lineitem_dir(tpch, tmp_path_factory):
    """SF0.002 lineitem as one parquet file of three row groups."""
    import pyarrow.parquet as pq
    d = tmp_path_factory.mktemp("lineitem")
    pq.write_table(tpch["lineitem"], str(d / "part-0.parquet"),
                   row_group_size=4096)
    return str(d)


PREFETCH = "spark.rapids.tpu.io.scan.prefetchBatches"


def _leaf_site_query(site, tables, lineitem_dir, trace=True):
    """Q1 over the parquet file with the scan's prefetch thread or
    without, or over ``lineitem.repartition(8, "l_orderkey")`` through the
    interpreted reorder kernel."""
    from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
    conf = {**BASE_CONF, "spark.rapids.tpu.sql.hasNans": "false",
            "spark.rapids.tpu.trace.enabled": "true" if trace else "false"}
    if site == "exchange":
        sess = TpuSession({**conf,
                           "spark.rapids.tpu.shuffle.kernel.mode":
                           "interpret"})
        lineitem = sess.create_dataframe(tables["lineitem"]).repartition(
            8, "l_orderkey")
    else:
        sess = TpuSession({**conf, PREFETCH:
                           "2" if site == "parquet_prefetch" else "0"})
        lineitem = sess.read.parquet(lineitem_dir)
    return QUERIES[1]({"lineitem": lineitem})


def _assert_one_tree(records, query_id=None):
    """``records`` hold exactly one whole tree: one root, every span with
    the root's query id, a parent among them and inside its interval."""
    roots = [r for r in records if r.parent_id is None]
    assert [r.name for r in roots] == ["query"], [
        (r.name, r.parent_id) for r in roots]
    root = roots[0]
    assert root.query_id is not None
    if query_id is not None:
        assert root.query_id == query_id
    by_id = {r.span_id: r for r in records}
    assert len(by_id) == len(records)
    for r in records:
        assert r.query_id == root.query_id, (r.name, r.query_id)
        if r is root:
            continue
        parent = by_id.get(r.parent_id)
        assert parent is not None, f"{r.name}: parent not in the tree"
        assert parent.ts_ns <= r.ts_ns, (r.name, parent.name)
        assert r.ts_ns + r.dur_ns <= parent.ts_ns + parent.dur_ns, (
            r.name, parent.name)
        assert 0 <= r.self_ns <= r.dur_ns
    return root


@pytest.mark.parametrize("qnum", [1, 6, 3])
def test_span_tree_per_query(qnum, tpch, monkeypatch):
    """Embedded collect(): the action's window is one tree under one
    ``query`` root, ids from an ordinal; the ring and the profiler hold
    the same spans under the same names; spans under an exec take its
    plan id; the parts of the tree sum."""
    annotated = _fake_annotations(monkeypatch)
    # a scan cache too small for any table, as SF1 lineitem finds the
    # default: every query uploads again
    sess = TpuSession({**TPCH_CONF,
                       "spark.rapids.tpu.sql.scanCache.maxBytes": "1"})
    first = _tpch_query(qnum, sess, tpch).collect()
    first_id = sess.last_trace[-1].query_id
    del annotated[:]
    out = _tpch_query(qnum, sess, tpch).collect()
    assert out.equals(first)
    records = sess.last_trace
    root = _assert_one_tree(records)
    assert root is records[-1]              # the root closes last
    assert root.query_id != first_id        # a fresh ordinal per action
    names = [r.name for r in records]
    for expected in ("plan", "action", "serving.admission_wait",
                     "scan_cache.not_kept", "transfer.upload", "upload.stage", "upload.wait",
                     "upload.assemble", "download.wait",
                     "download.to_arrow", "result.concat"):
        assert expected in names, (expected, sorted(set(names)))
    assert "transfer.upload_chunk" not in names
    plan = next(r for r in records if r.name == "plan")
    assert plan.args["execs"] >= 3 and plan.args["cpu_execs"] >= 1
    # the ring's names are the profiler's: <name>#<plan_id>, the action
    # under the name benchmark/reduce.py looks for
    ring = {tracing.ACTION_RANGE if r.name == "action"
            else tracing.profiler_name(r.name, r.plan_id)
            for r in records if r.dur_ns and r.name not in RING_ONLY}
    assert ring == set(annotated), ring ^ set(annotated)
    assert all(":" not in n and "#" in n
               for n in annotated if n != tracing.ACTION_RANGE)
    # a span under an exec carries that exec's plan id
    by_id = {r.span_id: r for r in records}
    uploads = [r for r in records if r.name == "transfer.upload"]
    for up in uploads:
        host_to_device = by_id[up.parent_id]
        assert host_to_device.name == "HostToDeviceExec"
        assert up.plan_id == host_to_device.plan_id is not None
    stages = [r for r in records if r.name == "upload.stage"]
    assert {by_id[r.parent_id].name for r in stages} == {"transfer.upload"}
    assert all(r.plan_id == by_id[r.parent_id].plan_id for r in stages)
    assert sum(r.args["rows"] for r in stages) == sum(
        r.args["rows"] for r in uploads)
    programs = [r for r in records if r.cat == "program"]
    assert programs and all(r.name[len("program."):] in KNOWN_KINDS
                            and r.args["first"] is False for r in programs)
    # self time: a span's duration less its live children on its thread
    action = next(r for r in records if r.name == "action")
    kids = [r for r in records if r.parent_id == root.span_id]
    assert root.self_ns == root.dur_ns - sum(k.dur_ns for k in kids)
    assert action.self_ns < action.dur_ns


def test_span_tree_of_a_served_query(tpch):
    """Served: the tree's id is the handle's, its root runs from
    submission (so the queue wait is inside), planning is in it, and two
    queries' trees do not mix."""
    sess = TpuSession(TPCH_CONF)
    handles = [sess.submit(_tpch_query(q, sess, tpch)) for q in (6, 1)]
    for h in handles:
        assert h.result(timeout=300).num_rows >= 1
    for h in handles:
        records = tracing.TRACER.since(0, query_id=h.query_id)
        root = _assert_one_tree(records, h.query_id)
        names = {r.name for r in records}
        assert {"serving.queue_wait", "plan", "action",
                "serving.admission_wait", "result.concat"} <= names, names
        wait = next(r for r in records if r.name == "serving.queue_wait")
        assert wait.parent_id == root.span_id and wait.ts_ns == root.ts_ns
        assert wait.dur_ns == pytest.approx(
            h.metrics["queue_wait_s"] * 1e9, abs=2e3)
        assert root.dur_ns == pytest.approx(h.metrics["wall_s"] * 1e9,
                                            rel=0.05, abs=5e6)


# --------------------------------------------- the leaf spans (PR 36)
SCAN_SPANS = {"scan.read_group", "scan.chunk_decode", "scan.chunk_io",
              "scan.decompress", "scan.arrow_read", "scan.unify",
              "stage.host", "stage.put", "stage.expand"}
#: what each leaf span of the scan hangs under
SCAN_PARENTS = {"scan.read_group": "TpuParquetScanExec",
                "scan.wait": "TpuParquetScanExec",
                "scan.backpressure": "TpuParquetScanExec",
                "scan.chunk_decode": "scan.read_group",
                "scan.arrow_read": "scan.read_group",
                "scan.unify": "scan.read_group",
                "scan.chunk_io": "scan.chunk_decode",
                "scan.decompress": "scan.chunk_decode",
                "stage.host": "upload.stage", "stage.put": "upload.stage",
                "stage.expand": "upload.stage"}


@pytest.mark.parametrize("site", ["parquet_prefetch", "parquet_serial"])
def test_parquet_scan_leaves_its_host_pipeline(site, tpch, lineitem_dir,
                                               monkeypatch):
    """A traced parquet scan: every leaf span of the host pipeline in the
    query's one tree, under its parent and inside its interval, with the
    scan exec's plan id, on the prefetch thread (or, with prefetch off, on
    the query's, and then no ``scan.wait``)."""
    import os
    monkeypatch.setattr(os, "cpu_count", lambda: 4)     # prefetch needs 2
    df = _leaf_site_query(site, tpch, lineitem_dir)
    df.collect()
    records = df.session.last_trace
    root = _assert_one_tree(records)
    by_id = {r.span_id: r for r in records}
    (scan,) = [r for r in records if r.name == "TpuParquetScanExec"]
    leaves = [r for r in records if r.name in SCAN_PARENTS]
    names = {r.name for r in leaves}
    assert SCAN_SPANS <= names, SCAN_SPANS - names
    for r in leaves:
        assert by_id[r.parent_id].name == SCAN_PARENTS[r.name], r.name
        assert r.plan_id == scan.plan_id and r.cat == "transfer"
    work = [r for r in leaves
            if r.name not in ("scan.wait", "scan.backpressure")]
    if site == "parquet_prefetch":
        # the work on the one prefetch thread, the wait on the query's
        assert len({r.tid for r in work}) == 1
        assert work[0].tid != root.tid
        waits = [r for r in leaves if r.name == "scan.wait"]
        assert waits and {r.tid for r in waits} == {root.tid}
    else:
        assert {r.tid for r in work} == {root.tid}
        assert not names & {"scan.wait", "scan.backpressure"}
    groups = [r for r in leaves if r.name == "scan.read_group"]
    assert [g.args["row_group"] for g in groups] == [0, 1, 2]
    assert sum(g.args["rows"] for g in groups) == tpch["lineitem"].num_rows
    decodes = [r for r in leaves if r.name == "scan.chunk_decode"]
    reads = [r for r in leaves if r.name == "scan.arrow_read"]
    encoded = []        # columns a group's batch carries still encoded
    for g in groups:
        assert g.args["columns"] == 7                   # Q1's, pruned
        mine = [d for d in decodes if d.parent_id == g.span_id]
        assert len(mine) == 5                           # the fixed-width
        assert sum(d.dur_ns for d in mine) <= g.dur_ns
        (read,) = [r for r in reads if r.parent_id == g.span_id]
        # a declined chunk goes to pyarrow's read, which names it, beside
        # the strings the page reader never takes
        declined = {d.args["column"] for d in mine
                    if d.args["form"] == "declined"}
        assert "l_extendedprice" in declined            # all but distinct
        assert set(read.args["columns"]) == declined | {"l_returnflag",
                                                        "l_linestatus"}
        assert read.args["rows"] == g.args["rows"] and read.args["bytes"] > 0
        encoded.append(7 - len(declined))
    for d in decodes:
        assert d.args["form"] in ("dict", "ree", "mixed", "declined")
        assert d.args["codec"] == "SNAPPY" and d.args["pages"] >= 2
        assert 0 < d.args["compressed_bytes"]
        assert d.args["decoded_bytes"] in (
            by_id[d.parent_id].args["rows"] * 8,
            by_id[d.parent_id].args["rows"] * 4)
        kids = [r for r in leaves if r.parent_id == d.span_id]
        (io,) = [k for k in kids if k.name == "scan.chunk_io"]
        assert io.args["bytes"] == d.args["compressed_bytes"]
        pages = [k for k in kids if k.name == "scan.decompress"]
        # a declined chunk stops after its prefix: its PLAIN tail, if any,
        # is never opened
        assert len(pages) == d.args["pages_decompressed"] <= d.args["pages"]
        assert d.args["literal_values"] + d.args["rle_values"] > 0
        assert all(k.args["compressed_bytes"] > 0 and k.args["bytes"] > 0
                   for k in pages)
    stages = [r for r in records if r.name == "upload.stage"]
    assert len(stages) == len(groups)
    for st, n_encoded in zip(stages, encoded):
        host, put, expand = sorted(
            (r for r in leaves if r.parent_id == st.span_id),
            key=lambda r: r.ts_ns)
        assert (host.name, put.name, expand.name) == (
            "stage.host", "stage.put", "stage.expand")
        assert host.args["bytes"] > 0
        # the encoded columns decode on the device by eager calls
        assert expand.args["columns"] == n_encoded
        assert expand.args["dispatches"] > expand.args["columns"]
    # three batches reach the aggregate: one concat, under its exec
    (concat,) = [r for r in records if r.name == "batch.concat"]
    assert by_id[concat.parent_id].cat == "exec"
    assert concat.args["batches"] == 3 and concat.args["columns"] == 7
    assert concat.args["rows"] <= tpch["lineitem"].num_rows


def test_stage_expand_counts_its_run_end_columns():
    """An upload with one run-end-encoded column beside a dictionary and a
    plain one: ``stage.expand`` notes the ``ree`` column and its live runs
    (of the slice, not of the whole array), and holds its one
    ``program.ree_expand`` call."""
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    ends = pa.array(np.array([3, 10, 64, 200], np.int32))
    ree = pa.RunEndEncodedArray.from_arrays(
        ends, pa.array([4, 5, 6, 7], pa.int32()))
    rows = 150
    table = pa.table({
        "r": ree.slice(5, rows),          # rows 5..154: runs 2, 3 and 4
        "d": pa.array(np.arange(rows) % 3).dictionary_encode(),
        "p": pa.array(np.arange(rows, dtype=np.int64))})
    mark = tracing.TRACER.mark()
    with tracing.TRACER.activate():
        DeviceBatch.from_arrow(table)
    records = tracing.TRACER.since(mark)
    (expand,) = [r for r in records if r.name == "stage.expand"]
    assert expand.args["columns"] == 2
    assert expand.args["ree_columns"] == 1
    assert expand.args["ree_runs"] == 3
    assert expand.args["dispatches"] > expand.args["columns"]
    calls = [r for r in records if r.parent_id == expand.span_id]
    assert [r.name for r in calls] == ["program.ree_expand"]


def test_one_batch_is_not_concatenated():
    """No ``batch.concat`` span where an aggregate gets a single batch."""
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.trace.enabled": "true"})
    _q(sess).collect()
    assert "batch.concat" not in {r.name for r in sess.last_trace}


#: the action's own blocks and the span each hangs under
ACTION_BLOCKS = {"query.prepare": "query", "query.cleanup": "query",
                 "query.metrics": "query", "query.schema": "query",
                 "action.download_dispatch": "action"}


@pytest.mark.parametrize("entry", ["collect", "served"])
def test_the_actions_own_work_has_names(entry, tpch):
    """Every block of ``_run_partitions`` and ``_collect`` outside the
    execs runs under a child of ``query`` or ``action``, by name; what is
    left to the two as self time is small."""
    sess = TpuSession(TPCH_CONF)
    df = _tpch_query(1, sess, tpch)
    df.collect()                                        # warm
    if entry == "collect":
        df.collect()
        records = sess.last_trace
    else:
        handle = sess.submit(df)
        handle.result(timeout=300)
        records = tracing.TRACER.since(0, query_id=handle.query_id)
    root = _assert_one_tree(records)
    by_id = {r.span_id: r for r in records}
    blocks = [r for r in records if r.name in ACTION_BLOCKS]
    assert {r.name for r in blocks} == set(ACTION_BLOCKS)
    for r in blocks:
        assert by_id[r.parent_id].name == ACTION_BLOCKS[r.name]
        assert r.cat == "action"
    (metrics,) = [r for r in blocks if r.name == "query.metrics"]
    assert metrics.args["execs"] == len(list(_iter_execs(sess.last_plan)))
    action = next(r for r in records if r.name == "action")
    if entry == "collect":
        # the two containers keep what a span's own bookkeeping costs
        assert root.self_ns + action.self_ns < 0.2 * root.dur_ns


def test_scan_cache_latch_is_a_span(monkeypatch):
    """A query latched behind another's upload of the same table records
    the wait; the builder records whether its batch was kept."""
    from spark_rapids_tpu.memory.scan_cache import DeviceScanCache

    class Batch:
        device_size_bytes = 100

    table = pa.table({"a": [1]})
    started, release = threading.Event(), threading.Event()

    def slow_upload():
        started.set()
        assert release.wait(30)
        return Batch()

    t = tracing.Tracer(capacity=64)
    monkeypatch.setattr(tracing, "TRACER", t)
    cache = DeviceScanCache(max_bytes=50)       # over budget: never kept
    got = []
    with t.activate():
        builder = threading.Thread(target=lambda: got.append(
            cache.get_or_put(table, 8, slow_upload)))
        builder.start()
        assert started.wait(30)
        # the waiter's poll while latched is what lets the builder finish,
        # so it has waited by then
        waiter = threading.Thread(target=lambda: got.append(
            cache.get_or_put(table, 8, Batch, cancel_check=release.set)))
        waiter.start()
        builder.join(30)
        waiter.join(30)
    assert len(got) == 2 and not builder.is_alive() and not waiter.is_alive()
    names = [r.name for r in t.since(0)]
    # the waiter waited, found nothing kept, and built again itself
    assert names.count("scan_cache.wait") == 1
    assert names.count("scan_cache.not_kept") == 2
    assert "scan_cache.hit" not in names and "scan_cache.miss" not in names


def test_programs_are_named_by_kind(tpch):
    """After Q1/Q6/Q3 no cached program is called ``fn`` or ``<lambda>``:
    each carries its kind, which the XLA module (``jit_<kind>``) and the
    program spans take."""
    from spark_rapids_tpu.serving.program_cache import global_program_cache
    sess = TpuSession({k: v for k, v in TPCH_CONF.items()
                       if "trace" not in k})
    for qnum in (1, 6, 3):
        _tpch_query(qnum, sess, tpch).collect()
    programs = list(global_program_cache()._programs.values())
    assert len(programs) >= 4
    kinds = {p.kind for p in programs}
    assert not kinds & {"fn", "<lambda>", "f"}, kinds
    assert kinds <= KNOWN_KINDS, kinds - KNOWN_KINDS
    assert {"agg", "join_size", "join_gather", "sort"} <= kinds, kinds
    for p in programs:
        assert p.fn.__name__ == p.kind
        assert p.fn.lower  # still the jitted callable


def test_tracing_off_costs_one_bool_read(monkeypatch):
    """Off: span() is the shared no-op, nothing reaches the ring, and a
    cached program's call reads the tracer's flag once and nothing more."""
    from spark_rapids_tpu.serving import program_cache as pc
    assert not tracing.TRACER.on
    assert tracing.span("x", "exec") is tracing._NULL_SPAN
    assert tracing.adopt(None) is tracing._NULL_SPAN
    mark = tracing.TRACER.mark()
    sess = TpuSession(BASE_CONF)
    _q(sess).collect()
    assert tracing.TRACER.mark() == mark            # nothing recorded

    class CountingTracer:
        reads = 0

        @property
        def on(self):
            CountingTracer.reads += 1
            return False

        def __getattr__(self, name):    # anything else would be a hook
            raise AssertionError(f"tracer.{name} touched with tracing off")

    prog = pc._Program(lambda x: x + 1, pc.ProgramCache(index_path="off"))
    assert prog(1) == 2                             # first call, untimed here
    counting = CountingTracer()
    monkeypatch.setattr(pc._tracing, "TRACER", counting)
    assert prog(2) == 3
    assert CountingTracer.reads == 1


def _leaf_site(site, lineitem_dir):
    """One new span site, driven alone: a callable that passes it once."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.columnar.dtypes import Schema
    from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
    from spark_rapids_tpu.io import parquet as io_parquet
    from spark_rapids_tpu.io.datasource import PartitionedFile
    from spark_rapids_tpu.io.parquet_pages import read_dict_column
    path = lineitem_dir + "/part-0.parquet"
    if site == "chunk_decode":
        pf = pq.ParquetFile(path)
        ci = pf.schema_arrow.names.index("l_quantity")
        return lambda: read_dict_column(path, pf.metadata, 0, ci,
                                        pa.float64(), want_runs=True)
    if site in ("file_tables_dict", "file_tables_plain"):
        schema = Schema.from_pa(pq.read_schema(path))
        return lambda: list(io_parquet._iter_file_tables(
            PartitionedFile(path), schema, Schema([]), (), 1 << 20, 1 << 31,
            device_dict=site == "file_tables_dict", device_rle=True))
    table = _table(64)
    if site == "from_arrow":
        return lambda: DeviceBatch.from_arrow(table)
    halves = [DeviceBatch.from_arrow(table.slice(0, 32)),
              DeviceBatch.from_arrow(table.slice(32))]
    return lambda: concat_device_batches(halves, halves[0].schema)


@pytest.mark.parametrize("site", ["chunk_decode", "file_tables_dict",
                                  "file_tables_plain", "from_arrow",
                                  "concat"])
def test_tracing_off_costs_one_bool_read_at_a_leaf_site(site, lineitem_dir,
                                                        monkeypatch):
    """Each new site, off: one read of the tracer's flag for each span it
    would have opened, no args dict, nothing in the ring."""

    class CountingTracer(tracing.Tracer):
        reads = 0

        @property
        def on(self):
            CountingTracer.reads += 1
            return self._on

        @on.setter
        def on(self, value):
            self._on = value

    call = _leaf_site(site, lineitem_dir)
    traced = tracing.Tracer(capacity=4096)
    monkeypatch.setattr(tracing, "TRACER", traced)
    with traced.activate():
        call()
    spans = traced.since(0)
    assert spans and all(r.args is None or r.args for r in spans)
    counting = CountingTracer(capacity=16)
    monkeypatch.setattr(tracing, "TRACER", counting)
    CountingTracer.reads = 0
    call()
    assert CountingTracer.reads == len(spans)
    assert counting.since(0) == []


def test_ring_counts_what_it_dropped():
    t = tracing.Tracer(capacity=16)
    with t.activate():
        for i in range(16):
            t.record(f"s{i}", "exec", i, 1)
        assert t.dropped == 0
        for i in range(5):
            t.record(f"t{i}", "exec", i, 1)
    assert t.dropped == 5 and t.since(0)[0].seq == 5
    t.configure(32)                 # a resize keeps what is held
    assert t.dropped == 5 and len(t.since(0)) == 16
    t.clear()
    assert t.dropped == 0


def test_producer_threads_adopt_the_spawning_span():
    t = tracing.Tracer(capacity=64)
    seen = {}
    with t.activate():
        with t.span("parent", "exec", plan_id=4, profile=False) as parent:
            spawning = tracing.current()
            assert spawning is parent

            def work():
                with tracing.adopt(spawning):
                    with t.span("child", "transfer", profile=False):
                        pass
                seen["after"] = tracing.current()

            th = threading.Thread(target=work)
            th.start()
            th.join(30)
            assert not th.is_alive()
    child, par = t.since(0)
    assert (child.name, child.parent_id, child.plan_id, child.query_id) == (
        "child", par.span_id, 4, par.query_id)
    assert child.tid != par.tid and seen["after"] is None
    # another thread's child is concurrency, not the parent's own time
    assert par.self_ns == par.dur_ns


# ------------------------------------------------- registry coverage (S4)
def test_every_registry_section_in_last_metrics_and_handle():
    """Every *_METRIC_NAMES registry entry must be present in its
    session.last_metrics section after an action that exercises the
    engine, and in QueryHandle.exec_metrics — the full-tuple contract
    (was only spot-checked per section before)."""
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.memory.outOfCore."
                       "forcePartitions": "2"})
    df = _q(sess)
    df.collect()
    sections = {"transfer": um.TRANSFER_METRIC_NAMES,
                "memory": um.MEMORY_METRIC_NAMES,
                "serving": um.SERVING_METRIC_NAMES}
    for section, name_tuple in sections.items():
        got = sess.last_metrics[section]
        missing = [n for n in name_tuple if n not in got]
        assert not missing, f"last_metrics[{section!r}] missing {missing}"
    handle = sess.submit(df)
    handle.result(timeout=300)
    for section, name_tuple in sections.items():
        got = handle.exec_metrics[section]
        missing = [n for n in name_tuple if n not in got]
        assert not missing, f"exec_metrics[{section!r}] missing {missing}"
    # the action exercised the memory section for real
    assert sess.last_metrics["memory"]["memory.spill_partitions"] >= 2


# ------------------------------------- recursion-depth attribution (S1 fix)
def test_recursion_depth_thread_scoped_attribution():
    """The PR 11 round-2 race: the shared re-armed global misattributed
    depth under CONCURRENT overlap. The fix binds the peak to the action
    scope — two overlapping actions each see exactly their own."""
    results = {}
    barrier = threading.Barrier(2)

    def run(name, depth):
        with um.action_depth_scope() as holder:
            barrier.wait()          # both scopes open concurrently
            if depth:
                um.note_recursion_depth(depth)
            barrier.wait()          # neither scope closed yet
            results[name] = holder.peak

    threads = [threading.Thread(target=run, args=("deep", 3)),
               threading.Thread(target=run, args=("shallow", 0))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == {"deep": 3, "shallow": 0}
    # the global keeps the process-lifetime high-water mark
    assert um.MEMORY_METRICS[um.MEM_RECURSION_DEPTH].value >= 3


def test_recursion_depth_per_query_and_per_action():
    sess = TpuSession({**BASE_CONF,
                       "spark.rapids.tpu.memory.outOfCore."
                       "forcePartitions": "2"})
    handle = sess.submit(_q(sess))
    handle.result(timeout=300)
    assert handle.metrics["recursion_depth_peak"] >= 1
    assert handle.exec_metrics["memory"]["memory.recursion_depth_peak"] >= 1
    # a LATER grace-free action reports 0 even though the process-global
    # lifetime maximum already advanced (per-action scope, not the global)
    clean = TpuSession(BASE_CONF)
    _q(clean).collect()
    assert clean.last_metrics["memory"]["memory.recursion_depth_peak"] == 0
    assert um.MEMORY_METRICS[um.MEM_RECURSION_DEPTH].value >= 1
