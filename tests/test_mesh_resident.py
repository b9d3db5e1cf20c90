"""TPC-H Q3 resident on a mesh of four devices: the mesh tier against the
benchmark's plain reference under the reference's own limits, the scan cache
holding mesh shards beside single-device batches, and what the mesh tier's
spans and counters say it moved. Runs on the virtual CPU devices of
``conftest.py`` at a scale of seconds; the chip's cell is
``tpch_sf1_mesh4.join`` (benchmark/)."""
import threading

import numpy as np
import pyarrow as pa
import pytest

from benchmark import correct
from benchmark.datagen import gen_tables
from benchmark.queries import q3
from benchmark.reference import q3 as q3_reference
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import bucket_capacity
from spark_rapids_tpu.execs import mesh_execs as me
from spark_rapids_tpu.memory import scan_cache
from spark_rapids_tpu.memory.scan_cache import (DeviceScanCache,
                                                charged_bytes)
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.parallel.mesh_batch import gather_mesh, scatter_arrow
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.metrics import TRANSFER_METRICS

N_DEV = 4
#: the confs of benchmark/configs/tpch_sf1_mesh4.json
MESH4 = {
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.tpu.sql.hasNans": "false",
    "spark.rapids.tpu.sql.mesh.enabled": "true",
    "spark.rapids.tpu.sql.mesh.numDevices": str(N_DEV),
}
#: at this scale every table is under the broadcast threshold: 1 byte keeps
#: both joins shuffled. At SF1 only the second is (6 M-row ``lineitem``):
#: ``customer`` pruned to its two columns estimates under the threshold, so
#: the first is a ``MeshBroadcastHashJoinExec`` there
SHUFFLED = {"spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1"}
TRACE = {"spark.rapids.tpu.trace.enabled": "true"}


def _uploaded():
    return TRANSFER_METRICS.snapshot()["transfer.upload_bytes"]


@pytest.fixture(scope="module")
def tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, 2**31 + 28)


@pytest.fixture(scope="module")
def two_q3(tables, eight_devices):
    """Two Q3s of one mesh session, traced: per query its answer, the bytes
    it uploaded and its spans."""
    session = TpuSession({**MESH4, **SHUFFLED, **TRACE})
    dfs = {name: session.createDataFrame(t) for name, t in tables.items()}
    runs = []
    for _ in range(2):
        before = _uploaded()
        answer = q3.build(dfs).collect()
        runs.append({"answer": answer, "uploaded": _uploaded() - before,
                     "spans": list(session.last_trace),
                     "plan": session.last_plan.tree_string()})
    return runs


def _spans(run, name):
    return [r for r in run["spans"] if r.name == name]


def test_q3_on_the_mesh_is_the_references_answer(two_q3, tables):
    ref = q3_reference.answer(tables)
    assert ref.num_rows == 10
    for run in two_q3:
        for exec_name in ("MeshShuffledHashJoinExec", "MeshHashAggregateExec"):
            assert exec_name in run["plan"], run["plan"]
        miss, gap = correct.compare(run["answer"], ref, q3_reference.EXACT)
        assert miss == 0
        assert gap <= q3_reference.REL_GAP_LIMIT


def test_the_float32_control_fails_the_limit(tables):
    miss, gap = correct.control_gaps(tables, ["q3"])["q3"]
    assert miss > 0 or gap > q3_reference.REL_GAP_LIMIT


def test_the_first_q3_uploads_the_tables_and_the_second_nothing(two_q3,
                                                                tables):
    first, second = two_q3
    built = [s for s in _spans(first, "mesh.scatter")
             if s.args["cached"] == "built"]
    assert sorted(s.args["rows"] for s in built) == sorted(
        t.num_rows for t in tables.values())
    assert first["uploaded"] == sum(s.args["bytes"] for s in built) > 0
    assert all(s.args["shards"] == N_DEV for s in built)
    # every upload is counted and recorded as a single-device one is
    uploads = _spans(first, "transfer.upload")
    assert len(uploads) == 3
    assert sum(s.args["bytes"] for s in uploads) == first["uploaded"]
    assert len(_spans(first, "upload.stage")) == sum(
        t.num_columns for t in tables.values())
    assert _spans(first, "upload.wait")

    assert [s.args["cached"] for s in _spans(second, "mesh.scatter")] == [
        "hit"] * 3
    assert second["uploaded"] == 0
    for name in ("transfer.upload", "upload.stage", "upload.wait"):
        assert not _spans(second, name)
    assert len(_spans(second, "scan_cache.hit")) == 3


def test_every_exchange_of_q3_says_what_it_moved(two_q3):
    second = two_q3[1]
    exchanges = _spans(second, "mesh.exchange")
    # both sides of both joins, and the sort's range repartition
    assert sorted(s.args["op"] for s in exchanges) == [
        "mjoin_lpart", "mjoin_lpart", "mjoin_rpart", "mjoin_rpart",
        "msort_part"]
    for s in exchanges:
        a = s.args
        assert 0 < a["moved_rows"] <= a["rows"]
        assert a["bytes"] <= a["wire_bytes"]
        assert a["max_shard_bytes"] <= a["bytes"] <= N_DEV * a["max_shard_bytes"]
        assert a["recv_min"] <= a["recv_max"] <= a["out_cap"]
        kids = [r for r in second["spans"] if r.parent_id == s.span_id]
        assert sorted(k.name for k in kids if k.name.startswith("mesh.")) == [
            "mesh.exchange.count", "mesh.exchange.move"]
        assert all(r.cat == tracing.LAYER_SHUFFLE for r in kids + [s]
                   if r.name.startswith("mesh."))
    (gather,) = _spans(second, "mesh.gather")
    assert gather.args["rows"] == 10 and gather.args["shards"] == N_DEV


def test_q3_moves_pruned_rows_and_its_projections_call_no_program(two_q3):
    second = two_q3[1]
    # a row's bytes on the wire: 8 + 1 a long or a double, 4 + 1 a date or
    # an int. customer: its key; orders: two keys, date, priority; their
    # join: what the aggregate and the next join read of it; lineitem: key,
    # price, discount; the sort: the answer's four columns
    widths = {}
    for s in _spans(second, "mesh.exchange"):
        assert s.args["bytes"] % s.args["moved_rows"] == 0
        widths.setdefault(s.args["op"], []).append(
            s.args["bytes"] // s.args["moved_rows"])
    assert {op: sorted(w) for op, w in widths.items()} == {
        "mjoin_lpart": [9, 19], "mjoin_rpart": [27, 28], "msort_part": [28]}
    # the pass's projections, and the select that reorders the answer, are
    # plain references: the shards' columns are selected, nothing is called
    assert second["plan"].count("MeshProjectExec") >= 6
    assert not _spans(second, "program.mproject")
    assert _spans(second, "program.mfilter")


def test_nothing_of_the_mesh_is_recorded_with_tracing_off(tables,
                                                          eight_devices):
    session = TpuSession({**MESH4, **SHUFFLED})
    mark = tracing.TRACER.mark()
    dfs = {name: session.createDataFrame(t) for name, t in tables.items()}
    q3.build(dfs).collect()
    assert tracing.TRACER.since(mark) == []


def test_a_broadcast_join_records_its_replication(eight_devices):
    rng = np.random.default_rng(28)
    fact = pa.table({"k": rng.integers(0, 50, 4000).astype(np.int64),
                     "v": rng.random(4000)})
    dim = pa.table({"k": np.arange(50, dtype=np.int64),
                    "w": np.arange(50, dtype=np.int64) % 5})
    session = TpuSession({**MESH4, **TRACE})
    out = (session.createDataFrame(fact).join(session.createDataFrame(dim), "k")
           .groupBy("w").agg(F.count("k").alias("c")).collect())
    assert sum(out.column("c").to_pylist()) == 4000
    assert "MeshBroadcastHashJoinExec" in session.last_plan.tree_string()
    (span,) = [r for r in session.last_trace if r.name == "mesh.replicate"]
    assert span.args["rows"] == 50 and span.args["shards"] == N_DEV
    assert span.cat == tracing.LAYER_SHUFFLE


# ------------------------------------------------------------ the scan cache
def _small_table(rows=1000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 1 << 40, rows).astype(np.int64),
                     "v": rng.random(rows)})


@pytest.fixture
def mesh(eight_devices):
    return make_mesh(N_DEV, devices=eight_devices[:N_DEV])


def test_a_mesh_entry_is_charged_its_bytes_over_the_devices(mesh):
    table = _small_table()
    mb = scatter_arrow(table, mesh, 64)
    db = DeviceBatch.from_arrow(table, 64)
    assert mb.device_size_bytes == N_DEV * mb.local_capacity * mb.row_bytes
    assert charged_bytes(mb) == mb.device_size_bytes // N_DEV
    assert charged_bytes(db) == db.device_size_bytes
    cache = DeviceScanCache(charged_bytes(mb) + charged_bytes(db))
    assert cache.put(table, 64, db)
    assert cache.put(table, 64, mb, mesh)
    # side by side: neither evicted nor served in the other's place
    assert cache.get(table, 64) is db
    assert cache.get(table, 64, mesh) is mb
    assert cache.get(table, 64, make_mesh(2, devices=list(
        mesh.devices.flat)[:2])) is None
    assert cache.total_bytes() == charged_bytes(mb) + charged_bytes(db)
    # what the device store does with the cache takes both kinds
    assert cache.shrink_by(1) == charged_bytes(db)
    assert cache.get(table, 64) is None and cache.get(table, 64, mesh) is mb
    cache.clear()
    assert cache.total_bytes() == 0 and cache.get(table, 64, mesh) is None


def test_a_mesh_session_and_a_single_device_one_share_no_entry(eight_devices):
    table = _small_table(seed=1)
    on_mesh, single = TpuSession(MESH4), TpuSession({})
    want = int(np.sum(table.column("k").to_numpy() % 2 == 0))
    for session in (on_mesh, single, on_mesh, single):
        before = _uploaded()
        df = session.createDataFrame(table).filter(F.col("k") % 2 == 0)
        assert df.collect().num_rows == want
        uploaded = _uploaded() - before
    assert uploaded == 0   # the last two were hits, each of its own entry
    cache = scan_cache.peek_cache()
    smax = single.conf.string_max_bytes
    db = cache.get(table, smax)
    mb = cache.get(table, smax, make_mesh(N_DEV, devices=eight_devices[:N_DEV]))
    assert isinstance(db, DeviceBatch) and mb.n_dev == N_DEV
    charges = sorted(nbytes for (ident, _, _), (_, _, nbytes)
                     in cache._entries.items() if ident == id(table))
    assert charges == sorted([db.device_size_bytes,
                              mb.device_size_bytes // N_DEV])


def test_an_entry_over_the_budget_is_not_kept_and_its_waiter_is_served(mesh):
    table = _small_table(seed=2)
    cache = DeviceScanCache(1024)    # one shard of the table is over it
    building, waiting, release = (threading.Event(), threading.Event(),
                                  threading.Event())
    builds, served = [], []

    def build():
        builds.append(threading.get_ident())
        building.set()
        assert release.wait(30)
        return scatter_arrow(table, mesh, 64)

    def query(cancel_check=None):
        served.append(cache.get_or_put(table, 64, build, cancel_check,
                                       mesh=mesh))

    with tracing.TRACER.activate():
        mark = tracing.TRACER.mark()
        first = threading.Thread(target=query)
        first.start()
        assert building.wait(30)
        # the second finds the latch taken; its cancel check runs while it
        # is blocked there
        waiter = threading.Thread(target=query, args=(waiting.set,))
        waiter.start()
        assert waiting.wait(30)
        release.set()
        first.join(30)
        waiter.join(30)
        names = [r.name for r in tracing.TRACER.since(mark)]
    assert len(served) == 2 and all(mb.num_rows == 1000 for mb in served)
    assert charged_bytes(served[0]) > cache.max_bytes
    # not kept: the waiter built its own, and nothing stays latched or held
    assert len(builds) == 2 and len(set(builds)) == 2
    assert names.count("scan_cache.not_kept") == 2
    assert "scan_cache.wait" in names
    assert "scan_cache.miss" not in names
    assert cache.total_bytes() == 0 and not cache._inflight


def test_clear_under_a_live_mesh_entry(mesh):
    table = _small_table(seed=3)
    cache = DeviceScanCache(1 << 30)
    builds = []

    def build():
        builds.append(1)
        return scatter_arrow(table, mesh, 64)

    held = cache.get_or_put(table, 64, build, mesh=mesh)
    assert cache.total_bytes() == charged_bytes(held)
    cache.clear()                     # the OOM recovery path's call
    assert cache.total_bytes() == 0
    # the query that holds the batch goes on reading it
    assert gather_mesh(held).to_arrow().equals(table)
    again = cache.get_or_put(table, 64, build, mesh=mesh)
    assert len(builds) == 2 and again is not held
    assert cache.get_or_put(table, 64, build, mesh=mesh) is again


# ------------------------------------------------------------- the exchange
def test_an_exchange_counts_the_rows_that_change_shard(eight_devices):
    """A hand-made batch through a hash repartition: ``moved_rows`` are the
    rows whose hash shard differs from the shard that held them."""
    from spark_rapids_tpu.columnar.dtypes import DType
    from spark_rapids_tpu.execs.exchange_execs import hash_partition_ids
    from spark_rapids_tpu.exprs.core import ColV
    n = 1000
    keys = (np.arange(n, dtype=np.int64) * 7919) % 1013
    table = pa.table({"k": keys, "v": np.arange(n, dtype=np.float64)})
    session = TpuSession({**MESH4, **TRACE})
    me.EXCHANGE_STATS.clear()
    out = session.createDataFrame(table).repartition(N_DEV, "k").collect()
    assert sorted(out.column("v").to_pylist()) == list(range(n))
    (span,) = [r for r in session.last_trace if r.name == "mesh.exchange"]
    assert span.args == me.EXCHANGE_STATS[-1]

    dest = hash_partition_ids(
        np, [ColV(DType.LONG, keys, np.ones(n, dtype=bool))], n, N_DEV)
    held = np.arange(n) // (n // N_DEV)       # rows split contiguously
    cmat = np.zeros((N_DEV, N_DEV), dtype=np.int64)
    np.add.at(cmat, (held, dest), 1)
    moved = int(np.sum(dest != held))
    row_bytes = (8 + 1) + (8 + 1)
    a = span.args
    assert a["rows"] == n and a["moved_rows"] == moved > 0
    assert a["bytes"] == moved * row_bytes
    off = cmat - np.diag(np.diagonal(cmat))
    assert a["max_shard_bytes"] == row_bytes * max(off.sum(axis=1).max(),
                                                   off.sum(axis=0).max())
    assert a["chunk_cap"] >= cmat.max()
    assert a["wire_bytes"] == N_DEV * (N_DEV - 1) * a["chunk_cap"] * row_bytes
    assert a["bytes"] <= a["wire_bytes"]
    assert a["recv_max"] == cmat.sum(axis=0).max()
    assert a["recv_min"] == cmat.sum(axis=0).min()


def test_exchange_stats_of_a_matrix_by_hand():
    cmat = np.array([[5, 1, 0, 0],
                     [0, 4, 2, 0],
                     [0, 0, 3, 0],
                     [7, 0, 0, 1]], dtype=np.int32)
    st = me.exchange_stats("op", cmat, 10, 16)
    assert st["rows"] == 23 and st["moved_rows"] == 10
    assert st["bytes"] == 100
    assert st["chunk_cap"] == bucket_capacity(7) >= 7
    assert st["wire_bytes"] == 4 * 3 * bucket_capacity(7) * 10
    assert st["max_shard_bytes"] == 70          # shard 3 sends, shard 0 takes
    assert (st["recv_max"], st["recv_min"]) == (12, 1)
    assert st["out_cap"] >= 12 and st["in_cap"] == 16
    still = me.exchange_stats("op", np.diag([3, 3, 3, 3]), 10, 16)
    assert still["moved_rows"] == still["bytes"] == still["max_shard_bytes"] == 0
