"""Transfer pipeline: chunked overlapped uploads, PipelinedExec bounded-async
dispatch, streaming collect, and the prefetch-producer lifecycle fixes."""
import gc
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar import transfer
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs.base import ExecContext, LeafExec
from spark_rapids_tpu.execs.pipeline import PipelinedExec
from spark_rapids_tpu.testing import assert_tables_equal
from spark_rapids_tpu.utils import metrics as um


def _mixed_table(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i": pa.array(rng.integers(0, 1000, n), pa.int64()),
        "d": pa.array(rng.random(n) * 1e9, pa.float64()),
        "s": pa.array([f"v{v}" for v in rng.integers(0, 50, n)],
                      pa.string()).dictionary_encode(),
        "nn": pa.array([None if v % 7 == 0 else int(v)
                        for v in rng.integers(0, 100, n)], pa.int32()),
        "b": pa.array([bool(v % 2) for v in range(n)]),
    })


def _assert_batches_bit_equal(single: DeviceBatch, chunked: DeviceBatch):
    """Live rows bit-exact; padding past num_rows is garbage by contract
    (columnar/column.py) so only validity/bits — which both paths zero-pad —
    compare across the full capacity."""
    assert chunked.num_rows == single.num_rows
    assert chunked.capacity == single.capacity
    n = single.num_rows
    for ci, (a, b) in enumerate(zip(single.columns, chunked.columns)):
        assert np.array_equal(np.asarray(a.data[:n]), np.asarray(b.data[:n])), ci
        assert np.array_equal(np.asarray(a.validity), np.asarray(b.validity)), ci
        if a.lengths is not None:
            assert np.array_equal(np.asarray(a.lengths[:n]),
                                  np.asarray(b.lengths[:n])), ci
        assert (a.bits is None) == (b.bits is None), ci
        if a.bits is not None:
            assert np.array_equal(np.asarray(a.bits), np.asarray(b.bits)), ci


# --------------------------------------------------------------- chunk bounds
def test_chunk_bounds_splits_oversized():
    t = pa.table({"a": np.arange(10_000)})
    bounds = transfer.chunk_bounds(t, 3000)
    assert bounds[0] == 0
    sizes = [b - a for a, b in zip(bounds, bounds[1:] + [10_000])]
    assert all(s <= 3000 for s in sizes)
    assert sum(sizes) == 10_000


def test_chunk_bounds_single_chunk():
    t = pa.table({"a": np.arange(100)})
    assert transfer.chunk_bounds(t, 0) == [0]
    assert transfer.chunk_bounds(t, 100) == [0]
    assert transfer.chunk_bounds(t, 1000) == [0]


def test_chunk_bounds_prefers_record_batch_edges():
    parts = [pa.record_batch([pa.array(np.arange(900))], names=["a"])
             for _ in range(4)]
    t = pa.Table.from_batches(parts)
    bounds = transfer.chunk_bounds(t, 1000)
    # record-batch edges (multiples of 900) are taken instead of raw 1000s
    assert bounds == [0, 900, 1800, 2700]


# ------------------------------------------------------- chunked upload
def test_chunked_upload_bit_equal_mixed_schema():
    t = _mixed_table()
    single = DeviceBatch.from_arrow(t, 16)
    chunked = transfer.upload_table(t, 16, chunk_rows=700, max_inflight=2)
    _assert_batches_bit_equal(single, chunked)
    assert single.to_arrow().equals(chunked.to_arrow())


def test_chunked_upload_double_bits_sibling_carried():
    t = pa.table({"d": pa.array(np.random.default_rng(1).random(3000) * 1e18)})
    single = DeviceBatch.from_arrow(t, 16)
    chunked = transfer.upload_table(t, 16, chunk_rows=500)
    assert chunked.columns[0].bits is not None
    _assert_batches_bit_equal(single, chunked)


def test_chunked_upload_all_null_and_empty_chunks():
    t = pa.table({"x": pa.array([None] * 1000, pa.int32()),
                  "y": pa.array(["s"] * 1000, pa.string())})
    single = DeviceBatch.from_arrow(t, 16)
    chunked = transfer.upload_table(t, 16, chunk_rows=130)
    _assert_batches_bit_equal(single, chunked)


def test_upload_small_table_takes_single_shot_path():
    t = _mixed_table(64)
    before = um.transfer_snapshot()
    b = transfer.upload_table(t, 16, chunk_rows=1000)
    assert um.transfer_delta(before)[um.TRANSFER_UPLOAD_CHUNKS] == 1
    _assert_batches_bit_equal(DeviceBatch.from_arrow(t, 16), b)


def test_upload_counts_transfer_metrics():
    before = um.transfer_snapshot()
    transfer.upload_table(_mixed_table(2000), 16, chunk_rows=300)
    delta = um.transfer_delta(before)
    assert delta[um.TRANSFER_UPLOAD_BYTES] > 0
    assert delta[um.TRANSFER_UPLOAD_SECONDS] > 0
    assert delta[um.TRANSFER_UPLOAD_CHUNKS] >= 5
    assert "transfer.upload_gb_per_sec" in delta


def test_stats_overlap_efficiency_bounds():
    t = _mixed_table(3000)
    before = um.transfer_snapshot()
    transfer.upload_table(t, 16, chunk_rows=400, max_inflight=3)
    delta = um.transfer_delta(before)
    assert 1 <= delta[um.TRANSFER_INFLIGHT_PEAK] <= 3
    assert delta[um.TRANSFER_UPLOAD_CHUNKS] == len(
        transfer.chunk_bounds(t, 400))
    assert delta[um.TRANSFER_UPLOAD_SECONDS] > 0


# ------------------------------------------------------- concat bits handling
def test_concat_device_batches_carries_bits():
    from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
    t1 = pa.table({"d": pa.array([1.5, 2.5, 3.5])})
    t2 = pa.table({"d": pa.array([4.5, 5.5])})
    b1 = DeviceBatch.from_arrow(t1, 16)
    b2 = DeviceBatch.from_arrow(t2, 16)
    out = concat_device_batches([b1, b2], b1.schema, 16)
    assert out.columns[0].bits is not None
    expect = np.array([1.5, 2.5, 3.5, 4.5, 5.5]).view(np.uint64)
    assert np.array_equal(np.asarray(out.columns[0].bits[:5]), expect)


def test_concat_device_batches_drops_partial_bits():
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
    b1 = DeviceBatch.from_arrow(pa.table({"d": pa.array([1.5, 2.5])}), 16)
    c = b1.columns[0]
    no_bits = DeviceBatch(b1.schema,
                          (DeviceColumn(c.dtype, c.data, c.validity),), 2)
    out = concat_device_batches([b1, no_bits], b1.schema, 16)
    assert out.columns[0].bits is None


# ------------------------------------------------------------- PipelinedExec
class _ListSource(LeafExec):
    """Device-batch source with optional injected fault at batch ``fail_at``
    and a cleanup flag so early-exit tests can assert the generator's
    finally ran."""

    is_device = True
    is_file_scan = True

    def __init__(self, batches, fail_at=None):
        super().__init__(batches[0].schema if batches else Schema([]))
        self.batches = batches
        self.fail_at = fail_at
        self.closed = False
        self.produced = 0

    def execute(self, ctx):
        try:
            for i, b in enumerate(self.batches):
                if self.fail_at is not None and i == self.fail_at:
                    raise RuntimeError(f"injected fault at batch {i}")
                self.produced += 1
                yield b
        finally:
            self.closed = True


def _batches(k, rows=8):
    return [DeviceBatch.from_arrow(
        pa.table({"v": pa.array(np.full(rows, i, np.int64))}), 16)
        for i in range(k)]


def test_pipelined_exec_preserves_order():
    src = _ListSource(_batches(12))
    pipe = PipelinedExec(src, depth=3)
    out = list(pipe.execute(ExecContext(TpuConf())))
    vals = [int(np.asarray(b.columns[0].data)[0]) for b in out]
    assert vals == list(range(12))
    assert src.closed


def test_pipelined_exec_propagates_injected_fault_in_order():
    src = _ListSource(_batches(10), fail_at=4)
    pipe = PipelinedExec(src, depth=2)
    got = []
    with pytest.raises(RuntimeError, match="injected fault at batch 4"):
        for b in pipe.execute(ExecContext(TpuConf())):
            got.append(int(np.asarray(b.columns[0].data)[0]))
    assert got == [0, 1, 2, 3]      # everything before the fault, in order
    assert src.closed


def test_pipelined_exec_early_close_stops_producer():
    src = _ListSource(_batches(50))
    pipe = PipelinedExec(src, depth=2)
    it = pipe.execute(ExecContext(TpuConf()))
    next(it)
    it.close()
    deadline = time.time() + 5
    while time.time() < deadline and not src.closed:
        time.sleep(0.01)
    assert src.closed
    # bounded: the producer never ran ahead by more than depth + handoff
    assert src.produced <= 2 + 2 + 1
    assert not [t for t in threading.enumerate()
                if t.name == "exec-pipeline" and t.is_alive()]


def test_pipelined_exec_depth_zero_passthrough():
    src = _ListSource(_batches(3))
    out = list(PipelinedExec(src, depth=0).execute(ExecContext(TpuConf())))
    assert len(out) == 3


def test_pipelined_exec_shares_semaphore_hold():
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    dm = DeviceManager.initialize()
    src = _ListSource(_batches(6))
    pipe = PipelinedExec(src, depth=2)
    ctx = ExecContext(TpuConf(), device_manager=dm)
    with dm.semaphore.held():
        assert dm.semaphore.active_holders == 1
        out = list(pipe.execute(ctx))
        assert len(out) == 6
        # producer nested into THIS task's hold: still one holder
        assert dm.semaphore.active_holders == 1
    assert dm.semaphore.active_holders == 0


class _PassThrough(LeafExec):
    """Device op with a pipelined child (device->host->device sandwich
    shape): nests pipeline boundaries like real plans do."""

    is_device = True

    def __init__(self, child):
        super().__init__(child.output)
        self.children = (child,)

    def execute(self, ctx):
        yield from self.children[0].execute(ctx)


def test_nested_pipelines_share_one_semaphore_permit():
    """Three nested pipeline boundaries under a 2-permit semaphore: every
    producer must fold into the OWNING TASK's hold (ctx.task_id), or the
    inner producers exhaust admission and the plan deadlocks."""
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    dm = DeviceManager.initialize()
    plan = PipelinedExec(_PassThrough(PipelinedExec(_PassThrough(
        PipelinedExec(_ListSource(_batches(5)), 2)), 2)), 2)
    done = {}

    def run():
        # the task thread builds its own ctx (as _run_partitions does), so
        # ctx.task_id is the thread that takes the semaphore hold
        ctx = ExecContext(TpuConf(), device_manager=dm)
        with dm.semaphore.held():
            done["out"] = list(plan.execute(ctx))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(20)
    assert not t.is_alive(), "nested pipelines deadlocked on the semaphore"
    assert len(done["out"]) == 5
    assert dm.semaphore.active_holders == 0


# ------------------------------------------------------- planner insertion
def _count_pipelined(plan):
    hits = 1 if isinstance(plan, PipelinedExec) else 0
    return hits + sum(_count_pipelined(c) for c in plan.children)


def test_planner_inserts_pipeline_over_scan(monkeypatch, tmp_path):
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": np.arange(1000, dtype=np.int64)}), path)
    sess = TpuSession()
    df = sess.read.parquet(path).filter(F.col("a") > 10)
    df.collect()
    assert _count_pipelined(sess.last_plan) == 1
    off = TpuSession({"spark.rapids.tpu.transfer.pipeline.enabled": "false"})
    df2 = off.read.parquet(path).filter(F.col("a") > 10)
    df2.collect()
    assert _count_pipelined(off.last_plan) == 0


def test_planner_skips_pipeline_on_single_core(monkeypatch, tmp_path):
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": np.arange(100, dtype=np.int64)}), path)
    sess = TpuSession()
    df = sess.read.parquet(path).filter(F.col("a") > 10)
    df.collect()
    assert _count_pipelined(sess.last_plan) == 0


# ------------------------------------------------------- parquet prefetch
def _write_grouped(tmp_path, rows=5000, groups=10):
    path = str(tmp_path / "g.parquet")
    pq.write_table(pa.table({
        "a": np.arange(rows, dtype=np.int64),
        "d": np.linspace(0.0, 1.0, rows),
    }), path, row_group_size=rows // groups)
    return path


def test_early_exit_limit_over_prefetched_scan(monkeypatch, tmp_path):
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    path = _write_grouped(tmp_path)
    sess = TpuSession({"spark.rapids.tpu.io.scan.prefetchBatches": "2",
                       "spark.rapids.tpu.sql.reader.batchSizeRows": "500"})
    out = sess.read.parquet(path).limit(7).collect()
    assert out.num_rows == 7
    gc.collect()
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t.name == "parquet-scan-prefetch" and t.is_alive()]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, "prefetch producer thread leaked after early exit"


def test_prefetched_scan_error_propagates(monkeypatch, tmp_path):
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    path = _write_grouped(tmp_path, rows=1000, groups=2)
    sess = TpuSession({"spark.rapids.tpu.io.scan.prefetchBatches": "2"})
    df = sess.read.parquet(path)
    os.remove(path)     # fault: file disappears between plan and execute
    from spark_rapids_tpu.io.parquet import _clipped_groups_cached
    _clipped_groups_cached.cache_clear()
    with pytest.raises(Exception):
        df.collect()


def test_prefetch_device_propagation(monkeypatch, tmp_path, eight_devices):
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    import jax
    target = jax.devices()[1]
    path = _write_grouped(tmp_path, rows=600, groups=2)
    from spark_rapids_tpu.io.datasource import PartitionedFile
    from spark_rapids_tpu.io.parquet import TpuParquetScanExec
    schema = Schema.from_pa(pq.read_schema(path))
    scan = TpuParquetScanExec((PartitionedFile(path),), schema)
    ctx = ExecContext(TpuConf({
        "spark.rapids.tpu.io.scan.prefetchBatches": "2"}), device=target)
    batches = list(scan.execute(ctx))
    assert batches
    for b in batches:
        assert next(iter(b.columns[0].data.devices())) == target


# ------------------------------------------------------- streaming collect
def _q1ish(df):
    return (df.filter(F.col("i") > 100)
              .groupBy("s").agg(F.min("nn").alias("mn"),
                                F.max("d").alias("mx"),
                                F.count(F.lit(1)).alias("c"))
              .sort("s"))


def test_streaming_collect_matches_sync_collect():
    t = _mixed_table(3000, seed=3)
    res = {}
    for mode in ("true", "false"):
        sess = TpuSession({
            "spark.rapids.tpu.transfer.streamingCollect.enabled": mode,
            "spark.rapids.tpu.sql.scanCache.enabled": "false",
            "spark.rapids.tpu.transfer.chunkRows": "700"})
        res[mode] = _q1ish(sess.create_dataframe(t)).collect()
    assert_tables_equal(res["true"], res["false"])


def test_streaming_collect_many_batches_order(tmp_path):
    path = _write_grouped(tmp_path, rows=4000, groups=8)
    sess = TpuSession({"spark.rapids.tpu.sql.reader.batchSizeRows": "500",
                       "spark.rapids.tpu.transfer.maxInflight": "2"})
    out = sess.read.parquet(path).collect()
    assert np.array_equal(np.asarray(out.column("a")),
                          np.arange(4000, dtype=np.int64))
    tm = sess.last_metrics.get("transfer", {})
    assert tm.get(um.TRANSFER_DOWNLOAD_BYTES, 0) > 0


def test_streaming_collect_empty_result():
    sess = TpuSession()
    t = pa.table({"a": pa.array([1, 2, 3], pa.int64())})
    out = sess.create_dataframe(t).filter(F.col("a") > 99).collect()
    assert out.num_rows == 0
