"""Fused partition-reorder kernel (shuffle/partition_kernel.py): pack ->
Pallas kernel -> consolidate, in interpreter mode on the CPU backend (on
the chip chip_smoke.py runs the compiled kernels). The reorder must move
every live row to exactly one partition piece bit-exactly; intra-partition
ORDER is not promised (shuffle semantics)."""
import datetime

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.shuffle import partition_kernel as pk


def _table(n, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "l": pa.array(rng.integers(-2**62, 2**62, n), type=pa.int64()),
        "i": pa.array(rng.integers(-2**31, 2**31 - 1, n), type=pa.int32()),
        "d": pa.array(np.round(rng.standard_normal(n) * 1e6, 2)),
        "s": pa.array([f"s{int(x)}" for x in rng.integers(0, 1000, n)]),
        "b": pa.array(rng.random(n) < 0.5),
        "dt": pa.array([datetime.date(2020, 1, 1)
                        + datetime.timedelta(days=int(x))
                        for x in rng.integers(0, 1000, n)],
                       type=pa.date32()),
        "ts": pa.array(rng.integers(0, 2**45, n), type=pa.timestamp("us")),
    })


def _with_nulls(t, seed=1):
    rng = np.random.default_rng(seed)
    cols = []
    for name in t.column_names:
        arr = t.column(name).combine_chunks()
        mask = rng.random(len(arr)) < 0.1
        cols.append(pa.array(arr.to_pylist(), type=arr.type,
                             mask=mask))
    return pa.table(dict(zip(t.column_names, cols)))


def _run(table, n_parts, seed=3):
    import jax.numpy as jnp
    batch = DeviceBatch.from_arrow(table, string_max_bytes=16)
    rng = np.random.default_rng(seed)
    pids_np = rng.integers(0, n_parts, batch.capacity).astype(np.int32)
    res = pk.split_batch_kernel(batch, jnp.asarray(pids_np), n_parts,
                                interpret=True)
    assert res is not None, "fast path unexpectedly refused the batch"
    out, stats, spec, geom = res
    pieces = {}
    for j in range(n_parts):
        sub = pk.consolidate(out, stats, j, spec, batch.schema, geom)
        if sub is not None:
            pieces[j] = sub.to_arrow()
    return batch, pids_np, pieces


def _rows_key(t):
    """Order-independent multiset of row tuples (timestamps normalized —
    the engine returns UTC-aware values, Spark's UTC-only semantics)."""
    def norm(v):
        return v.replace(tzinfo=None) if isinstance(v, datetime.datetime) \
            else v
    cols = [[norm(v) for v in t.column(i).to_pylist()]
            for i in range(t.num_columns)]
    return sorted(zip(*cols), key=repr)


@pytest.mark.parametrize("n_parts", [2, 4, 8])
def test_kernel_reorder_matches_reference(n_parts):
    table = _table(700)
    batch, pids, pieces = _run(table, n_parts)
    live_pids = pids[:table.num_rows]
    for j in range(n_parts):
        want = table.filter(pa.array(live_pids == j))
        got = pieces.get(j)
        if want.num_rows == 0:
            assert got is None or got.num_rows == 0
            continue
        assert got is not None and got.num_rows == want.num_rows, (
            f"partition {j}: {got and got.num_rows} != {want.num_rows}")
        assert _rows_key(got) == _rows_key(want), f"partition {j} differs"


def test_kernel_reorder_with_nulls():
    table = _with_nulls(_table(500, seed=7), seed=8)
    batch, pids, pieces = _run(table, 4, seed=9)
    live = pids[:table.num_rows]
    total = sum(p.num_rows for p in pieces.values())
    assert total == table.num_rows
    for j in range(4):
        want = table.filter(pa.array(live == j))
        if want.num_rows:
            assert _rows_key(pieces[j]) == _rows_key(want)


def test_kernel_refuses_wide_fanout():
    import jax.numpy as jnp
    batch = DeviceBatch.from_arrow(_table(100), string_max_bytes=16)
    pids = jnp.zeros(batch.capacity, jnp.int32)
    assert pk.split_batch_kernel(batch, pids, pk.MAX_PARTS + 1,
                                 interpret=True) is None


def test_kernel_overflow_falls_back():
    """Every row in one partition: the per-window segment bound (2x the
    even share) must overflow and return None (caller uses the sort path)."""
    import jax.numpy as jnp
    table = _table(600)
    batch = DeviceBatch.from_arrow(table, string_max_bytes=16)
    pids = jnp.zeros(batch.capacity, jnp.int32)   # all -> partition 0
    assert pk.split_batch_kernel(batch, pids, 8, interpret=True) is None


def test_kernel_widens_window_bound_for_clustered_keys():
    """Runs of 200 equal pids put 200 rows of a 512-row window into one
    partition — over the 128-row segment bound of an 8-way split, with every
    group total well inside its quota. The kernel must report the window
    overflow apart from a quota overflow and succeed at the doubled bound
    instead of sending the batch to the sort."""
    import jax.numpy as jnp
    table = _table(4000)
    batch = DeviceBatch.from_arrow(table, string_max_bytes=16)
    pids_np = ((np.arange(batch.capacity) // 200) % 8).astype(np.int32)
    assert pk.KernelGeom.plan(batch.capacity, 8, 1).q_w == 128
    res = pk.split_batch_kernel(batch, jnp.asarray(pids_np), 8,
                                interpret=True)
    assert res is not None, "clustered pids fell back to the sort"
    out, stats, spec, geom = res
    assert geom.q_w == 256
    live = pids_np[:table.num_rows]
    for j in range(8):
        got = pk.consolidate(out, stats, j, spec, batch.schema, geom)
        want = table.filter(pa.array(live == j))
        assert _rows_key(got.to_arrow()) == _rows_key(want), j


def test_uploaded_doubles_carry_bit_siblings():
    batch = DeviceBatch.from_arrow(_table(50), string_max_bytes=16)
    dcol = batch.columns[2]
    assert dcol.bits is not None
    # the f64 view is the bitcast of the bits
    assert np.asarray(dcol.data).view(np.uint64).tolist() == \
        np.asarray(dcol.bits).tolist()


def test_exchange_kernel_mode_matches_sort_path():
    """The engine's device exchange through the fused kernel (interpreter
    mode) must produce the same query results as the sort path."""
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.testing import assert_tables_equal

    rng = np.random.default_rng(11)
    n = 3000
    t = pa.table({
        "k": pa.array(rng.integers(0, 50, n), type=pa.int64()),
        "v": pa.array(np.round(rng.standard_normal(n) * 100, 2)),
        "s": pa.array([f"x{int(i)}" for i in rng.integers(0, 30, n)]),
    })

    def q(sess):
        return (sess.create_dataframe(t).repartition(4, "k")
                .groupBy("k").agg(F.sum("v").alias("sv"),
                                  F.count("s").alias("c"))
                .sort("k"))

    conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
    fast = TpuSession({**conf,
                       "spark.rapids.tpu.shuffle.kernel.mode": "interpret"})
    slow = TpuSession({**conf, "spark.rapids.tpu.shuffle.kernel.mode": "off"})
    out_fast = q(fast).collect()
    out_slow = q(slow).collect()
    assert_tables_equal(out_slow, out_fast, approx_float=1e-9)
    # the exchange says which path split its batches: the kernel declining
    # (mode off, non-TPU backend, overflow...) is otherwise invisible
    assert _split_counts(fast) == (1, 0)
    assert _split_counts(slow) == (0, 1)


def _split_counts(sess):
    """(kernel, sort) split-batch totals over the last plan's exchanges."""
    from spark_rapids_tpu.api.dataframe import _iter_execs
    from spark_rapids_tpu.execs import exchange_execs as xe
    exchanges = [nd for nd in _iter_execs(sess.last_plan)
                 if isinstance(nd, xe.TpuShuffleExchangeExec)]
    assert exchanges
    return (sum(e.metrics[xe.KERNEL_SPLIT_BATCHES].value for e in exchanges),
            sum(e.metrics[xe.SORT_SPLIT_BATCHES].value for e in exchanges))


def test_fused_program_shared_across_round_robin_offsets():
    """Round-robin offsets ride as runtime arguments (code review): two
    batches with different offsets must reuse ONE compiled fused program
    and still land every row."""
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.execs import tpu_execs
    from spark_rapids_tpu.execs.base import ExecContext, LeafExec
    from spark_rapids_tpu.execs.exchange_execs import (
        RoundRobinPartitioning, TpuShuffleExchangeExec)

    t = _table(600)
    batch = DeviceBatch.from_arrow(t, string_max_bytes=16)

    class _Leaf(LeafExec):
        is_device = True

        def execute(self, ctx):
            yield batch

    conf = TpuConf({"spark.rapids.tpu.shuffle.kernel.mode": "interpret",
                    "spark.rapids.tpu.sql.string.maxBytes": 16})
    ctx = ExecContext(conf)
    ex = TpuShuffleExchangeExec(RoundRobinPartitioning(4),
                                _Leaf(batch.schema))

    def fused_keys():
        return [k for k in tpu_execs._JIT_CACHE
                if isinstance(k, tuple) and k and k[0] == "exchange-fused"]

    r1 = ex._kernel_split(ctx, ex.partitioning, batch, 0, 4)
    n_after_first = len(fused_keys())
    r2 = ex._kernel_split(ctx, ex.partitioning, batch, 3, 4)
    assert len(fused_keys()) == n_after_first, \
        "new offset recompiled the fused exchange program"
    (w1, p1), (w2, p2) = r1, r2
    assert w1 == w2 == 0, "uniform round-robin pids overflowed a window"
    assert sum(b.num_rows for _, b in p1) == batch.num_rows
    assert sum(b.num_rows for _, b in p2) == batch.num_rows
    # offset shifts rows between partitions but preserves the multiset
    all1 = sorted(sum((_rows_key(b.to_arrow()) for _, b in p1), []), key=repr)
    all2 = sorted(sum((_rows_key(b.to_arrow()) for _, b in p2), []), key=repr)
    assert all1 == all2


def test_dma_index_plan_matches_take_order():
    """Code review (round 5): the DMA consolidation's host-side index math
    must place every row exactly where the take()-path puts it — simulated
    here in numpy, so CI covers it without a TPU. The DMA path itself is
    validated on-chip (docs/perf-notes.md, item 7; chip_smoke.py runs it)."""
    import numpy as np
    from spark_rapids_tpu.shuffle.partition_kernel import (BLOCK,
                                                           KernelGeom,
                                                           dma_index_plan)

    rng = np.random.default_rng(11)
    geom = KernelGeom.plan(4096, 5, 76)
    for trial in range(6):
        counts = rng.integers(0, geom.quota - 64, (geom.groups, geom.n))
        if trial == 0:
            counts[:, 2] = 0            # an empty partition
        prefix8, nb8, ridx, ri_cap, dst_rows = dma_index_plan(counts, geom)
        # staging rows: flat index g*quota + r identifies each source row
        for j in range(geom.n):
            cj = counts[:, j]
            nb = cj // BLOCK
            # take-path layout: full blocks (g asc), then remainders (g asc)
            want = []
            for g in range(geom.groups):
                want.extend(g * geom.quota + r for r in range(nb[g] * BLOCK))
            for g in range(geom.groups):
                want.extend(g * geom.quota + nb[g] * BLOCK + r
                            for r in range(cj[g] - nb[g] * BLOCK))
            # DMA simulation: quota-sized copies at prefix8 (later copies
            # overwrite earlier tails), remainder block at nb8
            dst = np.full(dst_rows, -1, np.int64)
            for g in range(geom.groups):
                off = prefix8[j, g]
                dst[off:off + geom.quota] = g * geom.quota + np.arange(
                    geom.quota)
            rem_tot = int((cj - nb * BLOCK).sum())
            dst[nb8[j]:nb8[j] + ri_cap] = ridx[j]
            got = dst[:int(cj.sum())].tolist()
            assert got == want, (trial, j)
