"""The device exchange's key sketch (`StageStats.key_distinct`): the k
smallest DISTINCT key hashes of each map-side batch by k masked minima —
one program a batch, dispatched after the split and read when the
statistics are — held to numpy's `_kmv_merge`, element for element."""
import copy
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.dataframe import _iter_execs
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.execs import exchange_execs as ee
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.execs.tpu_execs import _flatten, _unflatten_colvs
from spark_rapids_tpu.exprs.core import BoundReference, EvalCtx

MASK = 0xFFFFFFFF
EMPTY = np.zeros(0, dtype=np.uint32)


class _Batches(PhysicalExec):
    """A device child that yields the batches it was given."""
    is_device = True

    def __init__(self, batches):
        super().__init__((), batches[0].schema)
        self.batches = batches

    def execute(self, ctx):
        yield from self.batches


def _unmix(h: int) -> int:
    """The inverse of `_fmix32` (a bijection of uint32)."""
    h ^= h >> 16
    h = (h * pow(int(ee._H_M2), -1, 1 << 32)) & MASK
    h ^= (h >> 13) ^ (h >> 26)
    h = (h * pow(int(ee._H_M1), -1, 1 << 32)) & MASK
    return h ^ (h >> 16)


def _key_hashing_to(h: int) -> int:
    """The BIGINT key under 2**32 whose column hash is ``h``."""
    return _unmix(_unmix(h))


def _numpy_hashes(exchange, db, smax):
    """What the CPU engine hashes: the live rows' key hashes, by numpy."""
    colvs = _unflatten_colvs(db.schema, [np.asarray(a) for a in _flatten(db)])
    with np.errstate(invalid="ignore", over="ignore"):
        hashes = ee._key_hashes(np, exchange.partitioning.keys,
                                EvalCtx(np, colvs, db.capacity, smax))
    return [np.broadcast_to(ch, (db.capacity,))[:db.num_rows]
            for ch in hashes]


def _exchange(batches, ordinals=(0,), partitions=4):
    schema = batches[0].schema
    keys = tuple(BoundReference(o, schema[o].dtype, schema[o].nullable,
                                schema[o].name) for o in ordinals)
    return ee.TpuShuffleExchangeExec(
        ee.HashPartitioning(partitions, keys), _Batches(batches))


def _run(exchange):
    """Run the map side; (pools, sketch programs dispatched)."""
    cleanups = []
    try:
        exchange._ensure_map(ExecContext(cleanups=cleanups))
        dispatched = len(exchange._pending_sketches)
        return exchange._folded_sketches(), dispatched
    finally:
        for fn in cleanups:
            fn()


def _longs(values, valid=None):
    return pa.table({"k": pa.array(values, type=pa.int64(), mask=(
        None if valid is None else ~np.asarray(valid))),
        "v": pa.array(np.arange(len(values)), type=pa.int64())})


def _heavy_hitter():
    """300 keys and the one of them with the smallest hash 10,000 times."""
    keys = np.arange(1, 301, dtype=np.int64) * 7919
    lo = (keys & MASK).astype(np.uint32)
    hashes = ee._fmix32(np, ee._fmix32(np, lo))      # hi is 0
    hot = keys[int(np.argmin(hashes))]
    return [_longs(np.concatenate([np.repeat(hot, 10_000), keys]))]


def _dead_rows():
    """100 live rows of 2,048: the dead ones hold other keys, of which one
    hashes to 0 and would lead the pool."""
    keys = np.arange(5_000, 7_048, dtype=np.int64)
    keys[1_000] = 0
    db = DeviceBatch.from_arrow(_longs(keys))
    return [DeviceBatch(db.schema, db.columns, 100)]


def _extremes():
    """Hashes 0 and 0xFFFFFFFF among fewer than 64: both are genuine."""
    keys = [_key_hashing_to(MASK), _key_hashing_to(0), 11, 12, 13,
            _key_hashing_to(MASK), _key_hashing_to(MASK - 1)]
    return [_longs(np.asarray(keys, dtype=np.int64))]


def _strings():
    rng = np.random.default_rng(3)
    words = [f"order-{int(x):06d}" for x in rng.integers(0, 500, 3_000)]
    return [pa.table({"k": pa.array(words + [None, ""]),
                      "v": pa.array(np.arange(3_002), type=pa.int64())})]


def _doubles():
    rng = np.random.default_rng(4)
    vals = np.concatenate([rng.integers(0, 90, 1_000) / 8.0,
                           [np.nan, -np.nan, 0.0, -0.0, np.inf]])
    return [pa.table({"k": pa.array(vals, type=pa.float64()),
                      "v": pa.array(np.arange(len(vals)), type=pa.int64())})]


def _two_columns():
    rng = np.random.default_rng(5)
    return [pa.table({
        "a": pa.array(rng.integers(0, 40, 2_000), type=pa.int64()),
        "b": pa.array(rng.integers(0, 1_000, 2_000), type=pa.int32()),
        "v": pa.array(np.arange(2_000), type=pa.int64())})]


def _several_batches():
    rng = np.random.default_rng(6)
    return [_longs(rng.integers(0, 1 << 40, n)) for n in (700, 90, 3_000)]


CASES = {
    "fewer_than_64": (lambda: [_longs(np.arange(1_000) % 7)], (0,)),
    "more_than_64": (lambda: [_longs(np.arange(5_000) * 31)], (0,)),
    "heavy_hitter_smallest": (_heavy_hitter, (0,)),
    "dead_rows_garbage": (_dead_rows, (0,)),
    "capacity_under_64": (lambda: [DeviceBatch.from_arrow(
        _longs(np.asarray([5, 9, 5, 2, 9])), bucketed=False)], (0,)),
    "nulls": (lambda: [_longs(np.arange(400) % 50,
                              valid=np.arange(400) % 3 != 0)], (0,)),
    "all_null": (lambda: [_longs(np.arange(10), valid=np.zeros(10, bool))],
                 (0,)),
    "hashes_0_and_ffffffff": (_extremes, (0,)),
    "two_key_columns": (_two_columns, (0, 1)),
    "string_key": (_strings, (0,)),
    "double_key": (_doubles, (0,)),
    "several_batches": (_several_batches, (0,)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_pool_is_numpys_element_for_element(case):
    make, ordinals = CASES[case]
    batches = [b if isinstance(b, DeviceBatch) else DeviceBatch.from_arrow(b)
               for b in make()]
    exchange = _exchange(batches, ordinals)
    pools, dispatched = _run(exchange)
    assert dispatched == len(batches)            # once a batch, not a piece
    smax = ExecContext().string_max_bytes
    want = [EMPTY] * len(ordinals)
    for db in batches:
        want = [ee._kmv_merge(pool, h) for pool, h in zip(
            want, _numpy_hashes(exchange, db, smax))]
    assert len(pools) == len(want)
    for got, expected in zip(pools, want):
        assert got.dtype == np.uint32
        np.testing.assert_array_equal(got, expected)
    if case == "capacity_under_64":
        assert batches[0].capacity == 5
    if case == "hashes_0_and_ffffffff":
        assert pools[0][0] == 0 and pools[0][-1] == MASK
    if case == "dead_rows_garbage":
        assert 0 not in pools[0]
    if case == "heavy_hitter_smallest":
        assert len(pools[0]) == ee._KMV_K      # the hot hash evicted nothing


@pytest.mark.parametrize("hashes,live,k,want", [
    ([7, 7, 3, 9, 3], [1, 1, 1, 1, 1], 4, [3, 7, 9]),
    ([7, 7, 3, 9, 3], [1, 1, 0, 1, 0], 2, [7, 9]),
    ([MASK, MASK], [1, 1], 3, [MASK]),               # a genuine 0xFFFFFFFF
    ([MASK, 5], [0, 1], 3, [5]),                     # a dead one is nothing
    ([0, MASK, 0], [1, 1, 1], 2, [0, MASK]),
    ([4, 2], [0, 0], 2, []),                         # no live row
    ([int(ee._H_NULL), 1], [1, 1], 2, [1, int(ee._H_NULL)]),
], ids=["distinct", "masked", "top_only", "dead_top", "zero_and_top",
        "nothing_live", "h_null"])
def test_masked_minima_pick_the_k_smallest_distinct(hashes, live, k, want):
    pool, n = jax.jit(ee._kmv_pool_device, static_argnums=2)(
        jnp.asarray(hashes, dtype=np.uint32), jnp.asarray(live, dtype=bool), k)
    pool, n = np.asarray(pool), int(n)
    assert pool.shape == (k,) and n == len(want)
    assert pool[:n].tolist() == want
    # a round that found nothing repeats the one before
    assert all(v == (want[-1] if want else 0) for v in pool[n:])


def test_the_sketch_program_holds_no_sort_scatter_or_gather():
    db = DeviceBatch.from_arrow(
        _two_columns()[0].append_column("s", _strings()[0]["k"][:2_000])
        .append_column("d", pa.array(np.arange(2_000) / 3.0)))
    exchange = _exchange([db], (0, 1, 3, 4))       # long, int, string, double
    program = exchange._sketch_program(ExecContext(), db)
    args = (np.int32(db.num_rows), *_flatten(db))
    lowered = program.fn.lower(*args).as_text()
    assert "stablehlo.while" in lowered and "stablehlo.reduce" in lowered
    for op in ("sort", "scatter", "gather", "top_k", "cumsum"):
        assert op not in lowered, op
    primitives = {str(e.primitive) for e in _equations(
        jax.make_jaxpr(program.fn)(*args).jaxpr)}
    assert "scan" in primitives or "while" in primitives
    assert not primitives & {"sort", "scatter", "scatter-add", "scatter_add",
                             "gather", "top_k", "cumsum", "dynamic_slice"}


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _executed_exchange(session):
    return [n for n in _iter_execs(session.last_plan)
            if isinstance(n, ee.ShuffleExchangeExecBase)
            and isinstance(n.partitioning, ee.HashPartitioning)][0]


def test_stage_stats_reads_lazily_and_through_a_copy():
    t = pa.table({"k": pa.array(np.arange(1000) % 7, type=pa.int64()),
                  "v": pa.array(np.arange(1000), type=pa.int64())})
    s = TpuSession()
    s.create_dataframe(t).repartition(4, "k").filter(F.col("v") > 10).collect()
    ex = _executed_exchange(s)
    # collect() read nothing of the sketch: the pool is still the device's
    assert ex._key_sketches is None and len(ex._pending_sketches) == 1
    assert all(isinstance(a, jax.Array)
               for pair in ex._pending_sketches[0] for a in pair)
    twin = copy.copy(ex)
    assert twin.stage_stats().key_distinct == (7,)
    assert ex.stage_stats().key_distinct == (7,)      # the copy took nothing
    assert ex.stage_stats().key_distinct == (7,)      # and a second read
    assert ex._pending_sketches == []
    assert "ndv~7" in ex.stage_stats().describe()
    # a pickled exchange (a cluster task's) starts without map state
    fresh = pickle.loads(pickle.dumps(twin))
    assert fresh._pending_sketches == [] and fresh._key_sketches is None
    assert fresh.stage_stats() is None


def test_the_map_span_counts_the_sketch_programs():
    t = pa.table({"k": pa.array(np.arange(3000) % 11, type=pa.int64())})
    s = TpuSession({"spark.rapids.tpu.trace.enabled": "true"})
    s.create_dataframe(t).repartition(4, "k").collect()
    maps = {r.args["partitioning"]: r.args for r in s.last_trace
            if r.name == "exchange.map"}
    assert maps["hash"]["sketches"] == 1 and maps["hash"]["pieces"] == 4
    # the sketch is dispatched once the split's span has closed
    split = next(r for r in s.last_trace if r.name == "exchange.split"
                 and r.args["path"] != "single")
    sketch, = [r for r in s.last_trace
               if r.name == "program.exchange_sketch"]
    assert sketch.ts_ns >= split.ts_ns + split.dur_ns
    assert sketch.parent_id != split.span_id
    s.create_dataframe(t).repartition(4).collect()      # round robin
    maps = {r.args["partitioning"]: r.args for r in s.last_trace
            if r.name == "exchange.map"}
    assert maps["roundrobin"]["sketches"] == 0


def test_a_cluster_map_task_dispatches_no_sketch(monkeypatch):
    calls = []
    real = ee.ShuffleExchangeExecBase._sketch_keys_device
    monkeypatch.setattr(
        ee.ShuffleExchangeExecBase, "_sketch_keys_device",
        lambda self, ctx, db: (calls.append(db.num_rows),
                               real(self, ctx, db))[1])
    t = pa.table({"k": pa.array(np.arange(2000) % 13, type=pa.int64()),
                  "v": pa.array(np.arange(2000), type=pa.int64())})
    query = lambda s: (s.create_dataframe(t).repartition(4, "k")
                       .groupBy("k").agg(F.sum("v").alias("sv")))
    s = TpuSession({"spark.rapids.tpu.sql.cluster.numExecutors": "2"})
    try:
        out = query(s).collect()
        assert s._cluster_scheduler.last_stages      # it did run as a cluster
    finally:
        s._cluster_scheduler.close()
    assert out.num_rows == 13 and calls == []
    # the local engine's map side of the same query does sketch
    query(TpuSession()).collect()
    assert calls
