"""Hash-ordered grouping fast path: row-hash semantics, collision detection,
bounded-group reduction, and the filter/project fusion into aggregation."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs.core import ColV
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.testing import assert_tpu_and_cpu_equal

col = F.col


def _colv(vals, dtype=DType.LONG, validity=None):
    data = np.asarray(vals)
    v = (np.ones(len(vals), bool) if validity is None
         else np.asarray(validity, bool))
    return ColV(dtype, data, v)


def test_hash_equal_keys_equal_hashes():
    a = _colv([1, 2, 1, 2, 3])
    h = bk.hash64_cols(np, [a])
    assert h[0] == h[2] and h[1] == h[3]
    assert h[0] != h[1] and h[0] != h[4]


def test_hash_grouping_semantics_null_nan_negzero():
    # null == null, NaN == NaN, -0.0 == 0.0 (Spark grouping equality)
    f = ColV(DType.DOUBLE,
             np.array([np.nan, np.nan, -0.0, 0.0, 1.0, 0.0]),
             np.array([True, True, True, True, False, False]))
    h = bk.hash64_cols(np, [f])
    assert h[0] == h[1]          # NaN == NaN
    assert h[2] == h[3]          # -0.0 == 0.0
    assert h[4] == h[5]          # null == null regardless of payload
    assert h[0] != h[2] and h[2] != h[4]


def test_hash_string_width_consistent():
    d1 = np.zeros((2, 8), np.uint8)
    d1[0, :3] = list(b"abc")
    d1[1, :3] = list(b"abc")
    s = ColV(DType.STRING, d1, np.ones(2, bool),
             np.array([3, 3], np.int32))
    h = bk.hash64_cols(np, [s])
    assert h[0] == h[1]


def test_hash_string_no_structured_collisions():
    """Java-hashCode-style pairs ('Aa'/'BB') must not collide: a linear
    base-31 fold would, permanently defeating the fast path."""
    pairs = [(b"Aa", b"BB"), (b"AaAa", b"BBBB"), (b"Aa", b"C#")]
    for l, r in pairs:
        d = np.zeros((2, 8), np.uint8)
        d[0, :len(l)] = list(l)
        d[1, :len(r)] = list(r)
        s = ColV(DType.STRING, d, np.ones(2, bool),
                 np.array([len(l), len(r)], np.int32))
        h = bk.hash64_cols(np, [s])
        assert h[0] != h[1], (l, r)


def test_float_hash_compiles_without_bitcast():
    """The TPU x64 emulation cannot compile f64 bitcasts (signbit included);
    the float hash must stay pure-arithmetic or it only breaks on hardware."""
    import jax
    import jax.numpy as jnp

    def h(data, validity):
        return bk.hash64_cols(jnp, [ColV(DType.DOUBLE, data, validity)])

    jaxpr = str(jax.make_jaxpr(h)(np.array([1.5, -2.0, 0.0]),
                                  np.array([True, True, False])))
    assert "bitcast" not in jaxpr, jaxpr
    # and parity: traced result equals the numpy path
    out = jax.jit(h)(np.array([1.5, -2.0, 0.0]), np.array([True, True, False]))
    ref = bk.hash64_cols(np, [ColV(DType.DOUBLE, np.array([1.5, -2.0, 0.0]),
                                   np.array([True, True, False]))])
    assert np.array_equal(np.asarray(out), ref)


def test_collision_detected_and_order_correct():
    keys = [_colv([5, 7, 5, 7, 9, 5])]
    order, h = bk.hash_group_order(np, keys, 6)
    starts = bk.rows_equal_adjacent(np, keys, order, 6)
    assert not bool(bk.detect_hash_collision(np, h, order, starts, 6))
    assert int(starts.sum()) == 3
    # forge a collision: all hashes equal but keys differ
    forged = np.zeros(6, dtype=np.uint64)
    order2 = np.arange(6)
    starts2 = bk.rows_equal_adjacent(np, keys, order2, 6)
    assert bool(bk.detect_hash_collision(np, forged, order2, starts2, 6))


def test_group_aggregate_hash_matches_sort():
    from spark_rapids_tpu.exprs import Count, Literal, Sum, bind_expression
    from spark_rapids_tpu.exprs.core import EvalCtx, UnresolvedAttribute
    from spark_rapids_tpu.columnar.dtypes import Schema
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.ops.aggregate import group_aggregate

    rng = np.random.default_rng(5)
    t = pa.table({"k": rng.integers(0, 50, 500),
                  "v": rng.integers(-100, 100, 500)})
    schema = Schema.from_pa(t.schema)
    hb = HostBatch.from_arrow(t, 8)
    colvs = [ColV(c.dtype, c.data, c.validity, c.lengths) for c in hb.columns]
    ectx = EvalCtx(np, colvs, 500, 8)
    keys = (bind_expression(UnresolvedAttribute("k"), schema),)
    fns = (Sum(bind_expression(UnresolvedAttribute("v"), schema)),
           Count(Literal.of(1)))

    ks, rs, n_s = group_aggregate(np, ectx, keys, fns, 500, 500)
    kh, rh, n_h, collision = group_aggregate(np, ectx, keys, fns, 500, 500,
                                             grouping="hash")
    assert not bool(collision)
    assert int(n_s) == int(n_h) == 50
    # same groups, different order: compare as key->value maps
    def as_map(kcols, rcols, n):
        return {int(kcols[0].data[i]): (int(rcols[0].data[i]),
                                        int(rcols[1].data[i]))
                for i in range(int(n))}
    assert as_map(ks, rs, n_s) == as_map(kh, rh, n_h)


def test_fused_filter_agg_plan_and_results():
    rng = np.random.default_rng(9)
    t = pa.table({"k": rng.integers(0, 5, 300),
                  "v": rng.integers(0, 100, 300),
                  "w": rng.integers(0, 10, 300)})

    def build(sess):
        return (sess.create_dataframe(t)
                .filter(col("v") < 50)
                .select("k", (col("v") * col("w")).alias("vw"))
                .groupBy("k").agg(F.sum("vw").alias("s"),
                                  F.count().alias("n"))
                .sort("k"))

    cpu = assert_tpu_and_cpu_equal(build)
    # golden
    import pandas as pd
    pdf = t.to_pandas()
    pdf = pdf[pdf.v < 50]
    g = (pdf.assign(vw=pdf.v * pdf.w).groupby("k")
         .agg(s=("vw", "sum"), n=("vw", "count")))
    assert cpu.column("s").to_pylist() == g["s"].tolist()
    assert cpu.column("n").to_pylist() == g["n"].tolist()


def test_fusion_removes_filter_exec_from_plan():
    rng = np.random.default_rng(11)
    t = pa.table({"k": rng.integers(0, 5, 100),
                  "v": rng.integers(0, 100, 100)})
    sess = TpuSession({})
    df = (sess.create_dataframe(t).filter(col("v") > 10)
          .groupBy("k").agg(F.count().alias("n")).sort("k"))
    df.collect()
    plan = sess.last_plan.tree_string()
    assert "TpuHashAggregateExec" in plan
    assert "TpuFilterExec" not in plan, plan


def test_fusion_preserves_nondeterministic_project():
    """A project computing rand() must not be inlined twice."""
    rng = np.random.default_rng(13)
    t = pa.table({"k": rng.integers(0, 5, 100)})
    sess = TpuSession({"spark.rapids.tpu.sql.incompatibleOps.enabled": "true"})
    df = (sess.create_dataframe(t)
          .select("k", F.rand(42).alias("r"))
          .groupBy("k").agg(F.min("r").alias("lo"), F.max("r").alias("hi"))
          .sort("k"))
    out = df.collect()
    assert all(lo <= hi for lo, hi in zip(out.column("lo").to_pylist(),
                                          out.column("hi").to_pylist()))


def test_literal_group_key_after_fusion():
    """Project inlining can turn a grouping key into a literal (e.g.
    dropDuplicates over a withColumn(lit(...)) marker); scalar keys must
    broadcast before grouping."""
    t = pa.table({"k": pa.array([1, 2, 1, 3], type=pa.int64())})

    def build(sess):
        return (sess.create_dataframe(t)
                .withColumn("m", F.lit(1))
                .dropDuplicates()
                .sort("k"))

    cpu = assert_tpu_and_cpu_equal(build)
    assert cpu.column("k").to_pylist() == [1, 2, 3]
    assert cpu.column("m").to_pylist() == [1, 1, 1]


def test_group_cap_fallback_many_groups():
    """More groups than the scan-reduction bound re-runs the exact path."""
    from spark_rapids_tpu.ops import aggregate as agg_mod
    n = 2000
    t = pa.table({"k": np.arange(n), "v": np.ones(n, np.int64)})

    def build(sess):
        return (sess.create_dataframe(t).groupBy("k")
                .agg(F.sum("v").alias("s")).sort("k"))

    old = agg_mod.GROUP_CAP
    agg_mod.GROUP_CAP = 256
    try:
        cpu = assert_tpu_and_cpu_equal(build)
    finally:
        agg_mod.GROUP_CAP = old
    assert cpu.num_rows == n
    assert cpu.column("s").to_pylist() == [1] * n


# ---------------------------------------------------------------------------
# one-hot (sort-free, scatter-free) low-cardinality fast path
# ---------------------------------------------------------------------------
def _q1ish_inputs(n=400, nulls=True, seed=7):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 6, n)
    v = rng.integers(-100, 100, n).astype(np.float64)
    v[rng.random(n) < 0.1] = np.nan
    mk = pa.array(k, type=pa.int64())
    mv = pa.array(v, type=pa.float64(),
                  mask=(rng.random(n) < 0.15) if nulls else None)
    return pa.table({"k": mk, "v": mv})


def _run_group_aggregate(t, grouping, fns_builder=None):
    from spark_rapids_tpu.exprs import (Average, Count, Literal, Max, Min,
                                        Sum, bind_expression)
    from spark_rapids_tpu.exprs.core import EvalCtx, UnresolvedAttribute
    from spark_rapids_tpu.columnar.dtypes import Schema
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.ops.aggregate import group_aggregate

    schema = Schema.from_pa(t.schema)
    hb = HostBatch.from_arrow(t, 8)
    n = t.num_rows
    colvs = [ColV(c.dtype, c.data, c.validity, c.lengths) for c in hb.columns]
    ectx = EvalCtx(np, colvs, n, 8)
    b = lambda name: bind_expression(UnresolvedAttribute(name), schema)
    keys = (b("k"),)
    fns = (Sum(b("v")), Min(b("v")), Max(b("v")), Average(b("v")),
           Count(Literal.of(1)))
    return group_aggregate(np, ectx, keys, fns, n, n, grouping=grouping)


def _group_map(kcols, rcols, n):
    out = {}
    for i in range(int(n)):
        key = (int(kcols[0].data[i]) if kcols[0].validity[i] else None)
        vals = []
        for r in rcols:
            vals.append(float(r.data[i]) if r.validity[i] else None)
        out[key] = tuple(vals)
    return out


def test_onehot_matches_sort_with_nulls_and_nans():
    t = _q1ish_inputs()
    ks, rs, n_s = _run_group_aggregate(t, "sort")
    ko, ro, n_o, collision = _run_group_aggregate(t, "onehot")
    assert not bool(collision)
    assert int(n_s) == int(n_o)
    ms, mo = _group_map(ks, rs, n_s), _group_map(ko, ro, n_o)
    assert set(ms) == set(mo)
    for k in ms:
        for a, b in zip(ms[k], mo[k]):
            if a is None or b is None:
                assert a is b, (k, ms[k], mo[k])
            elif np.isnan(a) or np.isnan(b):
                assert np.isnan(a) and np.isnan(b), (k, ms[k], mo[k])
            else:
                assert abs(a - b) < 1e-9, (k, ms[k], mo[k])


def test_onehot_overflow_flagged():
    from spark_rapids_tpu.ops.aggregate import ONEHOT_CAP
    n = ONEHOT_CAP * 3
    t = pa.table({"k": np.arange(n), "v": np.ones(n, np.float64)})
    _, _, _, collision = _run_group_aggregate(t, "onehot")
    assert bool(collision)


def test_onehot_jit_matches_numpy():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.exprs import Count, Literal, Sum, bind_expression
    from spark_rapids_tpu.exprs.core import EvalCtx, UnresolvedAttribute
    from spark_rapids_tpu.columnar.dtypes import Schema
    from spark_rapids_tpu.columnar.host import HostBatch
    from spark_rapids_tpu.ops.aggregate import group_aggregate

    t = _q1ish_inputs(n=257)
    schema = Schema.from_pa(t.schema)
    hb = HostBatch.from_arrow(t, 8)
    n = t.num_rows
    b = lambda name: bind_expression(UnresolvedAttribute(name), schema)
    keys = (b("k"),)
    fns = (Sum(b("v")), Count(Literal.of(1)))

    flat = []
    for c in hb.columns:
        flat.append(c.data)
        flat.append(c.validity)

    def prog(*flat):
        colvs = [ColV(c.dtype, flat[2 * i], flat[2 * i + 1])
                 for i, c in enumerate(hb.columns)]
        ectx = EvalCtx(jnp, colvs, n, 8)
        ks, rs, ng, coll = group_aggregate(jnp, ectx, keys, fns, n, n,
                                           grouping="onehot")
        return ([k.data for k in ks] + [k.validity for k in ks]
                + [r.data for r in rs] + [r.validity for r in rs]
                + [ng, coll])

    jout = [np.asarray(a) for a in jax.jit(prog)(*flat)]
    colvs = [ColV(c.dtype, c.data, c.validity) for c in hb.columns]
    ectx = EvalCtx(np, colvs, n, 8)
    ks, rs, ng, coll = group_aggregate(np, ectx, keys, fns, n, n,
                                       grouping="onehot")
    assert not bool(coll) and not bool(jout[-1])
    assert int(ng) == int(jout[-2])
    m_np = _group_map(ks, rs, ng)
    kj = [ColV(DType.LONG, jout[0], jout[1])]
    rj = [ColV(DType.DOUBLE, jout[2], jout[4]),
          ColV(DType.LONG, jout[3], jout[5])]
    m_j = _group_map(kj, rj, int(jout[-2]))
    assert set(m_np) == set(m_j)
    for k in m_np:
        for a, b in zip(m_np[k], m_j[k]):
            if a is None or b is None:
                assert a is b, (k, m_np[k], m_j[k])
            elif np.isnan(a) or np.isnan(b):
                assert np.isnan(a) and np.isnan(b), (k, m_np[k], m_j[k])
            else:
                assert abs(a - b) < 1e-9, (k, m_np[k], m_j[k])


def test_key_words_null_vs_zero_and_float_canon():
    ints = ColV(DType.LONG, np.array([0, 0, 5]),
                np.array([True, False, True]))
    w = bk.key_words(np, ints)[0]
    vw = bk.validity_word(np, [ints])
    # data words canonicalize nulls to 0 — only the validity word separates
    # null from a genuine zero
    assert w[0] == w[1] and vw[0] != vw[1]

    f = ColV(DType.DOUBLE, np.array([-0.0, 0.0, np.nan, np.nan, 1.5, 2.5]),
             np.ones(6, bool))
    w0, w1 = bk.key_words(np, f)
    assert w0[0] == w0[1] and w1[0] == w1[1]      # -0.0 == 0.0
    assert w0[2] == w0[3] and w1[2] == w1[3]      # NaN == NaN
    assert (w0[4], w1[4]) != (w0[5], w1[5])       # distinct finites differ
    # injectivity across close values
    g = ColV(DType.DOUBLE, np.array([1.0, np.nextafter(1.0, 2.0)]),
             np.ones(2, bool))
    gw0, gw1 = bk.key_words(np, g)
    assert (gw0[0], gw1[0]) != (gw0[1], gw1[1])


def test_min_max_string_still_uses_hash_path():
    """String min/max is outside the one-hot path; the engine must fall back
    and stay correct."""
    t = pa.table({"k": pa.array([1, 1, 2, 2, 2]),
                  "s": pa.array(["b", "a", "z", "m", "q"])})

    def build(sess):
        return (sess.create_dataframe(t).groupBy("k")
                .agg(F.min("s").alias("lo"), F.max("s").alias("hi"))
                .sort("k"))

    cpu = assert_tpu_and_cpu_equal(build)
    assert cpu.column("lo").to_pylist() == ["a", "m"]
    assert cpu.column("hi").to_pylist() == ["b", "z"]
