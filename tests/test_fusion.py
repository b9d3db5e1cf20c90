"""Whole-stage fusion (plan/fusion.py + execs/fused_execs.py): chain
collapse, bit-identity against the unfused path, the WholeStageCodegen-style
plan rendering, encoded-domain survival inside a fused stage, program-cache
routing, and the variableFloatAgg CPU-fallback gate."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.execs.fused_execs import (FUSED_BATCHES_SAVED,
                                                FUSED_OPS,
                                                FusedAggregateStageExec,
                                                FusedStageExec)
from spark_rapids_tpu.plan.fusion import (fused_batches_not_materialized,
                                          fused_stages, fusion_stats)
from spark_rapids_tpu.testing import assert_tables_equal

_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
_OFF = {**_CONF, "spark.rapids.tpu.sql.fusion.enabled": "false"}


def _table(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 7, n).astype(np.int64)),
        "v": pa.array(np.round(rng.uniform(0, 100, n), 3)),
        "w": pa.array(rng.integers(-50, 50, n).astype(np.int32)),
        "s": pa.array(np.array(["red", "green", "blue", "teal"])[
            rng.integers(0, 4, n)]),
    })


def _chain(sess):
    df = sess.create_dataframe(_table())
    return (df.filter(F.col("v") > 20.0)
              .select((F.col("v") * 2.0).alias("v2"), "k", "s",
                      (F.col("w") + 1).alias("w1"))
              .filter(F.col("w1") != 0))


def test_chain_collapses_and_is_bit_identical():
    on, off = TpuSession(_CONF), TpuSession(_OFF)
    got = _chain(on).collect()
    ref = _chain(off).collect()
    assert got.equals(ref)                 # bit-identity, order included
    stages = fused_stages(on.last_plan)
    assert len(stages) == 1 and isinstance(stages[0], FusedStageExec)
    assert len(stages[0].fused_ops) == 3   # filter + project + filter
    assert not fused_stages(off.last_plan)
    # the interior batches never materialized: 2 per input batch
    assert fused_batches_not_materialized(on.last_plan) >= 2


def test_tree_string_renders_star_stage_ids():
    sess = TpuSession(_CONF)
    _chain(sess).collect()
    text = sess.last_plan.tree_string()
    assert "*(1) TpuFilterExec" in text, text
    assert "*(1) TpuProjectExec" in text, text
    # stats agree with the rendered plan
    stats = fusion_stats(sess.last_plan)
    assert stats["fused_stages"] == 1 and stats["fused_ops"] == 3


def test_aggregate_fold_is_a_fused_stage_and_bit_identical():
    def q(sess):
        df = sess.create_dataframe(_table())
        return (df.filter(F.col("v") > 50.0)
                  .groupBy("k")
                  .agg(F.sum("v").alias("sv"),
                       F.count(F.lit(1)).alias("c"))
                  .sort("k"))
    on, off = TpuSession(_CONF), TpuSession(_OFF)
    got, ref = q(on).collect(), q(off).collect()
    # the unfused path folds through fuse_device_ops with IDENTICAL
    # expression trees, so this is bitwise equality, floats included
    assert got.equals(ref)
    stages = fused_stages(on.last_plan)
    assert len(stages) == 1 and isinstance(stages[0], FusedAggregateStageExec)
    assert "*(1) TpuHashAggregateExec" in on.last_plan.tree_string()
    assert "TpuFilterExec" not in on.last_plan.tree_string()
    # filter + agg + the column-pruning Project(k, v) above the scan
    assert stages[0].metrics[FUSED_OPS].value == 3
    assert stages[0].metrics[FUSED_BATCHES_SAVED].value >= 1


def test_expand_chain_fuses_per_projection_variants():
    def q(sess):
        df = sess.create_dataframe(_table())
        return (df.filter(F.col("v") > 30.0)
                  .rollup("k", "s")
                  .agg(F.sum("v").alias("sv"),
                       F.count(F.lit(1)).alias("c")))
    on, off = TpuSession(_CONF), TpuSession(_OFF)
    got, ref = q(on).collect(), q(off).collect()
    assert_tables_equal(ref, got, ignore_order=True)
    stages = fused_stages(on.last_plan)
    # the Expand + the filter below it fuse into one multi-variant stage
    # (the rollup aggregate above consumes the variants)
    chain = [s for s in stages if isinstance(s, FusedStageExec)]
    assert chain, on.last_plan.tree_string()
    assert len(chain[0].variants) == 3     # (k,s), (k,null), (null,null)
    assert all(pred is not None for _, pred in chain[0].variants)


def test_fusion_disabled_by_conf():
    sess = TpuSession(_OFF)
    _chain(sess).collect()
    assert not fused_stages(sess.last_plan)
    assert "*(" not in sess.last_plan.tree_string()


def test_max_ops_splits_long_chains():
    sess = TpuSession({**_CONF, "spark.rapids.tpu.sql.fusion.maxOps": "2"})
    got = _chain(sess).collect()
    ref = _chain(TpuSession(_OFF)).collect()
    assert got.equals(ref)
    stages = fused_stages(sess.last_plan)
    assert stages and all(len(s.fused_ops) <= 2 for s in stages)


def test_float_agg_fallback_gating_respected():
    """Satellite regression (memory gotcha): a float-aggregate chain must
    NOT land on the device path — fused or not — unless variableFloatAgg is
    enabled; without this assert a fused-agg test can silently exercise the
    CPU engine and test nothing."""
    def q(sess):
        df = sess.create_dataframe(_table())
        return (df.filter(F.col("v") > 50.0).groupBy("k")
                  .agg(F.sum("v").alias("sv")).sort("k"))

    gated = TpuSession({"spark.rapids.tpu.sql.fusion.enabled": "true"})
    out_gated = q(gated).collect()
    plan = gated.last_plan.tree_string()
    # (the device filter below it still fuses with the column-pruning
    # Project(k, v) above the scan: that stage is not the aggregate's)
    assert not any(isinstance(s, FusedAggregateStageExec)
                   for s in fused_stages(gated.last_plan)), plan
    assert "TpuHashAggregateExec" not in plan, plan
    assert "CpuHashAggregateExec" in plan, plan

    allowed = TpuSession(_CONF)
    out_allowed = q(allowed).collect()
    assert any(isinstance(s, FusedAggregateStageExec)
               for s in fused_stages(allowed.last_plan)), \
        allowed.last_plan.tree_string()
    assert_tables_equal(out_gated, out_allowed, approx_float=1e-9)


def test_nondeterministic_exprs_break_the_chain():
    def q(sess):
        df = sess.create_dataframe(_table())
        return (df.filter(F.col("v") > 20.0)
                  .select("k", F.rand(42).alias("r"))
                  .filter(F.col("k") >= 0))
    sess = TpuSession(_CONF)
    q(sess).collect()
    # the rand() projection must not be substituted into anything (the
    # column-pruning Project(k, v) above the scan may fuse with the first
    # filter: it draws nothing)
    for s in fused_stages(sess.last_plan):
        assert all("r" not in schema.names() for _, schema in s.fused_ops)


def test_fused_stage_keeps_encoded_domain_predicate(tmp_path):
    """An encoded-eligible predicate inside a fused stage keeps running on
    dictionary indices (PR 4 composition)."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.utils import metrics as um
    t = _table(6000)
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, row_group_size=2000)

    def q(sess):
        df = sess.read.parquet(path)
        return (df.filter(F.col("s") == "red")
                  .select("k", (F.col("v") + 1.0).alias("v1"), "s"))

    def run(extra):
        sess = TpuSession({**_CONF, **extra,
                           "spark.rapids.tpu.sql.scanCache.enabled": "false"})
        before = um.TRANSFER_METRICS.snapshot()
        out = q(sess).collect()
        after = um.TRANSFER_METRICS.snapshot()
        ops = (after[um.TRANSFER_ENCODED_DOMAIN_OPS]
               - before[um.TRANSFER_ENCODED_DOMAIN_OPS])
        return out, ops, sess

    enc, enc_ops, sess = run({})
    stages = fused_stages(sess.last_plan)
    assert stages and stages[0].encoded_domain_ok
    assert enc_ops >= 1
    dec, dec_ops, _ = run(
        {"spark.rapids.tpu.sql.encodedDomain.enabled": "false"})
    assert dec_ops == 0
    assert enc.equals(dec)
    unfused, _, _ = run({"spark.rapids.tpu.sql.fusion.enabled": "false"})
    assert enc.equals(unfused)


def test_fused_programs_hit_the_program_cache_on_repeat():
    """Repeat submission of the same fused plan shape must be all hits —
    the fused plan-signature keys route through the serving ProgramCache."""
    from spark_rapids_tpu.serving.program_cache import global_program_cache
    sess = TpuSession(_CONF)
    df = _chain(sess)
    ref = df.collect()                      # compiles the fused programs
    cache = global_program_cache()
    before = cache.snapshot_counters()
    out = df.collect()
    after = cache.snapshot_counters()
    assert out.equals(ref)
    assert after["hits"] - before["hits"] >= 1
    assert after["misses"] - before["misses"] == 0


def _manual_env():
    """(conf, multi-batch device source exec, bound refs) for hand-built
    plan tests."""
    from spark_rapids_tpu.columnar.dtypes import DType
    from spark_rapids_tpu.columnar.transfer import upload_table_conf
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.execs.base import LeafExec
    from spark_rapids_tpu.exprs.core import BoundReference

    conf = TpuConf(_CONF)
    full = _table(3000)
    parts = [full.slice(0, 1000), full.slice(1000, 1000),
             full.slice(2000, 1000)]
    batches = [upload_table_conf(p, 16, conf) for p in parts]

    class _Source(LeafExec):
        is_device = True

        def execute(self, ctx):
            yield from batches

    src = _Source(batches[0].schema)
    k = BoundReference(0, DType.LONG, True, "k")
    v = BoundReference(1, DType.DOUBLE, True, "v")
    return conf, src, k, v


def _run_plan(plan, conf):
    from spark_rapids_tpu.execs.base import ExecContext
    ctx = ExecContext(conf)
    return pa.concat_tables([b.to_arrow() for b in plan.execute(ctx)])


def test_coalesce_above_expand_refuses_to_fuse():
    """Coalesce + Expand don't compose: unfused interleaves variant batches
    per ARRIVING batch while a concat-first fused stage would emit
    per-variant over the combined input — same rows, different order, and
    the contract is bit-identity order included. The pass must leave the
    chain unfused."""
    from spark_rapids_tpu.columnar.dtypes import Schema
    from spark_rapids_tpu.execs import tpu_execs as te
    from spark_rapids_tpu.execs.expand_execs import TpuExpandExec
    from spark_rapids_tpu.plan.fusion import fuse_stages

    conf, src, k, v = _manual_env()
    two_col = Schema(src.output.fields[:2])     # (k, v)
    chain = te.TpuCoalesceBatchesExec(
        TpuExpandExec(((k, v), (k, v)), src, two_col), target_bytes=1)
    ref = _run_plan(chain, conf)
    out = fuse_stages(chain, conf)
    assert not fused_stages(out), out.tree_string()
    assert _run_plan(out, conf).equals(ref)


def test_require_single_coalesce_above_filter_refuses_to_fuse():
    """A require_single coalesce concats exactly what reaches it; moving it
    below a selective filter would concat the RAW input into one HBM batch.
    The pass must refuse rather than regress peak memory."""
    from spark_rapids_tpu.execs import tpu_execs as te
    from spark_rapids_tpu.exprs.literals import Literal
    from spark_rapids_tpu.exprs.predicates import GreaterThan
    from spark_rapids_tpu.columnar.dtypes import DType
    from spark_rapids_tpu.plan.fusion import fuse_stages

    conf, src, k, v = _manual_env()
    chain = te.TpuCoalesceBatchesExec(
        te.TpuFilterExec(GreaterThan(v, Literal(90.0, DType.DOUBLE)), src),
        require_single=True)
    ref = _run_plan(chain, conf)
    out = fuse_stages(chain, conf)
    assert not fused_stages(out), out.tree_string()
    assert _run_plan(out, conf).equals(ref)

    # require_single BELOW the chain (nothing under it to distort) fuses
    below = te.TpuFilterExec(
        GreaterThan(v, Literal(90.0, DType.DOUBLE)),
        te.TpuProjectExec((k, v),
                          te.TpuCoalesceBatchesExec(src,
                                                    require_single=True)))
    fused = fuse_stages(below, conf)
    assert isinstance(fused, FusedStageExec), fused.tree_string()
    assert fused.coalesce == (1 << 31, True)
    assert _run_plan(fused, conf).equals(_run_plan(below, conf))


def test_coalesce_in_chain_and_multi_batch_input():
    """A manual multi-batch plan: Project -> Coalesce -> Filter fuses and
    matches the unfused execution batch-for-content."""
    from spark_rapids_tpu.columnar.dtypes import DType
    from spark_rapids_tpu.execs import tpu_execs as te
    from spark_rapids_tpu.exprs.arithmetic import Multiply
    from spark_rapids_tpu.exprs.literals import Literal
    from spark_rapids_tpu.exprs.misc import Alias
    from spark_rapids_tpu.exprs.predicates import GreaterThan
    from spark_rapids_tpu.plan.fusion import fuse_stages

    conf, src, k, v = _manual_env()
    chain = te.TpuProjectExec(
        (Alias(Multiply(v, Literal(3.0, DType.DOUBLE)), "v3"), Alias(k, "k")),
        te.TpuCoalesceBatchesExec(
            te.TpuFilterExec(GreaterThan(v, Literal(10.0, DType.DOUBLE)),
                             src),
            target_bytes=1))

    ref = _run_plan(chain, conf)
    fused = fuse_stages(chain, conf)
    assert isinstance(fused, FusedStageExec) and fused.coalesce is not None
    assert len(fused.fused_ops) == 3
    # only the Filter's interior output is elided — the coalesce concat
    # batch still materializes as the stage input and must not count
    assert fused.saved_per_batch == 1
    got = _run_plan(fused, conf)
    assert got.equals(ref)
    # target_bytes=1 flushes each of the 3 source batches individually
    assert fused.metrics[FUSED_BATCHES_SAVED].value == 3
