"""Distributed (mesh) execution of window / expand / generate / writes /
range partitioning — the operators the round-2 VERDICT flagged as gathering
to a single device. Every test asserts the Mesh* exec really ran (plan-shape
check) AND that results match the CPU engine."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession, Window
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.testing import (assert_tables_equal,
                                      assert_tpu_and_cpu_equal)

MESH_CONF = {
    "spark.rapids.tpu.sql.mesh.enabled": "true",
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
}


def _rand_table(n=4000, seed=11):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": rng.integers(0, 37, n).astype(np.int32),
        "b": rng.integers(0, 3, n).astype(np.int32),
        "v": rng.integers(-1000, 1000, n).astype(np.int64),
        "s": pa.array([f"row{int(i)}" for i in rng.integers(0, 50, n)]),
    })


def test_mesh_window_rank_and_agg(eight_devices):
    t = _rand_table()
    w = Window.partitionBy("k").orderBy("v")
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            "k", "v", "s",
            F.row_number().over(w).alias("rn"),
            F.rank().over(w).alias("rk"),
            F.sum("v").over(w).alias("running")),
        conf=MESH_CONF, ignore_order=True,
        expect_tpu_execs=["MeshWindowExec"])


def test_mesh_window_multi_part_keys(eight_devices):
    t = _rand_table(seed=5)
    w = Window.partitionBy("k", "b").orderBy("v", "s")
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            "k", "b", "v",
            F.avg("v").over(w).alias("ra"),
            F.lag("v", 1).over(w).alias("pv")),
        conf=MESH_CONF, ignore_order=True, approx_float=1e-9,
        expect_tpu_execs=["MeshWindowExec"])


def test_unpartitioned_window_gathers(eight_devices):
    """No partition keys -> one global frame: must run single-device behind a
    gather (Spark's single-partition requirement), and still match."""
    t = _rand_table(800, seed=3)
    w = Window.orderBy("v")
    cpu = assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            "v", F.row_number().over(w).alias("rn")),
        conf=MESH_CONF, ignore_order=True)
    assert cpu.num_rows == 800


def test_mesh_expand_rollup(eight_devices):
    t = _rand_table()
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).rollup("k", "b").agg(
            F.sum("v").alias("sv"), F.count("v").alias("cv")),
        conf=MESH_CONF, ignore_order=True,
        expect_tpu_execs=["MeshExpandExec"])


def test_mesh_expand_cube_strings(eight_devices):
    t = _rand_table(seed=19)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).cube("s", "b").agg(
            F.min("v").alias("mv"), F.max("s").alias("ms")),
        conf=MESH_CONF, ignore_order=True,
        expect_tpu_execs=["MeshExpandExec"])


def test_mesh_generate_explode(eight_devices):
    t = _rand_table(1200, seed=7)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            "k", F.explode(F.array(F.col("v"), F.col("v") * 2,
                                   F.lit(None))).alias("e")),
        conf=MESH_CONF, ignore_order=True,
        expect_tpu_execs=["MeshGenerateExec"])


def test_mesh_range_partition_sort(eight_devices):
    """Global sort on the mesh = sampled range repartition + local sort; the
    repartition must be a mesh exchange, not a gather."""
    t = _rand_table(6000, seed=23)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).sort("v", "k"),
        conf=MESH_CONF,
        expect_tpu_execs=["MeshSortExec"])


def test_mesh_write_parquet_roundtrip(tmp_path, eight_devices):
    t = _rand_table(3000, seed=29)
    path = str(tmp_path / "out_parquet")
    s = TpuSession(MESH_CONF)
    df = s.create_dataframe(t)
    stats = df.write.mode("overwrite").parquet(path)
    assert stats is not None and stats.num_rows == 3000
    # one part file per non-empty shard (distributed write, not a gather)
    assert stats.num_files > 1
    back = TpuSession().read.parquet(path).collect()
    assert_tables_equal(t, back, ignore_order=True)


def test_mesh_write_partitioned_csv(tmp_path, eight_devices):
    t = _rand_table(500, seed=31)
    path = str(tmp_path / "out_csv")
    s = TpuSession(MESH_CONF)
    stats = s.create_dataframe(t).write.mode("overwrite") \
        .partitionBy("b").csv(path)
    assert stats is not None and stats.num_rows == 500
    back = TpuSession().read.csv(path).collect()
    assert back.num_rows == 500


def test_mesh_write_plan_shape(tmp_path, eight_devices):
    """The write plan must lower to MeshWriteFilesExec (no gather)."""
    t = _rand_table(1000, seed=37)
    path = str(tmp_path / "plan_parquet")
    s = TpuSession(MESH_CONF)
    s.create_dataframe(t).write.mode("overwrite").parquet(path)
    plan_str = s.last_plan.tree_string() if s.last_plan else ""
    assert "MeshWriteFilesExec" in plan_str, plan_str
    assert "MeshGatherExec" not in plan_str, plan_str


# ---------------------------------------------------------- mesh aggregation
def test_mesh_agg_high_cardinality_repartition(eight_devices):
    """~50k distinct keys > aggRepartitionThreshold: the partial buffers must
    hash-repartition over ICI and merge per shard (no replicated blowup), and
    still match the CPU engine exactly."""
    rng = np.random.default_rng(41)
    n = 60000
    t = pa.table({
        "k": rng.integers(0, 50000, n).astype(np.int64),
        "v": rng.integers(-100, 100, n).astype(np.int64),
    })
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).groupBy("k").agg(
            F.sum("v").alias("sv"), F.count("v").alias("cv"),
            F.min("v").alias("mn")),
        conf={**MESH_CONF,
              "spark.rapids.tpu.sql.mesh.aggRepartitionThreshold": "1024"},
        ignore_order=True,
        expect_tpu_execs=["MeshHashAggregateExec"])


def test_mesh_agg_repartition_with_strings_and_nulls(eight_devices):
    rng = np.random.default_rng(43)
    n = 8000
    keys = [None if i % 97 == 0 else f"key_{int(i)}"
            for i in rng.integers(0, 3000, n)]
    t = pa.table({
        "k": pa.array(keys),
        "v": rng.standard_normal(n),
    })
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).groupBy("k").agg(
            F.avg("v").alias("av"), F.count(F.lit(1)).alias("c")),
        conf={**MESH_CONF,
              "spark.rapids.tpu.sql.mesh.aggRepartitionThreshold": "64",
              "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"},
        ignore_order=True, approx_float=1e-9,
        expect_tpu_execs=["MeshHashAggregateExec"])


def test_mesh_post_agg_stays_distributed(eight_devices):
    """Group-by output feeds a filter+sort: those must run as mesh execs now
    (the round-2 VERDICT flagged post-agg dropping to single-device)."""
    rng = np.random.default_rng(47)
    n = 20000
    t = pa.table({
        "k": rng.integers(0, 5000, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).groupBy("k").agg(
            F.sum("v").alias("sv")).filter(F.col("sv") > 300)
            .sort("sv", "k"),
        conf={**MESH_CONF,
              "spark.rapids.tpu.sql.mesh.aggRepartitionThreshold": "1024"},
        expect_tpu_execs=["MeshHashAggregateExec", "MeshFilterExec",
                          "MeshSortExec"])


def test_mesh_global_agg_no_keys(eight_devices):
    t = pa.table({"v": np.arange(10000, dtype=np.int64)})
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).agg(
            F.sum("v").alias("s"), F.count("v").alias("c"),
            F.max("v").alias("m")),
        conf=MESH_CONF,
        expect_tpu_execs=["MeshHashAggregateExec"])


@pytest.mark.parametrize("threshold", ["1024", "4"],
                         ids=["gathered-partials", "repartitioned-partials"])
def test_mesh_agg_nullable_int_values(eight_devices, threshold):
    """Ten int keys over a nullable int value column, one key all null:
    sum/count/min/max/avg of the shards' partials merge to the CPU engine's
    answer, whether the partials are gathered or hash-repartitioned."""
    rng = np.random.default_rng(3)
    n = 900
    k = rng.integers(0, 10, n).astype(np.int64)
    v = rng.integers(0, 100, n).astype(np.int64)
    null = (rng.random(n) >= 0.9) | (k == 7)
    t = pa.table({"k": k, "v": pa.array(v, pa.int64(), mask=null)})
    cpu = assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).groupBy("k").agg(
            F.sum("v").alias("sv"), F.count("v").alias("cv"),
            F.min("v").alias("mn"), F.max("v").alias("mx"),
            F.avg("v").alias("av")),
        conf={**MESH_CONF,
              "spark.rapids.tpu.sql.mesh.aggRepartitionThreshold": threshold},
        ignore_order=True, approx_float=1e-12,
        expect_tpu_execs=["MeshHashAggregateExec"])
    row7 = cpu.filter(pa.compute.equal(cpu["k"], 7)).to_pylist()
    assert row7 == [{"k": 7, "sv": None, "cv": 0, "mn": None, "mx": None,
                     "av": None}]


# ---------------------------------------------------------- shard-local scan
def _write_parts(tmp_path, n_files=6, rows=1500, seed=53, fmt="parquet"):
    import pyarrow.parquet as pq
    import pyarrow.orc as po_orc
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n_files):
        t = pa.table({
            "k": rng.integers(0, 100, rows).astype(np.int64),
            "v": rng.standard_normal(rows),
            "s": pa.array([f"f{i}_{int(x)}" for x in
                           rng.integers(0, 30, rows)]),
        })
        p = str(tmp_path / f"part-{i}.{fmt}")
        if fmt == "parquet":
            pq.write_table(t, p)
        else:
            po_orc.write_table(t, p)
        paths.append(p)
    return str(tmp_path)


def test_mesh_parquet_scan_shard_local(tmp_path, eight_devices):
    """Multi-file parquet scan on the mesh must read shard-local (plan shows
    MeshFileScatterExec, no driver-side concat) and match the CPU engine."""
    d = _write_parts(tmp_path)
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"}) \
        .read.parquet(d).collect()
    s = TpuSession(MESH_CONF)
    out = s.read.parquet(d).groupBy("k").agg(
        F.sum("v").alias("sv"), F.count("s").alias("c")).collect()
    plan_str = s.last_plan.tree_string()
    assert "MeshFileScatterExec" in plan_str, plan_str
    cpu_agg = TpuSession({"spark.rapids.tpu.sql.enabled": "false"}) \
        .read.parquet(d).groupBy("k").agg(
            F.sum("v").alias("sv"), F.count("s").alias("c")).collect()
    assert_tables_equal(cpu_agg, out, ignore_order=True, approx_float=1e-9)
    assert cpu.num_rows == 9000


def test_mesh_orc_scan_shard_local(tmp_path, eight_devices):
    d = _write_parts(tmp_path, n_files=4, rows=700, seed=59, fmt="orc")
    s = TpuSession(MESH_CONF)
    out = s.read.orc(d).select(
        "k", (F.col("v") * 2).alias("v2")).collect()
    plan_str = s.last_plan.tree_string()
    assert "MeshFileScatterExec" in plan_str, plan_str
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"}) \
        .read.orc(d).select("k", (F.col("v") * 2).alias("v2")).collect()
    assert_tables_equal(cpu, out, ignore_order=True, approx_float=1e-9)


def test_mesh_parquet_scan_with_pruning_filter(tmp_path, eight_devices):
    """Row-group pruning changes per-file metadata counts; the shard-local
    read must still size its shards exactly."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(61)
    for i in range(3):
        t = pa.table({"k": np.arange(i * 1000, (i + 1) * 1000,
                                     dtype=np.int64),
                      "v": rng.standard_normal(1000)})
        pq.write_table(t, str(tmp_path / f"p{i}.parquet"),
                       row_group_size=250)
    s = TpuSession(MESH_CONF)
    out = s.read.parquet(str(tmp_path)).filter(F.col("k") >= 2600) \
        .collect()
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"}) \
        .read.parquet(str(tmp_path)).filter(F.col("k") >= 2600).collect()
    assert_tables_equal(cpu, out, ignore_order=True, approx_float=1e-9)
    assert out.num_rows == 400


def test_mesh_csv_scan_falls_back_to_scatter(tmp_path, eight_devices):
    """CSV has no metadata counts: the mesh scan still works through the
    read-then-scatter fallback."""
    import csv as _csv
    for i in range(3):
        with open(tmp_path / f"c{i}.csv", "w", newline="") as fh:
            w = _csv.writer(fh)
            w.writerow(["a", "b"])
            for j in range(50):
                w.writerow([i * 100 + j, f"s{j}"])
    s = TpuSession(MESH_CONF)
    out = s.read.option("header", "true").csv(str(tmp_path)).collect()
    assert out.num_rows == 150


# ---------------------------------------------------------- AQE on the mesh
def _iter_plan(node):
    yield node
    for c in node.children:
        yield from _iter_plan(c)


def test_mesh_adaptive_broadcast_switch(eight_devices):
    """Plan-time estimates say 'big build side' (shuffled join); at runtime
    the filtered build materializes tiny — with AQE on, the mesh join must
    switch to the broadcast form from the OBSERVED size and still match."""
    rng = np.random.default_rng(67)
    n = 30000
    fact = pa.table({"k": rng.integers(0, 2000, n).astype(np.int64),
                     "v": rng.integers(0, 100, n).astype(np.int64)})
    dim = pa.table({
        "k": np.arange(2000, dtype=np.int64),
        # wide payload so the plan-time size estimate exceeds the threshold
        "pad": pa.array(["x" * 200] * 2000),
        "grp": pa.array([int(i % 7) for i in range(2000)],
                        type=pa.int64()),
    })

    def q(s):
        d = s.create_dataframe(dim).filter(F.col("grp") == 3) \
             .select("k", "grp")
        return s.create_dataframe(fact).join(d, "k") \
                .groupBy("grp").agg(F.sum("v").alias("sv"))

    threshold = str(64 * 1024)  # 64 KB: over the filtered build, under dim
    base = {**MESH_CONF,
            "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": threshold}
    s = TpuSession({**base, "spark.rapids.tpu.sql.adaptive.enabled": "true"})
    out = q(s).collect()
    joins = [nd for nd in _iter_plan(s.last_plan)
             if type(nd).__name__ == "MeshShuffledHashJoinExec"]
    assert joins, s.last_plan.tree_string()
    assert any(j.adapted_broadcast for j in joins), (
        "AQE should have switched the small observed build to broadcast")
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    exp = q(cpu).collect()
    assert_tables_equal(exp, out, ignore_order=True)

    # same query, AQE off: no switch
    s2 = TpuSession({**base,
                     "spark.rapids.tpu.sql.adaptive.enabled": "false"})
    out2 = q(s2).collect()
    joins2 = [nd for nd in _iter_plan(s2.last_plan)
              if type(nd).__name__ == "MeshShuffledHashJoinExec"]
    assert joins2 and not any(j.adapted_broadcast for j in joins2)
    assert_tables_equal(exp, out2, ignore_order=True)


def test_mesh_adaptive_right_join_switch(eight_devices):
    """Broadcasting the LEFT side (legal for right joins) also adapts."""
    rng = np.random.default_rng(71)
    # big at plan time (~800 KB estimate -> shuffled join), tiny at runtime
    # after the filter (~8 KB observed -> adaptive broadcast-left)
    left = pa.table({"k": np.arange(4000, dtype=np.int64),
                     "pad": pa.array(["y" * 200] * 4000)})
    big = pa.table({"k": rng.integers(0, 40, 20000).astype(np.int64),
                    "v": rng.integers(0, 9, 20000).astype(np.int64)})

    def q(s):
        l = s.create_dataframe(left).filter(F.col("k") < 40)
        return l.join(s.create_dataframe(big), "k", "right") \
                .groupBy("k").agg(F.count("v").alias("c"))

    conf = {**MESH_CONF,
            "spark.rapids.tpu.sql.adaptive.enabled": "true",
            "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "100000"}
    s = TpuSession(conf)
    out = q(s).collect()
    joins = [nd for nd in _iter_plan(s.last_plan)
             if type(nd).__name__ == "MeshShuffledHashJoinExec"]
    assert joins and any(j.adapted_broadcast for j in joins), (
        "the broadcast-left (bi==0) adaptive path should have fired")
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    assert_tables_equal(q(cpu).collect(), out, ignore_order=True)
