"""Mesh execution at capacity-forcing scale and under key skew — the
round-2 VERDICT's 'mesh tests never trigger capacity growth or skew'
gap. Asserts ride the exchange-sizing stats (the MapOutputStatistics
analog) and the ICI overflow re-run counter."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.execs import mesh_execs as me
from spark_rapids_tpu.testing import assert_tables_equal

pytestmark = pytest.mark.slow

MESH_CONF = {
    "spark.rapids.tpu.sql.mesh.enabled": "true",
    "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1",
}


def test_mesh_join_under_extreme_skew(eight_devices):
    """90% of fact rows share ONE join key: the hash exchange lands them all
    on one shard. The count pre-pass must size that shard's chunk ABOVE the
    even-split capacity (capacity growth), rows must be conserved, and the
    result must match the CPU engine."""
    rng = np.random.default_rng(83)
    n = 40000
    keys = np.where(rng.random(n) < 0.9, 7,
                    rng.integers(0, 1000, n)).astype(np.int64)
    fact = pa.table({"k": keys, "v": rng.integers(0, 100, n).astype(np.int64)})
    dim = pa.table({"k": np.arange(1000, dtype=np.int64),
                    "w": rng.integers(0, 10, 1000).astype(np.int64)})

    def q(s):
        return (s.create_dataframe(fact)
                .join(s.create_dataframe(dim), "k")
                .groupBy("w").agg(F.sum("v").alias("sv"),
                                  F.count("k").alias("c")))

    me.EXCHANGE_STATS.clear()
    s = TpuSession(MESH_CONF)
    out = q(s).collect()
    joins = [st for st in me.EXCHANGE_STATS if st["op"] == "mjoin_lpart"]
    assert joins, me.EXCHANGE_STATS
    st = joins[-1]
    even = st["rows"] // 8
    assert st["recv_max"] > 4 * even, (
        f"skewed shard should receive most rows: {st}")
    assert st["recv_max"] >= 0.85 * st["rows"], st
    # the receiving shard's capacity grew past the even split
    assert st["out_cap"] > even, st
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
    assert_tables_equal(q(cpu).collect(), out, ignore_order=True)


def test_mesh_tpch_at_capacity_forcing_scale(eight_devices):
    """TPC-H Q3 + Q18 at 25x the mesh suite's scale: per-shard row counts
    cross multiple capacity buckets (growth/shrink on every exchange) and
    results still match the CPU engine exactly."""
    from spark_rapids_tpu.benchmarks.tpch import BENCH_CONF
    from spark_rapids_tpu.benchmarks.tpch_data import gen_all
    from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
    tables = gen_all(0.05, seed=7)
    assert tables["lineitem"].num_rows > 250_000
    conf = {**BENCH_CONF, **MESH_CONF}
    me.EXCHANGE_STATS.clear()
    for qnum in (3, 18):
        s = TpuSession(conf)
        dfs = {k: s.create_dataframe(v) for k, v in tables.items()}
        out = QUERIES[qnum](dfs).collect()
        cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
        cdfs = {k: cpu.create_dataframe(v) for k, v in tables.items()}
        exp = QUERIES[qnum](cdfs).collect()
        assert_tables_equal(exp, out, ignore_order=True, approx_float=1e-9)
    # the exchanges really carried capacity-bucket-crossing volumes
    assert any(st["chunk_cap"] >= 4096 for st in me.EXCHANGE_STATS), (
        me.EXCHANGE_STATS[:10])


def test_ici_overflow_rerun_fires_on_real_exchange(eight_devices):
    """The overflow-detect-and-re-run driver (shuffle/ici.py): a skewed
    repartition starting from an undersized chunk MUST flag and re-run with
    doubled capacity until no row is clamped — counter asserted, rows
    conserved, content exact."""
    import jax
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.parallel.mesh import make_mesh
    from spark_rapids_tpu.parallel.mesh_batch import scatter_arrow
    from spark_rapids_tpu.shuffle import ici

    rng = np.random.default_rng(89)
    n = 8192
    # every row to shard 0: worst-case skew
    t = pa.table({"a": rng.integers(0, 1 << 30, n).astype(np.int64)})
    mesh = make_mesh(8)
    mb = scatter_arrow(t, mesh, 16)
    pids = jax.device_put(
        np.zeros(mesh.devices.size * mb.local_capacity, dtype=np.int32),
        jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")))
    from spark_rapids_tpu.parallel.mesh_batch import flatten_mesh
    reruns_before = ici.RERUN_COUNT
    out_rows, flat = ici.ici_repartition(
        mesh, mb.schema, mb.local_capacity, mb.rows_dev(), pids,
        flatten_mesh(mb), chunk_capacity=64)
    assert ici.RERUN_COUNT > reruns_before, (
        "undersized chunk must trigger at least one overflow re-run")
    rows = np.asarray(out_rows)
    assert int(rows.sum()) == n and int(rows[0]) == n, rows
    got = np.sort(np.asarray(flat[0])[:n])
    assert np.array_equal(got, np.sort(t.column("a").to_numpy()))


def test_mesh_tpch_at_32_devices():
    """Round-4 VERDICT item 7: mesh lowering past 8 devices. Runs in a
    subprocess (the 32-device CPU topology must be set before jax loads)
    and executes TPC-H Q1+Q3 on a 32-device mesh vs the CPU engine."""
    import os
    import subprocess
    import sys
    script = r"""
import jax
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch_data import gen_all
from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
from spark_rapids_tpu.testing import assert_tables_equal
assert jax.device_count() == 32, jax.devices()
tables = gen_all(0.002, seed=5)
mesh = TpuSession({
    "spark.rapids.tpu.sql.mesh.enabled": "true",
    "spark.rapids.tpu.sql.mesh.numDevices": "32",
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.tpu.sql.hasNans": "false",
    "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1"})
cpu = TpuSession({"spark.rapids.tpu.sql.enabled": "false"})
for qn in (1, 3):
    out = QUERIES[qn]({k: mesh.create_dataframe(v)
                       for k, v in tables.items()}).collect()
    exp = QUERIES[qn]({k: cpu.create_dataframe(v)
                       for k, v in tables.items()}).collect()
    assert_tables_equal(exp, out, ignore_order=True, approx_float=1e-6)
    print(f"q{qn} ok on 32-device mesh", flush=True)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "q3 ok on 32-device mesh" in r.stdout
