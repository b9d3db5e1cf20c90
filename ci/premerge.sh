#!/usr/bin/env bash
# Premerge gate (jenkins/Jenkinsfile.premerge analog): fast correctness on
# an 8-device virtual CPU mesh — no TPU hardware needed, suitable for every
# pull request.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

echo "== config docs in sync =="
python -m spark_rapids_tpu.analysis --check-configs

echo "== tpu-lint fast gate (--changed-only: findings filtered to the merge-base diff; project rules keep full interprocedural context) =="
# fail-fast ordering: a finding in the files this PR touches surfaces in
# seconds, before the full-package pass and the test suite spend minutes.
# The full run below remains the gate of record — the fast gate can only
# fail earlier, never pass something the full run would catch.
python -m spark_rapids_tpu.analysis --changed-only spark_rapids_tpu/

echo "== tpu-lint (full rule set R001-R018 incl. interprocedural R008-R010, the R012 race detector, the R013-R015 exception-flow ladder + the R016-R018 capture-provenance/program-cache key-soundness rules; fails on non-baselined findings) =="
# one pass, three outputs: the gate (exit code), the SARIF artifact CI
# publishes as code annotations, and the per-rule profile on stderr
lint_start=$(date +%s)
set +e
python -m spark_rapids_tpu.analysis --profile --format sarif \
  spark_rapids_tpu/ > tpu-lint.sarif 2> /tmp/tpu-lint-profile.txt
lint_rc=$?
set -e
lint_elapsed=$(( $(date +%s) - lint_start ))
cat /tmp/tpu-lint-profile.txt
if [ "${lint_rc}" -ne 0 ]; then
  # human-readable findings for the console; the sarif carries them for CI
  python - << 'PY'
import json
doc = json.load(open("tpu-lint.sarif"))
run = doc["runs"][0]
for r in run["results"]:
    loc = r["locations"][0]["physicalLocation"]
    print(f"{loc['artifactLocation']['uri']}:{loc['region']['startLine']}: "
          f"{r['ruleId']}: {r['message']['text']}")
props = run.get("properties", {})
for e in props.get("parseErrors", []):
    print(f"PARSE ERROR: {e}")
for s in props.get("staleBaseline", []):
    print(s)
PY
  echo "tpu-lint FAILED (${lint_rc})"
  exit 1
fi
# runtime guard: the interprocedural pass (call graph + CFG dataflow +
# thread-root/escape registry) must not quietly blow up premerge latency;
# when it trips, the profile names the culprits instead of leaving an
# undebuggable overrun
if [ "${lint_elapsed}" -gt 30 ]; then
  echo "tpu-lint runtime guard FAILED: ${lint_elapsed}s > 30s budget"
  echo "three slowest rules:"
  grep '^profile:' /tmp/tpu-lint-profile.txt | head -3
  exit 1
fi
echo "tpu-lint runtime: ${lint_elapsed}s (budget 30s); artifact: tpu-lint.sarif"

echo "== fast suite (slow markers excluded) =="
python -m pytest tests/ -x -q -m "not slow"

echo "== API surface validation =="
python -m spark_rapids_tpu.api_validation

echo "== serving smoke (4 concurrent queries through the scheduler) =="
python - << 'PY'
import numpy as np
import pyarrow as pa
from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.serving import QueryState

rng = np.random.default_rng(7)
table = pa.table({"k": rng.integers(0, 8, 4096).astype("int64"),
                  "v": rng.random(4096)})
sess = TpuSession({
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.tpu.serving.maxConcurrentQueries": "4"})
df = (sess.create_dataframe(table).filter(F.col("v") > 0.25)
      .groupBy("k").agg(F.sum("v").alias("s"), F.count(F.lit(1)).alias("c")))
expected = df.collect()
handles = [sess.submit(df, tenant=f"t{i % 2}") for i in range(4)]
for h in handles:
    assert h.result(timeout=300).equals(expected), h
    assert h.state is QueryState.DONE, h
stats = sess.scheduler.stats()
assert stats["states"]["DONE"] == 4, stats
assert stats["program_cache"]["hits"] > 0, stats
print("serving smoke ok:", stats["program_cache"])
PY

echo "== network serving smoke (server subprocess, TPC-H Q1 over TCP, streamed partials, bit-identity) =="
python - << 'PY'
import subprocess, sys, os, tempfile
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import gen_lineitem
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient
from spark_rapids_tpu.testing import assert_tables_equal

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
# stderr to a FILE: a chatty server would fill an undrained pipe
errf = tempfile.NamedTemporaryFile(prefix="serving-err-", delete=False,
                                   mode="w+")
proc = subprocess.Popen(
    [sys.executable, "-m", "spark_rapids_tpu.serving.server",
     "--tpch-lineitem", "0.002", "--partitions", "4",
     "--conf", "spark.rapids.tpu.sql.variableFloatAgg.enabled=true"],
    stdout=subprocess.PIPE, stderr=errf, text=True,
    env={**os.environ, "JAX_PLATFORMS": "cpu"})
line = proc.stdout.readline()
if not line.startswith("SERVING "):
    errf.seek(0)
    raise AssertionError((line, errf.read()[-2000:]))
_tag, host, port = line.split()
client = QueryServiceClient([f"{host}:{port}"], TpuConf(CONF))
try:
    q1_sql = (
        "SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty, "
        "sum(l_extendedprice) AS sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge, "
        "avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price, "
        "avg(l_discount) AS avg_disc, count(*) AS count_order FROM lineitem "
        "WHERE l_shipdate <= date '1998-09-02' "
        "GROUP BY l_returnflag, l_linestatus "
        "ORDER BY l_returnflag, l_linestatus")
    scan_sql = ("SELECT l_orderkey, l_extendedprice FROM lineitem "
                "WHERE l_discount > 0.05")
    sess = TpuSession(CONF)
    (sess.create_dataframe(gen_lineitem(scale=0.002, seed=42))
     .repartition(4).createOrReplaceTempView("lineitem"))
    # Q1 over the wire vs in-process collect of the same SQL (float-agg
    # carve-out per the documented contract)
    got = client.submit(q1_sql).result()
    assert_tables_equal(sess.sql(q1_sql).collect(), got, approx_float=1e-9)
    # >= 1 streamed partial batch BEFORE completion, assembly bit-identical
    h = client.submit(scan_sql)
    got2 = h.result()
    assert h.batches_delivered >= 2, h.batches_delivered
    assert h.metrics["first_batch_s"] < h.metrics["wall_s"], h.metrics
    assert got2.equals(sess.sql(scan_sql).collect())
    print("network serving smoke ok: batches =", h.batches_delivered,
          "first_batch_s =", h.metrics["first_batch_s"])
finally:
    client.close()
    proc.terminate()
    proc.wait(timeout=30)
PY

echo "== failover smoke (2 replicas, seeded kill_peer mid-stream, TPC-H Q1 bit-identical through failover) =="
python - << 'PY'
import time
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import gen_lineitem
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.memory.device_manager import DeviceManager
from spark_rapids_tpu.serving.client import QueryServiceClient
from spark_rapids_tpu.serving.server import QueryServer
from spark_rapids_tpu.testing import assert_tables_equal
from spark_rapids_tpu.utils import metrics as um

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        # slice the small Q1 result into 2-row wire frames so the seeded
        # kill lands MID-STREAM (frame 2) with frame 1 already delivered
        "spark.rapids.tpu.serving.net.maxStreamBatchRows": "2"}
Q1_SQL = (
    "SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty, "
    "sum(l_extendedprice) AS sum_base_price, "
    "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge, "
    "avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price, "
    "avg(l_discount) AS avg_disc, count(*) AS count_order FROM lineitem "
    "WHERE l_shipdate <= date '1998-09-02' "
    "GROUP BY l_returnflag, l_linestatus "
    "ORDER BY l_returnflag, l_linestatus")

def serve(faults=""):
    sess = TpuSession({**CONF, **({
        "spark.rapids.tpu.serving.net.faults.plan": faults,
        "spark.rapids.tpu.serving.net.faults.seed": "7"} if faults else {})})
    (sess.create_dataframe(gen_lineitem(scale=0.002, seed=42))
     .repartition(4).createOrReplaceTempView("lineitem"))
    server = QueryServer(sess)
    host, port = server.address
    return sess, server, f"{host}:{port}"

sess_a, server_a, addr_a = serve("kill_peer:req_type=data,after=2")
sess_b, server_b, addr_b = serve()
ref = sess_b.sql(Q1_SQL).collect()          # single-replica collect
client = QueryServiceClient([addr_a, addr_b], TpuConf({
    "spark.rapids.tpu.shuffle.maxRetries": "0",
    "spark.rapids.tpu.shuffle.connectTimeout": "2"}))
f0 = um.SERVING_METRICS[um.SERVING_FAILOVERS].value
r0 = um.SERVING_METRICS[um.SERVING_RESUMED_BATCHES].value
try:
    h = client.submit(Q1_SQL, replica=0)    # starts on A; A dies on frame 2
    got = h.result()
    # bit-identical through failover: exact columns bitwise, float aggs
    # to 1e-9 (the documented distributed float-sum carve-out)
    assert_tables_equal(ref, got, approx_float=1e-9)
    assert h.failovers == 1, h.failovers
    assert h.replica == addr_b
    assert um.SERVING_METRICS[um.SERVING_FAILOVERS].value - f0 == 1
    assert um.SERVING_METRICS[um.SERVING_RESUMED_BATCHES].value - r0 >= 1
    assert any(f[0] == "kill_peer" for f in server_a.transport.plan.fired)
    # zero leaks on the survivor
    deadline = time.time() + 10
    while server_b._queries and time.time() < deadline:
        time.sleep(0.05)
    assert not server_b._queries
    sess_a.scheduler.drain(timeout=60); sess_b.scheduler.drain(timeout=60)
    dm = DeviceManager.peek()
    if dm is not None:
        deadline = time.time() + 30
        while dm.semaphore.active_holders and time.time() < deadline:
            time.sleep(0.05)
        assert dm.semaphore.active_holders == 0
    print("failover smoke ok: failovers=1 resumed=",
          um.SERVING_METRICS[um.SERVING_RESUMED_BATCHES].value - r0)
finally:
    client.close()
    server_a.shutdown()
    server_b.shutdown()
PY

echo "== supervisor smoke (SIGKILL a supervised replica subprocess: restart + re-discovery + query completes) =="
python - << 'PY'
import tempfile, time
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import gen_lineitem
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import (QueryServiceClient,
                                             WireQueryError)
from spark_rapids_tpu.serving.lifecycle import OverloadedError
from spark_rapids_tpu.serving.supervisor import ReplicaSupervisor
from spark_rapids_tpu.utils import metrics as um

reg = tempfile.mkdtemp(prefix="fleet-reg-")
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.serving.net.registryDir": reg,
        "spark.rapids.tpu.serving.health.heartbeatSeconds": "0.2",
        "spark.rapids.tpu.serving.health.livenessWindowSeconds": "2",
        "spark.rapids.tpu.serving.fleet.superviseIntervalSeconds": "0.2",
        "spark.rapids.tpu.serving.fleet.restartBackoffMs": "100"}
sup = ReplicaSupervisor(TpuConf(CONF),
                        server_args=["--tpch-lineitem", "0.002",
                                     "--partitions", "4"])
sql = ("SELECT l_orderkey, l_extendedprice FROM lineitem "
       "WHERE l_discount > 0.05")
sess = TpuSession({"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"})
(sess.create_dataframe(gen_lineitem(scale=0.002, seed=42))
 .repartition(4).createOrReplaceTempView("lineitem"))
ref = sess.sql(sql).collect()
client = QueryServiceClient(registry_dir=reg, conf=TpuConf({
    "spark.rapids.tpu.shuffle.maxRetries": "0",
    "spark.rapids.tpu.shuffle.connectTimeout": "2",
    "spark.rapids.tpu.serving.health.probeIntervalSeconds": "0"}))

def query_until_ok(deadline_s=180):
    # a pass that races replica startup/discovery retries — but the
    # terminal result must be the bit-identical scan, never a wrong one
    deadline = time.time() + deadline_s
    while True:
        try:
            assert client.submit(sql).result().equals(ref)
            return
        except (WireQueryError, OverloadedError):
            if time.time() > deadline:
                raise
            time.sleep(0.5)

r0 = um.SERVING_METRICS[um.SERVING_RESTARTS].value
try:
    sup.start(1)
    query_until_ok()
    assert sup.fleet_stats()["slots"][0]["state"] == "UP"
    # SIGKILL the replica's OS process: death by exit, no shutdown hooks
    sup._slots[0].proc.proc.kill()
    deadline = time.time() + 60
    while um.SERVING_METRICS[um.SERVING_RESTARTS].value - r0 < 1:
        assert time.time() < deadline, "supervisor never restarted"
        time.sleep(0.2)
    query_until_ok()                # re-discovery + correct result
    slot = sup.fleet_stats()["slots"][0]
    assert slot["state"] in ("UP", "STARTING") and slot["restarts"] == 1, slot
    print("supervisor smoke ok:", sup.fleet_stats()["states"])
finally:
    client.close()
    sup.stop()
PY

echo "== recompute smoke (2-peer cluster, seeded mid-reduce kill_peer, lineage-scoped stage recompute, bit-identical) =="
python - << 'PY'
import pyarrow as pa
from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.shuffle.inprocess import _Fabric
from spark_rapids_tpu.testing import assert_tables_equal
from spark_rapids_tpu.utils import metrics as mt

BASE = {"spark.rapids.tpu.sql.cluster.numExecutors": "2",
        "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1",
        "spark.rapids.tpu.shuffle.retryBackoffMs": "5",
        "spark.rapids.tpu.shuffle.maxRetries": "1",
        "spark.rapids.tpu.shuffle.fetch.timeoutSeconds": "5"}
N = 4000
fact = pa.table({"k": [i % 8 for i in range(N)], "v": list(range(N)),
                 "f": [i * 0.25 for i in range(N)]})
dim = pa.table({"k": list(range(8)), "name": [f"n{i}" for i in range(8)]})

def run(s):
    return (s.create_dataframe(fact).repartition(4, "k").groupBy("k")
            .agg(F.sum("v").alias("sv"), F.sum("f").alias("sf"))
            .join(s.create_dataframe(dim), "k")
            .filter(F.col("sv") > -500).sort("sv", "k")).collect()

ref_s = TpuSession(dict(BASE))
ref = run(ref_s)
ref_s._cluster_scheduler.close()
_Fabric.reset()

# exec-1 dies mid-stream on its 1st outgoing data frame (the seeded Nth
# data frame); the stage driver must recompute ONLY its map tasks
s = TpuSession({**BASE,
                "spark.rapids.tpu.shuffle.transport.class":
                    "spark_rapids_tpu.shuffle.faults.FaultInjectingTransport",
                "spark.rapids.tpu.shuffle.faults.plan":
                    "kill_peer:owner=exec-1,req_type=data,after=1",
                "spark.rapids.tpu.shuffle.faults.seed": "7"})
before = mt.recompute_snapshot()
got = run(s)                                # zero caller-visible errors
delta = mt.recompute_delta(before)
sched = s._cluster_scheduler
total_maps = sum(st.num_tasks for st in sched.last_stages
                 if not st.is_result)
assert delta["shuffle.recomputes"] >= 1, delta
assert 1 <= delta["shuffle.recomputed_map_tasks"] < total_maps, (
    delta, total_maps)
assert delta["shuffle.recompute_escalations"] == 0, delta
dead = [ex.executor_id for ex in sched.executors
        if not sched._executor_alive(ex)]
assert dead == ["exec-1"], f"the seeded kill never fired: {dead}"
# bit-identical collect (float aggs within the documented 1e-9 carve-out)
assert_tables_equal(ref, got, ignore_order=True, approx_float=1e-9)
sched.close()
print("recompute smoke ok:", delta, f"total_maps={total_maps}")
PY

echo "== fusion smoke (4 queries fused vs unfused, bit-identical) =="
python - << 'PY'
from spark_rapids_tpu.api.dataframe import TpuSession
from spark_rapids_tpu.benchmarks.tpch import gen_lineitem, q1, q6
from spark_rapids_tpu.benchmarks.tpcds_data import gen_all
from spark_rapids_tpu.benchmarks.tpcds_queries import QUERIES
from spark_rapids_tpu.plan.fusion import fusion_stats
from spark_rapids_tpu.testing import assert_tables_equal

conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false"}
fused = TpuSession(conf)
unfused = TpuSession({**conf,
                      "spark.rapids.tpu.sql.fusion.enabled": "false"})
lineitem = gen_lineitem(scale=0.01, seed=42)
ds = gen_all(0.01, seed=0)
f_ds = {k: fused.create_dataframe(v) for k, v in ds.items()}
u_ds = {k: unfused.create_dataframe(v) for k, v in ds.items()}
runs = [("tpch-q1", q1(fused.create_dataframe(lineitem)),
         q1(unfused.create_dataframe(lineitem))),
        ("tpch-q6", q6(fused.create_dataframe(lineitem)),
         q6(unfused.create_dataframe(lineitem))),
        ("tpcds-q9", QUERIES["q9"](f_ds), QUERIES["q9"](u_ds)),
        ("tpcds-q28", QUERIES["q28"](f_ds), QUERIES["q28"](u_ds))]
stages = 0
for name, fdf, udf in runs:
    got, ref = fdf.collect(), udf.collect()
    assert_tables_equal(ref, got, approx_float=1e-9)
    st = fusion_stats(fused.last_plan)
    print(f"fusion smoke {name}: fused_stages={st['fused_stages']} "
          f"ops={st['fused_ops']}")
    stages += st["fused_stages"]
assert stages >= 4, "fusion smoke saw fewer than 4 fused stages"
assert fusion_stats(unfused.last_plan)["fused_stages"] == 0
print("fusion smoke ok")
PY

echo "== out-of-core smoke (tiny-budget Q1, grace partitions + bit-identity) =="
python - << 'PY'
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import BENCH_CONF, gen_lineitem, q1
from spark_rapids_tpu.memory.device_manager import DeviceManager
from spark_rapids_tpu.testing import assert_tables_equal

conf = {**BENCH_CONF, "spark.rapids.tpu.sql.string.maxBytes": "16",
        "spark.rapids.tpu.sql.scanCache.enabled": "false"}
lineitem = gen_lineitem(scale=0.01, seed=42)
ref = q1(TpuSession(conf).create_dataframe(lineitem)).collect()
DeviceManager.shutdown()
tiny = TpuSession({**conf,
                   "spark.rapids.tpu.memory.tpu.poolSizeBytes":
                       str(256 << 10),
                   "spark.rapids.tpu.memory.host.spillStorageSize":
                       str(256 << 10)})
got = q1(tiny.create_dataframe(lineitem)).collect()
mm = tiny.last_metrics["memory"]
# exact columns bitwise; variableFloatAgg sums to 1e-9 (the distributed
# float-sum contract, docs/out-of-core.md)
assert_tables_equal(ref, got, approx_float=1e-9)
assert mm["memory.spill_partitions"] >= 2, mm
assert mm["memory.bytes_spilled_to_host"] > 0, mm
DeviceManager.shutdown()
print("out-of-core smoke ok:", {k: mm[k] for k in
      ("memory.spill_partitions", "memory.recursion_depth_peak",
       "memory.bytes_spilled_to_host", "memory.bytes_spilled_to_disk")})
PY

echo "== adaptive smoke (seeded skewed join: skew-split fires, bit-identical to non-AQE) =="
python - << 'PY'
import numpy as np
import pyarrow as pa
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.testing import assert_tables_equal

rng = np.random.default_rng(7)
k = np.where(rng.random(2000) < 0.8, 0, rng.integers(1, 50, 2000))
fact = pa.table({"k": pa.array(k, type=pa.int64()),
                 "v": pa.array(np.arange(2000), type=pa.int64())})
dims = pa.table({"k": pa.array(np.arange(50), type=pa.int64()),
                 "w": pa.array(np.arange(50) * 10, type=pa.int64())})
SKEW = {"spark.rapids.tpu.sql.adaptive.enabled": "true",
        "spark.rapids.tpu.sql.adaptive.skewedPartitionThreshold.bytes": "64",
        "spark.rapids.tpu.sql.adaptive.skewedPartitionFactor": "2.0",
        "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeInBytes": "2048"}

def run(conf):
    s = TpuSession({"spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1",
                    **conf})
    lt = s.create_dataframe(fact).repartition(8).repartition(6, "k")
    rt = s.create_dataframe(dims).repartition(4).repartition(6, "k")
    return lt.join(rt, "k").collect(), s

on, s_on = run(SKEW)
ad = s_on.last_metrics["adaptive"]
assert ad["adaptive.skew_splits"] >= 1, ad
assert "skew-split" in s_on.last_plan.tree_string()
off, _ = run({})
cols = sorted(on.column_names)
order = [(c, "ascending") for c in cols]
assert_tables_equal(off.select(cols).sort_by(order),
                    on.select(cols).sort_by(order))
print("adaptive smoke ok:", ad)
PY

echo "== tracing smoke (Q1 traced action: EXPLAIN ANALYZE + Perfetto export, >= 1 span per layer) =="
python - << 'PY'
import json, tempfile
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import BENCH_CONF, gen_lineitem, q1
from spark_rapids_tpu.utils import tracing

export = tempfile.mktemp(prefix="premerge-trace-", suffix=".json")
# forced grace partitions: the memory layer (grace split + spill events)
# must appear alongside exec/transfer/serving in the exported trace
sess = TpuSession({**BENCH_CONF,
                   "spark.rapids.tpu.sql.string.maxBytes": "16",
                   "spark.rapids.tpu.trace.enabled": "true",
                   "spark.rapids.tpu.trace.export.path": export,
                   "spark.rapids.tpu.memory.outOfCore.forcePartitions": "2"})
lineitem = gen_lineitem(scale=0.005, seed=42)
handle = sess.submit(q1(sess.create_dataframe(lineitem)))
result = handle.result(timeout=300)
assert result.num_rows > 0
doc = json.load(open(export))
events = doc["traceEvents"]
assert events and all(e["ph"] in ("X", "i") for e in events), "bad export"
layers = {}
for e in events:
    layers[e["cat"]] = layers.get(e["cat"], 0) + 1
for layer in ("exec", "transfer", "memory", "serving"):
    assert layers.get(layer, 0) >= 1, f"no {layer} spans: {layers}"
analyzed = handle.explain_analyze()
assert "rows=" in analyzed and "wall=" in analyzed, analyzed
assert "spill=" in analyzed, analyzed          # forced grace is visible
assert handle.metrics["recursion_depth_peak"] >= 1, handle.metrics
print("tracing smoke ok:", layers)
PY

echo "PREMERGE OK"
