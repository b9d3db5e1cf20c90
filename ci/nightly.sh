#!/usr/bin/env bash
# Nightly pipeline (jenkins/spark-tests.sh analog): the FULL suite including
# the benchmark-correctness runs (TPC-H/DS/xBB/Mortgage, mesh TPC-H/scale,
# cluster two-process), then the on-chip smoke when RUN_TPU_BENCH=1.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

echo "== tpu-lint strict (baseline ignored: grandfathered debt stays visible; stale baseline entries AND stale inline suppressions fail with remove-me; R012 races, R013-R015 exception-flow AND R016-R018 program-cache key-soundness rules run with ZERO baseline entries) =="
python -m spark_rapids_tpu.analysis --strict --profile spark_rapids_tpu/

echo "== full suite (incl. slow) =="
python -m pytest tests/ -q

echo "== shuffle fault injection (deterministic chaos, fixed seed) =="
python -m pytest tests/test_shuffle_faults.py -q

echo "== shuffle fault injection over lz4-compressed payloads =="
# same chaos matrix with every payload lz4-compressed: corrupt-frame
# recovery (checksum over the on-wire bytes -> retry) is exercised on
# compressed frames, not just copy-codec ones
SHUFFLE_FAULTS_CODEC=lz4 python -m pytest tests/test_shuffle_faults.py -q

echo "== lineage-scoped stage recompute suite (seeded kill_peer, scope fidelity, spill crc) =="
python -m pytest tests/test_recompute.py -q

echo "== serving wire fault matrix (seeded chaos against query submission + result streams) =="
python - << 'PY'
import time
import numpy as np, pyarrow as pa
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient, WireQueryError
from spark_rapids_tpu.serving.server import QueryServer

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
rng = np.random.default_rng(7)
table = pa.table({"k": rng.integers(0, 8, 20000).astype("int64"),
                  "v": rng.random(20000)})
SQL = "SELECT k, v FROM t WHERE v > 0.5"

def serve(server_faults=""):
    sess = TpuSession({**CONF, **({"spark.rapids.tpu.serving.net.faults.plan":
                                   server_faults,
                                   "spark.rapids.tpu.serving.net.faults.seed":
                                   "7"} if server_faults else {})})
    sess.create_dataframe(table).repartition(4).createOrReplaceTempView("t")
    ref = sess.sql(SQL).collect()
    server = QueryServer(sess)
    host, port = server.address
    return sess, server, f"{host}:{port}", ref

# server-side send faults: every kind must still deliver a correct result
for kind in ("corrupt_frame:after=1", "delay_frame:after=1,delay_ms=80",
             "dup_frame:after=2", "corrupt_frame:after=1,count=2"):
    sess, server, addr, ref = serve(kind)
    client = QueryServiceClient([addr], TpuConf())
    got = client.submit(SQL).result()
    assert got.equals(ref), f"{kind}: wrong result"
    fired = server.transport.plan.fired
    assert fired, f"{kind}: fault never fired"
    client.close(); server.shutdown()
    print(f"wire fault ok: {kind} fired={len(fired)}")

# client-side drop mid-stream: prompt failure with batches-delivered count
sess, server, addr, ref = serve()
client = QueryServiceClient([addr], TpuConf({
    "spark.rapids.tpu.serving.net.faults.plan": "drop_conn:after=2",
    "spark.rapids.tpu.serving.net.faults.seed": "7",
    "spark.rapids.tpu.shuffle.maxRetries": "1"}))
t0 = time.perf_counter()
try:
    client.submit(SQL).result()
    raise AssertionError("drop_conn stream unexpectedly succeeded")
except WireQueryError as e:
    assert e.batches_delivered == 1, e.batches_delivered
    assert time.perf_counter() - t0 < 60, "drop must fail promptly"
    print(f"wire fault ok: drop_conn delivered={e.batches_delivered}")
client.close(); server.shutdown()

# submit-path request failure surfaces cleanly
sess, server, addr, ref = serve()
client = QueryServiceClient([addr], TpuConf({
    "spark.rapids.tpu.serving.net.faults.plan":
        "fail_request:req_type=serve.submit,after=1",
    "spark.rapids.tpu.serving.net.faults.seed": "3"}))
try:
    client.submit(SQL)
    raise AssertionError("injected submit failure did not surface")
except WireQueryError:
    pass
assert client.submit(SQL).result().equals(ref)
client.close(); server.shutdown()
print("wire fault matrix ok")
PY

echo "== two-replica warm start (shared program-cache index behind the routing client) =="
python - << 'PY'
import os, subprocess, sys, tempfile
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient

cache_dir = tempfile.mkdtemp(prefix="nightly-serving-")
ARGS = [sys.executable, "-m", "spark_rapids_tpu.serving.server",
        "--tpch-lineitem", "0.002",
        "--conf", "spark.rapids.tpu.sql.variableFloatAgg.enabled=true",
        "--conf", f"spark.rapids.tpu.serving.cache.dir={cache_dir}"]
SQL = ("SELECT l_returnflag, sum(l_extendedprice) AS rev FROM lineitem "
       "GROUP BY l_returnflag ORDER BY l_returnflag")
procs, client = [], None

def spawn():
    # stderr to a FILE: a chatty server would fill an undrained pipe
    errf = tempfile.NamedTemporaryFile(prefix="replica-err-",
                                       delete=False, mode="w+")
    proc = subprocess.Popen(ARGS, stdout=subprocess.PIPE, stderr=errf,
                            text=True,
                            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    procs.append(proc)
    line = proc.stdout.readline()
    if not line.startswith("SERVING "):
        errf.seek(0)
        raise AssertionError((line, errf.read()[-2000:]))
    _t, host, port = line.split()
    return f"{host}:{port}"

try:
    addr_a = spawn()
    client = QueryServiceClient([addr_a], TpuConf())
    ref = client.submit(SQL).result()       # replica A compiles cold
    client.close()
    addr_b = spawn()
    client = QueryServiceClient([addr_a, addr_b], TpuConf())
    got = client.submit(SQL, replica=1).result()
    assert got.equals(ref), "replica B result diverged"
    pc = client.stats(replica=1)["scheduler"]["program_cache"]
    assert pc["disk_hits"] >= 1, pc
    print("two-replica warm start ok:", pc)
finally:
    if client is not None:
        client.close()
    for p in procs:
        p.terminate()
        p.wait(timeout=30)
PY

echo "== replica-kill chaos matrix (seeded kill_peer across submit/stream/drain phases) =="
python - << 'PY'
import time
import numpy as np, pyarrow as pa
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient, WireQueryError
from spark_rapids_tpu.serving.server import QueryServer

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
CLIENT_CONF = {"spark.rapids.tpu.shuffle.maxRetries": "0",
               "spark.rapids.tpu.shuffle.connectTimeout": "2",
               "spark.rapids.tpu.serving.health.probeIntervalSeconds": "0",
               "spark.rapids.tpu.serving.failover."
               "breakerFailureThreshold": "1"}
rng = np.random.default_rng(7)
table = pa.table({"k": rng.integers(0, 8, 20000).astype("int64"),
                  "v": rng.random(20000)})
SQL = "SELECT k, v FROM t WHERE v > 0.5"

def serve(faults=""):
    sess = TpuSession({**CONF, **({
        "spark.rapids.tpu.serving.net.faults.plan": faults,
        "spark.rapids.tpu.serving.net.faults.seed": "7"} if faults else {})})
    sess.create_dataframe(table).repartition(4).createOrReplaceTempView("t")
    ref = sess.sql(SQL).collect()
    server = QueryServer(sess)
    host, port = server.address
    return sess, server, f"{host}:{port}", ref

# each phase kills replica A at a different point; the bar is always the
# same: every query the CALLER sees completes with the correct result
for phase, plan in (("submit", "kill_peer:req_type=serve.submit,after=1"),
                    ("stream", "kill_peer:req_type=data,after=2"),
                    ("drain", "kill_peer:req_type=serve.drain,after=1")):
    sess_a, server_a, addr_a, ref = serve(plan)
    sess_b, server_b, addr_b, _ = serve()
    client = QueryServiceClient([addr_a, addr_b], TpuConf(CLIENT_CONF))
    try:
        if phase == "drain":
            got = client.submit(SQL, replica=0).result()
            assert got.equals(ref)
            try:
                client.drain_replica(0)     # the kill fires HERE
            except WireQueryError:
                pass                        # replica died mid-drain
        else:
            # submit-phase: the 1st routed submit's handler kills A ->
            # the submission reroutes; stream-phase: frame 2 kills A ->
            # the stream resumes on B. Zero caller-visible errors.
            pin = 0 if phase == "stream" else None
            got = client.submit(SQL, replica=pin).result()
            assert got.equals(ref), f"{phase}: wrong result"
        # after the kill every new submission lands on the survivor
        for _ in range(2):
            assert client.submit(SQL).result().equals(ref)
        fired = [f for f in server_a.transport.plan.fired
                 if f[0] == "kill_peer"]
        assert fired, f"{phase}: the seeded kill never fired"
        print(f"replica-kill ok: {phase} fired={fired}")
    finally:
        client.close()
        server_a.shutdown(); server_b.shutdown()
        sess_a.scheduler.drain(timeout=60)
        sess_b.scheduler.drain(timeout=60)
print("replica-kill chaos matrix ok")
PY

echo "== cluster recompute chaos matrix (drop_conn / corrupt beyond retry / kill_peer, zero caller-visible errors) =="
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
        "spark.rapids.tpu.shuffle.fetch.timeoutSeconds": "10"}
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

# every column breaches the transfer-retry layer (PR 2) a different way;
# the bar is always the same: the lineage recompute layer absorbs it with
# zero caller-visible errors and a bit-identical collect
# - drop_conn count=0: exec-0's receive path from exec-1 is permanently
#   dead -> retries exhaust, exec-1's blocks replay onto exec-0
# - corrupt_frame count=0: every frame exec-1 sends fails the checksum
#   beyond retry -> same scoped replay, survivors serve locally
# - kill_peer: exec-1 dies mid-stream on its 1st data frame
MATRIX = (("drop_conn", "drop_conn:owner=exec-0,peer=exec-1,count=0"),
          ("corrupt-beyond-retry", "corrupt_frame:owner=exec-1,count=0"),
          ("kill_peer", "kill_peer:owner=exec-1,req_type=data,after=1"))
for name, plan in MATRIX:
    s = TpuSession({**BASE,
                    "spark.rapids.tpu.shuffle.transport.class":
                        "spark_rapids_tpu.shuffle.faults."
                        "FaultInjectingTransport",
                    "spark.rapids.tpu.shuffle.faults.plan": plan,
                    "spark.rapids.tpu.shuffle.faults.seed": "7"})
    before = mt.recompute_snapshot()
    got = run(s)                            # zero caller-visible errors
    delta = mt.recompute_delta(before)
    assert delta["shuffle.recomputes"] >= 1, (name, delta)
    assert delta["shuffle.recompute_escalations"] == 0, (name, delta)
    sched = s._cluster_scheduler
    total_maps = sum(st.num_tasks for st in sched.last_stages
                     if not st.is_result)
    assert delta["shuffle.recomputed_map_tasks"] < total_maps, (name, delta)
    assert_tables_equal(ref, got, ignore_order=True, approx_float=1e-9)
    sched.close()
    _Fabric.reset()
    print(f"recompute chaos ok: {name} {delta}")
print("cluster recompute chaos matrix ok")
PY

echo "== drain under load (zero dropped queries, transparent rerouting) =="
python - << 'PY'
import time
import numpy as np, pyarrow as pa
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient
from spark_rapids_tpu.serving.server import QueryServer
from spark_rapids_tpu.utils import metrics as um

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true"}
rng = np.random.default_rng(7)
table = pa.table({"k": rng.integers(0, 8, 50000).astype("int64"),
                  "v": rng.random(50000)})
SQL = "SELECT k, v FROM t WHERE v > 0.5"

def serve():
    sess = TpuSession(CONF)
    sess.create_dataframe(table).repartition(6).createOrReplaceTempView("t")
    ref = sess.sql(SQL).collect()
    server = QueryServer(sess)
    host, port = server.address
    return sess, server, f"{host}:{port}", ref

sess_a, server_a, addr_a, ref = serve()
sess_b, server_b, addr_b, _ = serve()
client = QueryServiceClient(
    [addr_a, addr_b],
    TpuConf({"spark.rapids.tpu.serving.health.probeIntervalSeconds": "0"}))
d0 = um.SERVING_METRICS[um.SERVING_DRAINS].value
try:
    # queries in flight on BOTH replicas when the drain lands
    inflight = [client.submit(SQL) for _ in range(6)]
    ack = client.drain_replica(0)
    assert ack["state"] == "DRAINING", ack
    # new submissions while A drains: transparent rerouting, no errors
    rerouted = [client.submit(SQL) for _ in range(6)]
    for h in rerouted:
        assert h.replica == addr_b, h.replica
    # ZERO dropped queries: every handle (in-flight at drain time and
    # after) completes with the correct result
    for h in inflight + rerouted:
        assert h.result().equals(ref), "drain dropped a query"
    assert um.SERVING_METRICS[um.SERVING_DRAINS].value - d0 == 1
    deadline = time.time() + 60
    while not server_a.drained() and time.time() < deadline:
        time.sleep(0.1)
    assert server_a.drained(), "drained replica never became exit-ready"
    served_a = sess_a.scheduler.stats()["submitted"]
    served_b = sess_b.scheduler.stats()["submitted"]
    assert served_a + served_b == 12, (served_a, served_b)
    print(f"drain under load ok: A served {served_a}, B served {served_b}, "
          f"zero dropped")
finally:
    client.close()
    server_a.shutdown(); server_b.shutdown()
    sess_a.scheduler.drain(timeout=60)
    sess_b.scheduler.drain(timeout=60)
PY

echo "== autoscale chaos (supervised fleet under sustained load + seeded kill_peer: scale up, heal, shed with retry-after, converge to floor — zero caller-visible errors) =="
python - << 'PY'
import threading
import time
import numpy as np, pyarrow as pa
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.serving.client import QueryServiceClient
from spark_rapids_tpu.serving.controller import FleetController
from spark_rapids_tpu.serving.lifecycle import OverloadedError
from spark_rapids_tpu.serving.server import QueryServer
from spark_rapids_tpu.serving.supervisor import ReplicaSupervisor
from spark_rapids_tpu.shuffle.tcp import scan_registry
from spark_rapids_tpu.utils import metrics as um

import tempfile
REG = tempfile.mkdtemp(prefix="autoscale-reg-")
rng = np.random.default_rng(7)
TABLE = pa.table({"k": rng.integers(0, 8, 20000).astype("int64"),
                  "v": rng.random(20000)})
SQL = "SELECT k, v FROM t WHERE v > 0.5"
SERVE_CONF = {
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.tpu.serving.net.registryDir": REG,
    "spark.rapids.tpu.serving.health.heartbeatSeconds": "0.1",
    "spark.rapids.tpu.serving.health.livenessWindowSeconds": "0.5",
    "spark.rapids.tpu.serving.maxConcurrentQueries": "1",
    "spark.rapids.tpu.serving.maxQueuedPerTenant": "2",
    "spark.rapids.tpu.serving.overload.retryAfterSeconds": "0.1",
    "spark.rapids.tpu.serving.stats.sampleIntervalSeconds": "0.2",
}
FLEET_CONF = {
    **SERVE_CONF,
    "spark.rapids.tpu.serving.fleet.minReplicas": "1",
    "spark.rapids.tpu.serving.fleet.maxReplicas": "3",
    "spark.rapids.tpu.serving.fleet.scaleUpWatermark": "0.8",
    "spark.rapids.tpu.serving.fleet.scaleDownWatermark": "0.2",
    "spark.rapids.tpu.serving.fleet.scaleUpStableTicks": "1",
    "spark.rapids.tpu.serving.fleet.scaleDownStableTicks": "4",
    "spark.rapids.tpu.serving.fleet.scaleUpCooldownSeconds": "1",
    "spark.rapids.tpu.serving.fleet.scaleDownCooldownSeconds": "2",
    "spark.rapids.tpu.serving.fleet.superviseIntervalSeconds": "0.1",
    "spark.rapids.tpu.serving.fleet.restartBackoffMs": "50",
    "spark.rapids.tpu.serving.fleet.crashLoopThreshold": "4",
    "spark.rapids.tpu.serving.fleet.crashLoopWindowSeconds": "1",
}

class InProcReplica:
    def __init__(self, conf):
        self.sess = TpuSession(conf)
        (self.sess.create_dataframe(TABLE).repartition(3)
         .createOrReplaceTempView("t"))
        self.server = QueryServer(self.sess)
        host, port = self.server.address
        self.addr = f"{host}:{port}"
        self._exited = False

    def poll(self):
        return 0 if self._exited else None

    def terminate(self):
        def run():
            self.server.drain()
            deadline = time.time() + 60
            while not self.server.drained() and time.time() < deadline:
                time.sleep(0.05)
            self.server.shutdown()
            self.sess.scheduler.shutdown(wait=False)
            self._exited = True
        threading.Thread(target=run, daemon=True).start()

    def kill(self):
        self.server.shutdown()
        self.sess.scheduler.shutdown(wait=False)
        self._exited = True

replicas = []
chaos_armed = [True]

def spawn(slot_index):
    conf = dict(SERVE_CONF)
    if slot_index == 0 and chaos_armed[0]:
        # the seeded chaos: slot 0's FIRST incarnation kills its own
        # transport after 3 served data frames (heartbeats stop, the
        # supervisor's missed-heartbeat path must heal it); the respawn
        # comes back clean
        chaos_armed[0] = False
        conf["spark.rapids.tpu.serving.net.faults.plan"] = \
            "kill_peer:req_type=data,after=3"
        conf["spark.rapids.tpu.serving.net.faults.seed"] = "7"
    r = InProcReplica(conf)
    replicas.append(r)
    return r

sup = ReplicaSupervisor(TpuConf(FLEET_CONF), spawn=spawn)
ctl = FleetController(TpuConf(FLEET_CONF), sup)
client = QueryServiceClient(registry_dir=REG, conf=TpuConf({
    "spark.rapids.tpu.shuffle.maxRetries": "0",
    "spark.rapids.tpu.shuffle.connectTimeout": "2",
    "spark.rapids.tpu.serving.overload.clientRetries": "0",
    "spark.rapids.tpu.serving.health.probeIntervalSeconds": "0"}))

ref_sess = TpuSession({"spark.rapids.tpu.sql."
                       "variableFloatAgg.enabled": "true"})
(ref_sess.create_dataframe(TABLE).repartition(3)
 .createOrReplaceTempView("t"))
REF = ref_sess.sql(SQL).collect()

m0 = {k: um.SERVING_METRICS[k].value
      for k in (um.SERVING_RESTARTS, um.SERVING_SCALE_UPS,
                um.SERVING_SCALE_DOWNS, um.SERVING_SHEDS)}
hard_errors = []            # anything but a structured retryable shed
shed_hints = []
completed = [0]
count_lock = threading.Lock()

def load_worker(n_queries):
    for _ in range(n_queries):
        while True:
            try:
                got = client.submit(SQL).result()
                assert got.equals(REF), "wrong result under chaos"
                with count_lock:
                    completed[0] += 1
                break
            except OverloadedError as e:
                # backpressure, not an error: the shed carries the hint
                # the caller honors before resubmitting
                with count_lock:
                    shed_hints.append(e.retry_after_s)
                time.sleep(max(e.retry_after_s, 0.05))
            except Exception as e:          # noqa: BLE001
                with count_lock:
                    hard_errors.append(repr(e))
                return

try:
    sup.start(2)
    workers = [threading.Thread(target=load_worker, args=(5,))
               for _ in range(10)]
    for w in workers:
        w.start()
    # the control loop runs while the flood is on (and a grace period
    # after, so the calm fleet walks back down to the floor)
    deadline = time.time() + 300
    while any(w.is_alive() for w in workers):
        assert time.time() < deadline, "load never completed"
        ctl.tick()
        time.sleep(0.2)
    while sup.active_count() > 1 and time.time() < deadline:
        ctl.tick()
        time.sleep(0.2)
    for w in workers:
        w.join(timeout=60)

    delta = {k: um.SERVING_METRICS[k].value - v for k, v in m0.items()}
    assert not hard_errors, f"caller-visible errors: {hard_errors[:5]}"
    assert completed[0] == 50, completed
    assert delta[um.SERVING_SCALE_UPS] >= 1, delta
    assert delta[um.SERVING_SCALE_DOWNS] >= 1, delta
    assert delta[um.SERVING_RESTARTS] >= 1, \
        f"seeded kill never healed: {delta}"
    assert delta[um.SERVING_SHEDS] >= 1, delta
    assert shed_hints and all(h > 0 for h in shed_hints), \
        "a shed without a retry-after hint"
    # converged: back at the floor, every slot UP or retired, none
    # crash-looped, and the registry holds exactly the live fleet
    assert sup.active_count() == 1, sup.fleet_stats()
    states = sup.fleet_stats()["states"]
    assert set(states) <= {"UP", "STOPPED"}, states
    deadline = time.time() + 10
    while (len(scan_registry(REG, stale_after_s=0.5)) != 1
           and time.time() < deadline):
        time.sleep(0.2)
    live = scan_registry(REG, stale_after_s=0.5)
    assert len(live) == 1, f"registry does not match the fleet: {live}"
    print(f"autoscale chaos ok: {delta}, sheds={len(shed_hints)}, "
          f"final fleet={states}")
finally:
    client.close()
    ctl.stop()
    sup.stop(graceful=True)
PY

echo "== out-of-core tight-budget chaos (1/4 working set + seeded alloc-failure injection) =="
python - << 'PY'
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.benchmarks.tpch import BENCH_CONF, gen_lineitem, q1, q6
from spark_rapids_tpu.memory import faults as mfaults
from spark_rapids_tpu.memory.device_manager import DeviceManager
from spark_rapids_tpu.testing import assert_tables_equal

conf = {**BENCH_CONF, "spark.rapids.tpu.sql.string.maxBytes": "16",
        "spark.rapids.tpu.sql.scanCache.enabled": "false"}
lineitem = gen_lineitem(scale=0.05, seed=42)
refs = {}
for name, build in (("q1", q1), ("q6", q6)):
    DeviceManager.shutdown()
    sess = TpuSession(conf)
    refs[name] = build(sess.create_dataframe(lineitem)).collect()
    upload = sess.last_metrics["transfer"]["transfer.upload_bytes"]
# device budget clamped to ~1/4 of the measured working set, PLUS seeded
# allocation-failure injection so the reactive path fires even where the
# footprint estimate would have predicted cleanly
budget = max(int(upload // 4), 64 << 10)
chaos = {**conf,
         "spark.rapids.tpu.memory.tpu.poolSizeBytes": str(budget),
         "spark.rapids.tpu.memory.host.spillStorageSize": str(budget),
         "spark.rapids.tpu.memory.faults.plan":
             "alloc_fail:op=*,after=1,count=2;budget_clamp:fraction=0.5",
         "spark.rapids.tpu.memory.faults.seed": "7"}
spilled = 0
for name, build in (("q1", q1), ("q6", q6)):
    DeviceManager.shutdown()
    mfaults.reset_plans()
    sess = TpuSession(chaos)
    got = build(sess.create_dataframe(lineitem)).collect()
    # completion + bit-identity under chaos is the acceptance bar (exact
    # columns bitwise, variableFloatAgg sums to 1e-9)
    assert_tables_equal(refs[name], got, approx_float=1e-9)
    mm = sess.last_metrics["memory"]
    spilled += mm["memory.bytes_spilled_to_host"]
    print(f"out-of-core chaos {name}: budget={budget} "
          f"partitions={mm['memory.spill_partitions']} "
          f"depth={mm['memory.recursion_depth_peak']} "
          f"spilled_host={mm['memory.bytes_spilled_to_host']} "
          f"spilled_disk={mm['memory.bytes_spilled_to_disk']} "
          f"pressure={mm['memory.pressure_events']}")
    if name == "q1":
        assert mm["memory.spill_partitions"] >= 2, mm
assert spilled > 0, "tight-budget chaos never spilled a byte"
# third phase: AMPLE budget + seeded allocation-failure injection — the
# plan-time footprint hint cannot predict this one, so the REACTIVE
# machinery (admission probes -> mid-stream partition switch) is what
# completes the query
DeviceManager.shutdown()
mfaults.reset_plans()
sess = TpuSession({**conf,
                   "spark.rapids.tpu.memory.faults.plan":
                       "alloc_fail:op=agg,after=1",
                   "spark.rapids.tpu.memory.faults.seed": "7"})
got = q1(sess.create_dataframe(lineitem)).collect()
assert_tables_equal(refs["q1"], got, approx_float=1e-9)
mm = sess.last_metrics["memory"]
assert mm["memory.pressure_events"] >= 1, mm
assert mm["memory.spill_partitions"] >= 2, mm
print(f"out-of-core chaos alloc_fail: partitions="
      f"{mm['memory.spill_partitions']} "
      f"pressure={mm['memory.pressure_events']}")
DeviceManager.shutdown()
print("out-of-core chaos ok")
PY

if [ "${RUN_TPU_BENCH:-0}" = "1" ]; then
    echo "== on-chip smoke (TPC-H SF1, one process; fails without a TPU) =="
    env -u JAX_PLATFORMS -u XLA_FLAGS python chip_smoke.py
fi
echo "NIGHTLY OK"
