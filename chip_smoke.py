"""On-chip smoke: TPC-H through the engine's normal entry points on one TPU.

One process, in order: device check, a collect leg (Q1, Q6, Q3 through
``TpuSession.collect``), an exchange leg (both Pallas kernels, compiled by
Mosaic; every row compared, partition by partition: the placement that the
benchmark's cell of the exchange, ``tpch_sf1_exchange.repartition``, cannot
see in Q1's answer, while the cell times what this leg only walks), a served
leg (``QueryServer`` + ``QueryServiceClient`` over TCP
localhost) and, on four or more devices, a mesh leg. Every answer is compared
with the CPU engine's on the same tables, outside the timed calls. The first
failed check ends the process with a non-zero code; without a TPU it fails
before any data is generated. The second-to-last line of stdout is the
smoke's summary (``summary: {...}``: legs, walls, programs compiled); the last
is ``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing else.

Walls printed here are smoke walls — one cold reading each, almost all of it
XLA compilation. They are not benchmark metrics. What is left out of the
default run is left out for the 1200 s a cold run may take, not for scale.

    python chip_smoke.py [--scale 1.0] [--seed 7] [--queries 1 6 3 18]
"""
import argparse
import importlib.metadata
import json
import re
import time

# the two switches the reference itself flips for TPC-H: float sums may
# reassociate, and the generator emits no NaN
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false"}
CPU_ENGINE = {"spark.rapids.tpu.sql.enabled": "false"}

#: the collect leg's default. Q18 (1.5M-group aggregate, three joins) runs
#: and passes on the chip but its thirteen programs' first calls take 842 s
#: cold (916 s to the first timed query; my chip run, PR 32, PERF.md section
#: 6), so it is asked for: --queries 1 6 3 18. Its cell in the benchmark is
#: tpch_sf1_highcard.q18
COLLECT_QUERIES = (1, 6, 3)
SERVED_QUERIES = (1, 6, 3)
#: final sort keys that can tie (tests/test_tpch_full.py): unordered compare
TIES = {3, 18}
EXCHANGE_PARTITIONS = 8
MESH_DEVICES = 4


def check(cond, message):
    """assert that survives ``python -O``."""
    if not cond:
        raise AssertionError(message)


def require_tpu():
    """Device leg: fail unless jax runs on a TPU; report the installation."""
    import jax
    import spark_rapids_tpu.device  # noqa: F401 - the package's jax set-up
    from spark_rapids_tpu import native
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax found platform {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    runtime = ".so" if native.try_get_lib() is not None else "python fallback"
    print(f"device: {device} backend={jax.default_backend()} {versions}")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    print(f"native runtime: {runtime}", flush=True)
    return device


class XlaCompileCounter:
    """Counts jax's persistent-cache traffic: compile requests, executables
    served from the cache, executables compiled and written to it."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "written"}

    def __init__(self):
        import jax.monitoring
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        name = self.EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1


def device_only_plan(sess, label):
    """Fail if the executed plan holds a Cpu*Exec other than the scan."""
    tree = sess.last_plan.tree_string()
    on_cpu = sorted(set(re.findall(r"\bCpu\w*Exec\b", tree))
                    - {"CpuLocalScanExec"})
    if on_cpu:
        print(sess.last_explain)
        print(tree)
    check(not on_cpu, f"{label}: planned on the CPU engine: {on_cpu}")
    return tree


def collect_leg(tables, tpu, cpu, dfs, queries):
    """Each query through collect(), fully placed, equal to the CPU engine."""
    from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
    from spark_rapids_tpu.testing import assert_tables_equal
    cpu_dfs = {k: cpu.createDataFrame(v) for k, v in tables.items()}
    results, walls = {}, {}
    for q in queries:
        t0 = time.perf_counter()
        got = QUERIES[q](dfs).collect()
        walls[f"q{q}"] = round(time.perf_counter() - t0, 3)
        device_only_plan(tpu, f"Q{q}")
        expected = QUERIES[q](cpu_dfs).collect()
        check(expected.num_rows > 0, f"Q{q}: reference answer is empty")
        assert_tables_equal(expected, got, ignore_order=q in TIES,
                            approx_float=1e-9)
        results[q] = got
        print(f"collect: Q{q} ok rows={got.num_rows} "
              f"smoke_wall_s={walls[f'q{q}']}", flush=True)
    return results, walls


def exchange_leg(tables, cpu):
    """lineitem.repartition(8, "l_orderkey").collect(), twice: the reorder
    kernel with the gather consolidation (default confs), then with the DMA
    compaction kernel. Every row of every column must come back bit for bit,
    the same number per partition as from the CPU engine, and the compiled
    kernel — not the sort, not the interpreter — must have split the batch.
    The exchange's key sketch (64 masked minima in the chip's own uint32
    arithmetic) must give the distinct-count estimate numpy's gives.

    The check is on whole rows because an aggregate to check it by would cost
    minutes: a cold 1.5M-group `agg` program compiles for 2-4 minutes on the
    chip (PERF.md section 5), the whole leg's share of the time limit."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.api.dataframe import _iter_execs
    from spark_rapids_tpu.columnar.dtypes import DType
    from spark_rapids_tpu.execs import exchange_execs as xe
    from spark_rapids_tpu.exprs.core import ColV
    from spark_rapids_tpu.shuffle import partition_kernel as pk

    def run(sess):
        """(collected rows, partition-major; the plan's one exchange)"""
        got = (sess.createDataFrame(tables["lineitem"])
               .repartition(EXCHANGE_PARTITIONS, "l_orderkey").collect())
        exchanges = [nd for nd in _iter_execs(sess.last_plan)
                     if isinstance(nd, xe.ShuffleExchangeExecBase)]
        check(len(exchanges) == 1, sess.last_plan.tree_string())
        return got, exchanges[0]

    def by_partition(rows, per_partition):
        """Rows tagged with the partition their position puts them in, in
        one canonical order (lineitem's primary key within a partition)."""
        part = np.repeat(np.arange(len(per_partition)), per_partition)
        return rows.append_column("part", pa.array(part)).sort_by(
            [("part", "ascending"), ("l_orderkey", "ascending"),
             ("l_linenumber", "ascending")])

    reference, _ = run(cpu)
    check(reference.num_rows == tables["lineitem"].num_rows,
          "exchange: the reference lost rows")
    orderkeys = tables["lineitem"].column("l_orderkey").to_numpy()
    with np.errstate(over="ignore"):
        hashes = xe._column_hash(np, ColV(
            DType.LONG, orderkeys, np.ones(len(orderkeys), dtype=bool)))
    host_ndv = xe._kmv_estimate(
        xe._kmv_merge(np.zeros(0, dtype=np.uint32), hashes))
    true_ndv = len(np.unique(orderkeys))
    walls, expected, expected_sizes = {}, None, None
    dma = "spark.rapids.tpu.shuffle.kernel.dmaConsolidate.enabled"
    for name, extra in (("gather", {}), ("dma", {dma: "true"})):
        sess = TpuSession({**CONF, **extra})
        t0 = time.perf_counter()
        got, exchange = run(sess)
        walls[name] = round(time.perf_counter() - t0, 3)
        device_only_plan(sess, f"exchange/{name}")
        by_kernel = exchange.metrics[xe.KERNEL_SPLIT_BATCHES].value
        by_sort = exchange.metrics[xe.SORT_SPLIT_BATCHES].value
        stats = exchange.stage_stats()
        per_partition = stats.partition_rows
        print(f"exchange/{name}: rows={got.num_rows} "
              f"per_partition={per_partition} kernel_batches={by_kernel} "
              f"sort_batches={by_sort} ndv={stats.key_distinct} "
              f"smoke_wall_s={walls[name]}", flush=True)
        check(stats.key_distinct == (host_ndv,)
              and abs(host_ndv - true_ndv) <= 0.4 * true_ndv,
              f"exchange/{name}: key_distinct {stats.key_distinct}, numpy's "
              f"sketch of the same keys {host_ndv}, distinct keys {true_ndv}")
        check(by_kernel >= 1 and by_sort == 0,
              f"exchange/{name}: kernel split {by_kernel} batch(es), "
              f"the sort {by_sort}")
        check(got.num_rows == reference.num_rows == sum(per_partition),
              f"exchange/{name}: {got.num_rows} rows came back")
        # both engines collect partition by partition: cut both at the
        # device's partition sizes, so a row in another partition than the
        # CPU engine put it in shows as a difference
        got = by_partition(got, per_partition)
        if per_partition != expected_sizes:
            expected = by_partition(reference, per_partition)
            expected_sizes = per_partition
        differing = [c for c in expected.column_names
                     if not got.column(c).equals(expected.column(c))]
        check(got.schema.equals(expected.schema) and not differing,
              f"exchange/{name}: rows differ from the CPU engine's in "
              f"{differing or 'schema'}")
    # ("pkern", spec, geom, cap, interpret): no reorder program interpreted
    reorder = [k for k in pk._PROGRAMS if k[0] == "pkern"]
    interpreted = [k for k in reorder if k[-1]]
    check(reorder and not interpreted,
          f"reorder programs: {len(reorder)}, interpreted: {len(interpreted)}")
    check(any(k[0] == "pdma" for k in pk._PROGRAMS),
          "the DMA compaction kernel never compiled")
    print(f"exchange: ok, {len(reorder)} reorder program(s) compiled by "
          f"Mosaic, 0 interpreted; DMA compaction compiled", flush=True)
    return walls


def served_leg(tpu, dfs, collected):
    """SQL over TCP through QueryServer/QueryServiceClient; answers equal the
    collect leg's; nothing left holding the device afterwards."""
    from spark_rapids_tpu.benchmarks.tpch_sql import SQL_QUERIES
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.serving.client import QueryServiceClient
    from spark_rapids_tpu.serving.server import QueryServer
    from spark_rapids_tpu.testing import assert_tables_equal
    for name, df in dfs.items():
        df.createOrReplaceTempView(name)
    server = QueryServer(tpu)
    host, port = server.address
    client = QueryServiceClient([f"{host}:{port}"], tpu.conf)
    try:
        t0 = time.perf_counter()
        handles = {q: client.submit(SQL_QUERIES[q], label=f"q{q}")
                   for q in SERVED_QUERIES}
        answers = {q: h.result() for q, h in handles.items()}
        wall = round(time.perf_counter() - t0, 3)
        for q, got in answers.items():
            assert_tables_equal(collected[q], got, ignore_order=q in TIES,
                                approx_float=1e-9)
        holders = DeviceManager.get().semaphore.active_holders
        check(holders == 0, f"served: {holders} semaphore holder(s) left")
        check(not server._queries,
              f"served: {len(server._queries)} query record(s) left")
    finally:
        client.close()
        server.shutdown()
    print(f"served: Q{', Q'.join(map(str, SERVED_QUERIES))} ok over "
          f"{host}:{port} smoke_wall_s={wall}", flush=True)
    return {"all": wall}


def mesh_leg(tables, collected_q3):
    """Q3 sharded over four devices: mesh join + aggregate in the plan, a
    scattered column really spread over four devices, same answer; the
    tables stay on the mesh, so a second Q3 scatters none."""
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.api.dataframe import _iter_execs
    from spark_rapids_tpu.benchmarks.tpch_queries import QUERIES
    from spark_rapids_tpu.execs.base import ExecContext
    from spark_rapids_tpu.execs.mesh_execs import MeshScatterExec
    from spark_rapids_tpu.testing import assert_tables_equal
    from spark_rapids_tpu.utils.metrics import TRANSFER_METRICS
    sess = TpuSession({
        **CONF, "spark.rapids.tpu.sql.mesh.enabled": "true",
        "spark.rapids.tpu.sql.mesh.numDevices": str(MESH_DEVICES)})
    dfs = {k: sess.createDataFrame(v) for k, v in tables.items()}
    t0 = time.perf_counter()
    got = QUERIES[3](dfs).collect()
    wall = round(time.perf_counter() - t0, 3)
    tree = device_only_plan(sess, "mesh/Q3")
    for name in ("MeshShuffledHashJoinExec", "MeshHashAggregateExec"):
        check(name in tree, f"mesh/Q3: no {name} in the plan:\n{tree}")
    assert_tables_equal(collected_q3, got, ignore_order=True,
                        approx_float=1e-9)
    scatters = [nd for nd in _iter_execs(sess.last_plan)
                if isinstance(nd, MeshScatterExec)]
    check(scatters, f"mesh/Q3: no MeshScatterExec in the plan:\n{tree}")
    # where the scattered tables lie: the scan cache serves each scatter
    # the batch the query read, so running them again uploads nothing
    uploaded = TRANSFER_METRICS.snapshot()["transfer.upload_bytes"]
    for scatter in scatters:
        (batch,) = list(scatter.execute(ExecContext(sess.conf)))
        devices = batch.columns[0].data.sharding.device_set
        check(len(devices) == MESH_DEVICES,
              f"mesh/Q3: scattered column lives on {len(devices)} device(s)")
    again = QUERIES[3](dfs).collect()
    assert_tables_equal(got, again)
    moved = TRANSFER_METRICS.snapshot()["transfer.upload_bytes"] - uploaded
    check(moved == 0, f"mesh/Q3: a second collect() scattered {moved} bytes "
                      "of tables that should be resident")
    print(f"mesh: Q3 ok over {sorted(d.id for d in devices)} "
          f"smoke_wall_s={wall}", flush=True)
    return {"q3": wall}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="TPC-H scale factor (default 1.0, the spec's "
                         "smallest official scale)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--queries", type=int, nargs="+", default=COLLECT_QUERIES,
                    help="TPC-H queries of the collect leg (default "
                         "%(default)s; must include the served leg's "
                         f"{SERVED_QUERIES})")
    args = ap.parse_args(argv)
    if not set(SERVED_QUERIES) <= set(args.queries):
        ap.error(f"--queries must include {SERVED_QUERIES}")

    t_start = time.perf_counter()
    device = require_tpu()
    xla = XlaCompileCounter()

    import jax
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.benchmarks.tpch_data import gen_all
    from spark_rapids_tpu.serving.program_cache import global_program_cache

    t0 = time.perf_counter()
    tables = gen_all(args.scale, args.seed)
    gen_s = round(time.perf_counter() - t0, 3)
    print(f"data: TPC-H SF{args.scale:g} seed={args.seed} "
          f"lineitem={tables['lineitem'].num_rows} rows, generated in "
          f"{gen_s}s", flush=True)

    tpu = TpuSession(CONF)
    cpu = TpuSession({**CONF, **CPU_ENGINE})
    dfs = {k: tpu.createDataFrame(v) for k, v in tables.items()}

    legs = {"datagen": {"all": gen_s}}
    collected, legs["collect"] = collect_leg(tables, tpu, cpu, dfs,
                                             args.queries)
    legs["exchange"] = exchange_leg(tables, cpu)
    legs["served"] = served_leg(tpu, dfs, collected)
    if jax.device_count() >= MESH_DEVICES:
        legs["mesh"] = mesh_leg(tables, collected[3])
        mesh = "ok"
    else:
        print(f"mesh: not run ({jax.device_count()} device)", flush=True)
        mesh = "not run"

    # the smoke's own record, then — last, alone — the line the chip check
    # reads: exactly {"ok", "device": {"platform", "kind", "count"}}
    print("summary: " + json.dumps({
        "ok": True,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n_devices": device["count"],
        "seed": args.seed,
        "scale": args.scale,
        "queries": list(args.queries),
        "smoke_wall_s": legs,
        "total_smoke_wall_s": round(time.perf_counter() - t_start, 3),
        "mesh": mesh,
        "programs": global_program_cache().stats(),
        "xla_persistent_cache": xla.counts,
        "claim": None,
    }))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
