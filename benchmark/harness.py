"""One run of one cell: set-up, the timed window, the reduction to metrics and
the comparison that decides ``correct``. ``run.py`` prints its result,
``rehearse.py`` drives the same code at a small scale on any backend, and
``sweep.py`` reuses the set-up for several offered rates.

From the program this takes the system under test (``TpuSession``,
``QueryServer``, ``QueryServiceClient``) and its counters. Traffic, metric
arithmetic, the peaks, the least-bytes count, the reference and the
comparison are the benchmark's own."""
import concurrent.futures
import functools
import importlib
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import types

from benchmark import (correct, least_bytes, loadgen, manifest, overload,
                       readers, reduce)
from benchmark.datagen import gen_tables

CPU_EXEC = re.compile(r"\bCpu\w*Exec\b")
SCAN = "CpuLocalScanExec"
#: how long past the window's close an answer is waited for before it counts
#: as one that never came
GRACE_S = 60.0
WARM_UP_PASSES = 5
TRACE_CONF = "spark.rapids.tpu.trace.enabled"
COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"


class Counters:
    """The program's counters, read at the window's two ends."""

    def __init__(self):
        import jax.monitoring
        self.xla_compile_requests = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == COMPILE_REQUEST:
            self.xla_compile_requests += 1

    def snapshot(self):
        from spark_rapids_tpu.serving.program_cache import global_program_cache
        from spark_rapids_tpu.utils.metrics import TRANSFER_METRICS
        programs = global_program_cache().stats()
        transfer = TRANSFER_METRICS.snapshot()
        return {"program_hits": programs["hits"],
                "program_misses": programs["misses"],
                # first call of each program: compile plus one execution
                "first_call_s": programs["compile_s"],
                "upload_bytes": transfer["transfer.upload_bytes"],
                # what a file scan staged in the file's own encoding, and
                # what the same columns would have staged decoded
                "encoded_bytes": transfer["transfer.encoded_bytes"],
                "decoded_equivalent_bytes":
                    transfer["transfer.decoded_equivalent_bytes"],
                "xla_compile_requests": self.xla_compile_requests}


@functools.lru_cache(maxsize=None)
def counters():
    """The process's one set of counters: a jax.monitoring listener cannot
    be taken off again, so a tool that sets up many cells shares it."""
    return Counters()


def check_device(chips, need_tpu):
    """The device as jax reports it; on a measured run, fails unless it is a
    TPU of a kind the table of peaks knows, with the chips the cell needs."""
    import jax
    import spark_rapids_tpu.device  # noqa: F401 - the program's jax set-up
    devices = jax.devices()
    first = devices[0]
    peaks = manifest.peaks().get(first.device_kind)
    if need_tpu:
        if first.platform != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, jax found platform "
                             f"{first.platform!r}")
        if peaks is None:
            raise SystemExit(f"benchmark: device kind {first.device_kind!r} "
                             "is not in benchmark/peaks.json")
        if len(devices) < chips:
            raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                             f"jax found {len(devices)}")
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices)}
    return device, peaks


def dataframes(session, tables, tables_from):
    """The cell's DataFrames as its configuration says they come
    (``tables_from``), and the directory written for them, if any.

    ``memory``: ``createDataFrame`` of each Arrow table. ``parquet``: each
    table written as ``<tmp>/<table>/part-0.parquet`` by pyarrow at its
    defaults and read by ``session.read.parquet(<tmp>/<table>)``; the
    directory lies under ``TMPDIR`` and is the caller's to remove."""
    if tables_from == "memory":
        return {name: session.createDataFrame(table)
                for name, table in tables.items()}, None
    import pyarrow.parquet as pq
    tmp = tempfile.mkdtemp(prefix="benchmark-tables-")
    try:
        dfs = {}
        for name, table in tables.items():
            table_dir = os.path.join(tmp, name)
            os.mkdir(table_dir)
            pq.write_table(table, os.path.join(table_dir, "part-0.parquet"))
            dfs[name] = session.read.parquet(table_dir)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return dfs, tmp


def setup(cell, seed, trace, scale=None, need_tpu=True, tables=None):
    """Everything before the window: device check, tables from the seed at
    the configuration's ``scale_factor`` (``scale``: another one, for a
    rehearsal; ``tables``: already made, by a tool that reads several cells
    in one process), the session (and server), the DataFrames as the
    configuration's ``tables_from`` says, every program the cell's queries
    use built."""
    mf = manifest.load()
    entry = manifest.workload_entry(mf, cell)
    workload = manifest.workload_file(cell)
    config = manifest.config_file(mf, entry["config"])
    device, peaks = check_device(entry["chips"], need_tpu)
    if scale is None:
        scale = config["scale_factor"]

    qids = sorted({q["id"] for q in workload["queries"]})
    sql = {q: manifest.query_sql(q) for q in qids}
    names = manifest.tables_named(qids, config["schema"])
    if tables is None:
        tables = gen_tables(names, scale, seed)
    tables = {name: tables[name] for name in names}

    from spark_rapids_tpu.api import TpuSession
    conf = dict(config["confs"])
    if trace:
        conf[TRACE_CONF] = "true"
    session = TpuSession(conf)
    dfs, tmp_dir = dataframes(session, tables, manifest.tables_from(config))
    st = types.SimpleNamespace(
        cell=cell, manifest=mf, entry=entry, workload=workload, config=config,
        device=device, peaks=peaks, counters=counters(), qids=qids, sql=sql,
        tables=tables, session=session, dfs=dfs, tmp_dir=tmp_dir,
        server=None, client=None, cpu_execs=set())
    try:
        if workload["entry"] == "served":
            from spark_rapids_tpu.serving.client import QueryServiceClient
            from spark_rapids_tpu.serving.server import QueryServer
            for name, df in dfs.items():
                df.createOrReplaceTempView(name)
            st.server = QueryServer(session)
            host, port = st.server.address
            st.client = QueryServiceClient([f"{host}:{port}"], session.conf)
        else:
            st.build = {
                q: importlib.import_module(f"benchmark.queries.{q}").build
                for q in qids}
        warm_up(st)
    except BaseException:
        teardown(st)
        raise
    return st


def run_query(st, qid, record=None):
    """One query through the cell's entry point, to its Arrow table. A served
    request's ``record`` gets what the server shipped with the result."""
    if st.client is None:
        return st.build[qid](st.dfs).collect()
    handle = st.client.submit(st.sql[qid], label=qid)
    table = handle.result()
    if record is not None:
        record["queue_wait_s"] = handle.metrics.get("queue_wait_s")
    return table


def note_plan(st):
    """Record any operator of the last plan that the CPU engine got."""
    tree = st.session.last_plan.tree_string()
    st.cpu_execs |= set(CPU_EXEC.findall(tree)) - {SCAN}


def warm_up(st):
    """Each query until a whole pass builds and compiles nothing."""
    for _ in range(WARM_UP_PASSES):
        before = st.counters.snapshot()
        for qid in st.qids:
            run_query(st, qid)
            note_plan(st)
        after = st.counters.snapshot()
        if readers.counter_delta(
                {"before": before, "after": after},
                ["xla_compile_requests", "program_misses"]) == 0:
            return
    raise RuntimeError(f"{st.cell}: still compiling after "
                       f"{WARM_UP_PASSES} warm-up passes")


def teardown(st):
    """Stop what set-up started; wait for it."""
    if st.client is not None:
        st.client.close()
        st.client = None
    if st.server is not None:
        st.server.shutdown()
        st.session.scheduler.shutdown(wait=True, timeout=GRACE_S)
        st.server = None
    if st.tmp_dir is not None:
        shutil.rmtree(st.tmp_dir, ignore_errors=True)
        st.tmp_dir = None


class SliceTracer:
    """Profiles the first seconds of a closed loop's window, and the whole of
    an open loop's (device ops and the program's host ranges; no Python
    tracer, which would slow the host). ``stop`` blocks for about as long
    as was traced, so it is called where nothing timed waits for it."""

    def __init__(self, slice_s, min_cycles=1):
        self.slice_s, self.min_cycles = slice_s, min_cycles
        self.log_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        self.running = False
        self.window_s = None
        self.done_qids = []
        self._lock = threading.Lock()

    def start(self):
        import jax.profiler
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        # level 1 keeps the program's TraceAnnotation ranges, which name the
        # idle gaps; the default's level 2 writes a trace 9 % larger (my
        # chip run, PR 24) and names nothing more
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.t_start = time.perf_counter()
        self.running = True

    def query_done(self, qid):
        with self._lock:
            if self.running:
                self.done_qids.append(qid)

    def due(self, cycles):
        return (self.running and cycles >= self.min_cycles
                and time.perf_counter() - self.t_start >= self.slice_s)

    def stop(self):
        import jax.profiler
        with self._lock:
            if not self.running:
                return
            self.running = False
            self.window_s = time.perf_counter() - self.t_start
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - self.t_start - self.window_s
        print(f"benchmark: trace of {self.window_s:.2f} s closed in "
              f"{self.stop_s:.2f} s", file=sys.stderr, flush=True)

    def reduce(self):
        """reduce.py's numbers for the slice, or None without device ops."""
        try:
            return reduce.reduce_file(reduce.find_xplane(self.log_dir),
                                      self.window_s)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


def closed_window(st, seconds, tracer=None):
    """One client, each query after the last returned. Ends with the cycle
    in which ``seconds`` ran out, so every kind of query weighs the same."""
    order = loadgen.cycle(st.workload)
    answers = {q: [] for q in st.qids}
    attempted = failed = cycles = 0
    before = st.counters.snapshot()
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    while True:
        for qid in order:
            attempted += 1
            try:
                answers[qid].append(run_query(st, qid))
            except Exception as e:  # a failed query is counted, not fatal
                failed += 1
                print(f"benchmark: {qid} failed: {e!r}", flush=True)
                continue
            if cycles == 0:
                note_plan(st)
            if tracer:
                tracer.query_done(qid)
        cycles += 1
        if tracer and tracer.due(cycles):
            tracer.stop()
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    if tracer:
        tracer.stop()
    done = attempted - failed
    return types.SimpleNamespace(
        answers=answers, attempted=attempted, failed=failed, unanswered=0,
        queries=done, before=before,
        after=st.counters.snapshot(), requests=[],
        end_to_end={"query_wall_s": wall / done if done else None})


def _serve_one(st, rec, t0, tracer):
    rec["sent"] = time.perf_counter() - t0
    try:
        rec["table"] = run_query(st, rec["qid"], rec)
    except Exception as e:  # refused or failed: counted in `failed`
        rec["error"] = repr(e)
    rec["done"] = time.perf_counter() - t0
    if tracer and "error" not in rec:
        tracer.query_done(rec["qid"])


def open_window(st, seconds, seed, tracer=None, rate=None):
    """Requests sent when they are due, whatever the server is doing; each
    timed from its due time. Every request due in the window is waited for,
    up to GRACE_S past the window's close (or past the last send, should the
    host have held the generator up beyond it): an answer that comes late is
    late, not missing.

    A traced run profiles the whole window and closes the trace only after
    the last answer is in. Closing it earlier, between two requests, holds
    the generator's thread for as long as the profiler takes to write: a
    42 s slice took some 52 s here and longer in the driver's check, where
    the last request then went out with no time left to answer (PR 24)."""
    workload = dict(st.workload, rate_per_s=rate or st.workload["rate_per_s"])
    schedule = loadgen.open_schedule(workload, seed, seconds)
    records = [{"due": due, "qid": qid} for due, qid in schedule]
    before = st.counters.snapshot()
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=len(records))
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    futures = []
    for rec in records:
        delay = t0 + rec["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        futures.append(pool.submit(_serve_one, st, rec, t0, tracer))
    close = max(t0 + seconds, time.perf_counter())
    concurrent.futures.wait(
        futures, timeout=close + GRACE_S - time.perf_counter())
    if tracer:
        tracer.stop()
    pool.shutdown(wait=False, cancel_futures=True)
    answers = {q: [] for q in st.qids}
    latencies, failed, unanswered = [], 0, 0
    for rec in records:
        rec["late_s"] = rec["sent"] - rec["due"] if "sent" in rec else None
        print("benchmark: request", {k: v for k, v in rec.items()
                                     if k != "table"},
              file=sys.stderr, flush=True)
        if "error" in rec:
            failed += 1
        elif "table" not in rec:
            unanswered += 1
        else:
            answers[rec["qid"]].append(rec["table"])
            latencies.append(rec["done"] - rec["due"])
    return types.SimpleNamespace(
        answers=answers, attempted=len(records), failed=failed + unanswered,
        unanswered=unanswered, queries=len(latencies), before=before, after=st.counters.snapshot(), requests=records,
        last_done=max((r.get("done", 0.0) for r in records), default=0.0),
        end_to_end={"latency_p50_s": loadgen.percentile(latencies, 50),
                    "latency_p90_s": loadgen.percentile(latencies, 90)})


def window(st, seconds, seed, tracer=None, rate=None):
    """The cell's loop; ``rate``: another offered rate (a sweep)."""
    if st.workload["loop"] == "open":
        return open_window(st, seconds, seed, tracer, rate)
    if st.workload["loop"] == "open_overload":
        return overload.window(st, seconds, GRACE_S, tracer, rate)
    return closed_window(st, seconds, tracer)


def memory_stats():
    import jax
    peak = limit = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) >= peak:
            peak = stats.get("peak_bytes_in_use", 0)
            limit = stats.get("bytes_limit", 0)
    return {"peak_bytes_in_use": peak, "bytes_limit": limit}


def run_cell(cell, seed, seconds, trace, t_start, scale=None, need_tpu=True):
    """The whole run. Returns (result object for the last line, the numbers
    compared)."""
    st = setup(cell, seed, trace, scale, need_tpu)
    try:
        tracer = None
        if trace:
            tracer = SliceTracer(st.workload.get("trace_slice_s", 5.0),
                                 st.workload.get("trace_min_cycles", 1))
        setup_s = time.perf_counter() - t_start
        win = window(st, seconds, seed, tracer)
        memory = memory_stats()
    finally:
        teardown(st)
    # the window has closed and the peak is read: now the reference
    ok, numbers = correct.judge(win.answers, st.tables, len(st.cpu_execs),
                                win.unanswered, win.failed - win.unanswered)
    device = dict(st.device, memory_peak_bytes=memory["peak_bytes_in_use"])
    values = dict(win.end_to_end, setup_s=setup_s)
    group = "per_layer" if trace else "end_to_end"
    breakdown = None
    if trace:
        traced = tracer.reduce()
        if traced is None:
            if need_tpu:
                raise RuntimeError("no operation ran on the device in the "
                                   "traced slice")
        else:
            traced["queries"] = len(tracer.done_qids)
            traced["least_bytes"] = sum(
                least_bytes.least_bytes(st.sql[q], st.tables,
                                        win.answers[q][0])
                for q in tracer.done_qids)
            device.update(busy_s=traced["busy_s"],
                          window_s=traced["window_s"])
            breakdown = {"device_ops": traced["device_ops"],
                         "idle_gaps": traced["idle_gaps"]}
        ctx = {"queries": win.queries, "before": win.before,
               "after": win.after, "requests": win.requests, "trace": traced,
               "memory": memory, "peaks": st.peaks}
        values = {m["name"]: readers.read(
            m["name"], manifest.metric_file(m["name"]), ctx)
            for m in manifest.metrics_of(st.manifest, cell, group)}
    result = {
        "correct": ok, "attempted": win.attempted, "failed": win.failed,
        "metrics": {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in manifest.metrics_of(st.manifest, cell, group)
            if values.get(m["name"]) is not None},
        "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    # last, each number compared beside its limit
    result["compared"] = {n["name"]: [n["value"], n["limit"]]
                          for n in numbers}
    return result, numbers, win
