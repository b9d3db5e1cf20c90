"""Multi-executor query execution over the shuffle-manager stack.

The load-bearing path for the accelerated shuffle protocol: a physical plan
is split into shuffle stages at exchange boundaries (Spark's DAGScheduler
role), map tasks run across executors writing each reduce partition's device
batches through the CachingShuffleWriter into that executor's spillable
shuffle catalog (RapidsShuffleInternalManager.scala:194 getWriter ->
RapidsCachingWriter), and reduce-side reads serve local blocks from the
catalog and fetch remote blocks through the transport client
(RapidsCachingReader.scala + RapidsShuffleIterator) — in-process fabric or
real TCP sockets, including executors in separate OS processes.

Contrast with the mesh engine (execs/mesh_execs.py): there an exchange is an
XLA collective inside one SPMD program; here it is the reference's
pull-based, executor-to-executor protocol. Both produce identical results —
tests assert query equality across the two paths and the single-process
engine.

Range partitioning runs its map stage as ONE task (bounds need a global
sample; the reference pays a separate sampling job for the same reason —
SamplingUtils) — the reduce side still fans out across executors.
"""
from __future__ import annotations

import atexit
import os
import pickle
import socket
import struct
import subprocess
import sys
import tempfile
import threading
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Dict, List, Optional, Sequence, Set, Tuple

import pyarrow as pa

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs.base import ExecContext, LeafExec, PhysicalExec
from spark_rapids_tpu.shuffle.manager import (CachingShuffleReader,
                                              CachingShuffleWriter, MapStatus,
                                              MapOutputTracker, ShuffleEnv,
                                              ShuffleFetchFailedError)
from spark_rapids_tpu.utils import errors as uerr
from spark_rapids_tpu.utils import metrics as mt

_TCP_TRANSPORT = "spark_rapids_tpu.shuffle.tcp.TcpTransport"


# ------------------------------------------------------------------ plan split
class ClusterShuffleReadExec(LeafExec):
    """Reduce-side leaf standing in for an exchange: reads one partition of a
    parent stage's shuffle through the executor's caching reader (the
    ShuffledBatchRDD + RapidsCachingReader composition)."""

    is_device = True

    #: map-output sizes exist only at run time (MapStatus); the stage
    #: scheduler's AQE coalescing consumes them there, not at plan time
    size_estimate_none_reason = ("remote map-output sizes are known only "
                                 "at run time (MapStatus)")

    def __init__(self, stage_index: int, output: Schema, num_parts: int):
        super().__init__(output)
        self.stage_index = stage_index
        self.num_parts = num_parts
        self.shuffle_id: Optional[int] = None  # driver assigns pre-pickle
        #: AQE partition coalescing (GpuCustomShuffleReaderExec.scala:122
        #: role on the cluster path): when set, consumer partition i reads
        #: the contiguous exchange partitions ``specs[i]`` — built by the
        #: driver from OBSERVED MapStatus sizes after the map stage ran
        self.specs: Optional[Tuple[Tuple[int, ...], ...]] = None

    @property
    def num_partitions(self) -> int:
        return len(self.specs) if self.specs is not None else self.num_parts

    def execute(self, ctx: ExecContext):
        cs = getattr(ctx, "cluster_shuffle", None)
        assert cs is not None, "cluster shuffle read outside a cluster task"
        tracker = MapOutputTracker()
        tracker.register_shuffle(self.shuffle_id)
        for st in cs.statuses[self.shuffle_id]:
            tracker.register_map_output(self.shuffle_id, st)
        pids = (self.specs[ctx.partition_id] if self.specs is not None
                else (ctx.partition_id,))
        for pid in pids:
            reader = CachingShuffleReader(cs.env, tracker, self.shuffle_id,
                                          pid)
            for batch in reader.read():
                self.count_output(batch.num_rows)
                yield batch


@dataclass
class _Stage:
    index: int
    #: exchange exec (shuffle stages) or the final plan (result stage); its
    #: subtree may contain ClusterShuffleReadExec leaves for dep stages
    root: PhysicalExec
    is_result: bool
    deps: List[int] = field(default_factory=list)
    shuffle_id: Optional[int] = None
    num_tasks: int = 1
    statuses: List[MapStatus] = field(default_factory=list)
    #: result stage only: collected tables in partition order
    result_tables: List = field(default_factory=list)
    #: broadcast stages: driver-built, shipped once per executor
    is_broadcast: bool = False
    broadcast_id: Optional[int] = None


def split_stages(final: PhysicalExec) -> Optional[List[_Stage]]:
    """Cut the plan at device shuffle-exchange boundaries. Returns None when
    the plan has exchanges the cluster cannot stage (CPU exchanges), handing
    execution back to the single-process engine."""
    from spark_rapids_tpu.execs.exchange_execs import (
        BroadcastExchangeExecBase, CpuShuffleExchangeExec, RangePartitioning,
        TpuShuffleExchangeExec)
    stages: List[_Stage] = []

    def walk(node: PhysicalExec, deps: List[int]) -> PhysicalExec:
        if isinstance(node, CpuShuffleExchangeExec):
            raise _Unstageable()
        if getattr(node, "cluster_unstageable", False):
            # extension point: an exec whose state genuinely cannot ship to
            # executor processes opts out of staging here (cached scans USED
            # to — they now ship via _ship_cached_entries; no in-tree exec
            # sets the flag today)
            raise _Unstageable()
        if isinstance(node, BroadcastExchangeExecBase):
            child_deps: List[int] = []
            new_child = walk(node.children[0], child_deps)
            if any(not stages[d].is_broadcast for d in child_deps):
                # the build side reads dep shuffles (AQE dynamic broadcast
                # after an exchange): the driver cannot serve executor
                # catalogs, so the exchange stays inline in the parent
                # stage (rebuilt per task — the pre-cut behavior)
                deps.extend(child_deps)
                return (node if new_child is node.children[0]
                        else node.with_children([new_child]))
            exchange = (node if new_child is node.children[0]
                        else node.with_children([new_child]))
            idx = len(stages)
            stages.append(_Stage(idx, exchange, is_result=False,
                                 is_broadcast=True, deps=child_deps))
            deps.append(idx)
            return ClusterBroadcastReadExec(idx, exchange.output,
                                            exchange.is_device)
        if isinstance(node, TpuShuffleExchangeExec):
            child_deps: List[int] = []
            new_child = walk(node.children[0], child_deps)
            exchange = node.with_children([new_child])
            idx = len(stages)
            n_parts = exchange.partitioning.num_partitions
            single_task = isinstance(exchange.partitioning,
                                     RangePartitioning)
            stage = _Stage(idx, exchange, is_result=False, deps=child_deps,
                           num_tasks=(1 if single_task
                                      else max(1, new_child.num_partitions)))
            stages.append(stage)
            deps.append(idx)
            return ClusterShuffleReadExec(idx, exchange.output, n_parts)
        new_kids = [walk(c, deps) for c in node.children]
        if any(a is not b for a, b in zip(new_kids, node.children)):
            return node.with_children(new_kids)
        return node

    class _Unstageable(Exception):
        pass

    try:
        result_deps: List[int] = []
        new_final = walk(final, result_deps)
    except _Unstageable:
        return None
    result = _Stage(len(stages), new_final, is_result=True, deps=result_deps,
                    num_tasks=max(1, new_final.num_partitions))
    stages.append(result)
    return stages


# ------------------------------------------------------------------ tasks
class ClusterBroadcastReadExec(LeafExec):
    """Stand-in for a broadcast exchange on the cluster path: yields the
    driver-built broadcast batch from the executor's BroadcastManager cache
    (GpuBroadcastExchangeExec's once-per-executor deserialized batch,
    GpuBroadcastExchangeExec.scala:47-66). The driver assigns broadcast_id
    pre-pickle and ships the IPC bytes to every executor before any
    consuming task runs."""

    num_partitions = 1

    #: the broadcast batch is built by the driver mid-run; its size is a
    #: runtime property of another stage's output
    size_estimate_none_reason = ("broadcast stage output is materialized "
                                 "at run time by the driver")

    def __init__(self, stage_index: int, output: Schema, device: bool):
        super().__init__(output)
        self.stage_index = stage_index
        self.is_device = device
        self.broadcast_id: Optional[int] = None  # driver assigns pre-pickle

    def execute(self, ctx: ExecContext):
        from spark_rapids_tpu.parallel.broadcast import BroadcastManager
        batch = BroadcastManager.get_batch(self.broadcast_id, self.is_device,
                                           ctx.string_max_bytes)
        self.count_output(batch.num_rows)
        yield batch


@dataclass
class ClusterTaskContext:
    env: ShuffleEnv
    statuses: Dict[int, List[MapStatus]]


@dataclass
class _TaskSpec:
    kind: str                        # "map" | "result"
    plan_blob: bytes                 # pickled stage root
    partitions: Tuple[int, ...]      # partition ids this task runs
    num_source_parts: int
    shuffle_id: Optional[int]
    num_reduce_parts: int
    dep_statuses: Dict[int, List[MapStatus]]
    conf: TpuConf


@dataclass
class _StageLineage:
    """The deterministic replay record of one map stage (Spark's lineage,
    SURVEY.md §5): everything needed to re-execute ANY of the stage's map
    tasks after its outputs are lost — the resolved sub-plan snapshot (an
    immutable pickle: the driver's ``fix`` transform mutates shared tree
    nodes, so the blob is the only stable copy), the plan-signature replay
    key (program-cache machinery — a replayed task must run the exact plan
    the original ran), the input split assignment per map id, and the dep
    stage indices whose LIVE statuses feed the replay (so a replay whose
    own inputs were lost recomputes them first, recursively)."""
    stage_index: int
    plan_blob: bytes
    signature: str
    num_source_parts: int
    num_reduce_parts: int
    dep_stage_indices: Tuple[int, ...]
    #: map_id -> the source partitions its task maps (identity for hash
    #: partitioning; ``{0: (0,)}`` for range — the single task re-samples
    #: and maps every partition, exactly like the original run)
    task_partitions: Dict[int, Tuple[int, ...]]


def _run_task(env: ShuffleEnv, spec: _TaskSpec) -> bytes:
    """Execute one task against this executor's shuffle env. Returns pickled
    [MapStatus...] for map tasks or arrow-IPC table bytes for result tasks."""
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    root = pickle.loads(spec.plan_blob)
    dm = DeviceManager.initialize(spec.conf)
    cleanups: List = []
    cs = ClusterTaskContext(env, spec.dep_statuses)

    def make_ctx(p: int) -> ExecContext:
        ctx = ExecContext(spec.conf, partition_id=p,
                          num_partitions=spec.num_source_parts,
                          device_manager=dm, cleanups=cleanups)
        ctx.cluster_shuffle = cs
        return ctx

    try:
        if spec.kind == "map":
            statuses = [
                _map_one_partition(root, make_ctx(p), p, env,
                                   spec.shuffle_id, spec.num_reduce_parts)
                for p in spec.partitions]
            return pickle.dumps(statuses)
        # result tasks keep (partition_id, ipc bytes) so the driver can
        # reassemble global partition order (sorted output depends on it)
        out: List[Tuple[int, bytes]] = []
        schema = root.output.to_pa()
        for p in spec.partitions:
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, schema) as w:
                for b in root.execute(make_ctx(p)):
                    w.write_table(b.to_arrow().cast(schema))
            out.append((p, sink.getvalue().to_pybytes()))
        return pickle.dumps(out)
    finally:
        for fn in cleanups:
            fn()


def _map_one_partition(exchange, ctx: ExecContext, p: int, env: ShuffleEnv,
                       shuffle_id: int, n_reduce: int) -> MapStatus:
    """The map side of one source partition: the exchange's own map-piece
    protocol (iter_map_pieces — shared with the single-process engine),
    cached through the caching writer (RapidsCachingWriter.write). A
    range-partitioned stage runs as one task, so it maps EVERY source
    partition here (bounds need the global sample)."""
    from spark_rapids_tpu.execs.exchange_execs import RangePartitioning
    tracker = MapOutputTracker()  # local; the real one lives on the driver
    tracker.register_shuffle(shuffle_id)
    writer = CachingShuffleWriter(env, tracker, shuffle_id, map_id=p,
                                  num_partitions=n_reduce)
    wanted = (None if isinstance(exchange.partitioning, RangePartitioning)
              else (p,))
    return writer.write(
        (j, sub) for _, j, sub in exchange.iter_map_pieces(ctx, wanted))


# ------------------------------------------------------------------ executors
class InProcessExecutor:
    """One executor inside the driver process: its own shuffle env (stores,
    catalog, transport server); tasks run on the caller thread pool."""

    def __init__(self, executor_id: str, conf: TpuConf, disk_dir: str):
        self.executor_id = executor_id
        self.env = ShuffleEnv(executor_id, conf, disk_dir=disk_dir)

    def submit(self, spec: _TaskSpec) -> bytes:
        return _run_task(self.env, spec)

    def alive(self) -> bool:
        """Liveness for recompute scheduling: an executor whose transport
        was killed (chaos kill_peer / real peer death) serves no tasks and
        is excluded from replay targets."""
        t = self.env.transport
        return not (getattr(t, "killed", False) or getattr(t, "_killed",
                                                           False))

    def cleanup_shuffle(self, shuffle_id: int) -> None:
        self.env.shuffle_catalog.remove_shuffle(shuffle_id)

    def cleanup_map_outputs(self, shuffle_id: int, map_id: int) -> None:
        self.env.shuffle_catalog.remove_map_outputs(shuffle_id, map_id)

    def send_broadcast(self, broadcast_id: int, ipc: bytes) -> None:
        # in-process executors share the driver's BroadcastManager, which
        # the scheduler already registered — nothing to ship
        pass

    def cleanup_broadcast(self, broadcast_id: int) -> None:
        pass  # driver-local removal covers the shared registry

    def put_cache(self, table_id: int, generation: int,
                  parts: List[bytes]) -> None:
        pass  # shares the driver's DeviceManager catalog — already there

    def cleanup_cache(self, table_id: int) -> None:
        pass  # CacheManager._free already dropped the shared buffers

    def close(self) -> None:
        self.env.close()


def _send_msg(sock: socket.socket, obj) -> None:
    blob = pickle.dumps(obj)
    sock.sendall(struct.pack(">I", len(blob)) + blob)


def _recv_msg(sock: socket.socket):
    hdr = b""
    while len(hdr) < 4:
        chunk = sock.recv(4 - len(hdr))
        if not chunk:
            raise ConnectionError("executor control socket closed")
        hdr += chunk
    n = struct.unpack(">I", hdr)[0]
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise ConnectionError("executor control socket closed")
        buf += chunk
    return pickle.loads(bytes(buf))


class ProcessExecutor:
    """One executor in its own OS process: the daemon builds a ShuffleEnv on
    the TCP transport and serves tasks over a control socket. Shuffle DATA
    never touches the control plane — it rides the shuffle TCP sockets
    between executor processes (metadata-via-driver, data-P2P, the
    reference's split).

    The control protocol is ASYNC: every request carries an id, the daemon
    runs tasks on its own threads, and a reader thread here routes responses
    back by id — so N tasks can be in flight per executor at once (the
    reference's task model: many concurrent tasks per executor, device
    entry gated by GpuSemaphore, not by the dispatch channel)."""

    def __init__(self, executor_id: str, conf: TpuConf):
        self.executor_id = executor_id
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        env = dict(os.environ)
        # executors are CPU-only today (docs/components.md): an accelerator
        # belongs to one process at a time and the driver holds it
        env.setdefault("JAX_PLATFORMS", "cpu")
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "spark_rapids_tpu.parallel.executor_daemon",
             "--executor-id", executor_id, "--control-port", str(port)],
            env=env)
        listener.settimeout(60)
        self.sock, _ = listener.accept()
        listener.close()
        self._send_lock = threading.Lock()
        self._pending_lock = threading.Lock()
        self._pending: Dict[int, list] = {}    # id -> [Event, response]
        self._dead = False                     # set when the reader exits
        self._ids = iter(range(1, 1 << 62))
        _send_msg(self.sock, {"type": "init", "conf": conf})
        resp = _recv_msg(self.sock)
        if resp.get("type") != "ready":
            raise RuntimeError(f"executor {executor_id} failed to start: "
                               f"{resp}")
        self._reader = threading.Thread(target=self._read_loop, daemon=True,
                                        name=f"{executor_id}-control-reader")
        self._reader.start()

    def _read_loop(self) -> None:
        try:
            while True:
                resp = _recv_msg(self.sock)
                with self._pending_lock:
                    slot = self._pending.pop(resp.get("id"), None)
                if slot is not None:
                    slot[1] = resp
                    slot[0].set()
        except (ConnectionError, OSError, EOFError):
            # executor died / socket closed: fail every in-flight request —
            # and every FUTURE one (the _dead flag; a send into a half-closed
            # socket can succeed, so waiting on a response would hang)
            with self._pending_lock:
                self._dead = True
                slots = list(self._pending.values())
                self._pending.clear()
            for slot in slots:
                slot[1] = self._lost_response()
                slot[0].set()

    def _lost_response(self) -> dict:
        return {"type": "error",
                "message": f"executor {self.executor_id} connection lost"}

    def _request(self, msg: dict) -> dict:
        rid = next(self._ids)
        slot = [threading.Event(), None]
        with self._pending_lock:
            if self._dead:
                return self._lost_response()
            self._pending[rid] = slot
        try:
            with self._send_lock:
                _send_msg(self.sock, {**msg, "id": rid})
        except (ConnectionError, OSError):
            with self._pending_lock:
                self._pending.pop(rid, None)
            return self._lost_response()
        slot[0].wait()
        return slot[1]

    def submit(self, spec: _TaskSpec) -> bytes:
        resp = self._request({"type": "task", "spec": spec})
        if resp["type"] == "error":
            payload = resp.get("error")
            decoded = (uerr.decode_error(payload) if payload is not None
                       else None)
            if isinstance(decoded, ShuffleFetchFailedError):
                # the daemon's scoped payload survived the control socket
                # via the wire codec (utils/errors.py): the recompute
                # driver keys off executor_id + blocks, which a flattened
                # traceback string would lose
                raise ShuffleFetchFailedError(
                    f"task failed on {self.executor_id}: {resp['message']}",
                    executor_id=decoded.executor_id,
                    blocks=decoded.blocks)
            # every other classified or OPAQUE error surfaces as a plain
            # driver-side failure (the recompute loop re-raises non-signals)
            raise RuntimeError(
                f"task failed on {self.executor_id}: {resp['message']}")
        return resp["blob"]

    def alive(self) -> bool:
        """Liveness probe over the control socket: a dead process (reader
        loop exited) or a daemon whose shuffle transport was killed counts
        as gone for recompute scheduling."""
        if self._dead:
            return False
        resp = self._request({"type": "ping"})
        return resp.get("type") == "pong" and not resp.get("killed", False)

    def cleanup_shuffle(self, shuffle_id: int) -> None:
        self._request({"type": "cleanup", "shuffle_id": shuffle_id})

    def cleanup_map_outputs(self, shuffle_id: int, map_id: int) -> None:
        self._request({"type": "cleanup_map", "shuffle_id": shuffle_id,
                       "map_id": map_id})

    def send_broadcast(self, broadcast_id: int, ipc: bytes) -> None:
        resp = self._request({"type": "broadcast", "bid": broadcast_id,
                              "blob": ipc})
        if resp.get("type") == "error":
            raise RuntimeError(f"broadcast push to {self.executor_id} "
                               f"failed: {resp['message']}")

    def cleanup_broadcast(self, broadcast_id: int) -> None:
        self._request({"type": "cleanup_broadcast", "bid": broadcast_id})

    def put_cache(self, table_id: int, generation: int,
                  parts: List[bytes]) -> None:
        resp = self._request({"type": "cache_put", "tid": table_id,
                              "gen": generation, "parts": parts})
        if resp.get("type") == "error":
            raise RuntimeError(f"cache push to {self.executor_id} failed: "
                               f"{resp['message']}")

    def cleanup_cache(self, table_id: int) -> None:
        self._request({"type": "cache_remove", "tid": table_id})

    def close(self) -> None:
        try:
            with self._send_lock:
                _send_msg(self.sock, {"type": "stop"})
            self.sock.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()


class _Unpicklable(Exception):
    """A stage subtree cannot ship to executors (e.g. a lambda UDF)."""


# ------------------------------------------------------------------ scheduler
class ClusterScheduler:
    """Stage-by-stage driver (the DAGScheduler role): map stages fan tasks
    across executors and register MapStatus with the driver tracker; the
    result stage's arrow output returns to the caller."""

    def __init__(self, conf: TpuConf):
        self._owned_dirs: List[str] = []
        self.conf = self._prepare_conf(conf)
        self.n = conf.get(cfg.CLUSTER_EXECUTORS)
        self._tmp = tempfile.mkdtemp(prefix="spark-rapids-tpu-cluster-")
        self._owned_dirs.append(self._tmp)
        if conf.get(cfg.CLUSTER_PROCESS_EXECUTORS):
            self.executors = [ProcessExecutor(f"exec-{i}", self.conf)
                              for i in range(self.n)]
        else:
            self.executors = [
                InProcessExecutor(f"exec-{i}", self.conf,
                                  os.path.join(self._tmp, f"exec-{i}"))
                for i in range(self.n)]
        self._next_shuffle = 0
        #: shuffle_id -> replay record, written when a map stage's tasks
        #: are built and consulted when a reduce-side fetch failure scopes
        #: lost map outputs back to this shuffle
        self._lineage: Dict[int, _StageLineage] = {}
        #: (executor identity, cache table_id) -> shipped generation
        self._shipped_caches: Dict[Tuple[int, int], int] = {}
        atexit.register(self.close)

    def _prepare_conf(self, conf: TpuConf) -> TpuConf:
        extra = {}
        if conf.get(cfg.CLUSTER_PROCESS_EXECUTORS):
            if not conf.get_raw("spark.rapids.tpu.shuffle.transport.class"):
                extra["spark.rapids.tpu.shuffle.transport.class"] = \
                    _TCP_TRANSPORT
            if not conf.shuffle_tcp_registry:
                reg = tempfile.mkdtemp(prefix="spark-rapids-tpu-registry-")
                self._owned_dirs.append(reg)
                extra["spark.rapids.tpu.shuffle.tcp.registryDir"] = reg
        return conf.with_overrides(extra) if extra else conf

    def _widen_scans(self, plan: PhysicalExec) -> PhysicalExec:
        """File scans default to one scan task; spread multi-file scans
        across the executors (FilePartition planning)."""
        import copy

        def fix(node: PhysicalExec) -> PhysicalExec:
            files = getattr(node, "files", None)
            if getattr(node, "is_file_scan", False) and files:
                n = min(len(files), 2 * len(self.executors))
                if n > 1 and node.scan_partitions == 1:
                    node = copy.copy(node)
                    node.scan_partitions = n
            return node
        return plan.transform_up(fix)

    def run(self, final: PhysicalExec) -> Optional[List[pa.Table]]:
        """Execute the plan across the cluster; None = plan not stageable
        (caller falls back to the single-process engine)."""
        final = self._widen_scans(final)
        stages = split_stages(final)
        if stages is None:
            return None
        self.last_stages = stages  # introspection for tests/explain
        self._ship_cached_entries(stages)
        shuffle_ids: List[int] = []
        broadcast_ids: List[int] = []
        try:
            for stage in stages:
                if stage.is_broadcast:
                    # the id list tracks the bid the moment it registers so
                    # a failed executor push still reaches cleanup
                    self._run_broadcast_stage(stage, stages, broadcast_ids)
                    continue
                if not stage.is_result:
                    stage.shuffle_id = self._next_shuffle
                    self._next_shuffle += 1
                    shuffle_ids.append(stage.shuffle_id)
                self._run_stage(stage, stages)
            result = stages[-1]
            return result.result_tables
        except _Unpicklable:
            # an unpicklable plan (e.g. lambda UDFs) cannot ship to
            # executors: fall back to the single-process engine
            return None
        finally:
            from spark_rapids_tpu.parallel.broadcast import BroadcastManager
            for sid in shuffle_ids:
                self._lineage.pop(sid, None)
                for ex in self.executors:
                    try:
                        ex.cleanup_shuffle(sid)
                    except Exception:
                        pass
            for bid in broadcast_ids:
                BroadcastManager.remove(bid)      # driver-local registry
                for ex in self.executors:
                    try:
                        ex.cleanup_broadcast(bid)
                    except Exception:
                        pass

    def _coalesce_stage_reads(self, stage: _Stage, stages: List[_Stage],
                              leaves: List[ClusterShuffleReadExec],
                              root: PhysicalExec) -> None:
        """AQE partition coalescing on the cluster path: group contiguous
        small reduce partitions of the stage's dep shuffles into single
        reduce tasks using the OBSERVED per-partition MapStatus sizes
        (GpuCustomShuffleReaderExec.scala:122 + coalesceShufflePartitions).
        All read leaves of one stage get IDENTICAL specs — a co-partitioned
        join's sides stay aligned, and contiguous grouping preserves
        range-partition order."""
        if not leaves or not self.conf.get(cfg.ADAPTIVE_ENABLED):
            return
        n = leaves[0].num_parts
        if n <= 1 or any(lf.num_parts != n for lf in leaves):
            return
        sizes = [0] * n
        for lf in leaves:
            dep = stages[lf.stage_index]
            if not dep.statuses:
                return
            for st in dep.statuses:
                for j, s in enumerate(st.partition_sizes):
                    sizes[j] += s
        from spark_rapids_tpu.plan.adaptive import coalesce_specs
        specs = coalesce_specs(
            sizes, self.conf.get(cfg.ADAPTIVE_ADVISORY_PARTITION_BYTES))
        if len(specs) >= n:
            return
        for lf in leaves:
            lf.specs = specs
        # a sibling source with MORE partitions than the coalesced reads
        # (e.g. a widened file scan under a union) would make the stage fan
        # past len(specs) and index out of range — coalescing only applies
        # when the reads govern the stage's partitioning
        src = root if stage.is_result else root.children[0]
        if src.num_partitions != len(specs):
            for lf in leaves:
                lf.specs = None

    def _ship_cached_entries(self, stages: List[_Stage]) -> None:
        """df.cache() on the cluster (round-4 VERDICT item 6): every cached
        entry scanned by this plan ships ONCE per executor process —
        generation-tracked, so re-materialized entries re-ship and repeat
        actions don't (the second-run-faster property). Executors register
        the partitions in their own spillable catalogs under the same
        BufferIds the scan execs resolve (HostColumnarToGpu.scala:222
        executor-side cache serving, re-targeted at the tiered store)."""
        from spark_rapids_tpu.execs.cache_execs import _CachedScanBase

        def walk(n: PhysicalExec):
            yield n
            for c in n.children:
                yield from walk(c)

        entries = {}
        for st in stages:
            for n in walk(st.root):
                if isinstance(n, _CachedScanBase):
                    entries[n.entry.table_id] = n.entry
        for e in entries.values():
            if e.buffer_ids is None:
                raise RuntimeError("cached plan reached the cluster "
                                   "scheduler unmaterialized")
            parts: Optional[List[bytes]] = None   # serialized lazily, once
            for ex in self.executors:
                key = (id(ex), e.table_id)
                if self._shipped_caches.get(key) == e.generation:
                    continue
                if parts is None:
                    parts = self._serialize_cached(e)
                ex.put_cache(e.table_id, e.generation, parts)
                self._shipped_caches[key] = e.generation

    def _serialize_cached(self, e) -> List[bytes]:
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        catalog = DeviceManager.get().catalog
        parts: List[bytes] = []
        for bid in e.buffer_ids:
            buf = catalog.acquire(bid)
            if buf is None:
                raise RuntimeError(f"cached buffer {bid} vanished while "
                                   "shipping to executors")
            try:
                table = buf.get_host_batch().to_arrow()
            finally:
                buf.close()
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, table.schema) as w:
                w.write_table(table)
            parts.append(sink.getvalue().to_pybytes())
        return parts

    def cleanup_cache(self, table_id: int) -> None:
        """unpersist() propagation: drop shipped copies everywhere."""
        for ex in self.executors:
            self._shipped_caches.pop((id(ex), table_id), None)
            try:
                ex.cleanup_cache(table_id)
            except Exception:
                pass

    def _run_broadcast_stage(self, stage: _Stage, stages: List[_Stage],
                             broadcast_ids: List[int]) -> None:
        """Build the broadcast batch ONCE on the driver and ship the
        serialized bytes to every executor (GpuBroadcastExchangeExec's
        driver-side build + TorrentBroadcast distribution,
        GpuBroadcastExchangeExec.scala:140-165). Tasks consume it through
        ClusterBroadcastReadExec -> BroadcastManager (one deserialize per
        executor process, not one per task)."""
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        from spark_rapids_tpu.parallel.broadcast import BroadcastManager

        # nested broadcasts in the build side read the driver-local registry
        root = stage.root.transform_up(lambda n: self._resolve_broadcast(
            n, stages))
        dm = DeviceManager.initialize(self.conf)
        cleanups: List = []
        ctx = ExecContext(self.conf, partition_id=0, num_partitions=1,
                          device_manager=dm, cleanups=cleanups)
        try:
            batch = next(iter(root.execute(ctx)))
            schema = root.output.to_pa()
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, schema) as w:
                w.write_table(batch.to_arrow().cast(schema))
            ipc = sink.getvalue().to_pybytes()
        finally:
            for fn in cleanups:
                fn()
        from spark_rapids_tpu.parallel.broadcast import BROADCAST_IDS
        stage.broadcast_id = next(BROADCAST_IDS)
        # track for cleanup BEFORE any push: a failed executor push must
        # not leak the driver entry or the blobs already pushed
        broadcast_ids.append(stage.broadcast_id)
        # driver-local registration first (serves in-process executors and
        # nested driver-side builds), then one push per process executor
        BroadcastManager.put(stage.broadcast_id, ipc)
        for ex in self.executors:
            ex.send_broadcast(stage.broadcast_id, ipc)

    @staticmethod
    def _resolve_broadcast(node: PhysicalExec,
                           stages: List[_Stage]) -> PhysicalExec:
        if isinstance(node, ClusterBroadcastReadExec):
            node.broadcast_id = stages[node.stage_index].broadcast_id
        return node

    def _run_stage(self, stage: _Stage, stages: List[_Stage]) -> None:
        from spark_rapids_tpu.execs.exchange_execs import RangePartitioning
        # resolve dep shuffle ids into the read leaves, then pickle
        dep_statuses: Dict[int, List[MapStatus]] = {}
        leaves: List[ClusterShuffleReadExec] = []

        def fix(node: PhysicalExec) -> PhysicalExec:
            if isinstance(node, ClusterShuffleReadExec):
                dep = stages[node.stage_index]
                node.shuffle_id = dep.shuffle_id
                dep_statuses[dep.shuffle_id] = dep.statuses
                leaves.append(node)
            return self._resolve_broadcast(node, stages)

        root = stage.root.transform_up(fix)
        self._coalesce_stage_reads(stage, stages, leaves, root)
        # task count reflects post-coalesce partitioning (a dep's observed
        # sizes may have shrunk this stage's input partition count)
        if stage.is_result:
            stage.num_tasks = max(1, root.num_partitions)
            num_source = stage.num_tasks
        else:
            num_source = max(1, root.children[0].num_partitions)
            single_task = isinstance(root.partitioning, RangePartitioning)
            stage.num_tasks = 1 if single_task else num_source
        try:
            blob = pickle.dumps(root)
        except Exception as e:  # lambda UDFs etc.: hand back to local engine
            raise _Unpicklable(str(e)) from e

        tasks = [_TaskSpec(
            kind="result" if stage.is_result else "map",
            plan_blob=blob, partitions=(p,),
            num_source_parts=num_source,
            shuffle_id=stage.shuffle_id,
            num_reduce_parts=(0 if stage.is_result else
                              stage.root.partitioning.num_partitions),
            dep_statuses=dep_statuses, conf=self.conf)
            for p in range(stage.num_tasks)]

        if not stage.is_result:
            # lineage capture: the blob is the immutable sub-plan snapshot
            # (fix mutates shared nodes, so re-pickling later would drift),
            # and the program-cache signature is the stable replay key a
            # re-execution is checked against
            from spark_rapids_tpu.serving.program_cache import plan_key
            self._lineage[stage.shuffle_id] = _StageLineage(
                stage_index=stage.index, plan_blob=blob,
                signature=plan_key(root, self.conf),
                num_source_parts=num_source,
                num_reduce_parts=stage.root.partitioning.num_partitions,
                dep_stage_indices=tuple(stage.deps),
                task_partitions={p: t.partitions
                                 for t in tasks for p in t.partitions})

        results = self._run_recomputing(tasks, stages, stage.deps, [0])

        if stage.is_result:
            per_part: List[Tuple[int, bytes]] = []
            for blob_out in results:
                if blob_out:
                    per_part.extend(pickle.loads(blob_out))
            tables: List[pa.Table] = []
            for _, ipc in sorted(per_part, key=lambda x: x[0]):
                with pa.ipc.open_stream(pa.BufferReader(ipc)) as r:
                    tables.append(r.read_all())
            stage.result_tables = tables
        else:
            statuses: List[MapStatus] = []
            for blob_out in results:
                statuses.extend(pickle.loads(blob_out))
            stage.statuses = statuses

    # -------------------------------------------------------- lineage recompute
    def _executor_alive(self, ex) -> bool:
        try:
            return bool(ex.alive())
        except Exception:
            return False

    @staticmethod
    def _dep_statuses(stages: List[_Stage],
                      dep_indices: Sequence[int]
                      ) -> Dict[int, List[MapStatus]]:
        """LIVE dep map statuses (broadcast deps have no shuffle): read at
        (re)dispatch time so a replay observes replacements a recompute
        round just made."""
        return {stages[d].shuffle_id: stages[d].statuses
                for d in dep_indices if stages[d].shuffle_id is not None}

    # rung 2 of the failure ladder: the lineage-recompute triage loop
    @uerr.triage_boundary
    def _run_recomputing(self, tasks: List[_TaskSpec], stages: List[_Stage],
                         dep_indices: Sequence[int], budget: List[int],
                         exclude: Set[str] = frozenset()
                         ) -> List[Optional[bytes]]:
        """Drive ``tasks`` to completion through the lineage-recompute loop
        (the stage half of Spark's "task retry IS stage re-execution"):

        - a task failing because the executor it ran ON died is merely LOST
          work — requeued on the survivors (its fetch error, if any, names
          whichever remote it happened to be reading and must not steer a
          recompute);
        - a ``ShuffleFetchFailedError`` from a live executor is the scoped
          recompute signal: the named peer's lost map tasks are re-executed
          from lineage on surviving peers, dep statuses refresh, and ONLY
          the unfinished tasks re-dispatch;
        - anything else is a real failure and surfaces unchanged.

        ``budget`` is the stage-attempt counter (one mutable cell shared
        with nested replays so a flapping fault cannot recurse forever);
        past ``shuffle.recompute.maxStageAttempts`` the fetch error
        re-surfaces and the serving failover path owns recovery."""
        results: List[Optional[bytes]] = [None] * len(tasks)
        work = list(enumerate(tasks))
        while True:
            live = [ex for ex in self.executors if self._executor_alive(ex)]
            targets = ([ex for ex in live if ex.executor_id not in exclude]
                       or live)
            if not targets:
                raise RuntimeError("no live executors remain to run stage "
                                   "tasks")
            errors = self._run_tasks(work, results, targets)
            if not errors:
                return results
            recompute: List[ShuffleFetchFailedError] = []
            only_lost = True
            for ex, e in errors:
                if not self._executor_alive(ex):
                    continue                  # lost work, not a signal
                only_lost = False
                if not isinstance(e, ShuffleFetchFailedError):
                    raise e
                recompute.append(e)
            if not only_lost:
                budget[0] += 1
                max_attempts = self.conf.get(
                    cfg.SHUFFLE_RECOMPUTE_MAX_STAGE_ATTEMPTS)
                if budget[0] > max_attempts:
                    mt.RECOMPUTE_METRICS[
                        mt.SHUFFLE_RECOMPUTE_ESCALATIONS].add(1)
                    raise recompute[0]
                for err in recompute:
                    self._recompute_lost_maps(err, stages, dep_indices,
                                              budget)
            refreshed = self._dep_statuses(stages, dep_indices)
            work = [(i, _dc_replace(tasks[i], dep_statuses=refreshed))
                    for i in range(len(tasks)) if results[i] is None]

    def _recompute_lost_maps(self, err: ShuffleFetchFailedError,
                             stages: List[_Stage],
                             dep_indices: Sequence[int],
                             budget: List[int]) -> None:
        """Scope one fetch failure to the map tasks that must replay. The
        error's blocks are the per-shuffle scope; a DEAD peer additionally
        widens to every map id it owned in the dep shuffles, because
        zero-row blocks never register in the catalog — the block list a
        single reduce partition observed can under-count a dead peer's map
        tasks whose pieces for THAT partition were empty."""
        by_shuffle: Dict[int, Set[int]] = {}
        for b in err.blocks:
            by_shuffle.setdefault(b.shuffle_id, set()).add(b.map_id)
        peer = err.executor_id
        peer_ex = next((ex for ex in self.executors
                        if ex.executor_id == peer), None)
        peer_dead = peer_ex is None or not self._executor_alive(peer_ex)
        if peer_dead:
            for d in dep_indices:
                sid = stages[d].shuffle_id
                if sid is None:
                    continue
                owned = {st.map_id for st in stages[d].statuses
                         if st.executor_id == peer}
                if owned:
                    by_shuffle.setdefault(sid, set()).update(owned)
        for sid in sorted(by_shuffle):
            self._replay_map_tasks(sid, sorted(by_shuffle[sid]), {peer},
                                   stages, budget)

    def _replay_map_tasks(self, shuffle_id: int, map_ids: List[int],
                          exclude: Set[str], stages: List[_Stage],
                          budget: List[int]) -> None:
        """Re-execute the lost map tasks of one shuffle from lineage on
        surviving peers and REPLACE their outputs exactly-once: stale
        catalog entries drop first on every live executor (a replay landing
        where the originals still live must not double rows for a later
        reader), then the fresh MapStatus entries replace the lost ones
        by map id in the owning stage's statuses."""
        lin = self._lineage.get(shuffle_id)
        if lin is None:
            raise RuntimeError(
                f"no lineage recorded for shuffle {shuffle_id}; cannot "
                f"recompute map tasks {map_ids}")
        from spark_rapids_tpu.serving.program_cache import plan_key
        root = pickle.loads(lin.plan_blob)
        sig = plan_key(root, self.conf)
        if sig != lin.signature:
            raise RuntimeError(
                f"lineage replay key mismatch for shuffle {shuffle_id}: "
                f"{sig} != {lin.signature} — replay would not be "
                f"deterministic, escalating")
        mt.RECOMPUTE_METRICS[mt.SHUFFLE_RECOMPUTES].add(1)
        mt.RECOMPUTE_METRICS[mt.SHUFFLE_RECOMPUTED_MAP_TASKS].add(
            len(map_ids))
        for ex in self.executors:
            if not self._executor_alive(ex):
                continue
            for m in map_ids:
                try:
                    ex.cleanup_map_outputs(shuffle_id, m)
                except Exception:
                    pass          # best-effort: a dying executor's catalog
        specs = [_TaskSpec(
            kind="map", plan_blob=lin.plan_blob,
            partitions=lin.task_partitions[m],
            num_source_parts=lin.num_source_parts,
            shuffle_id=shuffle_id, num_reduce_parts=lin.num_reduce_parts,
            dep_statuses=self._dep_statuses(stages, lin.dep_stage_indices),
            conf=self.conf)
            for m in map_ids]
        # the shared attempt budget rides into the nested run: a replay
        # whose own dep shuffle was lost recomputes it recursively, bounded
        # by the same maxStageAttempts cell
        blobs = self._run_recomputing(specs, stages, lin.dep_stage_indices,
                                      budget, exclude=exclude)
        fresh: List[MapStatus] = []
        for blob in blobs:
            fresh.extend(pickle.loads(blob))
        owner = stages[lin.stage_index]
        replaced = set(map_ids)
        # in-place: every dep_statuses dict built earlier references THIS
        # list object, so readers of the next dispatch see the replacement
        owner.statuses[:] = [st for st in owner.statuses
                             if st.map_id not in replaced] + fresh

    def _run_tasks(self, work: List[Tuple[int, _TaskSpec]],
                   results: List[Optional[bytes]],
                   executors: List) -> List[Tuple[object, Exception]]:
        """Run one round of (index, spec) work items across ``executors``:
        a work queue per executor drained by ``taskSlots`` worker threads,
        so up to executors * taskSlots tasks are in flight and stage
        wall-clock scales with partitions, not executors. Errors stop the
        round fast (remaining queued items are abandoned) and return as
        (executor, error) pairs for the recompute loop to triage — stage
        re-execution via lineage, SURVEY.md §5."""
        import collections
        # tasks pin to executors round-robin (Spark's locality preference:
        # an executor's map outputs stay in ITS shuffle catalog, so spreading
        # map tasks keeps reduce reads mostly local); each executor drains
        # its queue with `taskSlots` concurrent workers
        n_ex = len(executors)
        queues = [collections.deque() for _ in range(n_ex)]
        for k, item in enumerate(work):
            queues[k % n_ex].append(item)
        qlock = threading.Lock()
        errors: List[Tuple[object, Exception]] = []
        slots = max(1, self.conf.get(cfg.CLUSTER_TASK_SLOTS))

        # the collection point of the recompute triage: every task failure
        # (the scoped ShuffleFetchFailedError signal above all) lands in
        # the errors ledger for _run_recomputing to route — never dropped
        @uerr.triage_boundary
        def worker(home: int, ex) -> None:
            while not errors:
                with qlock:
                    if not queues[home]:
                        return
                    idx, spec = queues[home].popleft()
                try:
                    results[idx] = ex.submit(spec)
                except Exception as e:       # triaged after join
                    errors.append((ex, e))
                    return

        threads = [threading.Thread(target=worker, args=(i, ex),
                                    name=f"task-slot-{i}-{s}")
                   for i, ex in enumerate(executors)
                   for s in range(min(slots, len(queues[i])))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return errors

    def close(self) -> None:
        import shutil
        for ex in self.executors:
            try:
                ex.close()
            except Exception:
                pass
        self.executors = []
        for d in self._owned_dirs:
            shutil.rmtree(d, ignore_errors=True)
        self._owned_dirs = []


def cluster_scheduler_for(session) -> ClusterScheduler:
    """One scheduler (and executor set) per session, created lazily."""
    sched = getattr(session, "_cluster_scheduler", None)
    if sched is None or sched.n != session.conf.get(cfg.CLUSTER_EXECUTORS) \
            or not sched.executors:
        if sched is not None:
            sched.close()
        sched = ClusterScheduler(session.conf)
        session._cluster_scheduler = sched
    return sched
