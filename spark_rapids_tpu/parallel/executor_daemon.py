"""Cluster executor daemon: one OS process per executor.

Spawned by ProcessExecutor (parallel/cluster.py) with a control port; builds
a ShuffleEnv on the configured transport (TCP for cross-process topologies)
and serves tasks until told to stop. The control socket carries only task
specs and results — shuffle DATA moves executor-to-executor over the shuffle
transport's own sockets (the reference's metadata-via-driver / data-P2P
split, RapidsShuffleInternalManager.scala).

The executor-plugin-init analog (Plugin.scala RapidsExecutorPlugin): a fatal
init error exits the process, which the driver surfaces as a failed start.
"""
from __future__ import annotations

import argparse
import socket
import sys
import tempfile
import threading
import traceback


def _cache_put(conf, cached_parts, tid: int, parts) -> None:
    """Register a shipped df.cache() entry's partitions in THIS executor's
    spillable catalog under the driver's BufferIds (the executor-side cache
    serving of HostColumnarToGpu.scala:222, re-targeted at the tiered
    store: the batches spill device->host->disk under pressure like any
    cached buffer)."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.memory.buffer import BufferId
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.memory.store import CACHE_BUFFER_PRIORITY

    _cache_remove(cached_parts, tid)      # stale generation, if any
    dm = DeviceManager.initialize(conf)
    smax = conf.string_max_bytes
    ids = []
    try:
        for i, ipc in enumerate(parts):
            with pa.ipc.open_stream(pa.BufferReader(ipc)) as r:
                table = r.read_all()
            bid = BufferId(tid, i)
            dm.device_store.add_batch(bid,
                                      DeviceBatch.from_arrow(table, smax),
                                      CACHE_BUFFER_PRIORITY)
            ids.append(bid)
    except Exception:
        # mid-loop failure must not orphan the partitions already
        # registered (mirrors CacheManager._materialize's rollback)
        for bid in ids:
            dm.catalog.remove(bid)
        raise
    cached_parts[tid] = ids


def _cache_remove(cached_parts, tid: int) -> None:
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    ids = cached_parts.pop(tid, None)
    if ids:
        dm = DeviceManager.peek()
        if dm is not None:
            for bid in ids:
                dm.catalog.remove(bid)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--executor-id", required=True)
    ap.add_argument("--control-port", type=int, required=True)
    args = ap.parse_args()

    sock = socket.create_connection(("127.0.0.1", args.control_port),
                                    timeout=60)
    sock.settimeout(None)  # connect bound only; serving blocks indefinitely
    from spark_rapids_tpu.parallel.cluster import (_recv_msg, _run_task,
                                                   _send_msg)
    from spark_rapids_tpu.shuffle.manager import ShuffleEnv
    from spark_rapids_tpu.utils import errors as uerr

    env = None
    cached_parts: dict = {}      # df.cache() table_id -> [BufferId...]
    spill_dir = tempfile.mkdtemp(prefix=f"spill-{args.executor_id}-")
    try:
        msg = _recv_msg(sock)
        assert msg["type"] == "init", msg
        conf = msg["conf"]
        env = ShuffleEnv(args.executor_id, conf, disk_dir=spill_dir)
        _send_msg(sock, {"type": "ready"})

        # responses interleave across concurrent task threads: serialize the
        # socket writes; the driver routes them back by id
        send_lock = threading.Lock()

        def send(obj) -> None:
            with send_lock:
                _send_msg(sock, obj)

        while True:
            msg = _recv_msg(sock)
            kind = msg["type"]
            rid = msg.get("id")
            if kind == "stop":
                return 0
            if kind == "ping":
                # liveness probe: the control socket can outlive a killed
                # shuffle transport (chaos kill_peer), so report both
                t = env.transport
                killed = bool(getattr(t, "killed", False)
                              or getattr(t, "_killed", False))
                send({"type": "pong", "killed": killed, "id": rid})
                continue
            if kind == "cleanup":
                env.shuffle_catalog.remove_shuffle(msg["shuffle_id"])
                send({"type": "ok", "id": rid})
                continue
            if kind == "cleanup_map":
                env.shuffle_catalog.remove_map_outputs(msg["shuffle_id"],
                                                       msg["map_id"])
                send({"type": "ok", "id": rid})
                continue
            if kind == "broadcast":
                from spark_rapids_tpu.parallel.broadcast import \
                    BroadcastManager
                BroadcastManager.put(msg["bid"], msg["blob"])
                send({"type": "ok", "id": rid})
                continue
            if kind == "cleanup_broadcast":
                from spark_rapids_tpu.parallel.broadcast import \
                    BroadcastManager
                BroadcastManager.remove(msg["bid"])
                send({"type": "ok", "id": rid})
                continue
            if kind == "cache_put":
                try:
                    _cache_put(conf, cached_parts, msg["tid"], msg["parts"])
                    send({"type": "ok", "id": rid})
                except Exception:
                    send({"type": "error", "id": rid,
                          "message": traceback.format_exc()})
                continue
            if kind == "cache_remove":
                _cache_remove(cached_parts, msg["tid"])
                send({"type": "ok", "id": rid})
                continue
            if kind == "task":
                # one thread per in-flight task (the driver bounds in-flight
                # tasks to taskSlots per executor; device entry inside the
                # task is gated by the admission semaphore)
                @uerr.wire_boundary
                def run(spec=msg["spec"], rid=rid) -> None:
                    from spark_rapids_tpu.shuffle.manager import \
                        ShuffleFetchFailedError
                    try:
                        blob = _run_task(env, spec)
                        send({"type": "done", "blob": blob, "id": rid})
                    except ShuffleFetchFailedError as e:
                        # structured codec (utils/errors.py): the scoped
                        # payload must survive the control socket — the
                        # driver's recompute loop keys off executor_id +
                        # blocks, which a flattened traceback would lose
                        send({"type": "error", "id": rid,
                              "error": uerr.encode_error(e),
                              "message": str(e)})
                    except Exception as e:
                        # unregistered types ship OPAQUE (non-retryable
                        # driver-side) with the traceback as message
                        send({"type": "error", "id": rid,
                              "error": uerr.encode_error(
                                  e, message=traceback.format_exc()),
                              "message": traceback.format_exc()})

                threading.Thread(target=run, daemon=True).start()
                continue
            send({"type": "error", "id": rid,
                  "message": f"unknown control message {kind!r}"})
    except (ConnectionError, EOFError):
        return 0
    finally:
        if env is not None:
            env.close()
        import shutil
        shutil.rmtree(spill_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
