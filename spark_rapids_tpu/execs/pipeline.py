"""Bounded-async dispatch between exec stages.

The exec iterator protocol is strict pull-per-batch lockstep: the consumer
only asks for batch N+1 after it has finished with batch N, so the scan's
host staging, the host link, and device compute take turns instead of
running concurrently. ``PipelinedExec`` (planner-inserted at scan->compute
boundaries, plan/overrides.insert_pipeline, conf
``spark.rapids.tpu.transfer.pipeline.*``) runs its child's iterator on a
producer thread with a BOUNDED queue of ``depth`` batches — the bufferTime/
gpuDecodeTime overlap of GpuParquetScan generalized to any stage boundary,
with Sparkle's bounded-buffer discipline: the queue is the backpressure, and
the producer joins the consuming task's device-admission semaphore hold
(re-entrant per task id, GpuSemaphore.acquireIfNecessary semantics) so HBM
admission still sees ONE task.

Contract preserved from the synchronous protocol:
- batch ORDER: one FIFO queue, one producer;
- error propagation: producer exceptions re-raise at the consumer's next
  pull;
- early exit: a consumer that abandons the iterator (LimitExec) closes the
  child generator and unblocks the producer instead of leaking it.
"""
from __future__ import annotations

import queue
import threading
from contextlib import nullcontext
from typing import Iterator

from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.utils import tracing as _tracing

#: metric: high-water mark of queued batches at a pipeline boundary
PIPELINE_INFLIGHT_PEAK = "pipelineInflightPeak"

_POLL_S = 0.05


def _put_abortable(q: "queue.Queue", item, stop: threading.Event) -> bool:
    """Bounded put that gives up when the consumer went away — the producer
    must never block forever on a full queue (the leak this replaces)."""
    while not stop.is_set():
        try:
            q.put(item, timeout=_POLL_S)
            return True
        except queue.Full:
            continue
    return False


class PipelinedExec(PhysicalExec):
    """Keeps up to ``depth`` child batches in flight ahead of the consumer."""

    is_device = True

    def __init__(self, child: PhysicalExec, depth: int = 2):
        super().__init__((child,), child.output)
        self.depth = depth

    @property
    def name(self) -> str:
        return f"PipelinedExec(depth={self.depth})"

    def size_estimate(self):
        return self.children[0].size_estimate()

    def execute(self, ctx: ExecContext) -> Iterator:
        if self.depth <= 0:
            yield from self.children[0].execute(ctx)
            return
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        dm = ctx.device_manager
        peak = self.metrics[PIPELINE_INFLIGHT_PEAK]

        def produce() -> None:
            # share the OWNING TASK's semaphore hold (ctx.task_id): same task
            # id, so this nests instead of taking a second permit — nested
            # pipelines all fold into one hold — and admission still blocks
            # the producer when other tasks saturate the device
            from spark_rapids_tpu.serving.lifecycle import bind_query
            query = ctx.query
            tenant = query.tenant if query is not None else "default"
            cancel = (query.check_cancelled if query is not None else None)
            hold = (dm.semaphore.held(task_id=ctx.task_id, tenant=tenant,
                                      cancel_check=cancel)
                    if dm is not None else nullcontext())
            src = self.children[0].execute(ctx)
            try:
                # rebind the consumer's query on THIS thread so program-
                # cache and compile-time attribution follow the producer's
                # uploads/compiles, and cancellation stops the producer at
                # its next batch instead of filling the queue for a dead
                # consumer
                with bind_query(query), _tracing.adopt(spawning_span), hold:
                    for b in src:
                        ctx.check_cancelled()
                        peak.set_max(q.qsize() + 1)
                        if not _put_abortable(q, ("b", b), stop):
                            return
            except BaseException as e:  # noqa: BLE001 - reraised at consumer
                _put_abortable(q, ("e", e), stop)
                return
            finally:
                close = getattr(src, "close", None)
                if close is not None:
                    close()     # run the child generator's cleanup
            _put_abortable(q, ("end", None), stop)

        # the producer's spans are children of the span open here
        spawning_span = _tracing.current() if _tracing.TRACER.on else None
        worker = threading.Thread(target=produce, daemon=True,
                                  name="exec-pipeline")
        worker.start()
        try:
            while True:
                # bounded poll (R010): the producer normally wakes us, but
                # if it wedges mid-upload a cancelled consumer must still
                # observe its flag instead of blocking here forever
                try:
                    kind, val = q.get(timeout=_POLL_S)
                except queue.Empty:
                    ctx.check_cancelled()
                    continue
                if kind == "end":
                    return
                if kind == "e":
                    raise val
                self.count_output(val.num_rows)
                yield val
        finally:
            # normal end, consumer exception, or GeneratorExit: stop the
            # producer and drain so a blocked put wakes up
            stop.set()
            while worker.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    worker.join(_POLL_S)
