"""Transfer pipeline: chunked overlapped uploads and asynchronous downloads.

An early record (since deleted; the host link has not been measured on
today's machine) put TPC-H Q1's device compute at a small fraction of its
upload and download — a data-movement-bound engine, the regime Theseus
says a distributed accelerator query engine must engineer around and the
reference plugin covers with pinned-memory async H2D in
``HostToGpuCoalesceIterator``. This module makes the host link a pipeline
instead of a wall:

- **upload_table** splits large tables into row chunks so chunk N+1 stages on
  host (numpy staging is CPU work) while chunk N's asynchronous
  ``jax.device_put`` is in flight on the link, then reassembles the chunks on
  device through ``concat_device_batches`` (bits siblings included, so the
  result is bit-identical to a single-shot ``DeviceBatch.from_arrow``). At
  most ``max_inflight`` chunk uploads are outstanding — Sparkle's
  memory-hierarchy argument: bounded in-flight buffers, not unbounded queues.
- **start_download** begins a per-batch device->host copy
  (``copy_to_host_async``) the moment the producing program is dispatched, so
  D2H overlaps the remaining compute; ``PendingDownload.result()`` blocks only
  for that batch's buffers.

Counters land in the process-global ``TRANSFER_METRICS``
(utils/metrics.py); sessions expose the per-action delta plus link GB/s via
``session.last_metrics["transfer"]``.
"""
from __future__ import annotations

import time
from typing import Any, List

import pyarrow as pa

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax

from spark_rapids_tpu.columnar.batch import (DEFAULT_STRING_MAX_BYTES,
                                             DeviceBatch, fetched_to_arrow)
from spark_rapids_tpu.utils import metrics as um
from spark_rapids_tpu.utils import tracing as _tracing


def _batch_arrays(batch: DeviceBatch) -> List[Any]:
    arrs = []
    for c in batch.columns:
        arrs.append(c.data)
        arrs.append(c.validity)
        if c.lengths is not None:
            arrs.append(c.lengths)
        if c.bits is not None:
            arrs.append(c.bits)
        if c.encoding is not None:
            arrs.append(c.encoding.indices)
            arrs.append(c.encoding.values)
            if c.encoding.lengths is not None:
                arrs.append(c.encoding.lengths)
    return arrs


def _wait_uploaded(batch: DeviceBatch) -> None:
    """Block until every buffer of the batch is resident on device."""
    jax.block_until_ready(_batch_arrays(batch))


def chunk_bounds(table: pa.Table, chunk_rows: int) -> List[int]:
    """Chunk start offsets, aligned to the table's record-batch boundaries
    (for parquet readers those are row-group/page boundaries, so chunk
    staging slices are zero-copy) while keeping every chunk under about
    chunk_rows rows. Oversized record batches are split at chunk_rows."""
    n = table.num_rows
    if chunk_rows <= 0 or n <= chunk_rows:
        return [0]
    edges = {0}
    off = 0
    for b in table.to_batches():
        off += b.num_rows
        if off < n:
            edges.add(off)
    bounds = [0]
    for edge in sorted(edges | {n}):
        while edge - bounds[-1] > chunk_rows:
            bounds.append(bounds[-1] + chunk_rows)
        # take a record-batch edge only when the chunk grew big enough;
        # tiny trailing slivers merge into the previous chunk
        if edge != n and edge - bounds[-1] >= chunk_rows // 2:
            bounds.append(edge)
    return bounds


def upload_table(table: pa.Table,
                 string_max_bytes: int = DEFAULT_STRING_MAX_BYTES,
                 chunk_rows: int = 0, max_inflight: int = 2,
                 device: Any = None,
                 with_bits: bool = True) -> DeviceBatch:
    """Host arrow table -> DeviceBatch via the chunked overlapped pipeline.

    chunk_rows <= 0 (or a table at most one chunk big) takes the single-shot
    ``DeviceBatch.from_arrow`` path. Chunk count, in-flight peak and the
    host's blocking seconds go to ``TRANSFER_METRICS``.

    Spans: ``transfer.upload`` over the whole call, and under it
    ``upload.stage`` per chunk, ``upload.wait`` per bounded wait and
    ``upload.assemble``; they take the plan id of the exec that uploads.
    """
    m = um.TRANSFER_METRICS
    bounds = chunk_bounds(table, chunk_rows)
    n = table.num_rows
    ends = bounds[1:] + [n]
    chunks: List[DeviceBatch] = []
    inflight: List[DeviceBatch] = []
    stage_total = wait_total = 0.0
    peak = 0
    # args dicts build only when tracing is live — the per-upload and
    # per-chunk disabled cost stays one bool read
    with _tracing.span("transfer.upload", _tracing.LAYER_TRANSFER,
                       {"rows": n, "chunks": len(bounds)}
                       if _tracing.TRACER.on else None) as upload:
        for start, end in zip(bounds, ends):
            t0 = time.perf_counter()
            # staging (numpy work) for THIS chunk happens while the
            # previous chunks' device_puts are still in flight — that's
            # the overlap. Bucketed chunks: similar-sized chunks share one
            # power-of-two capacity, so the slice/concat programs of the
            # assembly below hit XLA's compile cache across tables instead
            # of compiling per exact chunk-size tuple (padding is built ON
            # DEVICE — no link bytes). The span's two ends are the staging
            # call's, which already exist — the async device_put is NOT
            # awaited, per R002.
            with _tracing.span("upload.stage", _tracing.LAYER_TRANSFER,
                               {"rows": end - start, "offset": start,
                                "inflight": len(inflight)}
                               if _tracing.TRACER.on else None) as stage:
                b = DeviceBatch.from_arrow(
                    table.slice(start, end - start) if len(bounds) > 1
                    else table,
                    string_max_bytes, device=device, with_bits=with_bits)
                if stage is not None:
                    stage.note(bytes=b.device_size_bytes)
            t1 = time.perf_counter()
            stage_total += t1 - t0
            chunks.append(b)
            inflight.append(b)
            peak = max(peak, len(inflight))
            # bounded: block on the OLDEST. A single-shot upload waits for
            # nothing — the async device_put overlapping the consumer's
            # work is the whole point on serial paths
            while len(bounds) > 1 and len(inflight) >= max_inflight:
                t0 = time.perf_counter()
                with _tracing.span("upload.wait", _tracing.LAYER_TRANSFER):
                    _wait_uploaded(inflight.pop(0))
                wait_total += time.perf_counter() - t0
        if len(chunks) > 1:
            # device-side assembly: slice + concat + one capacity pad, the
            # same cached-program shape every coalesce uses. No trailing
            # sync: the assembly is enqueued behind the in-flight
            # transfers and the caller's first use of the result awaits it.
            from spark_rapids_tpu.execs.tpu_execs import \
                concat_device_batches
            with _tracing.span("upload.assemble", _tracing.LAYER_TRANSFER):
                out = concat_device_batches(chunks, chunks[0].schema,
                                            string_max_bytes)
        else:
            out = chunks[0]
        if upload is not None:
            upload.note(inflight_peak=peak, bytes=out.device_size_bytes)
    m[um.TRANSFER_UPLOAD_BYTES].add(out.device_size_bytes)
    # the host's time in the upload's two blocking parts, staging and the
    # bounded waits: what the link rate of session.last_metrics divides by.
    # The dispatch of the assembly and what the device does after the last
    # wait are not in it.
    m[um.TRANSFER_UPLOAD_SECONDS].add(stage_total + wait_total)
    m[um.TRANSFER_UPLOAD_CHUNKS].add(len(chunks))
    m[um.TRANSFER_INFLIGHT_PEAK].set_max(peak)
    return out


def upload_table_conf(table: pa.Table, string_max_bytes: int, conf,
                      device: Any = None,
                      with_bits: bool = True) -> DeviceBatch:
    """upload_table with chunking parameters read from a TpuConf."""
    from spark_rapids_tpu import config as cfg
    return upload_table(table, string_max_bytes,
                        chunk_rows=conf.get(cfg.TRANSFER_CHUNK_ROWS),
                        max_inflight=conf.get(cfg.TRANSFER_MAX_INFLIGHT),
                        device=device, with_bits=with_bits)


# ------------------------------------------------------------------ downloads
class PendingDownload:
    """One result batch's in-flight device->host download. Created at
    dispatch time (the device queue is in order, so the copy starts as soon
    as the producing program finishes); ``result()`` blocks only on this
    batch's buffers and converts to arrow."""

    def __init__(self, batch: DeviceBatch):
        self._schema = batch.schema
        self._num_rows = batch.num_rows
        self._sliced = batch.sliced_buffers()
        nbytes = 0
        for data, validity, lengths in self._sliced:
            for arr in (data, validity, lengths):
                if arr is None:
                    continue
                nbytes += arr.size * arr.dtype.itemsize
                start = getattr(arr, "copy_to_host_async", None)
                if start is not None:
                    start()
        self.nbytes = nbytes

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def result(self) -> pa.Table:
        t0 = time.perf_counter()
        with _tracing.span("download.wait", _tracing.LAYER_TRANSFER):
            fetched = jax.device_get(self._sliced)
        self._sliced = fetched      # idempotent: device_get of host arrays
        dt = time.perf_counter() - t0
        m = um.TRANSFER_METRICS
        m[um.TRANSFER_DOWNLOAD_BYTES].add(self.nbytes)
        m[um.TRANSFER_DOWNLOAD_SECONDS].add(dt)
        with _tracing.span("download.to_arrow", _tracing.LAYER_TRANSFER):
            return fetched_to_arrow(self._schema, fetched, self._num_rows)


def start_download(batch: DeviceBatch) -> PendingDownload:
    return PendingDownload(batch)
