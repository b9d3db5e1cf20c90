"""Mesh-sharded columnar batches.

The distributed execution unit: one logical batch whose column arrays live
partitioned across a ``jax.sharding.Mesh`` data axis. Global array shape is
``[n_dev * local_capacity, ...]`` with ``NamedSharding(mesh, P('data'))``;
device d owns rows ``[d*local_capacity, (d+1)*local_capacity)`` and the live
rows of each shard are a prefix (the same padding invariant as DeviceBatch,
per shard).

This replaces the reference's executor-task partitioning of batches
(ShuffledBatchRDD partitions, one GPU per executor): a partition IS a mesh
shard, and every exchange between partitions is an XLA collective over ICI
instead of a UCX transfer (shuffle-plugin/.../ucx/UCX.scala:53).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar.batch import DeviceBatch, _arrow_to_staged
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import DType, Schema, bucket_capacity
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.utils import metrics as um
from spark_rapids_tpu.utils import tracing as _tracing


@dataclass(frozen=True)
class MeshBatch:
    """Columns sharded over the mesh data axis + per-shard live row counts."""

    schema: Schema
    columns: Tuple[DeviceColumn, ...]
    #: host-side int32[n_dev]: live rows per shard (each shard's live rows are
    #: a prefix of its local slice)
    rows_per_shard: np.ndarray
    mesh: Mesh

    @property
    def n_dev(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def local_capacity(self) -> int:
        cap = self.columns[0].capacity if self.columns else 0
        return cap // self.n_dev

    @property
    def num_rows(self) -> int:
        return int(self.rows_per_shard.sum())

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def device_size_bytes(self) -> int:
        """Bytes over all shards; each device holds 1/n_dev of them."""
        return sum(c.device_size_bytes for c in self.columns)

    @property
    def row_bytes(self) -> int:
        """One row's bytes over every column, validity byte and string
        length included: what moving a row between shards must carry."""
        return sum(c.row_bytes for c in self.columns)

    def rows_dev(self):
        """rows_per_shard as a device array sharded one-per-shard (the shape
        shard_map bodies see is [1])."""
        return jax.device_put(self.rows_per_shard.astype(np.int32),
                              NamedSharding(self.mesh, P(DATA_AXIS)))


def flatten_mesh(mb: MeshBatch) -> List:
    flat = []
    for c in mb.columns:
        flat.append(c.data)
        flat.append(c.validity)
        if c.lengths is not None:
            flat.append(c.lengths)
    return flat


def mesh_columns(schema: Schema, flat) -> Tuple[DeviceColumn, ...]:
    cols, i = [], 0
    for f in schema:
        if f.dtype is DType.STRING:
            cols.append(DeviceColumn(f.dtype, flat[i], flat[i + 1], flat[i + 2]))
            i += 3
        else:
            cols.append(DeviceColumn(f.dtype, flat[i], flat[i + 1]))
            i += 2
    return tuple(cols)


def staged_column_arrays(dtype: DType, col, string_max_bytes: int):
    """Chunk-normalize one arrow column and stage it to
    (data, validity, lengths) numpy arrays, validity defaulting to all-true
    — the single staging path for every host->mesh upload."""
    arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    if isinstance(arr, pa.ChunkedArray):
        arr = (arr.chunk(0) if arr.num_chunks == 1
               else pa.concat_arrays(arr.chunks))
    data, validity, lengths = _arrow_to_staged(dtype, arr, string_max_bytes)
    if validity is None:
        validity = np.ones(len(arr), dtype=bool)
    return data, validity, lengths


def _padded_global_arrays(staged, rows, per: int, local_cap: int) -> tuple:
    """One column's staged (data, validity, lengths) -> the global arrays a
    sharded device_put takes: shard d's ``rows[d]`` rows (``per`` to a
    shard, in the table's order) at the head of its ``local_cap`` slots,
    zeros behind them; no lengths array where the column has none."""
    out = []
    for a in staged:
        if a is None:
            continue
        g = np.zeros((len(rows) * local_cap,) + a.shape[1:], dtype=a.dtype)
        for d, live in enumerate(rows):
            g[d * local_cap:d * local_cap + live] = a[d * per:d * per + live]
        out.append(g)
    return tuple(out)


def scatter_arrow(table: pa.Table, mesh: Mesh, string_max_bytes: int,
                  max_inflight: int = 2) -> MeshBatch:
    """Host arrow table -> mesh batch: rows split contiguously across shards
    (shard-major order preserves the table's row order end to end), each shard
    padded to a shared power-of-two local capacity, one sharded device_put per
    column buffer. At most ``max_inflight`` columns are in flight: the next
    column stages on the host while the devices take the last.

    Counted and recorded as ``columnar/transfer.upload_table`` is: the transfer
    counters, ``transfer.upload`` over the whole call and under it
    ``upload.stage`` per column (arrow -> numpy -> padded global arrays, and
    the enqueue of their device_put) and ``upload.wait`` per bounded wait."""
    table = table.combine_chunks()
    schema = Schema.from_pa(table.schema)
    n = table.num_rows
    n_dev = int(mesh.devices.size)
    per = -(-n // n_dev) if n else 0
    local_cap = max(bucket_capacity(per), 1)
    rows = np.zeros(n_dev, dtype=np.int32)
    for d in range(n_dev):
        rows[d] = max(0, min(per, n - d * per))

    sharding = NamedSharding(mesh, P(DATA_AXIS))
    cols: List[DeviceColumn] = []
    inflight: List[tuple] = []
    busy_s = 0.0
    tracing = _tracing.TRACER.on
    with _tracing.span("transfer.upload", _tracing.LAYER_TRANSFER,
                       {"rows": n, "chunks": len(schema), "shards": n_dev}
                       if tracing else None) as upload:
        for i, f in enumerate(schema):
            t0 = time.perf_counter()
            with _tracing.span("upload.stage", _tracing.LAYER_TRANSFER,
                               {"rows": n, "column": f.name,
                                "inflight": len(inflight)}
                               if tracing else None) as stage:
                staged = _padded_global_arrays(
                    staged_column_arrays(f.dtype, table.column(i),
                                         string_max_bytes),
                    rows, per, local_cap)
                up = jax.device_put(staged, sharding)
                col = DeviceColumn(f.dtype, *up)
                if stage is not None:
                    stage.note(bytes=col.device_size_bytes)
            cols.append(col)
            inflight.append(up)
            # bounded: block on the OLDEST column, so that the host holds
            # the staged arrays of max_inflight columns and no more
            while len(inflight) >= max_inflight:
                with _tracing.span("upload.wait", _tracing.LAYER_TRANSFER):
                    jax.block_until_ready(inflight.pop(0))
            busy_s += time.perf_counter() - t0
        out = MeshBatch(schema, tuple(cols), rows, mesh)
        if upload is not None:
            upload.note(bytes=out.device_size_bytes)
    m = um.TRANSFER_METRICS
    m[um.TRANSFER_UPLOAD_BYTES].add(out.device_size_bytes)
    m[um.TRANSFER_UPLOAD_SECONDS].add(busy_s)
    m[um.TRANSFER_UPLOAD_CHUNKS].add(len(cols))
    return out


def scatter_device_batch(db: DeviceBatch, mesh: Mesh) -> MeshBatch:
    """Single-device batch -> mesh batch: the EXPLICIT reshard (host
    staging; the entry path for small single-device intermediates joining a
    mesh pipeline). This is a deliberate host hop and counts as one —
    in-mesh exchanges must never route through here (host_hop_bytes == 0 on
    the all_to_all path is a CI assert)."""
    um.TRANSFER_METRICS[um.TRANSFER_HOST_HOP_BYTES].add(db.device_size_bytes)
    return scatter_arrow(db.to_arrow(), mesh, _string_width(db))


def _string_width(db: DeviceBatch) -> int:
    w = 8
    for c in db.columns:
        if c.lengths is not None:
            w = max(w, c.data.shape[-1])
    return w


def gather_mesh(mb: MeshBatch) -> DeviceBatch:
    """Mesh batch -> one compacted single-device batch, preserving shard-major
    row order (shard 0 rows first). The compaction runs as one XLA program
    over the sharded arrays (GSPMD all-gathers over ICI); the result lands on
    the default device."""
    n_dev, cap = mb.n_dev, mb.local_capacity
    total_rows = mb.num_rows
    out_cap = max(bucket_capacity(total_rows), 1)
    rows = mb.rows_dev()
    # n_dev is keyed explicitly: the traced gather reshapes over
    # n_dev * cap, so two meshes sharing (schema, cap, out_cap) but
    # differing in device count must not share a program (R016)
    key = ("mesh-gather", mb.mesh, mb.schema, cap, n_dev,
           tuple(c.data.shape[1:] for c in mb.columns), out_cap)

    from spark_rapids_tpu.execs.tpu_execs import _cached_jit

    def build(mesh=mb.mesh, n_dev=n_dev, cap=cap, out_cap=out_cap,
              schema=mb.schema):
        def fn(rows, *flat):
            live = (jnp.arange(cap, dtype=np.int32)[None, :]
                    < rows[:, None]).reshape(n_dev * cap)
            order = jnp.argsort(~live, stable=True)[:out_cap]
            outs = []
            for a in flat:
                g = jax.lax.with_sharding_constraint(
                    a[order], NamedSharding(mesh, P()))
                outs.append(g)
            return tuple(outs)
        return fn

    fn = _cached_jit(key, build)
    with _tracing.span("mesh.gather", _tracing.LAYER_SHUFFLE) as sp:
        res = fn(rows, *flatten_mesh(mb))
        dev = jax.devices()[0]
        placed = jax.device_put(list(res), dev)
        out = DeviceBatch(mb.schema, mesh_columns(mb.schema, placed),
                          total_rows)
        if sp is not None:
            sp.note(rows=total_rows, bytes=out.device_size_bytes,
                    shards=n_dev)
    return out


def replicate_device_batch(db: DeviceBatch, mesh: Mesh) -> DeviceBatch:
    """Replicate a single-device batch's arrays across the mesh (the
    all-gather role of GpuBroadcastExchangeExec's per-executor batch cache:
    XLA broadcasts the buffers over ICI)."""
    sharding = NamedSharding(mesh, P())
    cols = []
    with _tracing.span("mesh.replicate", _tracing.LAYER_SHUFFLE,
                       {"rows": db.num_rows, "bytes": db.device_size_bytes,
                        "shards": int(mesh.devices.size)}
                       if _tracing.TRACER.on else None):
        for c in db.columns:
            data = jax.device_put(c.data, sharding)
            validity = jax.device_put(c.validity, sharding)
            lengths = (jax.device_put(c.lengths, sharding)
                       if c.lengths is not None else None)
            cols.append(DeviceColumn(c.dtype, data, validity, lengths))
    return DeviceBatch(db.schema, tuple(cols), db.num_rows)
