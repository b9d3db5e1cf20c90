"""Device batch and host<->device movement.

Reference analogs:
- ``ColumnarBatch`` of GpuColumnVectors (GpuColumnVector.java:40 area);
- ``GpuColumnarBatchBuilder`` (GpuColumnVector.java:41) which builds on host then
  uploads — here ``DeviceBatch.from_arrow`` stages through numpy and uploads once;
- ``HostColumnarToGpu.scala:222`` (host ColumnarBatch -> device) and
  ``GpuColumnarToRowExec.scala:35`` (device -> host rows) — ``to_arrow`` is the
  download path.

A DeviceBatch is columns padded to a common *capacity* (power-of-two bucket) with a
host-side ``num_rows``; padding rows are invalid. Static shapes are what lets XLA
reuse one compiled program per (schema, capacity) instead of recompiling per batch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.column import DeviceColumn, null_column
from spark_rapids_tpu.columnar.dtypes import DType, Field, Schema, bucket_capacity
from spark_rapids_tpu.utils import metrics as um
from spark_rapids_tpu.utils import tracing as _tracing

DEFAULT_STRING_MAX_BYTES = 256


@dataclass(frozen=True)
class DeviceBatch:
    schema: Schema
    columns: Tuple[DeviceColumn, ...]
    num_rows: int

    def __post_init__(self):
        caps = {c.capacity for c in self.columns}
        if len(caps) > 1:
            raise ValueError(f"mixed capacities in batch: {caps}")

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(self.num_rows)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def device_size_bytes(self) -> int:
        return sum(c.device_size_bytes for c in self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def column_by_name(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.index_of(name)]

    def with_columns(self, schema: Schema, columns: Sequence[DeviceColumn],
                     num_rows: Optional[int] = None) -> "DeviceBatch":
        return DeviceBatch(schema, tuple(columns),
                           self.num_rows if num_rows is None else num_rows)

    # ------------------------------------------------------------------ arrow I/O
    @staticmethod
    def from_arrow(table: pa.Table, string_max_bytes: int = DEFAULT_STRING_MAX_BYTES,
                   bucketed: bool = True, device: Any = None,
                   with_bits: bool = True) -> "DeviceBatch":
        """Host arrow table -> device batch (single upload per buffer).

        Encoded columns never decode on host:

        - pa.DictionaryArray (the parquet page reader keeps the file's own
          dictionary encoding, io/parquet_pages.py) ships as narrow indices
          + the small dictionary and decodes ON DEVICE with a gather; the
          encoded form is RETAINED on the column (DeviceColumn.encoding) so
          downstream operators can work on the index domain.
        - pa.RunEndEncodedArray (RLE-dominant parquet chunks) ships as
          (run_ends, per-run values), its run list padded to a power-of-two
          bucket, and expands in HBM in the cached ``ree_expand`` program
          (columnar/encoding.ree_expand_program: a scatter of the run ends, a
          cumsum, one gather a buffer), one call a column.

        (Host-side re-encoding of plain columns was tried and cut: on the
        1-core bench rig np.unique staging cost exceeds the link saving.)"""
        from spark_rapids_tpu.columnar import encoding as ce
        from spark_rapids_tpu.serving.program_cache import (
            global_program_cache, named_jit)
        # three leaf spans under upload.stage: the host conversion, the
        # put, and the eager device-side decode (dispatches, not awaited)
        with _tracing.span("stage.host", _tracing.LAYER_TRANSFER) as sp:
            table = table.combine_chunks()
            schema = Schema.from_pa(table.schema)
            n = table.num_rows
            cap = bucket_capacity(n, bucketed)
            # stage every column on host at its EXACT row count, then ship ONE
            # device_put tree (per-buffer transfers each pay a fixed host-link
            # round trip). Capacity padding and the validity masks of null-free
            # columns are built on device — no reason to move zeros over the link.
            staged = []
            encoded = {}     # column index -> "string" | "fixed" | "ree"
            enc_meta = {}    # column index -> (token, unique) for dict columns
            ree_runs = {}    # column index -> live runs of a "ree" column
            enc_bytes = 0    # bytes actually staged for the link
            dec_bytes = 0    # bytes the decoded forms would have staged

            def _nb(*arrs) -> int:
                return sum(a.nbytes for a in arrs if a is not None)

            for i, f in enumerate(schema):
                arr = table.column(i).combine_chunks()
                if isinstance(arr, pa.ChunkedArray):
                    arr = (arr.chunk(0) if arr.num_chunks == 1
                           else pa.concat_arrays(arr.chunks))
                if (isinstance(arr, pa.Array)
                        and pa.types.is_run_end_encoded(arr.type)):
                    ends, vals = ce.ree_staged(arr)
                    if len(ends) == 0 or f.dtype is DType.STRING:
                        # empty slice / string REE (never produced by the scan):
                        # host-decode and take the plain path below
                        arr = ce.ree_to_plain(arr)
                    else:
                        rvalid = (None if vals.null_count == 0
                                  else _arrow_validity(vals))
                        vd, _, _ = _arrow_to_staged(f.dtype, vals,
                                                    string_max_bytes)
                        vbits = (vd.view(np.uint64)
                                 if f.dtype is DType.DOUBLE and with_bits
                                 else None)
                        encoded[i] = "ree"
                        ree_runs[i] = len(ends)
                        # the encoding's bytes; the bucket pad below (fewer
                        # entries than the live runs) is the program's
                        enc_bytes += _nb(ends, rvalid, vd, vbits)
                        dec_bytes += (n * vd.dtype.itemsize
                                      + (n * 8 if vbits is not None else 0)
                                      + _nb(rvalid))
                        ends, (rvalid, vd, vbits) = ce.pad_runs(
                            ends, cap, (rvalid, vd, vbits))
                        staged.append((ends, rvalid, vd, vbits))
                        continue
                if (isinstance(arr, pa.DictionaryArray)
                        and len(arr.dictionary) > 0):
                    # device-side decode (GpuParquetScan.scala:576 analog for
                    # the dictionary encoding): ship the narrow index vector +
                    # the small dictionary, gather on device — 2-8x fewer
                    # bytes over the host link than the decoded column.
                    # Strings gather their byte-matrix rows + lengths.
                    idx = arr.indices
                    validity = (None if idx.null_count == 0
                                else _arrow_validity(idx))
                    k = len(arr.dictionary)
                    np_idx = np.asarray(idx.fill_null(0)).astype(
                        np.uint8 if k <= 0xFF else
                        np.uint16 if k <= 0xFFFF else np.int32)
                    if f.dtype is DType.STRING:
                        dmat, dlen = _strings_to_matrix(
                            arr.dictionary.cast(pa.string()), string_max_bytes)
                        encoded[i] = "string"
                        staged.append((np_idx, validity, dmat, dlen))
                        enc_bytes += _nb(np_idx, validity, dmat, dlen)
                        dec_bytes += (n * dmat.shape[1] + n * 4 + _nb(validity))
                        unique = ce.dictionary_is_unique(dmat, dlen)
                    else:
                        dd, _, _ = _arrow_to_staged(f.dtype, arr.dictionary,
                                                    string_max_bytes)
                        dbits = (dd.view(np.uint64)
                                 if f.dtype is DType.DOUBLE and with_bits
                                 else None)
                        encoded[i] = "fixed"
                        staged.append((np_idx, validity, dd, dbits))
                        enc_bytes += _nb(np_idx, validity, dd, dbits)
                        dec_bytes += (n * dd.dtype.itemsize
                                      + (n * 8 if dbits is not None else 0)
                                      + _nb(validity))
                        unique = ce.dictionary_is_unique(dd)
                    enc_meta[i] = (ce.field_token(table.schema, i), unique)
                    continue
                if isinstance(arr, pa.DictionaryArray):
                    arr = arr.cast(arr.type.value_type)   # empty dict
                d, v, l = _arrow_to_staged(f.dtype, arr, string_max_bytes)
                # DOUBLE columns also ship their IEEE bit pattern: device f64
                # STORAGE is true 64-bit but no device op can extract its bits
                # (f64->u64 bitcast does not lower; arithmetic is ~49-bit), so
                # the shuffle kernel's byte packing needs the host-made sibling.
                # with_bits=False skips it for consumers that never reach that
                # kernel (mesh-sharded scans: exchange is an all_to_all)
                bits = (d.view(np.uint64)
                        if f.dtype is DType.DOUBLE and with_bits else None)
                staged.append((d, v, l, bits))
                plain = _nb(d, v, l, bits)
                enc_bytes += plain
                dec_bytes += plain
            if sp is not None:
                sp.note(bytes=enc_bytes)
        m = um.TRANSFER_METRICS
        m[um.TRANSFER_ENCODED_BYTES].add(enc_bytes)
        m[um.TRANSFER_DECODED_EQUIV_BYTES].add(dec_bytes)
        with _tracing.span("stage.put", _tracing.LAYER_TRANSFER):
            up = (jax.device_put(staged, device) if device is not None
                  else jax.device_put(staged))
        with _tracing.span("stage.expand", _tracing.LAYER_TRANSFER) as sp:
            # shared all-valid mask, on the same device as the data
            alive = jnp.arange(cap, dtype=jnp.int32) < n
            if device is not None:
                alive = jax.device_put(alive, device)
            nd = 2      # eager device calls issued below, counted as issued
            pad = cap - n
            cols = []
            for i, (f, slot) in enumerate(zip(schema, up)):
                enc = None
                if encoded.get(i) == "ree":
                    # HBM expansion of the RLE runs, one cached program call
                    # a column, shared by every run list of the bucket. The
                    # decoded column exists ONLY on device.
                    ends, rv, vd, vbits = slot
                    key = ("ree_expand", vd.dtype.str, cap,
                           int(ends.shape[0]), vbits is not None,
                           rv is not None)
                    prog = global_program_cache().get_or_build(
                        key, lambda: named_jit("ree_expand",
                                               ce.ree_expand_program(cap)))
                    d, bits, v = prog(ends, np.int32(ree_runs[i]),
                                      np.int32(n), vd, vbits, rv)
                    l = None
                    nd += 1
                elif i in encoded:
                    # padded gather: index padding rows point at dict slot 0;
                    # their garbage values land beyond the live prefix
                    idx, v, dd, extra = slot
                    idx32 = idx.astype(jnp.int32)
                    if pad:
                        idx32 = jnp.concatenate(
                            [idx32, jnp.zeros(pad, jnp.int32)], axis=0)
                    d = jnp.take(dd, idx32, axis=0)
                    nd += 2 + 2 * bool(pad) + (extra is not None)
                    if encoded[i] == "string":
                        l = jnp.take(extra, idx32, axis=0)
                        bits = None
                        enc_lengths = extra
                    else:
                        bits = (jnp.take(extra, idx32, axis=0)
                                if extra is not None else None)
                        l = None
                        enc_lengths = None
                    token, unique = enc_meta[i]
                    if unique:
                        # the retained encoding pads its dictionary to a
                        # power-of-two bucket ON DEVICE (zero link bytes): the
                        # padded size is the jit-key shape, so per-row-group
                        # dictionary growth doesn't recompile encoded-domain
                        # programs
                        k_real = int(dd.shape[0])
                        dpad = ce.dict_bucket(k_real) - k_real
                        dd_enc, len_enc = dd, enc_lengths
                        if dpad:
                            dd_enc = jnp.concatenate(
                                [dd, jnp.zeros((dpad,) + dd.shape[1:],
                                               dd.dtype)], axis=0)
                            if enc_lengths is not None:
                                len_enc = jnp.concatenate(
                                    [enc_lengths,
                                     jnp.zeros(dpad, enc_lengths.dtype)],
                                    axis=0)
                            nd += 2 + 2 * (enc_lengths is not None)
                        enc = ce.DictEncoding(idx32, dd_enc, k_real, len_enc,
                                              token)
                else:
                    d, v, l, bits = slot
                    if pad:
                        d = jnp.concatenate(
                            [d, jnp.zeros((pad,) + d.shape[1:], d.dtype)],
                            axis=0)
                        if l is not None:
                            l = jnp.concatenate([l, jnp.zeros(pad, l.dtype)],
                                                axis=0)
                        if bits is not None:
                            bits = jnp.concatenate(
                                [bits, jnp.zeros(pad, bits.dtype)], axis=0)
                        nd += 2 * (1 + (l is not None) + (bits is not None))
                if v is not None:
                    if pad and v.shape[0] != cap:
                        v = jnp.concatenate([v, jnp.zeros(pad, jnp.bool_)])
                        nd += 2
                    validity = v
                else:
                    validity = alive
                cols.append(DeviceColumn(f.dtype, d, validity, l, bits,
                                         encoding=enc))
            if sp is not None:
                sp.note(columns=len(encoded), dispatches=nd,
                        ree_columns=len(ree_runs),
                        ree_runs=sum(ree_runs.values()))
        return DeviceBatch(schema, tuple(cols), n)

    def sliced_buffers(self) -> List[Tuple]:
        """Device-side (data, validity, lengths_or_None) slices of the live
        rows, ready to download: slicing happens ON DEVICE so only live rows
        cross the host link. The streaming-collect path uses this to start
        asynchronous per-batch downloads (columnar/transfer.py)."""
        n = self.num_rows
        sliced = []
        for col in self.columns:
            # DOUBLE columns with a bit sibling download the BITS: a device
            # u64->f64 bitcast rounds to the emulated ~49-bit arithmetic
            # precision, so the bits are the lossless representation
            data = col.bits if col.bits is not None else col.data
            sliced.append((data[:n], col.validity[:n],
                           col.lengths[:n] if col.lengths is not None else None))
        return sliced

    def to_arrow(self) -> pa.Table:
        """Download to a host arrow table (GpuColumnarToRow analog). All
        column buffers are sliced to the live rows on device and fetched in a
        single device_get so transfers overlap instead of paying one
        host-link round trip per buffer."""
        fetched = jax.device_get(self.sliced_buffers())
        return fetched_to_arrow(self.schema, fetched, self.num_rows)

    # ------------------------------------------------------------------ helpers
    @staticmethod
    def empty(schema: Schema, string_max_bytes: int = DEFAULT_STRING_MAX_BYTES,
              capacity: int = 0) -> "DeviceBatch":
        cap = max(capacity, 1)
        cols = tuple(null_column(f.dtype, cap, string_max_bytes) for f in schema)
        return DeviceBatch(schema, cols, 0)


def fetched_to_arrow(schema: Schema, fetched, num_rows: int) -> pa.Table:
    """Host buffers (one (data, validity, lengths) triple per column, as laid
    out by ``DeviceBatch.sliced_buffers``) -> arrow table."""
    arrays: List[pa.Array] = []
    for f, (data, validity, lengths) in zip(schema, fetched):
        data = np.asarray(data)
        if f.dtype is DType.DOUBLE and data.dtype == np.uint64:
            data = data.view(np.float64)
        arrays.append(_numpy_to_arrow(f.dtype, data,
                                      np.asarray(validity),
                                      None if lengths is None
                                      else np.asarray(lengths), num_rows))
    return pa.Table.from_arrays(arrays, schema=schema.to_pa())


def _arrow_to_staged(dtype: DType, arr: pa.Array, string_max_bytes: int):
    """Arrow column -> exact-size host (data, validity_or_None, lengths).
    validity is None when the column has no nulls (device builds the mask)."""
    validity = None if arr.null_count == 0 else _arrow_validity(arr)
    if dtype is DType.STRING:
        sarr = arr.cast(pa.string()) if not pa.types.is_string(arr.type) else arr
        mat, lengths = _strings_to_matrix(sarr, string_max_bytes)
        return mat, validity, lengths
    if dtype is DType.TIMESTAMP:
        np_data = np.asarray(arr.cast(pa.int64()).fill_null(0))
    elif dtype is DType.DATE:
        np_data = np.asarray(arr.cast(pa.int32()).fill_null(0))
    elif dtype is DType.BOOLEAN:
        np_data = np.asarray(arr.fill_null(False))
    else:
        np_data = np.asarray(arr.fill_null(0))
    return np_data.astype(dtype.np_dtype(), copy=False), validity, None


def _arrow_validity(arr: pa.Array) -> np.ndarray:
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=np.bool_)
    import pyarrow.compute as pc
    return np.asarray(pc.is_valid(arr))


def string_width_bucket(max_len: int, cap: int) -> int:
    """Per-column device string width: the power-of-two bucket covering the
    longest value, clamped to the session cap. Narrow columns (flags, codes)
    then cost a fraction of the cap in staging, transfer, and device compute;
    binary kernels align mixed widths on the fly (ops/strings.align_widths)."""
    w = 8
    while w < max_len:
        w *= 2
    return min(w, cap)


def _strings_to_matrix(arr: pa.StringArray, max_bytes: int) -> Tuple[np.ndarray, np.ndarray]:
    """Arrow (offsets, bytes) -> fixed-width byte matrix + lengths, at the
    column's adaptive width bucket.

    Vectorized: the concatenated UTF-8 payload is row-major in arrow, so a boolean
    ragged mask scatters it into the matrix in one numpy op.
    """
    n = len(arr)
    if n == 0:
        return np.zeros((0, string_width_bucket(0, max_bytes)), np.uint8),             np.zeros(0, np.int32)
    arr = arr.fill_null("")
    offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                            count=n + 1, offset=arr.offset * 4)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    if lengths.max(initial=0) > max_bytes:
        raise ValueError(
            f"string of {lengths.max()} bytes exceeds device string width {max_bytes} "
            f"(spark.rapids.tpu.sql.string.maxBytes)")
    width = string_width_bucket(int(lengths.max(initial=0)), max_bytes)
    data_buf = arr.buffers()[2]
    payload = (np.frombuffer(data_buf, dtype=np.uint8,
                             count=int(offsets[-1]) - int(offsets[0]),
                             offset=int(offsets[0]))
               if data_buf is not None else np.zeros(0, np.uint8))
    mat = np.zeros((n, width), dtype=np.uint8)
    mask = np.arange(width, dtype=np.int32)[None, :] < lengths[:, None]
    mat[mask] = payload
    return mat, lengths


def _device_to_arrow(dtype: DType, col: DeviceColumn, num_rows: int) -> pa.Array:
    data, validity, lengths = col.to_numpy(num_rows)
    return _numpy_to_arrow(dtype, data, validity, lengths, num_rows)


def _numpy_to_arrow(dtype: DType, data: np.ndarray, validity: np.ndarray,
                    lengths: Optional[np.ndarray], num_rows: int) -> pa.Array:
    mask = ~validity  # arrow mask semantics: True = null
    if dtype is DType.STRING:
        sel = np.arange(int(lengths.max()) if num_rows else 0)[None, :] < lengths[:, None]
        payload = data[:, :sel.shape[1]][sel] if num_rows else np.zeros(0, np.uint8)
        offsets = np.zeros(num_rows + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1:])
        return pa.StringArray.from_buffers(
            num_rows,
            pa.py_buffer(offsets.tobytes()),
            pa.py_buffer(payload.tobytes()),
            pa.py_buffer(np.packbits(validity, bitorder="little").tobytes()),
            int(mask.sum()))
    null_count = int(mask.sum())
    validity_buf = (None if null_count == 0
                    else pa.py_buffer(np.packbits(validity, bitorder="little").tobytes()))
    if dtype is DType.BOOLEAN:
        data_buf = pa.py_buffer(np.packbits(data, bitorder="little").tobytes())
    else:
        data_buf = pa.py_buffer(np.ascontiguousarray(data).tobytes())
    storage_type = {DType.TIMESTAMP: pa.int64(), DType.DATE: pa.int32()}.get(
        dtype, dtype.pa_type())
    out = pa.Array.from_buffers(storage_type, num_rows, [validity_buf, data_buf],
                                null_count)
    return out.cast(dtype.pa_type()) if storage_type != dtype.pa_type() else out
