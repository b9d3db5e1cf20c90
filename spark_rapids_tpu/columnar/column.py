"""Device column: the TPU-side equivalent of GpuColumnVector.

Reference analog: GpuColumnVector.java:40 wraps an ``ai.rapids.cudf.ColumnVector``
(device buffer + Arrow-style validity bitmask + string offsets). The TPU layout is
re-designed for XLA:

- every buffer is a jax.Array with a *static, bucketed* shape (see
  dtypes.bucket_capacity) so compiled programs are reused across batches;
- validity is a ``bool[capacity]`` vector, not a bitmask — the VPU is fine with
  byte masks and XLA fuses mask math into consumers;
- strings are a ``uint8[capacity, max_bytes]`` matrix plus an ``int32[capacity]``
  length vector (fixed-width layout): substring/upper/concat/compare become plain
  vectorized array ops on the MXU/VPU instead of offset-chasing kernels;
- rows at index >= num_rows (padding) always have validity False, length 0 and
  zeroed data, so reductions can run over the full capacity unconditionally.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DType


@dataclass(frozen=True)
class DeviceColumn:
    """One column of a device batch. Immutable (functional updates only)."""

    dtype: DType
    data: jax.Array                  # [capacity] or [capacity, max_bytes] for strings
    validity: jax.Array              # bool[capacity]
    lengths: Optional[jax.Array] = None  # int32[capacity], strings only
    #: DOUBLE columns only: the IEEE-754 bit pattern as uint64, kept from
    #: upload time. The X64-rewritten backend cannot bitcast f64->u64 (only
    #: u64->f64), so the accelerated shuffle's byte packing needs the bits
    #: carried alongside; device-computed doubles instead ride an exact
    #: three-float32 expansion (shuffle/partition_kernel.py).
    bits: Optional[jax.Array] = None
    #: columns that arrived dictionary-encoded keep their narrow index
    #: vector + small dictionary on device (columnar/encoding.DictEncoding)
    #: so filters/group-by/join keys can run on the index domain instead of
    #: the decoded values (exprs/encoded.py); invariant:
    #: data == take(encoding.values, encoding.indices) row-wise. Kernels
    #: that rebuild columns drop it (their output is no longer the gather).
    encoding: Optional["DictEncoding"] = None  # noqa: F821

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def max_bytes(self) -> int:
        if self.dtype is not DType.STRING:
            raise ValueError("max_bytes only defined for string columns")
        return int(self.data.shape[1])

    @property
    def device_size_bytes(self) -> int:
        total = self.data.size * self.data.dtype.itemsize
        total += self.validity.size
        if self.lengths is not None:
            total += self.lengths.size * 4
        if self.bits is not None:
            total += self.bits.size * 8
        return total

    @property
    def row_bytes(self) -> int:
        """One row's bytes at the stored width, validity byte and string
        length included: what moving a row (between partitions, between
        shards) must carry. From the arrays' shapes alone."""
        width = int(np.prod(self.data.shape[1:]))
        return (self.data.dtype.itemsize * width + 1
                + (4 if self.lengths is not None else 0))

    def __post_init__(self):
        if self.dtype is DType.STRING and self.lengths is None:
            raise ValueError("string column requires lengths vector")

    # ---------------------------------------------------------------------------
    @staticmethod
    def from_numpy(dtype: DType, data: np.ndarray, validity: Optional[np.ndarray],
                   capacity: int, max_bytes: int = 0,
                   lengths: Optional[np.ndarray] = None,
                   device: Any = None) -> "DeviceColumn":
        """Pad host buffers to ``capacity`` and upload. Padding rows are invalid/zero."""
        staged = DeviceColumn.stage_numpy(dtype, data, validity, capacity,
                                          max_bytes, lengths)
        put = (lambda x: jax.device_put(x, device)) if device is not None \
            else jax.device_put
        return DeviceColumn(dtype, *[put(a) if a is not None else None
                                     for a in staged])

    @staticmethod
    def stage_numpy(dtype: DType, data: np.ndarray,
                    validity: Optional[np.ndarray], capacity: int,
                    max_bytes: int = 0, lengths: Optional[np.ndarray] = None):
        """Capacity-padded host buffers ready for upload — split out so batch
        builders can stage every column first and ship ONE device_put tree
        (per-array transfers pay a fixed host-link round trip each)."""
        n = data.shape[0]
        if n > capacity:
            raise ValueError(f"{n} rows > capacity {capacity}")
        if validity is None:
            validity = np.ones(n, dtype=np.bool_)
        vals = np.zeros(capacity, dtype=np.bool_)
        vals[:n] = validity
        if dtype is DType.STRING:
            assert lengths is not None
            mat = np.zeros((capacity, max_bytes), dtype=np.uint8)
            mat[:n, :data.shape[1]] = data
            lens = np.zeros(capacity, dtype=np.int32)
            lens[:n] = lengths
            return (mat, vals, lens)
        buf = np.zeros(capacity, dtype=dtype.np_dtype())
        buf[:n] = data
        return (buf, vals, None)

    def to_numpy(self, num_rows: int):
        """Download the first ``num_rows`` rows. The slice happens ON DEVICE so
        only the live rows cross the host link — downloading a capacity-sized
        buffer to read 4 result rows is pure waste (and host links can be
        orders of magnitude slower than HBM)."""
        data = np.asarray(self.data[:num_rows])
        validity = np.asarray(self.validity[:num_rows])
        lengths = (np.asarray(self.lengths[:num_rows])
                   if self.lengths is not None else None)
        return data, validity, lengths


def null_column(dtype: DType, capacity: int, max_bytes: int = 0) -> DeviceColumn:
    """All-null column of the given capacity."""
    validity = jnp.zeros(capacity, dtype=jnp.bool_)
    if dtype is DType.STRING:
        data = jnp.zeros((capacity, max_bytes), dtype=jnp.uint8)
        lengths = jnp.zeros(capacity, dtype=jnp.int32)
        return DeviceColumn(dtype, data, validity, lengths)
    data = jnp.zeros(capacity, dtype=dtype.np_dtype())
    return DeviceColumn(dtype, data, validity)
