"""Encoded columnar forms that cross the host link instead of decoded bytes.

Where the host link is the wall (columnar/transfer.py), this module stops
shipping decoded bytes over it
(ROADMAP item 1; "GPU Acceleration of SQL Analytics on Compressed Data"
measures order-of-magnitude effective-bandwidth gains from exactly this
shape). Three cooperating pieces:

- **Run-end-encoded staging** (`ree_staged`, `pad_runs`,
  `ree_expand_program`): a
  parquet column chunk whose index stream is RLE-dominant uploads as
  (run_ends, per-run values) pairs — often hundreds of bytes for millions
  of rows — and expands in HBM in one cached program per shape,
  ``ree_expand`` (a scatter of the run ends, a cumsum, one gather a
  buffer), the TPU analog of the reference's device-side decode
  (GpuParquetScan.scala:576). The host never materializes the decoded
  column.
- **DictEncoding** (`DictEncoding`, `EncSpec`, flatten helpers): a device
  batch column that arrived dictionary-encoded KEEPS its narrow index
  vector and small dictionary alongside the decoded data, so downstream
  operators can run filters, group-by keys and equi-join keys directly on
  the int32 index domain (late materialization; exprs/encoded.py).
- **DictionaryUnifier**: per-scan host-side remap of each row group's
  dictionary into one growing, prefix-compatible dictionary per column, so
  batches of one scan share a dictionary identity (``token``) and
  ``concat_device_batches`` can carry the encoding across batch boundaries
  instead of dropping it at the first coalesce.
"""
from __future__ import annotations

import uuid
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.dtypes import DType

#: pa.Field metadata key carrying the DictionaryUnifier token of a column
DICT_TOKEN_META = b"spark_rapids_tpu.dict_token"


# ---------------------------------------------------------------------------
# run-end-encoded host staging + device expansion
# ---------------------------------------------------------------------------
def ree_staged(arr: "pa.RunEndEncodedArray") -> Tuple[np.ndarray, pa.Array]:
    """Normalize a (possibly sliced) REE array to slice-relative
    ``(run_ends int32, values)``: run_ends are clipped to the slice and the
    values array keeps only the runs the slice touches. O(runs), not O(rows)
    — slicing stays cheap however long the runs are."""
    ends = np.asarray(arr.run_ends, dtype=np.int64)
    off, n = arr.offset, len(arr)
    if n == 0:
        return np.zeros(0, np.int32), arr.values.slice(0, 0)
    first = int(np.searchsorted(ends, off, side="right"))
    last = int(np.searchsorted(ends, off + n - 1, side="right"))
    rel = np.clip(ends[first:last + 1] - off, 0, n).astype(np.int32)
    rel[-1] = n
    return rel, arr.values.slice(first, last + 1 - first)


def ree_to_plain(arr: "pa.RunEndEncodedArray") -> pa.Array:
    """Expand an REE array on HOST (CPU-engine / fallback paths only; the
    device path expands in HBM in the ``ree_expand`` program)."""
    ends, vals = ree_staged(arr)
    if len(ends) == 0:
        return vals
    counts = np.diff(np.concatenate([[0], ends.astype(np.int64)]))
    take = np.repeat(np.arange(len(ends), dtype=np.int64), counts)
    return vals.take(pa.array(take))


def pad_runs(run_ends: np.ndarray, capacity: int,
             per_run: Sequence[Optional[np.ndarray]]
             ) -> Tuple[np.ndarray, List[Optional[np.ndarray]]]:
    """Pad a staged run list to its power-of-two bucket (``dict_bucket``),
    so that run lists of one bucket share one ``ree_expand`` program: pad
    ends sit at ``capacity``, which the expansion's scatter drops, and pad
    values are zeros (False) that no row reads."""
    pad = dict_bucket(len(run_ends)) - len(run_ends)
    if not pad:
        return run_ends, list(per_run)
    ends = np.concatenate([run_ends, np.full(pad, capacity, np.int32)])
    return ends, [None if a is None
                  else np.concatenate([a, np.zeros(pad, a.dtype)])
                  for a in per_run]


def ree_expand_program(capacity: int):
    """The traced body of the ``ree_expand`` program: run-end expansion to
    ``capacity`` rows. Row i belongs to run #{run ends <= i}
    (``searchsorted(run_ends, i, side="right")``), counted by a scatter of
    ones at the run ends and an inclusive cumsum: no search, no loop,
    O(capacity + runs) for any run count. Ends at or past ``capacity`` (the
    last live one where the batch is full, and the pad of ``pad_runs``) are
    dropped; rows past the last live end (capacity padding) clamp to run
    ``num_runs - 1``, and the batch's live mask excludes them. ``num_runs``
    and ``num_rows`` are traced scalars, so run lists of one bucket share
    the program whatever their count. Returns the gathered ``(values,
    bits, valid)``: ``bits`` None where not given, ``valid`` None where the
    runs carry none (the batch's shared mask then serves), else AND-ed
    with the live rows."""
    import jax.numpy as jnp

    def ree_expand(run_ends, num_runs, num_rows, values, bits, valid):
        hits = jnp.zeros(capacity, np.int32).at[run_ends].add(1, mode="drop")
        idx = jnp.minimum(jnp.cumsum(hits, dtype=np.int32), num_runs - 1)

        def take(a):
            return None if a is None else jnp.take(a, idx, axis=0)
        if valid is not None:
            valid = jnp.logical_and(
                take(valid), jnp.arange(capacity, dtype=np.int32) < num_rows)
        return take(values), take(bits), valid
    return ree_expand


def ree_encoded_nbytes(num_runs: int, elem_size: int) -> int:
    """On-link bytes of the REE form: int32 run ends + one value per run."""
    return num_runs * (4 + elem_size)


# ---------------------------------------------------------------------------
# device-side dictionary encoding (late materialization)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DictEncoding:
    """The encoded form of a device column, kept alongside the decoded data:
    ``data == take(values, indices)`` row-wise (strings: byte-matrix rows +
    lengths). ``token`` identifies the dictionary stream a batch came from
    (DictionaryUnifier): same token => dictionaries are prefix-compatible,
    so concatenation and encoded-domain joins need no remap.

    ``values`` is PADDED to a power-of-two bucket (device-side zeros, no
    link bytes): the padded size is what enters jit cache keys (EncSpec.k),
    so a unified dictionary growing by a few entries per row group does not
    recompile every encoded-domain program — the R001 discipline applied to
    dictionaries. ``k_real`` is the live entry count; indices never point
    past it, and value-sensitive kernels (the join remap) mask pad slots
    with it as a traced scalar."""

    indices: Any                      # int32[capacity] device array
    values: Any                       # [k] or [k, width] device array
    k_real: int                       # live dictionary entries (<= k)
    lengths: Optional[Any] = None     # int32[k], strings only
    token: Optional[str] = None

    @property
    def k(self) -> int:
        return int(self.values.shape[0])


def dict_bucket(k: int) -> int:
    """Power-of-two padding bucket for dictionary and run-list device
    arrays."""
    from spark_rapids_tpu.columnar.dtypes import bucket_capacity
    return bucket_capacity(k, minimum=8)


@dataclass(frozen=True)
class EncSpec:
    """Static shape of one column's DictEncoding — everything a jitted
    program needs to know at trace time (part of every jit cache key that
    involves encoded-domain execution)."""
    ordinal: int
    dtype: DType
    k: int
    width: int = 0                    # string matrix width; 0 otherwise

    @property
    def is_string(self) -> bool:
        return self.dtype is DType.STRING


class EncView:
    """Trace-time view of one encoding: the index vector plus the dictionary
    as a ColV over ``k`` (padded) rows — all-valid; parquet/unified
    dictionaries hold no nulls, nullness rides the index validity.
    ``k_real`` is the traced live-entry count (pad slots are garbage that
    indices never reference; only value-sensitive kernels mask with it)."""

    def __init__(self, xp, spec: EncSpec, indices, values, k_real,
                 lengths=None):
        from spark_rapids_tpu.exprs.core import ColV
        self.spec = spec
        self.indices = indices
        self.k_real = k_real
        self.values = ColV(spec.dtype, values,
                           xp.ones(spec.k, dtype=np.bool_), lengths)


def enc_specs_of(batch) -> Tuple[EncSpec, ...]:
    """EncSpecs for every column of ``batch`` that still carries its
    dictionary encoding (only useful encodings: k below the row capacity)."""
    specs = []
    for i, c in enumerate(batch.columns):
        e = c.encoding
        if e is None or e.k_real >= batch.capacity:
            continue
        width = int(e.values.shape[1]) if e.values.ndim > 1 else 0
        specs.append(EncSpec(i, c.dtype, e.k, width))
    return tuple(specs)


def flatten_encodings(batch, specs: Sequence[EncSpec]) -> List[Any]:
    """Device arrays of the named encodings in the fixed flat order
    [indices, values(, lengths), k_real] per spec — appended after the
    regular column flat args at jit boundaries. ``k_real`` rides as a
    TRACED scalar (like num_rows) so dictionary growth inside one padding
    bucket never recompiles."""
    flat: List[Any] = []
    for s in specs:
        e = batch.columns[s.ordinal].encoding
        flat.append(e.indices)
        flat.append(e.values)
        if e.lengths is not None:
            flat.append(e.lengths)
        flat.append(np.int32(e.k_real))
    return flat


def unflatten_encodings(xp, specs: Sequence[EncSpec], flat
                        ) -> Dict[int, EncView]:
    views: Dict[int, EncView] = {}
    i = 0
    for s in specs:
        if s.is_string:
            views[s.ordinal] = EncView(xp, s, flat[i], flat[i + 1],
                                       flat[i + 3], flat[i + 2])
            i += 4
        else:
            views[s.ordinal] = EncView(xp, s, flat[i], flat[i + 1],
                                       flat[i + 2])
            i += 3
    return views


def dictionary_is_unique(values: np.ndarray,
                         lengths: Optional[np.ndarray] = None) -> bool:
    """Encoded-domain execution equates rows by dictionary INDEX, which is
    only sound when dictionary values are pairwise distinct. Parquet and
    unifier dictionaries are; user-built pa.DictionaryArrays may not be —
    check before claiming the encoding (k is small, so this is cheap)."""
    if values.ndim > 1:
        rows = np.concatenate(
            [values, np.zeros((len(values), 1), values.dtype)
             if lengths is None else lengths[:, None].astype(values.dtype)],
            axis=1)
        return len(np.unique(rows, axis=0)) == len(rows)
    return len(np.unique(values)) == len(values)


def field_token(schema: pa.Schema, i: int) -> Optional[str]:
    meta = schema.field(i).metadata
    if meta and DICT_TOKEN_META in meta:
        return meta[DICT_TOKEN_META].decode()
    return None


# ---------------------------------------------------------------------------
# host-side dictionary unification (per scan)
# ---------------------------------------------------------------------------
class DictionaryUnifier:
    """Grow one dictionary per column across a scan's row groups / files.

    Each row group's local dictionary is remapped into the column's global
    dictionary (append-only, so earlier batches' indices stay valid — the
    dictionaries of any two batches with the same token are prefix-
    compatible). The remap is a tiny LUT gather: O(k) dictionary work plus
    one vectorized O(n) int gather per chunk, nothing like a decode.

    Float dictionaries dedupe by BIT PATTERN, not Python ``==``: -0.0 and
    0.0 are distinct entries (collapsing them would flip signs in decoded
    rows) and equal-bit NaNs dedupe instead of growing the dictionary per
    row group; values are stored as numpy scalars so reconstruction is
    bit-exact."""

    def __init__(self):
        self._cols: Dict[str, Tuple[str, Dict[Any, int], List[Any]]] = {}

    def _state(self, name: str):
        st = self._cols.get(name)
        if st is None:
            st = (uuid.uuid4().hex, {}, [])
            self._cols[name] = st
        return st

    def token_of(self, name: str) -> Optional[str]:
        st = self._cols.get(name)
        return st[0] if st else None

    def unify(self, name: str, arr: pa.DictionaryArray
              ) -> Tuple[pa.DictionaryArray, str]:
        """Remap one chunk's dictionary into the column's global dictionary;
        returns the remapped array + the column token."""
        token, index_of, values = self._state(name)
        dict_type = arr.dictionary.type
        bitwise = pa.types.is_floating(dict_type)
        np_t = dict_type.to_pandas_dtype() if bitwise else None
        if bitwise and arr.dictionary.null_count == 0:
            local = list(np.asarray(arr.dictionary))
            keys = [v.tobytes() for v in local]
        elif bitwise:
            # null dictionary entries (never produced by the page reader):
            # keep the byte-key domain so chunks of one column never mix
            # key kinds; python floats preserve -0.0 and the standard NaN
            local = [None if v is None else np.dtype(np_t).type(v)
                     for v in arr.dictionary.to_pylist()]
            keys = [None if v is None else v.tobytes() for v in local]
        else:
            local = arr.dictionary.to_pylist()
            keys = local
        lut = np.empty(len(local), dtype=np.int32)
        for j, (key, v) in enumerate(zip(keys, local)):
            gi = index_of.get(key)
            if gi is None:
                gi = len(values)
                index_of[key] = gi
                values.append(v)
            lut[j] = gi
        k = len(values)
        idx_t = (pa.int8() if k <= 127 else
                 pa.int16() if k <= 0x7FFF else pa.int32())
        local_idx = np.asarray(arr.indices.fill_null(0)).astype(np.int64)
        remapped = lut[local_idx].astype(idx_t.to_pandas_dtype())
        mask = (None if arr.indices.null_count == 0
                else np.asarray(arr.indices.is_null()))
        indices = pa.array(remapped, type=idx_t, mask=mask)
        if bitwise and all(v is not None for v in values):
            global_vals = pa.array(np.array(values, dtype=np_t))
        else:
            global_vals = pa.array(values, type=dict_type)
        return pa.DictionaryArray.from_arrays(indices, global_vals), token


def with_dict_tokens(table: pa.Table, tokens: Dict[str, str]) -> pa.Table:
    """Stamp dictionary tokens into the table's field metadata so they
    survive slicing/coalescing and reach DeviceBatch.from_arrow without a
    side channel."""
    if not tokens:
        return table
    fields = []
    for f in table.schema:
        if f.name in tokens:
            meta = dict(f.metadata or {})
            meta[DICT_TOKEN_META] = tokens[f.name].encode()
            fields.append(f.with_metadata(meta))
        else:
            fields.append(f)
    return pa.table(list(table.columns), schema=pa.schema(fields))
