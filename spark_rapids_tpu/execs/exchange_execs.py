"""Shuffle exchange operators + partitioning implementations.

Reference analogs:
- GpuShuffleExchangeExec (execution/GpuShuffleExchangeExec.scala, 254 LoC) —
  partitions each child batch on device, hands the pieces to the shuffle
  manager, and reads one reduce partition back;
- the partitioning impls: GpuHashPartitioning.scala (murmur3 hash +
  Table.partition, partitionInternal:86), GpuRangePartitioning +
  GpuRangePartitioner (sample-based bounds via SamplingUtils),
  GpuRoundRobinPartitioning, GpuSinglePartitioning;
- the common split path Table.contiguousSplit (GpuPartitioning.scala:44-75) —
  here ONE stable argsort by target partition id + per-partition counts, then
  host-static slices, all inside a single jitted XLA program per
  (partitioning, schema, capacity) key;
- ShuffledBatchRDD / GpuShuffleDependency (execution/ShuffledBatchRDD.scala) —
  the reduce side reads through the caching shuffle manager, so map outputs
  stay resident on device (spilling host/disk under memory pressure).

The CPU exchange stands in for Spark's stock shuffle (the non-accelerated
columnar path through GpuColumnarBatchSerializer): an in-memory split with the
exact same generic kernels run under numpy, so CPU-vs-TPU compare tests cover
the partitioning math itself.
"""
from __future__ import annotations

import functools
import itertools
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import (DType, Field, Schema,
                                              bucket_capacity)
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.execs.cpu_execs import _colvs_to_host, _host_colvs
from spark_rapids_tpu.execs.tpu_execs import (_cached_jit, _flatten,
                                              _unflatten_colvs)
from spark_rapids_tpu.exprs.core import (ColV, EvalCtx, Expression,
                                         flatten_colvs)
from spark_rapids_tpu.exprs.misc import SortOrder
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.utils import tracing as _tracing


# ------------------------------------------------------------------ partitionings
@dataclass(frozen=True)
class Partitioning:
    """Base partitioning spec (GpuPartitioning analog). ``kind`` names it
    in the ``exchange.map`` span's ``partitioning`` arg."""
    num_partitions: int

    @property
    def expressions(self) -> Tuple[Expression, ...]:
        return ()


@dataclass(frozen=True)
class SinglePartitioning(Partitioning):
    """Everything into one partition (GpuSinglePartitioning analog)."""
    num_partitions: int = 1
    kind = "single"


@dataclass(frozen=True)
class RoundRobinPartitioning(Partitioning):
    """Row-cycling distribution (GpuRoundRobinPartitioning analog; start
    offset varies per map partition/batch like Spark's per-partition start)."""
    kind = "roundrobin"


@dataclass(frozen=True)
class HashPartitioning(Partitioning):
    """Key-hash distribution (GpuHashPartitioning analog — murmur3-style
    finalizer over the key columns instead of cudf's murmur3 kernel)."""
    keys: Tuple[Expression, ...] = ()
    kind = "hash"

    @property
    def expressions(self) -> Tuple[Expression, ...]:
        return self.keys


@dataclass(frozen=True)
class RangePartitioning(Partitioning):
    """Sample-based contiguous key ranges (GpuRangePartitioning +
    GpuRangePartitioner analog). Bounds are computed at map time from a
    deterministic sample of the input (SamplingUtils role)."""
    orders: Tuple[SortOrder, ...] = ()
    kind = "range"

    @property
    def expressions(self) -> Tuple[Expression, ...]:
        return self.orders


# ------------------------------------------------------------------ hash kernel
_H_M1 = np.uint32(0x85EBCA6B)
_H_M2 = np.uint32(0xC2B2AE35)
_H_NULL = np.uint32(0x9E3779B9)
_H_SEED = np.uint32(42)


def _fmix32(xp, h):
    """murmur3 32-bit finalizer (the mixer GpuHashPartitioning gets from cudf's
    murmur3 kernel; bit-exact Spark parity is not required for correctness —
    only that equal keys map to equal partitions on both engines)."""
    h = xp.bitwise_xor(h, xp.right_shift(h, np.uint32(16)))
    h = (h * _H_M1).astype(np.uint32)
    h = xp.bitwise_xor(h, xp.right_shift(h, np.uint32(13)))
    h = (h * _H_M2).astype(np.uint32)
    h = xp.bitwise_xor(h, xp.right_shift(h, np.uint32(16)))
    return h


def _column_hash(xp, v: ColV) -> "np.ndarray":
    """Per-row uint32 hash of one key column. Equal values (incl. NaN≡NaN,
    -0.0≡0.0, Spark grouping semantics) hash equal."""
    if v.dtype is DType.STRING:
        smax = v.data.shape[-1]
        weights = np.empty(smax, dtype=np.uint32)
        w = 1
        for i in range(smax):
            weights[i] = w
            w = (w * 37) & 0xFFFFFFFF
        h = xp.sum(v.data.astype(np.uint32) * xp.asarray(weights)[None, :],
                   axis=-1, dtype=np.uint32)
        h = xp.bitwise_xor(h, v.lengths.astype(np.uint32))
        return _fmix32(xp, h)
    if v.dtype.is_floating:
        d = v.data.astype(np.float64)
        # canonicalize: all NaNs equal, -0.0 == 0.0
        d = xp.where(xp.isnan(d), np.float64(np.nan), d)
        d = xp.where(d == 0, np.float64(0.0), d)
        if xp is np:
            bits = d.view(np.int64)
        else:
            bits = jax.lax.bitcast_convert_type(d, jnp.int64)
    elif v.dtype is DType.BOOLEAN:
        bits = v.data.astype(np.int64)
    else:
        bits = v.data.astype(np.int64)
    lo = (bits & np.int64(0xFFFFFFFF)).astype(np.uint32)
    hi = xp.right_shift(bits, np.int64(32)).astype(np.uint32)
    return _fmix32(xp, xp.bitwise_xor(_fmix32(xp, lo), hi))


def hash_partition_ids(xp, keys: Sequence[ColV], cap: int, n: int,
                       seed=None):
    """Target partition id per row from the key columns. ``seed`` (default
    the exchange seed) lets the out-of-core grace partitioner re-partition
    with a DIFFERENT hash per recursion depth, so key groups that collided
    mod n at one level separate at the next (memory/grace.py)."""
    h = xp.full((cap,), _H_SEED if seed is None else np.uint32(seed),
                dtype=np.uint32)
    for v in keys:
        ch = _column_hash(xp, v)
        if ch.ndim == 0:  # scalar key (literal)
            ch = xp.broadcast_to(ch, (cap,))
        valid = v.validity
        if getattr(valid, "ndim", 1) == 0:
            valid = xp.broadcast_to(valid, (cap,))
        ch = xp.where(valid, ch, _H_NULL)
        h = _fmix32(xp, (h * np.uint32(31) + ch).astype(np.uint32))
    return (h % np.uint32(n)).astype(np.int32)


def _lex_gt_bounds(xp, row_passes: List, bound_passes: List):
    """pid per row = number of bounds strictly less than the row, comparing the
    sortable key transforms lexicographically (GpuRangePartitioner's
    binary-search equivalent, vectorized over all bounds at once)."""
    cap = row_passes[0].shape[0]
    nb = bound_passes[0].shape[0]
    gt = xp.zeros((cap, nb), dtype=bool)
    eq = xp.ones((cap, nb), dtype=bool)
    for r, b in zip(row_passes, bound_passes):
        rb = r[:, None]
        bb = b[None, :]
        gt = xp.logical_or(gt, xp.logical_and(eq, rb > bb))
        eq = xp.logical_and(eq, rb == bb)
    return xp.sum(gt, axis=1).astype(np.int32)


def range_partition_ids(xp, orders: Sequence[SortOrder], row_keys: Sequence[ColV],
                        bound_keys: Sequence[ColV], cap: int):
    from spark_rapids_tpu.ops.strings import align_widths
    row_passes: List = []
    bound_passes: List = []
    for o, rv, bv in zip(orders, row_keys, bound_keys):
        if rv.lengths is not None:
            # rows and bounds must share a width or their sort-key chunk
            # counts diverge and the lexicographic passes misalign
            rd, bd = align_widths(xp, rv.data, bv.data)
            rv = ColV(rv.dtype, rd, rv.validity, rv.lengths)
            bv = ColV(bv.dtype, bd, bv.validity, bv.lengths)
        row_passes.extend(bk._key_passes(xp, rv, o.ascending, o.nulls_first))
        bound_passes.extend(bk._key_passes(xp, bv, o.ascending, o.nulls_first))
    return _lex_gt_bounds(xp, row_passes, bound_passes)


# ------------------------------------------------------------------ split kernel
def split_by_pid(xp, colvs: Sequence[ColV], pids, num_rows, n: int):
    """Stable partition-major reorder + per-partition counts — the
    Table.partition + contiguousSplit analog. Dead (padding) rows sort to a
    virtual partition n at the back. One variadic sort carries every column
    (no per-column gathers). Returns (reordered colvs, counts[n])."""
    cap = pids.shape[0]
    alive = bk.alive_mask(xp, cap, num_rows)
    key = xp.where(alive, pids, np.int32(n))
    out, _ = bk.sort_colvs(xp, [key], colvs)
    if xp is np:
        counts = np.bincount(key, minlength=n + 1)[:n].astype(np.int64)
    else:
        # NOT jnp.bincount: that lowers to a scatter-add (~15x slower than
        # the whole sort on TPU); a one-hot compare+reduce is vectorized
        counts = jnp.sum(
            key[None, :] == jnp.arange(n, dtype=key.dtype)[:, None],
            axis=1, dtype=jnp.int64)
    return out, counts


def _slice_padded(colvs: Sequence[ColV], schema: Schema, start: int,
                  cnt: int) -> DeviceBatch:
    """One contiguous slice of partition-major columns -> a fresh DeviceBatch
    (live rows first, re-bucketed capacity, zero padding).

    Runs as ONE jitted program keyed by the OUTPUT bucket only —
    ``start``/``cnt`` are device arguments (dynamic_slice + mask), so every
    partition of every exchange with the same shape bucket reuses one
    compiled slice instead of dispatching per-column eager ops."""
    cap = bucket_capacity(cnt)
    key = ("slice_padded", schema, colvs[0].validity.shape[0] if colvs else 0,
           cap, tuple(v.data.shape[1:] for v in colvs))

    def build(schema=schema, cap=cap,
              in_cap=colvs[0].validity.shape[0] if colvs else 0):
        def fn(start, cnt, *flat):
            cols = _unflatten_colvs(schema, flat)
            live = jnp.arange(cap, dtype=np.int32) < cnt
            # a slice starting near the tail would be clamped by XLA and
            # misalign rows: extend the source by `cap` zero rows so every
            # in-range start stays exact
            s = jnp.clip(start, 0, in_cap)

            def ext(a):
                return jnp.concatenate(
                    [a, jnp.zeros((cap,) + a.shape[1:], a.dtype)], axis=0)

            outs = []
            for v in cols:
                data = jax.lax.dynamic_slice_in_dim(ext(v.data), s, cap, 0)
                data = jnp.where(
                    live.reshape((cap,) + (1,) * (data.ndim - 1)), data, 0)
                validity = jnp.logical_and(
                    jax.lax.dynamic_slice_in_dim(ext(v.validity), s, cap, 0),
                    live)
                outs.append(data)
                outs.append(validity)
                if v.lengths is not None:
                    outs.append(jnp.where(
                        live,
                        jax.lax.dynamic_slice_in_dim(ext(v.lengths), s, cap,
                                                     0),
                        0))
            return tuple(outs)
        return fn

    from spark_rapids_tpu.execs.tpu_execs import _cached_jit
    import jax
    fn = _cached_jit(key, build)
    res = fn(np.int32(start), np.int32(cnt), *flatten_colvs(list(colvs)))
    cols = []
    i = 0
    for f in schema:
        if f.dtype is DType.STRING:
            cols.append(DeviceColumn(f.dtype, res[i], res[i + 1], res[i + 2]))
            i += 3
        else:
            cols.append(DeviceColumn(f.dtype, res[i], res[i + 1]))
            i += 2
    return DeviceBatch(schema, tuple(cols), cnt)


def _exchange_encodings(ctx, db: DeviceBatch) -> dict:
    """Columns whose dictionary encoding rides THROUGH the exchange (conf
    sql.exchange.keepEncodings): only token-carrying encodings qualify — the
    token marks a scan-wide unified dictionary, so every piece of every
    batch of one exchange shares prefix-compatible values and downstream
    concat/encoded-domain operators keep composing."""
    from spark_rapids_tpu import config as _cfg
    if not ctx.conf.get(_cfg.EXCHANGE_KEEP_ENCODINGS):
        return {}
    return {ci: c.encoding for ci, c in enumerate(db.columns)
            if c.encoding is not None and c.encoding.token is not None}


def _encoded_split_preferred(ctx, part, db: DeviceBatch, enc) -> bool:
    """Whether the encoded sort-path split should PREEMPT the fused Pallas
    reorder. When the kernel cannot run anyway (off-TPU backend, kernel
    mode off, range bounds) the encoded sort strictly beats the plain sort
    — always take it. When the kernel IS available, demoting the whole
    batch to the variadic sort must buy real bytes: require the index form
    to save at least a quarter of the batch's per-row exchange bytes, so
    one small encoded INT column among wide decoded columns does not cost
    the streaming-HBM-pass kernel."""
    from spark_rapids_tpu import config as _cfg
    mode = ctx.conf.get(_cfg.SHUFFLE_KERNEL_MODE)
    kernel_possible = (mode != "off"
                       and (mode == "interpret"
                            or jax.default_backend() == "tpu")
                       and not isinstance(part, RangePartitioning))
    if not kernel_possible:
        return True
    saved = total = 0
    for ci, c in enumerate(db.columns):
        width = int(np.prod(c.data.shape[1:])) if c.data.ndim > 1 else 1
        row_b = c.data.dtype.itemsize * width + 1       # + validity byte
        if c.lengths is not None:
            row_b += 4
        total += row_b
        if ci in enc:
            saved += max(0, row_b - 5)    # indices: 4 B + validity byte
    return total > 0 and saved / total >= 0.25


def _materialize_encoded_piece(piece: DeviceBatch, schema: Schema,
                               enc) -> DeviceBatch:
    """Wire piece (indices in place of encoded columns' data) -> real batch:
    one k-bounded gather per encoded column rebuilds the decoded form, and
    the piece keeps the encoding (same dictionary, same token)."""
    from spark_rapids_tpu.columnar.encoding import DictEncoding
    cols = []
    for ci, f in enumerate(schema):
        wc = piece.columns[ci]
        if ci not in enc:
            cols.append(wc)
            continue
        e = enc[ci]
        pcap = wc.capacity
        has_len = e.lengths is not None
        key = ("exchange-enc-piece", f.dtype, pcap, e.k,
               tuple(e.values.shape[1:]), has_len)

        def build(pcap=pcap, has_len=has_len):
            def fn(idx, cnt, values, *dlen):
                live = jnp.arange(pcap, dtype=np.int32) < cnt
                data = values[idx]
                data = jnp.where(
                    live.reshape((pcap,) + (1,) * (data.ndim - 1)), data, 0)
                outs = [data]
                if has_len:
                    outs.append(jnp.where(live, dlen[0][idx], 0))
                return tuple(outs)
            return fn

        fn = _cached_jit(key, build)
        res = fn(wc.data, np.int32(piece.num_rows), e.values,
                 *((e.lengths,) if has_len else ()))
        lengths = res[1] if has_len else None
        encoding = DictEncoding(wc.data, e.values, e.k_real, e.lengths,
                                e.token)
        cols.append(DeviceColumn(f.dtype, res[0], wc.validity, lengths,
                                 encoding=encoding))
    return DeviceBatch(schema, tuple(cols), piece.num_rows)


# ------------------------------------------------------------------ bounds
_SAMPLE_TARGET = 4096

#: sentinel distinguishing "cannot fuse pids into the kernel" (try the
#: two-dispatch path) from "kernel path refused entirely" (None -> sort)
_NOT_FUSABLE = object()

#: exchange exec metrics: map-side batches split by the Pallas reorder
#: kernel vs by the variadic sort (the kernel declines silently otherwise)
KERNEL_SPLIT_BATCHES = "kernelSplitBatches"
SORT_SPLIT_BATCHES = "sortSplitBatches"


def _sample_bounds(orders: Sequence[SortOrder], sampled: List[List[ColV]],
                   n: int) -> Optional[List[ColV]]:
    """Range bounds from per-batch key samples (numpy ColVs, live rows only).
    Returns one ColV per order key holding the n-1 bound values."""
    if not sampled or n <= 1:
        return None
    merged: List[ColV] = []
    for ki in range(len(orders)):
        parts = [batch_keys[ki] for batch_keys in sampled]
        datas = [np.asarray(p.data) for p in parts]
        if parts[0].lengths is not None:
            # per-batch adaptive widths: pad samples to the common bucket
            from spark_rapids_tpu.ops.strings import pad_width
            W = max(d.shape[-1] for d in datas)
            datas = [pad_width(np, d, W) for d in datas]
        data = np.concatenate(datas)
        validity = np.concatenate([np.asarray(p.validity) for p in parts])
        lengths = (np.concatenate([np.asarray(p.lengths) for p in parts])
                   if parts[0].lengths is not None else None)
        merged.append(ColV(parts[0].dtype, data, validity, lengths))
    total = merged[0].validity.shape[0]
    if total == 0:
        return None
    passes: List = []
    for o, v in zip(orders, merged):
        passes.extend(bk._key_passes(np, v, o.ascending, o.nulls_first))
    order = np.lexsort(tuple(reversed([np.asarray(p) for p in passes])))
    # quantile positions: bound i splits at (i+1)/n of the sorted sample
    idx = [order[min(total - 1, ((i + 1) * total) // n)] for i in range(n - 1)]
    idx = np.asarray(idx, dtype=np.int64)
    return [bk.take_colv(np, v, idx) for v in merged]


def _sample_rows(colvs: List[ColV], num_rows: int, k: int) -> List[ColV]:
    """Deterministic evenly-spaced row sample (SamplingUtils stand-in)."""
    if num_rows <= 0:
        idx = np.zeros(0, dtype=np.int64)
    else:
        k = min(k, num_rows)
        idx = np.linspace(0, num_rows - 1, k).astype(np.int64)
    return [bk.take_colv(np, v, idx) for v in colvs]


# ------------------------------------------------------------------ stage stats
#: k-minimum-values sketch width: 64 smallest distinct key hashes bound the
#: per-column distinct estimate's error around 1/sqrt(k) ~ 12% — plenty for
#: the order-of-magnitude placement/fanout decisions AQE makes from it
_KMV_K = 64


def _kmv_merge(pool: "np.ndarray", hashes: "np.ndarray") -> "np.ndarray":
    """Fold new uint32 hash values into a k-minimum-values pool: the
    ``_KMV_K`` smallest DISTINCT hashes seen so far (sorted ascending)."""
    if hashes.size == 0:
        return pool
    # dedup BEFORE truncating: the k smallest VALUES of a skewed batch are
    # copies of one heavy-hitter hash, which would evict every other
    # distinct hash from the pool and collapse the estimate
    return np.unique(np.concatenate([pool, np.unique(hashes)]))[:_KMV_K]


def _kmv_pool_device(h, live, k: int):
    """(pool, n) of one device batch: the ``k`` smallest DISTINCT values of
    the uint32 vector ``h`` under the mask ``live``, ascending, and how many
    were found. ``k`` rounds of a masked minimum over the loop-invariant
    vector (round i: the least hash above round i-1's), each ONE
    memory-bound reduce: no sort, no scatter, no gather over the rows. A
    round that finds nothing repeats the one before; the host merges
    ``pool[:n]``."""
    top = np.uint32(0xFFFFFFFF)
    # a round's minimum is `top` when nothing is left AND when a live hash
    # of 0xFFFFFFFF is all that is left: one pass, once, tells the two apart
    has_top = jnp.any(live & (h == top))
    h = jnp.where(live, h, top)

    def step(carry, _):
        prev, n = carry
        first = n == 0
        least = jnp.min(jnp.where(first | (h > prev), h, top))
        found = (least != top) | (has_top & (first | (prev != top)))
        pick = jnp.where(found, least, prev)
        return (pick, n + found.astype(np.int32)), pick

    (_, n), pool = jax.lax.scan(step, (np.uint32(0), np.int32(0)), None,
                                length=k)
    return pool, n


def _key_hashes(xp, keys, ectx: EvalCtx) -> List:
    """Per-row uint32 hash of each key expression, nulls hashed alike."""
    out = []
    for e in keys:
        v = e.eval(ectx)
        ch = _column_hash(xp, v)
        if ch.ndim == 0:        # scalar key (literal): one value
            ch = xp.broadcast_to(ch, (1,))
        valid = v.validity
        if getattr(valid, "ndim", 1) == 0:
            valid = xp.broadcast_to(valid, ch.shape)
        out.append(xp.where(valid, ch, _H_NULL))
    return out


def _kmv_estimate(pool: "np.ndarray") -> int:
    """Distinct-count estimate from a KMV pool: with the pool unfull every
    distinct hash was kept (the estimate is exact up to hash collisions);
    full, the classic (k-1) / kth-minimum density estimator applies."""
    if pool.size < _KMV_K:
        return int(pool.size)
    kth = int(pool[_KMV_K - 1])
    return int((_KMV_K - 1) * (1 << 32) / max(kth, 1))


@dataclass(frozen=True)
class StageStats:
    """Observed statistics of one materialized shuffle map stage (the
    MapOutputStatistics analog, widened): exact per-reduce-partition row
    counts, the per-partition byte sizes AQE plans against (rows x static
    row width — the same MapStatus convention ``map_output_stats`` uses),
    and a cheap KMV distinct estimate per hash-partitioning key column.
    Attached to the executed exchange; surfaced through EXPLAIN ANALYZE
    and the ``adaptive`` metrics section."""
    partition_rows: Tuple[int, ...]
    partition_bytes: Tuple[int, ...]
    #: distinct-count estimate per partitioning key column (hash
    #: partitioning only; empty otherwise)
    key_distinct: Tuple[int, ...]

    @property
    def total_rows(self) -> int:
        return sum(self.partition_rows)

    @property
    def total_bytes(self) -> int:
        return sum(self.partition_bytes)

    @property
    def median_bytes(self) -> int:
        sizes = sorted(self.partition_bytes)
        return sizes[len(sizes) // 2] if sizes else 0

    def describe(self) -> str:
        nz = [s for s in self.partition_bytes if s]
        out = (f"parts={len(self.partition_bytes)} rows={self.total_rows} "
               f"bytes={self.total_bytes}"
               + (f" max={max(nz)} median={self.median_bytes}" if nz else ""))
        if self.key_distinct:
            out += " ndv~" + "/".join(str(d) for d in self.key_distinct)
        return out


# ------------------------------------------------------------------ exec base
class ShuffleExchangeExecBase(PhysicalExec):
    def size_estimate(self):
        # a repartition moves rows, it does not create or drop them
        return self.children[0].size_estimate()

    def __init__(self, partitioning: Partitioning, child: PhysicalExec):
        super().__init__((child,), child.output)
        self.partitioning = partitioning
        self._lock = threading.Lock()
        self._map_done = False
        #: rows written per reduce partition, filled by _run_map (the
        #: MapStatus sizes that drive AQE decisions)
        self._part_rows: Dict[int, int] = {}
        #: rows per (map partition, reduce partition) — the map-axis
        #: resolution skew-split readers slice on (PartialReducerSpec)
        self._map_part_rows: Dict[Tuple[int, int], int] = {}
        #: KMV pool per hash-partitioning key column (sorted uint32
        #: ndarrays), folded at map time; None until the map side ran
        self._key_sketches: Optional[List["np.ndarray"]] = None
        #: the device engine's sketches not folded yet: per map-side batch
        #: one (pool, found) pair of device arrays per key column, read
        #: when the statistics are (`stage_stats`), never waited for alone
        self._pending_sketches: List[Tuple] = []

    def __getstate__(self):
        # cluster tasks receive pickled exchanges; map state is per-process
        state = dict(self.__dict__)
        state["_lock"] = None
        state["_map_done"] = False
        state["_part_rows"] = {}
        state["_map_part_rows"] = {}
        state["_key_sketches"] = None
        state["_pending_sketches"] = []
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def __copy__(self):
        # copy.copy (with_children/transform_up rewrites) must PRESERVE map
        # state — only pickling resets it (adaptive reuses executed
        # exchanges through copies; a reset would re-run the whole map)
        new = self.__class__.__new__(self.__class__)
        new.__dict__.update(self.__dict__)
        return new

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    def _child_contexts(self, ctx: ExecContext) -> Iterator[ExecContext]:
        return _child_contexts(self.children[0], ctx)

    def _ensure_map(self, ctx: ExecContext) -> None:
        """Run the map side exactly once (all three consumers — both engines'
        reads and AQE's statistics — share this lifecycle)."""
        with self._lock:
            if not self._map_done:
                self._run_map(ctx)
                self._map_done = True

    def map_output_stats(self, ctx: ExecContext) -> List[int]:
        """Estimated bytes per reduce partition, forcing the map side to run
        (Spark's MapOutputStatistics — what AQE reads before re-planning)."""
        from spark_rapids_tpu.execs.cpu_execs import _row_width
        self._ensure_map(ctx)
        width = _row_width(self.output)
        return [self._part_rows.get(p, 0) * width
                for p in range(self.num_partitions)]

    def stage_stats(self, ctx: Optional[ExecContext] = None
                    ) -> Optional[StageStats]:
        """The executed stage's observed statistics, or None when the map
        side has not run (and no ctx was given to force it)."""
        if not self._map_done:
            if ctx is None:
                return None
            self._ensure_map(ctx)
        from spark_rapids_tpu.execs.cpu_execs import _row_width
        width = _row_width(self.output)
        rows = tuple(self._part_rows.get(p, 0)
                     for p in range(self.num_partitions))
        ndv = tuple(_kmv_estimate(pool) for pool in self._folded_sketches())
        return StageStats(rows, tuple(r * width for r in rows), ndv)

    def _sketch_keys(self, ectx: EvalCtx, num_rows: int) -> None:
        """Fold one host batch's key-column hashes into the per-column KMV
        pools (hash partitioning only)."""
        part = self.partitioning
        if not isinstance(part, HashPartitioning) or num_rows <= 0:
            return
        self._merge_sketches([ch[:num_rows]
                              for ch in _key_hashes(np, part.keys, ectx)])

    def _sketch_program(self, ctx: ExecContext, db: DeviceBatch):
        """The device sketch of one batch: ONE cached program per (keys,
        schema, capacity) — the row count rides as a runtime argument, so
        batches of different sizes share it — hashes each key column once
        and takes the k smallest DISTINCT hash VALUES by k masked minima
        (`_kmv_pool_device`): a (pool, found) pair per key column."""
        part = self.partitioning
        schema, cap, smax = db.schema, db.capacity, ctx.string_max_bytes
        key = ("exchange-sketch", part.keys, schema, cap, smax)

        def build(keys=part.keys, schema=schema, cap=cap, smax=smax):
            def fn(num_rows, *flat):
                ectx = EvalCtx(jnp, _unflatten_colvs(schema, flat), cap, smax)
                live = jnp.arange(cap, dtype=np.int32) < num_rows
                return tuple(
                    _kmv_pool_device(jnp.broadcast_to(ch, (cap,)), live,
                                     min(_KMV_K, cap))
                    for ch in _key_hashes(jnp, keys, ectx))
            return fn

        return _cached_jit(key, build)

    def _sketch_keys_device(self, ctx: ExecContext, db: DeviceBatch) -> None:
        """`_sketch_keys` for a device batch, dispatched and not read: the
        pools stay on the device in `_pending_sketches` (bounded: _KMV_K
        uint32s per column per batch, never key data) until `stage_stats`
        folds them, so the host never waits for a sketch alone."""
        self._pending_sketches.append(self._sketch_program(ctx, db)(
            np.int32(db.num_rows), *_flatten(db)))

    def _folded_sketches(self) -> List["np.ndarray"]:
        """The KMV pools, the pending device pools read (one transfer for
        all of them) and folded in first. A copy of the exec shares the
        pending list and may fold it again: the merge is idempotent."""
        with self._lock:
            pending, self._pending_sketches = self._pending_sketches, []
            fetched = jax.device_get(pending)
            for pools in fetched:
                self._merge_sketches([pool[:n] for pool, n in pools])
            return list(self._key_sketches or ())

    def _merge_sketches(self, hashes) -> None:
        if self._key_sketches is None:
            self._key_sketches = [np.zeros(0, dtype=np.uint32)
                                  for _ in hashes]
        for ki, ch in enumerate(hashes):
            self._key_sketches[ki] = _kmv_merge(self._key_sketches[ki],
                                                np.asarray(ch))

    def map_slices(self, pid: int, num_slices: int) -> List[Tuple[int, ...]]:
        """Contiguous map-id groups covering reduce partition ``pid``,
        balanced by observed per-map-task row counts — the slice axis of a
        PartialReducerSpec (Spark's ShufflePartitionsUtil map-range split).
        Returns fewer than ``num_slices`` groups when the map side has too
        few contributing tasks to split that fine."""
        contrib = sorted((m, r) for (m, p), r in self._map_part_rows.items()
                         if p == pid and r > 0)
        if not contrib:
            return []
        total = sum(r for _, r in contrib)
        num_slices = max(1, min(num_slices, len(contrib)))
        target = total / num_slices
        slices: List[Tuple[int, ...]] = []
        group: List[int] = []
        acc = 0
        for m, r in contrib:
            group.append(m)
            acc += r
            if acc >= target * (len(slices) + 1) and \
                    len(slices) + 1 < num_slices:
                slices.append(tuple(group))
                group = []
        if group:
            slices.append(tuple(group))
        return slices

    def execute_partial(self, ctx: ExecContext,
                        map_ids: Tuple[int, ...]) -> Iterator:
        """Read ONE reduce partition (``ctx.partition_id``) restricted to
        the given map tasks' output — the PartialReducerPartitionSpec read
        path. Engine subclasses override."""
        raise NotImplementedError(self.name)


def _child_contexts(child: PhysicalExec, ctx: ExecContext) -> Iterator[ExecContext]:
    """One ExecContext per partition of ``child`` (map-side / build-side walk)."""
    child_parts = child.num_partitions
    for p in range(child_parts):
        yield ExecContext(ctx.conf, partition_id=p,
                          num_partitions=child_parts,
                          device_manager=ctx.device_manager,
                          cleanups=ctx.cleanups,
                          cluster_shuffle=ctx.cluster_shuffle,
                          placement=ctx.placement)


class CpuShuffleExchangeExec(ShuffleExchangeExecBase):
    """In-memory exchange for the CPU engine (the stock-Spark-shuffle role)."""

    def execute(self, ctx: ExecContext) -> Iterator[HostBatch]:
        self._ensure_map(ctx)
        for _map_p, hb in self._parts.get(ctx.partition_id, []):
            self.count_output(hb.num_rows)
            yield hb

    def execute_partial(self, ctx: ExecContext,
                        map_ids: Tuple[int, ...]) -> Iterator[HostBatch]:
        self._ensure_map(ctx)
        wanted = set(map_ids)
        for map_p, hb in self._parts.get(ctx.partition_id, []):
            if map_p in wanted:
                self.count_output(hb.num_rows)
                yield hb

    def _run_map(self, ctx: ExecContext) -> None:
        n = self.partitioning.num_partitions
        #: reduce pid -> [(map partition, batch)]: the map id rides along so
        #: partial-reducer reads can slice one reduce partition by map task
        self._parts: Dict[int, List[Tuple[int, HostBatch]]] = {}
        if ctx.cleanups is not None:
            # release the shuffled copy when the action finishes (the exec tree
            # outlives the action via session.last_plan)
            ctx.cleanups.append(self._release)
        part = self.partitioning

        # only range partitioning needs the two-pass staging (bounds sampling)
        bounds = None
        if isinstance(part, RangePartitioning):
            staged: List[Tuple[int, int, HostBatch]] = []
            for cctx in self._child_contexts(ctx):
                for bi, hb in enumerate(self.children[0].execute(cctx)):
                    staged.append((cctx.partition_id, bi, hb))
            sampled = []
            per = max(1, _SAMPLE_TARGET // max(1, len(staged)))
            for _, _, hb in staged:
                colvs = _host_colvs(hb)
                ectx = EvalCtx(np, colvs, hb.num_rows, ctx.string_max_bytes)
                keys = [o.child.eval(ectx) for o in part.orders]
                sampled.append(_sample_rows(keys, hb.num_rows, per))
            bounds = _sample_bounds(part.orders, sampled, n)
            batches = iter(staged)
        else:
            batches = ((cctx.partition_id, bi, hb)
                       for cctx in self._child_contexts(ctx)
                       for bi, hb in enumerate(self.children[0].execute(cctx)))

        for map_p, bi, hb in batches:
            colvs = _host_colvs(hb)
            cap = hb.num_rows
            offset = _round_robin_offset(part, map_p, bi)
            ectx = EvalCtx(np, colvs, cap, ctx.string_max_bytes)
            with np.errstate(invalid="ignore", over="ignore"):
                pids = _compute_pids(np, part, ectx, cap, offset, bounds)
                self._sketch_keys(ectx, cap)
            sorted_cols, counts = split_by_pid(np, colvs, pids, hb.num_rows, n)
            offsets = np.concatenate([[0], np.cumsum(counts)])
            for j in range(n):
                cnt = int(counts[j])
                if cnt == 0:
                    continue
                start = int(offsets[j])
                sub = [ColV(v.dtype,
                            np.asarray(v.data)[start:start + cnt],
                            np.asarray(v.validity)[start:start + cnt],
                            (np.asarray(v.lengths)[start:start + cnt]
                             if v.lengths is not None else None))
                       for v in sorted_cols]
                self._parts.setdefault(j, []).append(
                    (map_p, _colvs_to_host(self.output, sub, cnt)))
                self._part_rows[j] = self._part_rows.get(j, 0) + cnt
                self._map_part_rows[(map_p, j)] = \
                    self._map_part_rows.get((map_p, j), 0) + cnt

    def _release(self) -> None:
        self._parts = {}
        self._part_rows = {}
        self._map_part_rows = {}
        self._key_sketches = None
        self._pending_sketches = []
        self._map_done = False


def _round_robin_offset(part: Partitioning, map_partition: int,
                        batch_index: int) -> int:
    """Start offset of the row cycle; only round robin distinguishes batches
    (keeps jit cache keys independent of batch identity for the others)."""
    if isinstance(part, RoundRobinPartitioning):
        return (map_partition * 7919 + batch_index) % part.num_partitions
    return 0


def _compute_pids(xp, part: Partitioning, ectx: EvalCtx, cap: int,
                  offset, bounds: Optional[List[ColV]]):
    """``offset`` may be a python int or a traced int32 scalar — the fused
    exchange program passes it as a RUNTIME argument so one compiled
    program serves every round-robin batch offset."""
    if isinstance(part, SinglePartitioning) or part.num_partitions == 1:
        return xp.zeros(cap, dtype=np.int32)
    if isinstance(part, RoundRobinPartitioning):
        return ((xp.arange(cap, dtype=np.int32)
                 + xp.asarray(offset).astype(np.int32))
                % np.int32(part.num_partitions)).astype(np.int32)
    if isinstance(part, HashPartitioning):
        keys = [e.eval(ectx) for e in part.keys]
        return hash_partition_ids(xp, keys, cap, part.num_partitions)
    if isinstance(part, RangePartitioning):
        if bounds is None:
            return xp.zeros(cap, dtype=np.int32)
        row_keys = [o.child.eval(ectx) for o in part.orders]
        return range_partition_ids(xp, part.orders, row_keys, bounds, cap)
    raise NotImplementedError(type(part).__name__)


# ------------------------------------------------------------------ TPU exchange
class _LocalShuffleEnv:
    """Minimal single-executor env facade over the DeviceManager's spillable
    store (the GpuShuffleEnv role for the in-process engine — map outputs are
    cached on device and spill HBM->host->disk under pressure)."""

    def __init__(self, device_manager):
        from spark_rapids_tpu.shuffle.catalog import ShuffleBufferCatalog
        self.executor_id = "local"
        self.shuffle_catalog = ShuffleBufferCatalog(
            device_manager.catalog, device_manager.device_store)


_EXCHANGE_IDS = itertools.count()


def _local_shuffle_env(ctx: ExecContext) -> _LocalShuffleEnv:
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    dm = ctx.device_manager or DeviceManager.initialize(ctx.conf)
    env = getattr(dm, "_exchange_shuffle_env", None)
    if env is None:
        env = _LocalShuffleEnv(dm)
        dm._exchange_shuffle_env = env
    return env


class TpuShuffleExchangeExec(ShuffleExchangeExecBase):
    """Device exchange: partition each child batch on device (one jitted
    sort+count program), cache the pieces in the spillable shuffle catalog,
    read one reduce partition back per consumer (GpuShuffleExchangeExec +
    RapidsCachingWriter/Reader composition)."""

    is_device = True

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        return self._read_partition(ctx, None)

    def execute_partial(self, ctx: ExecContext,
                        map_ids: Tuple[int, ...]) -> Iterator[DeviceBatch]:
        return self._read_partition(ctx, set(map_ids))

    def _read_partition(self, ctx: ExecContext,
                        map_filter) -> Iterator[DeviceBatch]:
        """One reduce partition's cached blocks, optionally restricted to a
        set of map tasks (the PartialReducerPartitionSpec read: blocks are
        keyed (shuffle, map, partition), so a map-axis slice is a filter —
        no data moves or re-splits)."""
        self._ensure_map(ctx)
        env = _local_shuffle_env(ctx)
        for block in env.shuffle_catalog.blocks_for_partition(
                self._shuffle_id, ctx.partition_id):
            if map_filter is not None and block.map_id not in map_filter:
                continue
            # one span per block, closed before the yield: the reduce
            # side's own time, without the consumer's
            with _tracing.span("exchange.fetch",
                               _tracing.LAYER_SHUFFLE) as sp:
                batches = []
                for buf, _meta in env.shuffle_catalog.acquire_buffers(block):
                    try:
                        batches.append(buf.get_batch())
                    finally:
                        buf.close()
                if sp is not None:
                    sp.note(partition=ctx.partition_id, map_id=block.map_id,
                            rows=sum(b.num_rows for b in batches),
                            bytes=sum(b.device_size_bytes for b in batches))
            for batch in batches:
                self.count_output(batch.num_rows)
                yield batch

    # ---- map side ------------------------------------------------------------
    def iter_map_pieces(self, ctx: ExecContext, partition_ids=None,
                        sketch=None) -> Iterator[Tuple[int, int, DeviceBatch]]:
        """(source_partition, reduce_pid, sub_batch) triples — THE map-side
        partition protocol, shared by the single-process engine and cluster
        map tasks. Range partitioning stages the requested partitions and
        samples bounds first (the SamplingUtils pass); everything else
        splits each batch as it is produced, so peak footprint is one batch
        plus the spillable shuffle cache. ``sketch`` (the local engine's,
        under hash partitioning; a cluster map task passes none) is called
        with each non-empty batch once its split has been dispatched and
        its ``exchange.split`` span has closed: the pools of a batch's
        pieces merge to the batch's own, so one sketch per batch does."""
        part = self.partitioning
        n = part.num_partitions
        child = self.children[0]

        def contexts():
            for cctx in self._child_contexts(ctx):
                if partition_ids is None or \
                        cctx.partition_id in partition_ids:
                    yield cctx

        if isinstance(part, RangePartitioning):
            staged = [(cctx.partition_id, bi, db)
                      for cctx in contexts()
                      for bi, db in enumerate(child.execute(cctx))]
            bounds = self._device_bounds(ctx, part, staged, n)
            for map_p, bi, db in staged:
                if db.num_rows == 0:
                    continue
                for j, sub in self._split_batch(ctx, part, db, 0, n, bounds):
                    yield map_p, j, sub
            return
        for cctx in contexts():
            for bi, db in enumerate(child.execute(cctx)):
                if db.num_rows == 0:
                    continue
                offset = _round_robin_offset(part, cctx.partition_id, bi)
                pieces = self._split_batch(ctx, part, db, offset, n, None)
                if sketch is not None:
                    sketch(db)
                for j, sub in pieces:
                    yield cctx.partition_id, j, sub

    def _run_map(self, ctx: ExecContext) -> None:
        from spark_rapids_tpu.shuffle.catalog import ShuffleBlockId
        from spark_rapids_tpu.shuffle.table_meta import (DevicePackLayout,
                                                         batch_string_max,
                                                         uniform_string_batch,
                                                         layout_to_meta)
        env = _local_shuffle_env(ctx)
        sid = next(_EXCHANGE_IDS)
        self._shuffle_id = sid
        if ctx.cleanups is not None:
            ctx.cleanups.append(
                lambda: env.shuffle_catalog.remove_shuffle(sid))
        part = self.partitioning
        sketch = (functools.partial(self._sketch_keys_device, ctx)
                  if isinstance(part, HashPartitioning) else None)
        # one span per run of the map side; its args come from what the host
        # holds anyway (piece row counts, array shapes, the exec's metrics)
        with _tracing.span("exchange.map", _tracing.LAYER_SHUFFLE) as span:
            if span is not None:
                split0 = (self.metrics[KERNEL_SPLIT_BATCHES].value,
                          self.metrics[SORT_SPLIT_BATCHES].value)
            pieces = rows = nbytes = 0
            sketches0 = len(self._pending_sketches)
            for map_p, j, sub in self.iter_map_pieces(ctx, sketch=sketch):
                if span is not None:
                    pieces += 1
                    rows += sub.num_rows
                    nbytes += sub.num_rows * sum(
                        c.row_bytes for c in sub.columns)
                sub = uniform_string_batch(sub)
                layout = DevicePackLayout.for_batch_shape(
                    sub.schema, sub.capacity, batch_string_max(sub))
                meta = layout_to_meta(layout, sub.num_rows)
                env.shuffle_catalog.add_batch(
                    ShuffleBlockId(sid, map_p, j), sub, meta)
                self._part_rows[j] = self._part_rows.get(j, 0) + sub.num_rows
                self._map_part_rows[(map_p, j)] = \
                    self._map_part_rows.get((map_p, j), 0) + sub.num_rows
            if span is not None:
                span.note(
                    partitioning=part.kind,
                    partitions=part.num_partitions, rows=rows, bytes=nbytes,
                    pieces=pieces,
                    sketches=len(self._pending_sketches) - sketches0,
                    kernel_batches=(self.metrics[KERNEL_SPLIT_BATCHES].value
                                    - split0[0]),
                    sort_batches=(self.metrics[SORT_SPLIT_BATCHES].value
                                  - split0[1]))

    def _split_batch(self, ctx, part, db: DeviceBatch, offset: int, n: int,
                     bounds):
        """One map-side batch -> its (reduce pid, piece) pairs. The
        ``exchange.split`` span runs from the split program's call to the
        host's read of its counts (a read the slicing needs anyway) and the
        dispatch of each piece's consolidation; the pieces are handed on
        (and the batch sketched) after it closes."""
        with _tracing.span("exchange.split", _tracing.LAYER_SHUFFLE) as span:
            path, widenings, pieces = self._split_pieces(ctx, part, db,
                                                         offset, n, bounds)
            if span is not None:
                span.note(path=path, widenings=widenings, rows=db.num_rows,
                          cap=db.capacity)
        return pieces

    def _split_pieces(self, ctx, part, db: DeviceBatch, offset: int, n: int,
                      bounds) -> Tuple[str, int, List[Tuple[int, DeviceBatch]]]:
        """(path, widenings, pieces): which split ran (``single``: the batch
        passes through; ``encoded`` / ``kernel`` / ``sort``: one jitted
        program of pids + partition-major reorder + counts), how many runs
        of the reorder kernel were thrown away for a window overflow before
        the one that stood, and every non-empty piece."""
        schema = db.schema
        cap = db.capacity
        smax = ctx.string_max_bytes
        if isinstance(part, SinglePartitioning) or n == 1:
            return "single", 0, [(0, db)]
        enc = _exchange_encodings(ctx, db)
        if enc and _encoded_split_preferred(ctx, part, db, enc):
            # dictionary-encoded columns ride the exchange as int32 INDICES
            # + the shared dictionary instead of materializing decoded
            # values (the PR 4 repack headroom): the reorder moves 4
            # bytes/row where a decoded string column moves its full
            # byte-matrix row
            self.metrics[SORT_SPLIT_BATCHES].add(1)
            return "encoded", 0, self._split_batch_encoded(
                ctx, part, db, offset, n, bounds, enc)
        # fused Pallas reorder (shuffle/partition_kernel.py): one streaming
        # HBM pass instead of the variadic sort; quota overflow, non-packable
        # schemas or inexact f64 expansion fall back to the sort path below
        if bounds is None:
            res = self._kernel_split(ctx, part, db, offset, n)
            if res is not None:
                self.metrics[KERNEL_SPLIT_BATCHES].add(1)
                return ("kernel",) + res
        self.metrics[SORT_SPLIT_BATCHES].add(1)
        bounds_flat = tuple(flatten_colvs(bounds)) if bounds else ()
        nb = bounds[0].validity.shape[0] if bounds else 0
        # n is keyed: the traced program returns an n-length counts vector,
        # so repartitions differing only in partition count must not share
        # a compiled split (R016)
        key = ("exchange", part, schema, cap, smax, nb, offset, n)

        def build(part=part, schema=schema, cap=cap, smax=smax,
                  offset=offset, nb=nb, n=n):
            def fn(num_rows, *args):
                bnd = None
                consumed = 0
                if nb:
                    bnd = []
                    for o in part.orders:
                        dt = o.child.dtype()
                        if dt is DType.STRING:
                            bnd.append(ColV(dt, args[consumed],
                                            args[consumed + 1],
                                            args[consumed + 2]))
                            consumed += 3
                        else:
                            bnd.append(ColV(dt, args[consumed],
                                            args[consumed + 1]))
                            consumed += 2
                flat = args[consumed:]
                colvs = _unflatten_colvs(schema, flat)
                ectx = EvalCtx(jnp, colvs, cap, smax)
                pids = _compute_pids(jnp, part, ectx, cap, offset, bnd)
                sorted_cols, counts = split_by_pid(jnp, colvs, pids,
                                                   num_rows, n)
                return tuple(flatten_colvs(sorted_cols)) + (counts,)
            return fn

        fn = _cached_jit(key, build)
        res = fn(np.int32(db.num_rows), *bounds_flat, *_flatten(db))
        counts = np.asarray(res[-1])
        sorted_cols = _unflatten_colvs(schema, res[:-1])
        offsets = np.concatenate([[0], np.cumsum(counts)])
        return "sort", 0, [
            (j, _slice_padded(sorted_cols, schema, int(offsets[j]),
                              int(counts[j])))
            for j in range(n) if counts[j]]

    def _split_batch_encoded(self, ctx, part, db: DeviceBatch, offset: int,
                             n: int, bounds, enc):
        """Sort-path exchange carrying encoded columns as INDICES.

        The reorder program's inputs are the WIRE form — an int32 index
        vector replaces each encoded column's decoded data (and lengths) —
        plus the shared dictionaries; pid computation decodes rows on the
        fly with a gather INSIDE the program, but the variadic sort itself
        moves only 4 bytes/row for encoded columns. Output pieces re-attach
        the dictionary under the SAME token (downstream encoded-domain
        operators and concat carry keep working) and materialize their
        decoded data with one gather per piece."""
        from spark_rapids_tpu.utils import metrics as um
        schema, cap, smax = db.schema, db.capacity, ctx.string_max_bytes
        wire_schema = Schema([
            Field(f.name, DType.INT, f.nullable) if ci in enc else f
            for ci, f in enumerate(schema)])
        wire_flat: List = []
        dict_flat: List = []
        enc_sig = []
        for ci, f in enumerate(schema):
            c = db.columns[ci]
            if ci in enc:
                e = enc[ci]
                wire_flat += [e.indices, c.validity]
                dict_flat.append(e.values)
                has_len = e.lengths is not None
                if has_len:
                    dict_flat.append(e.lengths)
                enc_sig.append((ci, e.k, tuple(e.values.shape[1:]), has_len))
            else:
                wire_flat += [c.data, c.validity]
                if c.lengths is not None:
                    wire_flat.append(c.lengths)
        bounds_flat = tuple(flatten_colvs(bounds)) if bounds else ()
        nb = bounds[0].validity.shape[0] if bounds else 0
        # n keyed for the same reason as the decoded sort path: the counts
        # vector the program returns has length n (R016)
        key = ("exchange-enc", part, schema, wire_schema, cap, smax, nb,
               offset, tuple(enc_sig), n)

        def build(part=part, schema=schema, wire_schema=wire_schema,
                  cap=cap, smax=smax, offset=offset, nb=nb,
                  enc_sig=tuple(enc_sig), n=n):
            def fn(num_rows, *args):
                bnd = None
                consumed = 0
                if nb:
                    bnd = []
                    for o in part.orders:
                        dt = o.child.dtype()
                        step = 3 if dt is DType.STRING else 2
                        bnd.append(ColV(dt, *args[consumed:consumed + step]))
                        consumed += step
                dicts = {}
                for ci, _k, _w, has_len in enc_sig:
                    values = args[consumed]
                    consumed += 1
                    dlen = None
                    if has_len:
                        dlen = args[consumed]
                        consumed += 1
                    dicts[ci] = (values, dlen)
                wire_cols = _unflatten_colvs(wire_schema, args[consumed:])
                eval_cols = []
                for ci, f in enumerate(schema):
                    wc = wire_cols[ci]
                    if ci in dicts:
                        values, dlen = dicts[ci]
                        data = values[wc.data]
                        lengths = dlen[wc.data] if dlen is not None else None
                        eval_cols.append(ColV(f.dtype, data, wc.validity,
                                              lengths))
                    else:
                        eval_cols.append(wc)
                ectx = EvalCtx(jnp, eval_cols, cap, smax)
                pids = _compute_pids(jnp, part, ectx, cap, offset, bnd)
                sorted_wire, counts = split_by_pid(jnp, wire_cols, pids,
                                                   num_rows, n)
                return tuple(flatten_colvs(sorted_wire)) + (counts,)
            return fn

        fn = _cached_jit(key, build)
        res = fn(np.int32(db.num_rows), *bounds_flat, *dict_flat, *wire_flat)
        um.TRANSFER_METRICS[um.TRANSFER_EXCHANGE_ENCODED_OPS].add(1)
        counts = np.asarray(res[-1])
        sorted_wire = _unflatten_colvs(wire_schema, res[:-1])
        offsets = np.concatenate([[0], np.cumsum(counts)])
        return [
            (j, _materialize_encoded_piece(
                _slice_padded(sorted_wire, wire_schema, int(offsets[j]),
                              int(counts[j])), schema, enc))
            for j in range(n) if counts[j]]

    def _fused_pids_split(self, ctx, part, db: DeviceBatch, offset: int,
                          n: int, interpret: bool):
        """ONE program for pids + pack + Pallas reorder (separate
        pids/pack/kernel dispatches were the warm exchange's dominant
        residue). Returns _NOT_FUSABLE when the partitioning hashes a
        DOUBLE key: the fused
        form would hash bitcast(bits) where the two-dispatch path hashes
        the column's (emulated) f64 data, and those can disagree in the
        low mantissa on this backend."""
        from spark_rapids_tpu.shuffle import partition_kernel as pk
        if isinstance(part, HashPartitioning):
            # walk each key's FULL expression tree: a non-DOUBLE key over a
            # DOUBLE subexpression (cast(dbl AS string), dbl > 0, ...) still
            # evaluates f64 arithmetic inside the fused program, where the
            # columns are bitcast u64 siblings rather than emulated f64
            def _touches_double(e):
                try:
                    if e.dtype() is DType.DOUBLE:
                        return True
                except TypeError:
                    return True
                return any(_touches_double(c) for c in e.children)
            if any(_touches_double(k) for k in part.keys):
                return _NOT_FUSABLE
        schema, cap, smax = db.schema, db.capacity, ctx.string_max_bytes

        def run(spec, geom):
            # offset rides as a RUNTIME argument, not a cache-key component:
            # a round-robin repartition cycles offsets per source batch, and
            # each distinct key value would retrace the heavyweight
            # pack+Pallas program (the pids math is shape-stable in offset)
            # schema is keyed explicitly: spec.plans usually pins it, but
            # the traced fn zips schema's dtypes against the plans and
            # nothing in PackSpec's equality promises the field types
            # round-trip (R016)
            key = ("exchange-fused", part, spec, geom, schema, cap, smax,
                   interpret)

            def build(part=part, spec=spec, geom=geom, schema=schema,
                      cap=cap, smax=smax, interpret=interpret):
                inner = pk.reorder_program(spec, geom, cap, interpret)

                def fn(num_rows, offset_rt, *flat):
                    # rebuild eval-ready columns from _deflate order (f64
                    # data re-derived from the u64 bits sibling)
                    colvs, i = [], 0
                    for plan, f in zip(spec.plans, schema):
                        main = flat[i]
                        validity = flat[i + 1]
                        i += 2
                        lengths = None
                        if plan.kind == "string":
                            lengths = flat[i]
                            i += 1
                        data = (jax.lax.bitcast_convert_type(main,
                                                             jnp.float64)
                                if plan.kind == "f64bits" else main)
                        colvs.append(ColV(f.dtype, data, validity, lengths))
                    ectx = EvalCtx(jnp, colvs, cap, smax)
                    pids = _compute_pids(jnp, part, ectx, cap, offset_rt,
                                         None)
                    return inner(num_rows, pids, *flat)
                return fn

            return _cached_jit(key, build)(
                np.int32(db.num_rows), np.int32(offset),
                *pk._deflate(spec, db))

        return pk.split_widening(db, n, interpret, run)

    def _kernel_split(self, ctx, part, db: DeviceBatch, offset: int, n: int):
        """The fused-kernel split: compute pids (same hash/round-robin math
        as the sort path), run pack+kernel, consolidate each partition into
        one DeviceBatch. Returns (kernel runs thrown away for a window
        overflow, [(pid, piece), ...]), or None when the fast path does not
        apply — the caller falls back to the sort-based reorder."""
        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.shuffle import partition_kernel as pk
        mode = ctx.conf.get(_cfg.SHUFFLE_KERNEL_MODE)
        if mode == "off":
            return None
        interpret = (mode == "interpret")
        if not interpret and jax.default_backend() != "tpu":
            return None
        if isinstance(part, RangePartitioning):
            return None                       # bounds path stays on sort
        schema, cap, smax = db.schema, db.capacity, ctx.string_max_bytes
        res = self._fused_pids_split(ctx, part, db, offset, n, interpret)
        if res is _NOT_FUSABLE:
            # two-dispatch fallback: separate pids program, then pack+kernel
            pid_key = ("exchange-pids", part, schema, cap, smax, offset)

            def build(part=part, schema=schema, cap=cap, smax=smax,
                      offset=offset):
                def fn(*flat):
                    colvs = _unflatten_colvs(schema, flat)
                    ectx = EvalCtx(jnp, colvs, cap, smax)
                    return _compute_pids(jnp, part, ectx, cap, offset, None)
                return fn

            pids = _cached_jit(pid_key, build)(*_flatten(db))
            res = pk.split_batch_kernel(db, pids, n, interpret=interpret)
        if res is None:
            return None
        out, stats, spec, geom = res
        # pipelined-DMA consolidation first (round-5: per-partition
        # semaphores, n copies in flight, barrier-free unpack on the
        # materialized compact); falls back to the per-partition
        # shape-stable gather program off-TPU / when disabled
        pieces = []
        if ctx.conf.get(_cfg.SHUFFLE_DMA_CONSOLIDATE):
            subs = pk.consolidate_all(out, stats, spec, schema, geom)
            if subs is not None:
                return geom.widen, [(j, sub) for j, sub in enumerate(subs)
                                    if sub is not None]
        for j in range(n):
            sub = pk.consolidate(out, stats, j, spec, schema, geom)
            if sub is not None:
                pieces.append((j, sub))
        return geom.widen, pieces

    def _device_bounds(self, ctx, part: RangePartitioning,
                       staged, n: int) -> Optional[List[ColV]]:
        """Evaluate order keys AND gather the deterministic row sample on
        device; only the sampled rows (<= _SAMPLE_TARGET total) cross the
        host link. The sample index rides as a runtime argument padded to a
        fixed length, so one compiled program serves every batch of this
        shape (previously the full cap-sized key columns were downloaded
        per batch and sampled on host — the R002 full-column-download
        shape)."""
        if not staged:
            return None
        per = max(1, _SAMPLE_TARGET // len(staged))
        # the device index rides at the power-of-two bucket of `per`, so the
        # program count stays bounded per (schema, cap) instead of retracing
        # for every distinct staged-batch count; the host keeps only the
        # first k sampled rows either way
        per_cap = int(bucket_capacity(per))
        sampled = []
        for _, _, db in staged:
            if db.num_rows == 0:
                continue
            schema, cap, smax = db.schema, db.capacity, ctx.string_max_bytes
            k = min(per, db.num_rows)
            idx = np.zeros(per_cap, dtype=np.int32)
            idx[:k] = np.linspace(0, db.num_rows - 1, k).astype(np.int32)
            key = ("exchange-keys", part.orders, schema, cap, smax, per_cap)

            def build(orders=part.orders, schema=schema, cap=cap, smax=smax):
                def fn(idx, *flat):
                    colvs = _unflatten_colvs(schema, flat)
                    ectx = EvalCtx(jnp, colvs, cap, smax)
                    keys = [bk.take_colv(jnp, o.child.eval(ectx), idx)
                            for o in orders]
                    return tuple(flatten_colvs(keys))
                return fn

            fn = _cached_jit(key, build)
            # justified download: per (<= 4096 / num batches) sampled rows
            # per key column, not full columns  # tpu-lint: disable=R002
            flat = [np.asarray(a)
                    for a in fn(jnp.asarray(idx), *_flatten(db))]
            keys = []
            i = 0
            for o in part.orders:
                dt = o.child.dtype()
                if dt is DType.STRING:
                    keys.append(ColV(dt, flat[i][:k], flat[i + 1][:k],
                                     flat[i + 2][:k]))
                    i += 3
                else:
                    keys.append(ColV(dt, flat[i][:k], flat[i + 1][:k]))
                    i += 2
            sampled.append(keys)
        return _sample_bounds(part.orders, sampled, n)


# ------------------------------------------------------------------ broadcast
class BroadcastExchangeExecBase(PhysicalExec):
    """Broadcast exchange (GpuBroadcastExchangeExec analog,
    execution/GpuBroadcastExchangeExec.scala): materializes the child fully —
    every child partition — into ONE batch, built once and served to every
    consumer partition. The reference builds the batch on the driver and caches
    the deserialized device copy once per executor
    (SerializeConcatHostBuffersDeserializeBatch:47-66); here the single cached
    batch plays that per-executor role, released when the action finishes."""

    def size_estimate(self):
        # a broadcast replicates the child batch, it does not grow it
        return self.children[0].size_estimate()

    def __init__(self, child: PhysicalExec):
        super().__init__((child,), child.output)
        self._lock = threading.Lock()
        self._cached = None

    def __getstate__(self):
        # plans ship to cluster executors by pickle: the lock is process-local
        # and the cached build batch must never ride the control plane
        state = dict(self.__dict__)
        state["_lock"] = None
        state["_cached"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def __copy__(self):
        # copy.copy preserves the cached build (plan rewrites above an
        # executed broadcast must not rebuild it); only pickling drops it
        new = self.__class__.__new__(self.__class__)
        new.__dict__.update(self.__dict__)
        return new

    @property
    def num_partitions(self) -> int:
        return 1

    def _materialize(self, ctx: ExecContext):
        child = self.children[0]
        batches = []
        for cctx in _child_contexts(child, ctx):
            batches.extend(child.execute(cctx))
        return batches

    def _release(self) -> None:
        self._cached = None

    def execute(self, ctx: ExecContext):
        with self._lock:
            if self._cached is None:
                if ctx.cleanups is not None:
                    ctx.cleanups.append(self._release)
                self._cached = self._build(ctx)
                # count build rows once, not once per consuming partition
                self.count_output(self._cached.num_rows)
        yield self._cached


class CpuReusedExchangeExec(PhysicalExec):
    """Spark's ReusedExchangeExec shape: a pointer at an exchange elsewhere
    in the plan whose output this node re-reads instead of recomputing.
    Enters through imported Catalyst plans; the overrides engine must give
    it the SAME on/off-device decision as its referent (the exchange-reuse
    consistency check, RapidsMeta.scala:443).

    The referent is modeled as a regular CHILD (the same exec object the
    main branch holds) so every plan pass — transitions, fusion — rewrites
    the reused subtree too; execution re-runs it (recompute-not-reuse, like
    every exchange consumer in this engine outside the AQE path)."""

    def __init__(self, referent: PhysicalExec):
        super().__init__((referent,), referent.output)

    def size_estimate(self):
        return self.referent.size_estimate()   # same rows, zero recompute

    @property
    def referent(self) -> PhysicalExec:
        return self.children[0]

    @property
    def num_partitions(self) -> int:
        return self.referent.num_partitions

    def execute(self, ctx: ExecContext):
        yield from self.referent.execute(ctx)


class CpuQueryStageExec(PhysicalExec):
    """AQE stage wrapper shape (ShuffleQueryStageExec /
    BroadcastQueryStageExec): a materialized stage boundary around an
    exchange. Imported Catalyst plans carry these; the overrides engine
    tags THROUGH the wrapper and conversion unwraps it (the
    optimizeAdaptiveTransitions role, GpuTransitionOverrides.scala:47)."""

    def __init__(self, child: PhysicalExec, stage_id: int = 0):
        super().__init__((child,), child.output)
        self.stage_id = stage_id

    def size_estimate(self):
        return self.children[0].size_estimate()   # wrapper: same rows

    def execute(self, ctx: ExecContext):
        yield from self.children[0].execute(ctx)


class TpuReusedExchangeExec(PhysicalExec):
    """Device form of a reused exchange. Execution re-reads the (converted)
    referent child; the AQE path (plan/adaptive.py) is where materialized
    stage output is actually served without recompute — this node preserves
    the plan SHAPE and the consistency contract for imported Catalyst
    plans. The referent rides as a child so transition insertion fixes its
    host/device boundaries like any other subtree."""

    is_device = True

    def __init__(self, referent: PhysicalExec):
        super().__init__((referent,), referent.output)

    def size_estimate(self):
        return self.referent.size_estimate()   # same rows, zero recompute

    @property
    def referent(self) -> PhysicalExec:
        return self.children[0]

    @property
    def num_partitions(self) -> int:
        return self.referent.num_partitions

    def execute(self, ctx: ExecContext):
        yield from self.referent.execute(ctx)


class CpuBroadcastExchangeExec(BroadcastExchangeExecBase):
    def _build(self, ctx: ExecContext) -> HostBatch:
        from spark_rapids_tpu.execs.cpu_execs import concat_host_batches
        return concat_host_batches(self._materialize(ctx), self.output)


class TpuBroadcastExchangeExec(BroadcastExchangeExecBase):
    """Device-side broadcast: the concatenated build batch stays in HBM."""

    is_device = True

    def _build(self, ctx: ExecContext) -> DeviceBatch:
        from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
        return concat_device_batches(self._materialize(ctx), self.output,
                                     ctx.string_max_bytes)
