"""Batch-level kernels: filter compaction, sort, group-by, segment reduction.

These replace the cuDF Table ops the reference leans on (Table.filter,
Table.orderBy, Table.groupBy().aggregate(), contiguousSplit) with XLA-native
formulations designed around static shapes:

- outputs keep the input capacity; the *logical* row/group count is returned as a
  traced scalar (the "row-count sidecar" pattern for dynamic cardinality on TPU);
- compaction and grouping ride on stable argsort — XLA's sort is highly tuned for
  TPU, and a sort-based group-by avoids data-dependent hash-table shapes entirely;
- string keys sort exactly (byte-lexicographic == Spark's UTF8String order) via
  big-endian uint64 chunk passes, least-significant chunk first;
- everything here is traceable and fuses into the surrounding jit program.

All functions take/return ColV and plain arrays; ``xp`` is numpy or jax.numpy.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs.core import ColV


def _stable_argsort(xp, keys):
    if xp is np:
        return np.argsort(keys, kind="stable")
    return xp.argsort(keys, stable=True)


# ---------------------------------------------------------------------------
# 64-bit row hashing (the grouping fast path's sort key)
# ---------------------------------------------------------------------------
_HSEED = np.uint64(0x243F6A8885A308D3)
_HNULL = np.uint64(0x452821E638D01377)
_HGOLD = np.uint64(0x9E3779B97F4A7C15)


def _mix64(xp, z):
    """splitmix64 finalizer (wrapping uint64 arithmetic)."""
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _float_canon(xp, d):
    """Canonical frexp decomposition of float64 data: returns
    (sign, e, mi, zero, inf, nan) with m in [1,2) scaled so mi = m*2^52 is an
    exact integer, identical on every engine (no bitcasts — the TPU x64
    emulation cannot compile an f64 bitcast). Shared by the hash and the
    injective key-word encodings so both see the same classes."""
    sign = d < 0
    ax = xp.abs(d)
    nan = xp.isnan(d)
    inf = xp.isinf(d)
    finite_pos = xp.logical_and(ax > 0,
                                xp.logical_not(xp.logical_or(nan, inf)))
    ax_safe = xp.where(finite_pos, ax, 1.0)
    e = xp.clip(xp.floor(xp.log2(ax_safe)), -1074.0, 1023.0)
    m = ax_safe / xp.exp2(e)
    for _ in range(2):  # each step fixes one off-by-one in the estimate
        too_big = m >= 2.0
        too_small = m < 1.0
        e = xp.where(too_big, e + 1.0, xp.where(too_small, e - 1.0, e))
        m = xp.where(too_big, m * 0.5, xp.where(too_small, m * 2.0, m))
    mi = (m * np.float64(2 ** 52)).astype(np.int64)
    return sign, e, mi, ax == 0, inf, nan


def _hash64_col(xp, v: ColV):
    """Per-row 64-bit hash of one column; equal keys (Spark grouping
    semantics: null==null, NaN==NaN, -0.0==0.0) hash equal."""
    if v.dtype is DType.STRING:
        W = v.data.shape[-1]
        # pack each 8-byte chunk into a uint64 (injective) and mix it through
        # splitmix64 with a per-chunk offset before combining — a linear
        # base-31 fold has structured everyday collisions ("Aa" == "BB")
        # that would permanently defeat the hash fast path
        pad = (-W) % 8
        data = v.data
        if pad:
            data = xp.concatenate(
                [data, xp.zeros(data.shape[:-1] + (pad,), dtype=np.uint8)],
                axis=-1)
        shifts = xp.asarray((np.arange(7, -1, -1) * 8).astype(np.uint64))
        chunks = data.reshape(data.shape[:-1] + (-1, 8)).astype(np.uint64)
        words = xp.sum(chunks << shifts, axis=-1)           # [n, W/8]
        n_words = words.shape[-1]
        bits = v.lengths.astype(np.uint64)
        for i in range(n_words):
            # wrapping multiply precomputed in python ints: numpy warns on
            # scalar uint64 overflow even though wrapping is intended
            off = np.uint64(((i + 1) * int(_HGOLD)) & 0xFFFFFFFFFFFFFFFF)
            bits = _mix64(xp, bits ^ _mix64(xp, words[..., i] + off))
    elif v.dtype.is_floating:
        # arithmetic mantissa/exponent decomposition (shared _float_canon) —
        # both engines must use the SAME derivation so group output order
        # matches across CPU and device. (mi, e) is the unique normalized
        # frexp pair on every engine, and m * 2^52 is an exact integer.
        d = v.data.astype(np.float64)
        sign, e, mi, zero, inf, nan = _float_canon(xp, d)
        bits = (mi.astype(np.uint64)
                ^ _mix64(xp, e.astype(np.int64).astype(np.uint64) + _HGOLD)
                ^ (xp.where(sign, np.uint64(1), np.uint64(0))
                   << np.uint64(63)))
        # canonical classes: +/-0.0 hash as one value, every NaN as one
        # value, +/-inf as their own values (distinct from finite 1.0)
        bits = xp.where(zero, xp.full_like(bits, np.uint64(0)), bits)
        bits = xp.where(inf, xp.full_like(bits, np.uint64(0x7FF0000000000000))
                        ^ (xp.where(sign, np.uint64(1), np.uint64(0))
                           << np.uint64(63)), bits)
        bits = xp.where(nan, xp.full_like(bits, np.uint64(0x7FF8000000000000)),
                        bits)
    elif v.dtype is DType.BOOLEAN:
        bits = v.data.astype(np.uint64)
    else:
        bits = v.data.astype(np.int64).astype(np.uint64)
    h = _mix64(xp, bits + _HGOLD)
    return xp.where(v.validity, h, _HNULL)


def hash64_cols(xp, cols: Sequence[ColV]):
    """Combined 64-bit row hash over the key columns."""
    n = cols[0].validity.shape[0]
    h = xp.full((n,), _HSEED, dtype=np.uint64)
    for v in cols:
        h = _mix64(xp, (h ^ _hash64_col(xp, v)) * _HGOLD + _HGOLD)
    return h


def hash_group_order(xp, keys: Sequence[ColV], alive_or_n):
    """Grouping fast path: one stable argsort over the 64-bit key hash instead
    of a full multi-key lexsort (string keys make the exact sort especially
    expensive: their order needs rank sub-sorts). Equal keys land contiguous
    (equal hash + stable order); boundaries still come from exact key
    comparison (rows_equal_adjacent), so the only hazard is two DIFFERENT keys
    sharing a hash — detect_hash_collision flags that and callers fall back to
    the exact sort. Returns (order, hashes)."""
    cap = keys[0].validity.shape[0]
    alive = alive_mask(xp, cap, alive_or_n)
    h = hash64_cols(xp, keys)
    # dead rows sort last: their key is the max uint64, unreachable by h >> 1
    sort_key = xp.where(alive, h >> np.uint64(1),
                        np.uint64(0xFFFFFFFFFFFFFFFF))
    order = _stable_argsort(xp, sort_key)
    return order, h


def detect_hash_collision(xp, hashes, order, starts, alive_or_n):
    """True when any group boundary separates two alive rows with the SAME
    sort key — i.e. two distinct keys collided. (A run holding two distinct
    keys always has an adjacent differing pair, so the adjacent check is
    sufficient to detect every split-group hazard.) Rows sort by h >> 1, so
    the comparison must use the same shifted key: hashes differing only in
    the lowest bit still interleave in sort order."""
    cap = order.shape[0]
    alive = alive_mask(xp, cap, alive_or_n)
    hs = hashes[order] >> np.uint64(1)
    prev_h = xp.concatenate([hs[:1], hs[:-1]])
    a = alive[order]
    prev_a = xp.concatenate([xp.zeros(1, dtype=bool), a[:-1]])
    return xp.any(xp.logical_and(
        xp.logical_and(starts, hs == prev_h),
        xp.logical_and(a, prev_a)))


def as_column(xp, v: ColV, capacity: int) -> ColV:
    """Broadcast a scalar ColV (a literal, e.g. after project inlining) to a
    full column so row-wise kernels can index it."""
    scalar = (v.data.ndim == 1 if v.dtype is DType.STRING
              else v.data.ndim == 0)
    if not scalar:
        return v
    if v.dtype is DType.STRING:
        W = v.data.shape[-1]
        data = xp.broadcast_to(xp.reshape(v.data, (1, W)), (capacity, W))
        lengths = xp.broadcast_to(xp.reshape(v.lengths, (1,)), (capacity,))
    else:
        data = xp.broadcast_to(xp.reshape(v.data, (1,)), (capacity,))
        lengths = None
    validity = xp.broadcast_to(xp.reshape(v.validity, (1,)), (capacity,))
    return ColV(v.dtype, data, validity, lengths)


def take_colv(xp, v: ColV, indices) -> ColV:
    """Permute/gather rows of a column."""
    if v.dtype is DType.STRING:
        return ColV(v.dtype, v.data[indices], v.validity[indices],
                    v.lengths[indices])
    return ColV(v.dtype, v.data[indices], v.validity[indices])


# ---------------------------------------------------------------------------
# variadic payload sort — the TPU replacement for argsort + gathers
# ---------------------------------------------------------------------------
# On TPU a random-access gather of n rows costs ~2x the SORT of n rows (the
# sorting network streams memory; gathers do not vectorize), so
# "argsort + one gather per column" is the single most expensive pattern in
# the engine. XLA's variadic sort moves payload operands WITH the keys, so
# one lax.sort replaces the argsort and every gather.

def multi_sort(xp, passes: Sequence, payloads: Sequence):
    """Stable lexicographic sort by ``passes`` (most significant first),
    carrying ``payloads`` along. Returns (sorted_passes, sorted_payloads)."""
    if xp is np:
        order = np.lexsort(tuple(reversed([np.asarray(p) for p in passes])))
        return ([np.asarray(p)[order] for p in passes],
                [np.asarray(p)[order] for p in payloads])
    import jax
    res = jax.lax.sort(tuple(passes) + tuple(payloads),
                       num_keys=len(passes), is_stable=True)
    return list(res[:len(passes)]), list(res[len(passes):])


def _pack_bytes(xp, data):
    """[n, W] uint8 -> list of [n] uint64 big-endian words (strings ride a
    variadic sort as a few word operands instead of a 2-D gather)."""
    n, W = data.shape
    n_words = (W + 7) // 8
    pad = n_words * 8 - W
    if pad:
        data = xp.concatenate([data, xp.zeros((n, pad), np.uint8)], axis=-1)
    chunks = data.reshape(n, n_words, 8).astype(np.uint64)
    shifts = xp.asarray(np.arange(56, -8, -8, dtype=np.uint64))
    words = xp.sum(chunks << shifts[None, None, :], axis=-1)
    return [words[:, i] for i in range(n_words)]


def _unpack_bytes(xp, words: Sequence, W: int):
    stacked = xp.stack(list(words), axis=1)          # [n, n_words]
    shifts = xp.asarray(np.arange(56, -8, -8, dtype=np.uint64))
    bytes_ = ((stacked[:, :, None] >> shifts[None, None, :])
              & np.uint64(0xFF)).astype(np.uint8)
    n = stacked.shape[0]
    return bytes_.reshape(n, len(words) * 8)[:, :W]


#: XLA TPU compile time for a variadic sort grows steeply with TOTAL
#: operand count (keys + payloads; multi-key stable sorts with many
#: payloads have been observed to wedge the compiler outright); above this
#: bound the argsort+gather fallback is the safer end-to-end choice
MAX_SORT_PAYLOADS = 16


def sort_colvs(xp, passes: Sequence, colvs: Sequence[ColV],
               extras: Sequence = ()):
    """Sort whole columns by the key passes in ONE pass: device side uses a
    single variadic lax.sort (string payloads packed into uint64 words,
    duplicate arrays sorted once, all validity vectors bit-packed into one
    word operand); the CPU side keeps lexsort + gathers. Returns
    (sorted colvs, sorted extras). Ordering is identical across engines
    (both stable lexicographic)."""
    if xp is np:
        order = np.lexsort(tuple(reversed([np.asarray(p) for p in passes])))
        return ([take_colv(np, v, order) for v in colvs],
                [np.asarray(e)[order] for e in extras])
    # dedup payload arrays by identity: BoundReference evaluation returns the
    # SAME tracer for repeated uses of a column (sum(x) and avg(x) share x),
    # so each distinct buffer rides the sort once
    slot_of: dict = {}
    payloads: List = []
    bools: List = []          # validity vectors, bit-packed into u64 words
    bool_slot: dict = {}

    def add(a):
        key = id(a)
        if key not in slot_of:
            slot_of[key] = len(payloads)
            payloads.append(a)
        return slot_of[key]

    def _is_half(a) -> bool:
        # 4-byte payloads pair up into u64 words: sort cost is per OPERAND
        # (~equal for u32 and u64 on TPU), so two halves in one word halve
        # the payload movement of every narrow column
        return (getattr(a, "dtype", None) is not None
                and a.ndim == 1 and a.dtype.itemsize == 4
                and a.dtype.kind in "iuf")

    def add_bool(a):
        key = id(a)
        if key not in bool_slot:
            bool_slot[key] = len(bools)
            bools.append(a)
        return bool_slot[key]

    specs = []
    for v in colvs:
        if v.dtype is DType.STRING:
            words = _pack_bytes(xp, v.data)
            specs.append((v.dtype, [add(w) for w in words],
                          v.data.shape[-1], add(v.lengths),
                          add_bool(v.validity)))
        else:
            specs.append((v.dtype, None, 0, add(v.data),
                          add_bool(v.validity)))
    extra_slots = []
    for e in extras:
        if getattr(e, "dtype", None) == np.bool_:
            extra_slots.append(("b", add_bool(e)))
        else:
            extra_slots.append(("p", add(e)))
    n_bool_words = (len(bools) + 63) // 64
    packed_bools = []
    for w in range(n_bool_words):
        chunk = bools[w * 64:(w + 1) * 64]
        word = None
        for i, b in enumerate(chunk):
            piece = b.astype(np.uint64) << np.uint64(i)
            word = piece if word is None else word | piece
        packed_bools.append(word)

    import jax.lax as _lax

    def _u32(a):
        return (a if a.dtype == np.uint32
                else _lax.bitcast_convert_type(a, np.uint32))

    def _from_u32(a, dtype):
        return (a if dtype == np.uint32
                else _lax.bitcast_convert_type(a, dtype))

    halves = [i for i, a in enumerate(payloads) if _is_half(a)]
    fulls = [i for i, a in enumerate(payloads) if not _is_half(a)]
    n_ops = len(fulls) + (len(halves) + 1) // 2
    if n_ops + n_bool_words + len(passes) > MAX_SORT_PAYLOADS:
        # too many operands for a fast compile: one sort for the permutation,
        # then gathers (the pre-variadic pattern); checked BEFORE any packing
        # work is traced
        cap = passes[0].shape[0]
        iota = xp.arange(cap, dtype=np.int32)
        _, (order,) = multi_sort(xp, passes, [iota])
        return ([take_colv(xp, v, order) for v in colvs],
                [e[order] for e in extras])

    operands = [payloads[i] for i in fulls]
    for w in range(0, len(halves), 2):
        word = _u32(payloads[halves[w]]).astype(np.uint64) << np.uint64(32)
        if w + 1 < len(halves):
            word = word | _u32(payloads[halves[w + 1]]).astype(np.uint64)
        operands.append(word)

    all_payloads = operands + packed_bools

    _, sp = multi_sort(xp, passes, all_payloads)
    recovered: List = [None] * len(payloads)
    for k, i in enumerate(fulls):
        recovered[i] = sp[k]
    base = len(fulls)
    for w in range(0, len(halves), 2):
        word = sp[base + w // 2]
        recovered[halves[w]] = _from_u32(
            (word >> np.uint64(32)).astype(np.uint32),
            payloads[halves[w]].dtype)
        if w + 1 < len(halves):
            recovered[halves[w + 1]] = _from_u32(
                word.astype(np.uint32), payloads[halves[w + 1]].dtype)
    n_operands = len(operands)
    sorted_bools = []
    for w in range(n_bool_words):
        word = sp[n_operands + w]
        sorted_bools.extend(
            ((word >> np.uint64(i)) & np.uint64(1)).astype(bool)
            for i in range(min(64, len(bools) - w * 64)))
    out = []
    for dt, word_slots, W, data_slot, valid_slot in specs:
        if word_slots is not None:
            data = _unpack_bytes(xp, [recovered[s] for s in word_slots], W)
            out.append(ColV(dt, data, sorted_bools[valid_slot],
                            recovered[data_slot]))
        else:
            out.append(ColV(dt, recovered[data_slot],
                            sorted_bools[valid_slot]))
    sorted_extras = [sorted_bools[s] if kind == "b" else recovered[s]
                     for kind, s in extra_slots]
    return out, sorted_extras


def starts_from_sorted(xp, sorted_keys: Sequence[ColV], sorted_alive):
    """Group-start marks over ALREADY-SORTED key columns (the adjacent
    compare of rows_equal_adjacent without the order indirection)."""
    cap = sorted_alive.shape[0]
    first = xp.arange(cap) == 0
    new_group = xp.zeros(cap, dtype=bool)

    def prev(a):
        return xp.concatenate([a[:1], a[:-1]], axis=0)

    for v in sorted_keys:
        a_valid = v.validity
        b_valid = prev(v.validity)
        if v.dtype is DType.STRING:
            same_data = xp.logical_and(
                xp.all(v.data == prev(v.data), axis=-1),
                v.lengths == prev(v.lengths))
        elif v.dtype.is_floating:
            a, b = v.data, prev(v.data)
            same_data = xp.logical_or(
                a == b, xp.logical_and(xp.isnan(a), xp.isnan(b)))
        else:
            same_data = v.data == prev(v.data)
        same = xp.where(xp.logical_and(a_valid, b_valid), same_data,
                        a_valid == b_valid)
        new_group = xp.logical_or(new_group, xp.logical_not(same))
    new_group = xp.logical_or(new_group, first)
    return xp.logical_and(new_group, sorted_alive)


def detect_hash_collision_sorted(xp, hs_sorted, starts, sorted_alive):
    """Collision flag over hash-sorted rows: a group boundary between two
    alive rows with the same (shifted) hash means two distinct keys collided."""
    prev_h = xp.concatenate([hs_sorted[:1], hs_sorted[:-1]])
    prev_a = xp.concatenate([xp.zeros(1, dtype=bool), sorted_alive[:-1]])
    return xp.any(xp.logical_and(
        xp.logical_and(starts, hs_sorted == prev_h),
        xp.logical_and(sorted_alive, prev_a)))


def compact(xp, mask, columns: Sequence[ColV], num_rows):
    """Move rows where mask is true to the front, preserving order; invalidate the
    rest. Returns (columns, new_count). Replaces cudf Table.filter.

    ``mask`` must already be False for padding rows (>= num_rows). One
    variadic sort on device (no per-column gathers).
    """
    keep = xp.asarray(mask, dtype=bool)
    new_count = xp.sum(keep).astype(np.int32)
    cap = keep.shape[0]
    alive = xp.arange(cap, dtype=np.int32) < new_count
    sorted_cols, _ = sort_colvs(
        xp, [xp.logical_not(keep).astype(np.int8)], columns)
    out = [g.with_validity(xp.logical_and(g.validity, alive))
           for g in sorted_cols]
    return out, new_count


def _null_rank(xp, v: ColV, nulls_first: bool):
    """Null position key (explicit in SortOrder, independent of direction)."""
    return xp.where(v.validity, np.int8(0), np.int8(-1 if nulls_first else 1))


def _key_passes(xp, v: ColV, ascending: bool, nulls_first: bool) -> List:
    """One sort key -> list of argsort passes, most significant first.

    Each pass is an int/float array whose ascending order realizes the desired
    order for that component. Composition runs least-significant pass first
    (stable LSD).
    """
    # descending integer keys use bitwise complement (~x is monotone decreasing
    # with no overflow at INT_MIN, unlike unary minus)
    def flip_i(k):
        return k if ascending else ~k

    def flip_f(k):
        return k if ascending else -k

    passes: List = []
    if v.dtype is DType.STRING:
        W = v.data.shape[-1]
        n_chunks = (W + 7) // 8
        pad = n_chunks * 8 - W
        data = v.data
        if pad:
            data = xp.concatenate(
                [data, xp.zeros(data.shape[:-1] + (pad,), dtype=np.uint8)],
                axis=-1)
        chunks = data.reshape(data.shape[0], n_chunks, 8).astype(np.uint64)
        shifts = xp.asarray(np.arange(56, -8, -8, dtype=np.uint64))
        keys = xp.sum(chunks << shifts, axis=-1)  # big-endian uint64 per chunk
        # unsigned -> order-preserving signed so argsort compares byte order.
        # passes[0] (chunk 0) is applied last in LSD composition = most
        # significant; the length tiebreak at the end is least significant.
        for i in range(n_chunks):
            signed = (keys[:, i] ^ np.uint64(2 ** 63)).astype(np.int64)
            passes.append(flip_i(signed))
        passes.append(flip_i(v.lengths.astype(np.int64)))
    elif v.dtype.is_floating:
        d = v.data.astype(np.float64)
        nan = xp.isnan(d)
        val = xp.where(nan, np.float64(np.inf), d)
        # -0.0 == 0.0 for ordering; canonicalize to avoid backend-dependent ties
        val = xp.where(val == 0, np.float64(0.0), val)
        # Spark: NaN is the largest double. Primary comparison is (is_nan, value)
        passes = [flip_i(nan.astype(np.int8)), flip_f(val)]
    elif v.dtype is DType.BOOLEAN:
        passes.append(flip_i(v.data.astype(np.int8)))
    else:
        passes.append(flip_i(v.data.astype(np.int64)))
    # most significant overall: null rank
    return [_null_rank(xp, v, nulls_first)] + passes


def alive_mask(xp, capacity: int, alive_or_n):
    """Normalize a row-liveness spec: an int num_rows -> prefix mask; an array
    passes through (scattered liveness appears after all-gather of partials)."""
    if isinstance(alive_or_n, (int, np.integer)):
        return xp.arange(capacity, dtype=np.int32) < alive_or_n
    if getattr(alive_or_n, "ndim", None) == 0:
        return xp.arange(capacity, dtype=np.int32) < alive_or_n
    return alive_or_n


def sort_indices(xp, keys: Sequence[Tuple[ColV, bool, bool]], alive_or_n):
    """Lexicographic multi-key sort -> row permutation (dead rows last).

    keys: (column, ascending, nulls_first), most significant first. Implemented
    as stable argsort passes composed least-significant-first (LSD); XLA's sort
    is used with stability so earlier passes' order survives ties.
    """
    cap = keys[0][0].validity.shape[0]
    alive = alive_mask(xp, cap, alive_or_n)
    order = xp.arange(cap, dtype=np.int32)
    all_passes: List = []
    for v, asc, nf in keys:
        all_passes.extend(_key_passes(xp, v, asc, nf))
    for k in reversed(all_passes):
        order = order[_stable_argsort(xp, k[order])]
    # most significant of all: dead/padding rows to the back
    is_pad = xp.logical_not(alive[order]).astype(np.int8)
    order = order[_stable_argsort(xp, is_pad)]
    return order


def rows_equal_adjacent(xp, keys: Sequence[ColV], order, alive_or_n):
    """After sorting by `order`, mark rows that START a new group.

    Spark grouping semantics: null == null, NaN == NaN (keys are normalized
    upstream for -0.0).
    """
    cap = order.shape[0]
    prev = xp.concatenate([order[:1], order[:-1]])
    new_group = xp.zeros(cap, dtype=bool)
    first = xp.arange(cap) == 0
    for v in keys:
        a_valid = v.validity[order]
        b_valid = v.validity[prev]
        if v.dtype is DType.STRING:
            same_data = xp.logical_and(
                xp.all(v.data[order] == v.data[prev], axis=-1),
                v.lengths[order] == v.lengths[prev])
        elif v.dtype.is_floating:
            a, b = v.data[order], v.data[prev]
            same_data = xp.logical_or(a == b,
                                      xp.logical_and(xp.isnan(a), xp.isnan(b)))
        else:
            same_data = v.data[order] == v.data[prev]
        same = xp.where(xp.logical_and(a_valid, b_valid), same_data,
                        a_valid == b_valid)
        new_group = xp.logical_or(new_group, xp.logical_not(same))
    new_group = xp.logical_or(new_group, first)
    # padding rows never start a group
    alive = alive_mask(xp, cap, alive_or_n)
    return xp.logical_and(new_group, alive[order])


def segment_pick(xp, validity, seg_ids, num_segments: int, kind: str,
                 alive=None, ignore_nulls: bool = False):
    """Row index of the first/last participating row per segment.

    Participation: alive rows (non-padding); with ignore_nulls additionally
    valid rows. Returns (pick_index, has_pick) — callers gather data/lengths/
    validity with pick_index themselves (needed for string columns with
    multiple per-row arrays).
    """
    n = validity.shape[0]
    if alive is None:
        alive = xp.ones_like(validity)
    candidate = xp.logical_and(alive, validity) if ignore_nulls else alive
    idx = xp.arange(n, dtype=np.int64)
    if xp is np:
        sentinel = n + 1 if kind == "first" else -1
        pick = np.full(num_segments, sentinel, dtype=np.int64)
        key = np.where(candidate, idx, sentinel)
        op = np.minimum if kind == "first" else np.maximum
        op.at(pick, seg_ids, key)
    else:
        import jax
        ops = jax.ops
        if kind == "first":
            key = xp.where(candidate, idx, np.int64(n + 1))
            pick = ops.segment_min(key, seg_ids, num_segments=num_segments)
        else:
            key = xp.where(candidate, idx, np.int64(-1))
            pick = ops.segment_max(key, seg_ids, num_segments=num_segments)
    has = xp.logical_and(pick >= 0, pick < n)
    return xp.clip(pick, 0, max(n - 1, 0)), has


def segment_reduce(xp, data, validity, seg_ids, num_segments: int, kind: str,
                   ignore_nulls: bool = False):
    """Per-segment reduction. data/validity are row-aligned; seg_ids in
    [0, num_segments); rows with seg_id == num_segments-1 reserved for padding
    are fine because their validity is False.

    Returns (seg_data, seg_validity). For first/last, picks the value at the
    first/last (valid, if ignore_nulls) row of each segment.
    """
    if xp is np:
        return _segment_reduce_np(data, validity, seg_ids, num_segments, kind,
                                  ignore_nulls)
    import jax
    import jax.numpy as jnp
    ops = jax.ops
    counts = ops.segment_sum(validity.astype(np.int32), seg_ids,
                             num_segments=num_segments)
    seg_valid = counts > 0
    if kind == "sum":
        contrib = jnp.where(validity, data, 0).astype(data.dtype)
        return ops.segment_sum(contrib, seg_ids, num_segments=num_segments), seg_valid
    if kind in ("min", "max"):
        return (_segment_minmax_jax(jnp, ops, data, validity, seg_ids,
                                    num_segments, kind), seg_valid)
    if kind in ("first", "last"):
        pick, has = segment_pick(jnp, validity, seg_ids, num_segments, kind,
                                 ignore_nulls=ignore_nulls)
        return data[pick], jnp.logical_and(has, validity[pick])
    raise ValueError(kind)


def _segment_minmax_jax(jnp, ops, data, validity, seg_ids, num_segments, kind):
    if data.dtype == np.bool_:
        d = data.astype(np.int8)
        neutral = np.int8(1 if kind == "min" else 0)
        contrib = jnp.where(validity, d, neutral)
        f = ops.segment_min if kind == "min" else ops.segment_max
        return f(contrib, seg_ids, num_segments=num_segments).astype(np.bool_)
    if np.issubdtype(np.dtype(data.dtype), np.floating):
        neutral = np.asarray(np.inf if kind == "min" else -np.inf,
                             dtype=data.dtype)
        # Spark NaN ordering: NaN is the largest value
        nan = jnp.isnan(data)
        d = jnp.where(nan, jnp.asarray(np.inf, dtype=data.dtype), data)
        contrib = jnp.where(validity, d, neutral)
        f = ops.segment_min if kind == "min" else ops.segment_max
        res = f(contrib, seg_ids, num_segments=num_segments)
        # a max that saw any NaN must return NaN; a min returns NaN only if every
        # valid value was NaN
        valid_nan = jnp.logical_and(nan, validity)
        nan_count = ops.segment_sum(valid_nan.astype(np.int32), seg_ids,
                                    num_segments=num_segments)
        valid_count = ops.segment_sum(validity.astype(np.int32), seg_ids,
                                      num_segments=num_segments)
        if kind == "max":
            res = jnp.where(nan_count > 0,
                            jnp.asarray(np.nan, dtype=data.dtype), res)
        else:
            res = jnp.where(jnp.logical_and(valid_count > 0,
                                            nan_count == valid_count),
                            jnp.asarray(np.nan, dtype=data.dtype), res)
        return res
    neutral = (np.iinfo(np.dtype(data.dtype)).max if kind == "min"
               else np.iinfo(np.dtype(data.dtype)).min)
    contrib = jnp.where(validity, data, neutral)
    f = ops.segment_min if kind == "min" else ops.segment_max
    return f(contrib, seg_ids, num_segments=num_segments)


def _segment_reduce_np(data, validity, seg_ids, num_segments, kind, ignore_nulls):
    """Eager numpy reference implementation (CPU engine path)."""
    seg_ids = np.asarray(seg_ids)
    validity = np.asarray(validity)
    counts = np.zeros(num_segments, dtype=np.int64)
    np.add.at(counts, seg_ids, validity.astype(np.int64))
    seg_valid = counts > 0
    if kind == "sum":
        out = np.zeros(num_segments, dtype=data.dtype)
        np.add.at(out, seg_ids, np.where(validity, data, 0))
        return out, seg_valid
    if kind in ("min", "max"):
        return _np_minmax(data, validity, seg_ids, num_segments, kind), seg_valid
    if kind in ("first", "last"):
        pick, has = segment_pick(np, validity, seg_ids, num_segments, kind,
                                 ignore_nulls=ignore_nulls)
        return data[pick], has & validity[pick]
    raise ValueError(kind)


def _np_minmax(data, validity, seg_ids, num_segments, kind):
    isfloat = np.issubdtype(data.dtype, np.floating)
    if data.dtype == np.bool_:
        d = data.astype(np.int8)
        neutral = 1 if kind == "min" else 0
        out = np.full(num_segments, neutral, dtype=np.int8)
        getattr(np, "minimum" if kind == "min" else "maximum").at(
            out, seg_ids, np.where(validity, d, neutral))
        return out.astype(np.bool_)
    if isfloat:
        nan = np.isnan(data)
        d = np.where(nan, np.inf, data)
        neutral = np.inf if kind == "min" else -np.inf
        out = np.full(num_segments, neutral, dtype=data.dtype)
        getattr(np, "minimum" if kind == "min" else "maximum").at(
            out, seg_ids, np.where(validity, d, neutral))
        valid_nan = nan & validity
        nan_count = np.zeros(num_segments, dtype=np.int64)
        np.add.at(nan_count, seg_ids, valid_nan.astype(np.int64))
        valid_count = np.zeros(num_segments, dtype=np.int64)
        np.add.at(valid_count, seg_ids, validity.astype(np.int64))
        if kind == "max":
            out = np.where(nan_count > 0, np.nan, out)
        else:
            out = np.where((valid_count > 0) & (nan_count == valid_count),
                           np.nan, out)
        return out.astype(data.dtype)
    neutral = (np.iinfo(data.dtype).max if kind == "min"
               else np.iinfo(data.dtype).min)
    out = np.full(num_segments, neutral, dtype=data.dtype)
    getattr(np, "minimum" if kind == "min" else "maximum").at(
        out, seg_ids, np.where(validity, data, neutral))
    return out


def key_words(xp, v: ColV) -> List:
    """Injective uint64 encoding of one grouping-key column: a static-length
    word list such that two rows are grouping-equal (Spark semantics:
    null==null, NaN==NaN, -0.0==0.0) IFF all their words are equal. Invalid
    rows canonicalize every word to 0 — pair with a validity word (see
    ``validity_word``) to separate null from a zero-encoded value.

    Used by the one-hot aggregation path for EXACT hash-collision detection:
    per group, min(word) != max(word) for any word proves two distinct keys
    shared a hash.
    """
    if v.dtype is DType.STRING:
        W = v.data.shape[-1]
        pad = (-W) % 8
        data = v.data
        if pad:
            data = xp.concatenate(
                [data, xp.zeros(data.shape[:-1] + (pad,), dtype=np.uint8)],
                axis=-1)
        shifts = xp.asarray((np.arange(7, -1, -1) * 8).astype(np.uint64))
        chunks = data.reshape(data.shape[:-1] + (-1, 8)).astype(np.uint64)
        words = xp.sum(chunks << shifts, axis=-1)
        out = [xp.where(v.validity, words[..., i], np.uint64(0))
               for i in range(words.shape[-1])]
        out.append(xp.where(v.validity, v.lengths.astype(np.uint64),
                            np.uint64(0)))
        return out
    if v.dtype.is_floating:
        d = v.data.astype(np.float64)
        sign, e, mi, zero, inf, nan = _float_canon(xp, d)
        # finite: w0 = mi (in [2^52, 2^53)); specials use small codes that a
        # finite mi can never take. w1 = sign/exponent field.
        w0 = mi.astype(np.uint64)
        w0 = xp.where(zero, np.uint64(1), w0)
        w0 = xp.where(inf, np.uint64(2), w0)
        w0 = xp.where(nan, np.uint64(3), w0)
        w1 = ((e.astype(np.int64) + np.int64(1074)).astype(np.uint64)
              | (xp.where(sign, np.uint64(1), np.uint64(0)) << np.uint64(13)))
        w1 = xp.where(zero, np.uint64(0), w1)
        w1 = xp.where(nan, np.uint64(0), w1)
        w1 = xp.where(inf, xp.where(sign, np.uint64(1), np.uint64(0)), w1)
        return [xp.where(v.validity, w0, np.uint64(0)),
                xp.where(v.validity, w1, np.uint64(0))]
    if v.dtype is DType.BOOLEAN:
        return [xp.where(v.validity, v.data.astype(np.uint64), np.uint64(0))]
    bits = v.data.astype(np.int64).astype(np.uint64)
    return [xp.where(v.validity, bits, np.uint64(0))]


def validity_word(xp, keys: Sequence[ColV]):
    """One uint64 packing every key column's validity bit (<=64 columns)."""
    w = None
    for i, v in enumerate(keys[:64]):
        piece = v.validity.astype(np.uint64) << np.uint64(i)
        w = piece if w is None else w | piece
    return w


#: block width of the segmented scan: rows scan block-locally first, then the
#: per-block carries; and the least the scan form is worth setting up for
_SEG_BLOCK_B = 512

_SEG_OPS = {"sum": "add", "min": "minimum", "max": "maximum"}


def segment_reduce_form(capacity: int) -> str:
    """Which form SortedSegmentStacker takes at this capacity: ``"scan"``, or
    ``"plain"`` under 4*B rows or where B does not divide them (such batches
    are tiny). Static, so an exec can say it on its span without a read."""
    B = _SEG_BLOCK_B
    return "plain" if capacity % B or capacity < 4 * B else "scan"


def _shift_left(xp, a, d: int, fill):
    """``a`` moved ``d`` places towards index 0 along its last axis, the
    vacated tail filled with ``fill``."""
    pad = xp.full(a.shape[:-1] + (d,), fill, dtype=a.dtype)
    return xp.concatenate([a[..., d:], pad], axis=-1)


def _scan_steps(xp, f, vs, kinds):
    """log2(n) shift-and-combine steps of a reverse inclusive segmented scan
    along the last axis: on return ``vs[c][..., i]`` reduces rows ``i..`` up
    to the first row ``j >= i`` with ``f[..., j]`` set (or the axis' end),
    and ``f[..., i]`` says that such a row exists. Elementwise passes over
    shifted copies only: no gather, no scatter, no strided slice."""
    d, n = 1, f.shape[-1]
    while d < n:
        # past the axis' end a neutral element comes in: combining it
        # changes nothing
        vs = [xp.where(f, v, getattr(xp, _SEG_OPS[k])(
            v, _shift_left(xp, v, d, _neutral(xp, k, v.dtype))))
            for k, v in zip(kinds, vs)]
        f = xp.logical_or(f, _shift_left(xp, f, d, False))
        d *= 2
    return f, vs


def segmented_scan(xp, ends, kinds: Sequence[str], cols: Sequence):
    """Reverse inclusive segmented scan of every column at once: row ``i``
    receives the sum/min/max (``kinds[c]``) of ``cols[c]`` over rows ``i``
    to the last row of its segment, ``ends`` marking each segment's last
    row — so a segment's FIRST row holds the whole segment's reduction.

    A scan that resets at the marks reduces a segment's own rows only
    (never a difference of running totals: a float group that cancels to
    0.0 must not pick up its neighbours' residue, nor an ``inf`` next door
    turn it into NaN). Long axes scan block-locally first (B columns: 9
    steps over the whole array), then the per-block carries (n/B rows)
    recursively, then one pass applies each block's carry to the rows its
    open segment covers."""
    B = _SEG_BLOCK_B
    n = ends.shape[0]
    cols = list(cols)
    if segment_reduce_form(n) == "plain":     # short: one flat pass
        return _scan_steps(xp, ends, cols, kinds)[1]
    nb = n // B
    lf, lv = _scan_steps(xp, ends.reshape(nb, B),
                         [c.reshape(nb, B) for c in cols], kinds)
    # a block's reduction lands on its first column; the carry INTO block b
    # is the scan of the blocks after it
    below = segmented_scan(xp, lf[:, 0], kinds, [v[:, 0] for v in lv])
    out = []
    for k, v, c in zip(kinds, lv, below):
        carry = _shift_left(xp, c, 1, _neutral(xp, k, c.dtype))[:, None]
        out.append(xp.where(lf, v, getattr(xp, _SEG_OPS[k])(v, carry))
                   .reshape(n))
    return out


class SortedSegmentStacker:
    """Every per-group reduction of one aggregation over rows SORTED by
    group (non-decreasing ``gids``, dead rows last), in one go, together
    with each group's representative key: its first sorted row.

    Register contributions with :meth:`add` (the caller applies its own
    neutral-element masking, so dead rows and nulls reduce to nothing),
    call :meth:`run` once with the key columns, then fetch the reduced
    columns via the returned handles. Output row g is group g, for the
    first ``num_segments`` groups.

    TPU scatters and gathers cost ~100 ns a row whatever the segment space
    (0.75-0.80 s for one 64-bit column of 8.4 M rows on a v5e), so the
    **scan** form has neither: :func:`segmented_scan` leaves every group's
    reduction on its first row, and ONE compaction sort keyed on "not a
    group start" moves those rows to the front in group order, carrying the
    key columns and every reduced column as operands (sort_colvs). Without
    keys there is one group and nothing to compact. On a v5e at 8.4 M rows:
    4 ms the scans of a float64 and an int32 column, 58 ms the sort.

    The **plain** form (segment_reduce_form: tiny batches) is one stacked
    scatter per (kind, dtype) over all rows and a gather of the keys."""

    def __init__(self, xp, gids, num_segments: int):
        self.xp = xp
        self.gids = gids
        self.num_segments = num_segments
        self._kinds = []
        self._cols = []
        self._results = None

    def add(self, kind: str, contrib):
        assert self._results is None
        self._kinds.append(kind)
        self._cols.append(contrib)
        return len(self._cols) - 1

    def get(self, handle):
        return self._results[handle]

    def run(self, keys: Sequence[ColV] = (), starts=None,
            alive=None) -> List[ColV]:
        """Reduce what was registered; returns the key columns of the
        groups (``starts`` marks each group's first row, ``alive`` the live
        rows; neither is read without keys)."""
        form = segment_reduce_form(self.gids.shape[0])
        run = self._scan if form == "scan" else self._scatter
        out_keys, self._results = run(keys, starts, alive)
        return out_keys

    def _scan(self, keys, starts, alive):
        xp = self.xp
        cap = self.gids.shape[0]
        # a group ends where the next one starts; the last row ends the last
        ends = (_shift_left(xp, starts, 1, True) if keys
                else xp.arange(cap, dtype=np.int32) == cap - 1)
        reduced = segmented_scan(xp, ends, self._kinds, self._cols)
        out_keys = []
        if keys:
            out_keys, reduced = sort_colvs(
                xp, [xp.logical_not(starts).astype(np.int8)], keys, reduced)
        n = self.num_segments
        return [ColV(k.dtype, k.data[:n], k.validity[:n],
                     None if k.lengths is None else k.lengths[:n])
                for k in out_keys], [a[:n] for a in reduced]

    def _scatter(self, keys, starts, alive):
        import jax
        xp = self.xp
        cap = self.gids.shape[0]
        n = self.num_segments
        seg_ids = xp.minimum(self.gids, n - 1)
        ops = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}

        def reduce(kind, arrs):
            res = ops[kind](xp.stack(arrs, axis=1), seg_ids, num_segments=n)
            return [res[:, j] for j in range(len(arrs))]

        # a scatter costs by the call: one per (kind, dtype), stacked
        buckets = {}
        for h, (kind, c) in enumerate(zip(self._kinds, self._cols)):
            buckets.setdefault((kind, str(c.dtype)), []).append(h)
        results = [None] * len(self._cols)
        for (kind, _), hs in buckets.items():
            for h, col in zip(hs, reduce(kind, [self._cols[h] for h in hs])):
                results[h] = col
        out_keys = []
        if keys:
            idx = xp.arange(cap, dtype=np.int64)
            (at,) = reduce("min", [xp.where(alive, idx, np.int64(cap + 1))])
            pick = xp.clip(at, 0, cap - 1)
            out_keys = [v.with_validity(xp.logical_and(at < cap, v.validity))
                        for v in (take_colv(xp, k, pick) for k in keys)]
        return out_keys, results


def _neutral(xp, kind: str, dt):
    if kind == "sum":
        return xp.zeros((), dtype=dt)
    if np.issubdtype(dt, np.floating):
        return xp.asarray(np.inf if kind == "min" else -np.inf, dt)
    return xp.asarray(np.iinfo(dt).max if kind == "min"
                      else np.iinfo(dt).min, dt)


def take_columns(xp, columns: Sequence[ColV], indices) -> List[ColV]:
    """Permute many columns by one index vector, stacking same-dtype 1-D
    buffers so the device does one gather per dtype group instead of one per
    buffer (~2x on TPU for wide batches; gathers dominate compact/sort)."""
    if xp is np:
        return [take_colv(xp, v, indices) for v in columns]
    slots = {}   # dtype str -> list of (col_idx, role, array)
    for i, v in enumerate(columns):
        entries = [(i, "data", v.data), (i, "validity", v.validity)]
        if v.lengths is not None:
            entries.append((i, "lengths", v.lengths))
        for e in entries:
            arr = e[2]
            if arr.ndim == 1:
                slots.setdefault(str(arr.dtype), []).append(e)
            else:
                slots.setdefault(f"2d{i}{e[1]}", []).append(e)
    gathered = {}
    for key, entries in slots.items():
        if len(entries) == 1 or key.startswith("2d"):
            for i, role, arr in entries:
                gathered[(i, role)] = arr[indices]
        else:
            m = xp.stack([arr for _, _, arr in entries], axis=1)[indices]
            for j, (i, role, _) in enumerate(entries):
                gathered[(i, role)] = m[:, j]
    out = []
    for i, v in enumerate(columns):
        out.append(ColV(v.dtype, gathered[(i, "data")],
                        gathered[(i, "validity")],
                        gathered.get((i, "lengths"))))
    return out
