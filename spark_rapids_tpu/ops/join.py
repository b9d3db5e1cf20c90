"""Equi-join kernels (reference: shims/spark300/GpuHashJoin.scala:220-230 —
cudf Table.innerJoin/leftJoin/leftSemiJoin/leftAntiJoin/fullJoin).

TPU re-design: no device hash table (dynamic shapes). Rows of the two sides
join iff they fall into the same *key group* of one sort over the union of
both sides' keys. Join cardinality is dynamic, so the kernel is split:

  phase 1 (size):   one jit program computes per-emit-group counts, offsets and
                    the total output size (a traced scalar, synced to host once);
  phase 2 (gather): a second jit program with the bucketed static output
                    capacity gathers the matching row pairs.

This is the two-pass size-then-gather pattern for dynamic cardinality on XLA.
Spark semantics: null keys never match (any-null rows are excluded from
grouping); NaN keys match each other; supported: inner, left, right, full,
left_semi, left_anti, cross.

Phase 1 is sorts that carry their operands, and scans, and nothing else
(``join_size``): sort 1 brings the union into key order with the key words
and the row index riding along, group boundaries are the places where a
sorted word differs from its neighbour's, per-group counts are differences of
a running count that one cummax carries forward from the group's first row
and one cummin carries back from its last, and sort 2 takes the counts to
the output layout (stream rows in row order, then the build rows in key
order). What decided it, on the v5e, per Q3 (its two joins: S+B = 4.46 M
and 1.08 M rows; device trace of the kernel before PR 31, which held five
sorts and 17 gathers of S+B rows, 24 once the TPU split the 64-bit ones): the
gathers took 1.745 s of the kernel's 1.854 (nine of 0.10-0.16 s each, fifteen
of 0.040-0.054), the ten sorts of four or five operands 0.083 s together,
the scans 0.025; a scatter-add costs several sorts (docs/perf-notes.md). One
gather costs two to ten sorts, so everything a gather used to fetch is
carried by a sort or propagated by a scan; positions, counts and row indices
are 32 bits wide (64-bit words are emulated) and only the emit offsets and
the total are 64.

Emit-group layout: groups [0, S) are stream (left) rows — each emits its match
count (or 1 null-padded row for left/full when unmatched, or 0/1 for
semi/anti); groups [S, S+B) are build (right) rows — each emits 1 when
unmatched under right/full. A single exclusive-scan over all S+B groups gives
output offsets for both halves.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs.core import ColV
from spark_rapids_tpu.ops import batch_kernels as bk

JOIN_KINDS = ("inner", "left", "right", "full", "left_semi", "left_anti", "cross")


def _any_null(xp, keys: Sequence[ColV]):
    out = None
    for k in keys:
        inv = xp.logical_not(k.validity)
        out = inv if out is None else xp.logical_or(out, inv)
    return out


def _concat_colv(xp, a: ColV, b: ColV) -> ColV:
    if a.lengths is not None:
        from spark_rapids_tpu.ops.strings import align_widths
        ad, bd = align_widths(xp, a.data, b.data)
        a = ColV(a.dtype, ad, a.validity, a.lengths)
        b = ColV(b.dtype, bd, b.validity, b.lengths)
    data = xp.concatenate([a.data, b.data], axis=0)
    validity = xp.concatenate([a.validity, b.validity], axis=0)
    lengths = (xp.concatenate([a.lengths, b.lengths], axis=0)
               if a.lengths is not None else None)
    return ColV(a.dtype, data, validity, lengths)


def _exclusive_cumsum(xp, x):
    c = xp.cumsum(x)
    return c - x


def _sort_carried(xp, operands: Sequence, num_keys: int) -> List:
    """Sort by the first ``num_keys`` operands (lexicographic, the first most
    significant) and carry the others along: every operand comes back in the
    sorted order, so nothing is gathered through a permutation afterwards.
    Never stable (a stable sort compiles three times as long for the TPU):
    ``join_size``'s key tuples are unique, and where ``_string_rank``'s tie
    the tied rows get one rank whatever their order, so both engines agree."""
    if xp is np:
        order = np.lexsort(tuple(reversed(
            [np.asarray(o) for o in operands[:num_keys]])))
        return [np.asarray(o)[order] for o in operands]
    import jax
    return list(jax.lax.sort(tuple(operands), num_keys=num_keys,
                             is_stable=False))


def _cummax(xp, x):
    if xp is np:
        return np.maximum.accumulate(x)
    import jax
    return jax.lax.cummax(x)


def _cummin_from_end(xp, x):
    if xp is np:
        return np.minimum.accumulate(x[::-1])[::-1]
    import jax
    return jax.lax.cummin(x, reverse=True)


def _shift_down(xp, x):
    """x[i-1] at position i (x[0] at 0)."""
    return xp.concatenate([x[:1], x[:-1]])


def _string_rank(xp, v: ColV):
    """A STRING key as ONE int32 word: the dense rank of (bytes, length)
    among the rows, equal for equal strings, in byte order. Folded from the
    least significant uint64 chunk up, two narrow sorts a chunk inside one
    loop: the compiler sees two sorts whatever the width. (A sort keyed by
    every chunk at once compiles for minutes a key word on the TPU: over 25
    for a 32-byte key, PERF.md section 6, PR 31.)"""
    stack = xp.stack([xp.asarray(w, dtype=np.int64)
                      for w in bk._key_passes(xp, v, True, True)[1:]])
    n, G = stack.shape
    pos = xp.arange(G, dtype=np.int32)

    def fold(i, rank):
        # rank orders the chunks below this one; (chunk, rank) orders these
        w = stack[n - 1 - i]
        w_s, rank_s, row_s = _sort_carried(xp, [w, rank, pos], num_keys=2)
        starts = xp.logical_or(w_s != _shift_down(xp, w_s),
                               rank_s != _shift_down(xp, rank_s))
        dense = xp.cumsum(xp.logical_or(starts, pos == 0), dtype=np.int32)
        return _sort_carried(xp, [row_s, dense], num_keys=1)[1]

    rank = xp.zeros(G, dtype=np.int32)
    if xp is np:
        for i in range(n):
            rank = fold(i, rank)
        return rank
    import jax
    return jax.lax.fori_loop(0, n, fold, rank)


def _match_words(xp, v: ColV) -> List:
    """The words whose equality is Spark's key equality and whose order
    groups equal keys: ``bk._key_passes`` without the null rank (a null key
    never matches; its row is sorted behind with the dead ones). Every word
    is a key operand of sort 1, so the key's dtype decides how many there
    are: a string is ranked to one, integers stay as narrow as they are
    stored (64-bit compares are emulated on the TPU)."""
    if v.dtype is DType.STRING:
        return [_string_rank(xp, v)]
    if np.dtype(v.data.dtype).kind in "iu":
        return [v.data]
    return bk._key_passes(xp, v, True, True)[1:]


def join_size(xp, l_keys: Sequence[ColV], r_keys: Sequence[ColV],
              l_alive, r_alive, how: str):
    """Phase 1. Returns a dict of device arrays:
    emit_counts [S+B], emit_offsets [S+B], total (scalar), border [B] (the
    build rows in key-group order, ties by row index, rows that cannot match
    last), start_b [S] (PER STREAM ROW: the row's group's first build-row
    index within ``border``), matches_l [S].

    Two carried-operand sorts of the S+B union and three scans (five for
    right/full); no gather, no scatter, no inverse permutation:

      sort 1  (cannot match, key words..., row index) -> the same in key order
      shift   group starts/ends: a sorted word differs from its neighbour's
      scans   cb = cumsum(is a build row); the group's first exclusive cb
              carried forward (cummax), its last inclusive cb carried back
              (cummin from the end): their difference is the group's build
              count, the first is start_b. Stream counts the same way from
              position - cb, only where right/full read them.
      sort 2  by destination: a stream row to its row index, a build row to
              S + its rank in key order -> [:S] is per stream row in row
              order, [S:] is ``border``.

    Lowered for one LONG key (S = 256, B = 4,096; tests/test_join_kernel.py
    pins it): 2 sorts, 0 gathers, 0 scatters. The formulation before PR 31
    held 5 sorts and 17 gathers of S+B rows after CSE, nine of 64-bit words.
    """
    S = l_keys[0].validity.shape[0] if l_keys else l_alive.shape[0]
    B = r_keys[0].validity.shape[0] if r_keys else r_alive.shape[0]
    G = S + B

    if how == "cross":
        B_count = xp.sum(r_alive).astype(np.int64)
        emit_counts = xp.where(l_alive, B_count, 0).astype(np.int64)
        emit_counts = xp.concatenate(
            [emit_counts, xp.zeros(B, dtype=np.int64)])
        emit_offsets = _exclusive_cumsum(xp, emit_counts)
        total = xp.sum(emit_counts)
        # build rows in original order, compacted to the front
        border = bk._stable_argsort(xp, xp.logical_not(r_alive))
        return dict(emit_counts=emit_counts, emit_offsets=emit_offsets,
                    total=total, border=border.astype(np.int32),
                    start_b=xp.zeros(S, dtype=np.int64),
                    matches_l=xp.where(l_alive, B_count, 0).astype(np.int64))
    if how not in JOIN_KINDS:
        raise ValueError(how)
    needs_matched_b = how in ("right", "full")

    l_ok = xp.logical_and(l_alive, xp.logical_not(_any_null(xp, l_keys)))
    r_ok = xp.logical_and(r_alive, xp.logical_not(_any_null(xp, r_keys)))
    ok = xp.concatenate([l_ok, r_ok])
    words = []
    for lk, rk in zip(l_keys, r_keys):
        words.extend(_match_words(xp, _concat_colv(xp, lk, rk)))
    # rows that cannot match carry constant words: behind, by row index
    words = [xp.where(ok, w, xp.zeros((), w.dtype)) for w in words]
    pos = xp.arange(G, dtype=np.int32)

    # ---- sort 1: key order; the words, the row index and ok ride along
    srt = _sort_carried(
        xp, [xp.logical_not(ok).astype(np.int8)] + words + [pos],
        num_keys=len(words) + 2)
    nok_s, words_s, row_s = srt[0], srt[1:-1], srt[-1]
    ok_s = nok_s == 0
    is_b = row_s >= S

    # ---- group marks from the sorted words themselves
    starts = pos == 0
    for w in [nok_s] + list(words_s):
        starts = xp.logical_or(starts, w != _shift_down(xp, w))
    ends = xp.concatenate([starts[1:], xp.ones(1, dtype=bool)])

    # ---- counts by scans. cb counts every build row: the rows that cannot
    # match lie behind every group, so inside a group cb counts live ones
    is_b32 = is_b.astype(np.int32)
    cb = xp.cumsum(is_b32, dtype=np.int32)

    def group_span(incl, excl):
        first = _cummax(xp, xp.where(starts, excl, np.int32(0)))
        last = _cummin_from_end(xp, xp.where(ends, incl, np.int32(G + 1)))
        return first, last - first

    start_b_s, cnt_b_s = group_span(cb, cb - is_b32)
    cnt_b_s = xp.where(ok_s, cnt_b_s, np.int32(0))
    start_b_s = xp.where(ok_s, start_b_s, np.int32(0))
    if needs_matched_b:
        # stream rows up to a position: the position's rank less cb
        cs = pos + np.int32(1) - cb
        _, cnt_s_s = group_span(cs, cs - (np.int32(1) - is_b32))
        cnt_s_s = xp.where(ok_s, cnt_s_s, np.int32(0))
    else:
        cnt_s_s = np.int32(0)

    # ---- sort 2: back to the output layout. dest is a permutation of
    # [0, G): stream rows to their row index, build rows behind them by rank
    # in key order (cb - 1; the rows that cannot match follow by row index,
    # as sort 1 left them). Two payload words: a stream row brings its
    # (matches, start_b), a build row its (row index, group's stream count)
    dest = xp.where(is_b, np.int32(S - 1) + cb, row_s)
    _, pay0, pay1 = _sort_carried(
        xp, [dest, xp.where(is_b, row_s - np.int32(S), cnt_b_s),
             xp.where(is_b, cnt_s_s, start_b_s)], num_keys=1)
    matches_l = pay0[:S]
    start_b_stream = pay1[:S]
    border = pay0[S:]

    if needs_matched_b:
        # per build row in ROW order: one more sort, of the B-slice alone
        _, cnt_s_row = _sort_carried(xp, [border, pay1[S:]], num_keys=1)
        emit_r = xp.logical_and(r_alive, cnt_s_row == 0).astype(np.int64)
    else:
        emit_r = xp.zeros(B, dtype=np.int64)
    if how in ("left", "full"):
        emit_l = xp.where(l_alive, xp.maximum(matches_l, 1), 0)
    elif how == "left_semi":
        emit_l = matches_l > 0
    elif how == "left_anti":
        emit_l = xp.logical_and(l_alive, matches_l == 0)
    else:   # inner, right
        emit_l = matches_l

    emit_counts = xp.concatenate([emit_l.astype(np.int64), emit_r])
    emit_offsets = _exclusive_cumsum(xp, emit_counts)
    total = xp.sum(emit_counts)
    return dict(emit_counts=emit_counts, emit_offsets=emit_offsets, total=total,
                border=border, start_b=start_b_stream, matches_l=matches_l)


def join_gather(xp, sized: dict, S: int, B: int, out_cap: int, how: str):
    """Phase 2: output row -> (left_row, left_valid, right_row, right_valid).

    left/right_row are gather indices into the original batches; *_valid False
    means that side is null-padded (outer joins) or absent (semi/anti emit only
    the left side).
    """
    emit_offsets = sized["emit_offsets"]
    emit_counts = sized["emit_counts"]
    border = sized["border"]
    start_b = sized["start_b"]
    matches_l = sized["matches_l"]
    total = sized["total"]

    p = xp.arange(out_cap, dtype=np.int64)
    in_range = p < total
    g = _searchsorted_right(xp, emit_offsets, p) - 1
    g = xp.clip(g, 0, S + B - 1).astype(np.int64)
    k = p - emit_offsets[g]

    from_stream = g < S
    srow = xp.clip(g, 0, S - 1)
    brow_unmatched = xp.clip(g - S, 0, max(B - 1, 0))

    if how == "cross":
        bpos = xp.clip(k, 0, max(B - 1, 0))
        right_row = border[bpos]
        left_valid = xp.logical_and(in_range, from_stream)
        right_valid = left_valid
        return (srow.astype(np.int32), left_valid,
                right_row.astype(np.int32), right_valid, total)

    has_match = matches_l[srow] > 0
    bpos = xp.clip(start_b[srow] + k, 0, max(B - 1, 0))
    right_from_match = border[bpos]

    if how in ("left_semi", "left_anti"):
        left_row = srow
        left_valid = in_range
        right_row = xp.zeros_like(srow)
        right_valid = xp.zeros_like(in_range)
        return (left_row.astype(np.int32), left_valid,
                right_row.astype(np.int32), right_valid, total)

    left_row = xp.where(from_stream, srow, 0)
    left_valid = xp.logical_and(in_range, from_stream)
    right_row = xp.where(from_stream, right_from_match, brow_unmatched)
    right_valid = xp.logical_and(
        in_range, xp.logical_or(xp.logical_and(from_stream, has_match),
                                xp.logical_not(from_stream)))
    return (left_row.astype(np.int32), left_valid,
            right_row.astype(np.int32), right_valid, total)


def gather_join_output(xp, l_cols: Sequence[ColV], r_cols: Sequence[ColV],
                       left_row, left_valid, right_row, right_valid
                       ) -> List[ColV]:
    """Materialize output columns from gather indices; a False side-valid bit
    nulls out that side's columns (outer padding)."""
    out: List[ColV] = []
    for v in l_cols:
        g = bk.take_colv(xp, v, left_row)
        out.append(g.with_validity(xp.logical_and(g.validity, left_valid)))
    for v in r_cols:
        g = bk.take_colv(xp, v, right_row)
        out.append(g.with_validity(xp.logical_and(g.validity, right_valid)))
    return out


def _searchsorted_right(xp, a, v):
    """searchsorted(side='right') that lowers well on TPU: the default
    binary-search lowering measured 7.1 s for 8.4M queries on this chip;
    method='sort' (one co-sort of a and v) is ~320 ms."""
    if xp is np:
        return np.searchsorted(a, v, side="right")
    return xp.searchsorted(a, v, side="right", method="sort")


