"""Sort-based group-by aggregation pipeline.

The TPU replacement for cuDF's hash groupby (reference: aggregate.scala:227
GpuHashAggregateExec -> Table.groupBy().aggregate()): keys are sorted (XLA's TPU
sort is excellent and shape-static), group boundaries become segment ids, and
aggregation buffers reduce over the sorted segments by segmented scans and one
compaction sort that carries keys and reduced buffers, never by a scatter or a
gather of the batch's capacity (bk.SortedSegmentStacker). The whole pipeline — key
evaluation, buffer projection, sort, boundary detection, reduction, final
evaluation — traces into ONE XLA program; group count is a traced scalar
(row-count sidecar).

Used eagerly with numpy by the CPU engine and traced with jax.numpy by the TPU
exec, so both paths share one semantics definition.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs.aggregates import AggregateFunction
from spark_rapids_tpu.exprs.core import ColV, EvalCtx
from spark_rapids_tpu.ops import batch_kernels as bk


def group_aggregate(xp, ctx: EvalCtx, key_exprs, agg_fns: Sequence[AggregateFunction],
                    num_rows, capacity: int, evaluate: bool = True,
                    grouping: str = "sort", extra_mask=None):
    """Full grouped aggregation over one batch.

    Returns (key_cols, result_cols, num_groups): reduced key columns, final
    aggregate result columns (one per agg fn), and the traced group count.
    With no keys, produces exactly one group (Spark's global aggregate,
    including the empty-input row).

    With ``evaluate=False`` this is the *Partial* mode of the reference's
    GpuHashAggregateExec (aggregate.scala modes Partial/Final): result_cols are
    the reduced aggregation BUFFERS (flattened across fns) rather than final
    values, ready for ``merge_aggregate`` after an exchange/all-gather.

    ``grouping="hash"`` orders rows by a 64-bit key hash (one argsort) instead
    of the exact multi-key lexsort and returns a 4th value: a traced collision
    flag. When it is True two distinct keys shared a hash and the result may
    have split groups — the caller must re-run with grouping="sort".

    ``grouping="onehot"`` is the sort-free low-cardinality fast path (the
    scatter/one-hot segment-reduce the reference gets from cuDF's hash
    groupby, aggregate.scala:728): distinct key hashes are extracted with a
    bounded min-extraction loop (<= ONEHOT_CAP groups), group ids come from a
    searchsorted against that tiny table, and every reduction is a masked
    one-hot reduce — no sort, no scatter, ~20x the sort path on TPU for
    TPC-H Q1. Returns the same 4-tuple as "hash"; the collision flag also
    covers group-count overflow and is EXACT (per-group min/max equality of
    injective key words), so callers fall back to "hash"/"sort" on True.
    Requires keys and no string min/max buffers (see onehot_supported).

    ``extra_mask`` excludes rows (a fused upstream filter predicate): a masked
    row participates in no group, exactly as if it had been compacted away.
    """
    if grouping == "onehot" and not key_exprs:
        grouping = "hash"  # no-key aggregate: one group, nothing to one-hot

    alive = bk.alive_mask(xp, capacity, num_rows)
    if extra_mask is not None:
        alive = xp.logical_and(alive, extra_mask)

    # scalar keys/buffers (literals, e.g. after project inlining) broadcast to
    # full columns so the grouping kernels can index them
    keys = [bk.as_column(xp, e.eval(ctx), capacity) for e in key_exprs]
    # padding rows must not merge with null-key groups: mask handled via `alive`
    projections: List[List[ColV]] = []
    for fn in agg_fns:
        bufs = [bk.as_column(xp, b, capacity) for b in fn.project(ctx)]
        # padding rows never contribute
        projections.append([b.with_validity(xp.logical_and(b.validity, alive))
                            for b in bufs])

    if keys and grouping == "onehot":
        return _onehot_aggregate(xp, keys, projections, agg_fns, alive,
                                 capacity, evaluate)

    collision = xp.asarray(False)
    out_cap = capacity
    if keys:
        # ONE variadic sort carries every key and aggregation buffer with the
        # sort keys — no argsort + per-column gathers (a TPU gather costs
        # ~2x the sort itself; see bk.multi_sort)
        flat_projs = [b for bufs in projections for b in bufs]
        if grouping == "hash":
            h = bk.hash64_cols(xp, keys)
            hs = h >> np.uint64(1)
            # dead rows sort last: max uint64, unreachable by h >> 1
            passes = [xp.where(alive, hs,
                               np.uint64(0xFFFFFFFFFFFFFFFF))]
            extras = [alive, hs]
        else:
            passes = [xp.logical_not(alive).astype(np.int8)]
            for k in keys:
                passes.extend(bk._key_passes(xp, k, True, True))
            extras = [alive]
        sorted_all, sorted_extras = bk.sort_colvs(
            xp, passes, list(keys) + flat_projs, extras)
        sorted_keys = sorted_all[:len(keys)]
        sorted_alive = sorted_extras[0]
        starts = bk.starts_from_sorted(xp, sorted_keys, sorted_alive)
        if grouping == "hash":
            collision = bk.detect_hash_collision_sorted(
                xp, sorted_extras[1], starts, sorted_alive)
        gids = xp.cumsum(starts.astype(np.int32)) - 1
        gids = xp.clip(gids, 0, capacity - 1)
        num_groups = xp.sum(starts).astype(np.int32)
        sorted_projs = []
        i = len(keys)
        for bufs in projections:
            sorted_projs.append(sorted_all[i:i + len(bufs)])
            i += len(bufs)
    else:
        gids = xp.zeros(capacity, dtype=np.int32)
        num_groups = xp.asarray(np.int32(1))
        sorted_alive = alive
        sorted_keys = []
        sorted_projs = projections
        starts = None

    if keys and grouping == "hash":
        # bounded group space: the reduction emits GROUP_CAP-sized outputs;
        # more groups than that re-runs through the exact sort path (flagged
        # exactly like a hash collision)
        out_cap = min(capacity, GROUP_CAP)
        collision = xp.logical_or(collision, num_groups > out_cap)
    key_cols, reduced_per_fn = _reduce_phase(
        xp, sorted_keys, list(zip(agg_fns, sorted_projs)), gids, capacity,
        sorted_alive, starts, out_cap)

    group_alive = xp.arange(out_cap, dtype=np.int32) < num_groups
    result_cols = []
    for fn, reduced in zip(agg_fns, reduced_per_fn):
        if evaluate:
            out = fn.evaluate(xp, reduced)
            result_cols.append(out.with_validity(
                xp.logical_and(out.validity, group_alive)))
        else:
            result_cols.extend(
                b.with_validity(xp.logical_and(b.validity, group_alive))
                for b in reduced)

    key_cols = [k.with_validity(xp.logical_and(k.validity, group_alive))
                for k in key_cols]
    if grouping == "hash":
        return key_cols, result_cols, num_groups, collision
    return key_cols, result_cols, num_groups


#: static group-space bound of the hash-ordered mode's output; queries producing
#: more groups re-run through the exact sort path
GROUP_CAP = 65536

#: static group-space bound of the one-hot fast path; more groups than this
#: flips the collision/overflow flag and the caller re-runs with "hash"
ONEHOT_CAP = 64

_U64MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


def onehot_supported(agg_fns: Sequence[AggregateFunction]) -> bool:
    """The one-hot path covers every reduction except string min/max (those
    need the rank sort the path exists to avoid)."""
    for fn in agg_fns:
        for spec in fn.buffer_specs():
            if spec.dtype is DType.STRING and spec.kind in ("min", "max"):
                return False
    return True


def onehot_keys_supported(keys) -> bool:
    """validity_word packs one bit per key column into a u64; beyond that the
    exact null-vs-zero-encoding check would lose coverage."""
    return 0 < len(keys) <= 64


def grouping_modes(keys, agg_fns: Sequence[AggregateFunction]) -> List[str]:
    """Escalation order for an aggregate exec: each mode re-runs only on the
    previous one's flagged collision/overflow. The single policy for both the
    single-device and the mesh aggregate."""
    modes = []
    if onehot_keys_supported(keys) and onehot_supported(agg_fns):
        modes.append("onehot")
    return modes + ["hash", "sort"]


def _onehot_aggregate(xp, keys, projections, agg_fns, alive, capacity: int,
                      evaluate: bool):
    """Sort-free grouped aggregation over <= ONEHOT_CAP groups.

    hash -> bounded distinct extraction -> searchsorted gid -> masked one-hot
    reductions. All group-id plumbing is 32/64-bit elementwise + [n, G]
    reduces, which XLA fuses into a handful of HBM passes; there is no sort
    and no scatter anywhere. Collision exactness: per group, every injective
    key word (bk.key_words) must be constant — checked with masked min/max
    reduces — so a collided or overflowed run is ALWAYS flagged.
    """
    G = ONEHOT_CAP
    h = bk.hash64_cols(xp, keys)
    # reserve the all-ones value for dead rows (a real hash there would make
    # its group indistinguishable from padding; the clamp maps it onto
    # MAX-1, and if that collides with a genuine MAX-1 group the exact word
    # check below flags it)
    h = xp.minimum(h, _U64MAX - np.uint64(1))
    hm = xp.where(alive, h, _U64MAX)

    if xp is np:
        cand = np.unique(hm)
        overflow = np.asarray(cand[cand != _U64MAX].shape[0] > G)
        cand = np.concatenate([cand[:G],
                               np.full(max(0, G - cand.shape[0]), _U64MAX,
                                       dtype=np.uint64)])
    else:
        import jax

        def body(i, st):
            cand, prev, first = st
            nxt = xp.min(xp.where(xp.logical_or(first, hm > prev), hm,
                                  _U64MAX))
            return cand.at[i].set(nxt), nxt, xp.zeros((), bool)

        cand0 = xp.full((G,), _U64MAX)
        cand, _, _ = jax.lax.fori_loop(
            0, G, body, (cand0, np.uint64(0), xp.ones((), bool)))
        # dead rows carry hm == MAX and are NOT an overflow; a real hash can
        # never be MAX (clamped above)
        overflow = xp.logical_and(
            cand[G - 1] != _U64MAX,
            xp.any(xp.logical_and(hm > cand[G - 1], hm != _U64MAX)))
    num_groups = xp.sum(cand != _U64MAX).astype(np.int32)

    gid = xp.clip(xp.searchsorted(cand, hm), 0, G - 1).astype(np.int32)
    E = xp.logical_and(gid[:, None] == xp.arange(G, dtype=np.int32)[None, :],
                       alive[:, None])
    idx = xp.arange(capacity, dtype=np.int64)

    def masked_min(contrib, neutral):
        return xp.min(xp.where(E, contrib[:, None], neutral), axis=0)

    def masked_max(contrib, neutral):
        return xp.max(xp.where(E, contrib[:, None], neutral), axis=0)

    def masked_sum(contrib):
        return xp.sum(xp.where(E, contrib[:, None], 0), axis=0)

    # exact collision detection over injective key words + packed validity
    words = [bk.validity_word(xp, keys)]
    for v in keys:
        words.extend(bk.key_words(xp, v))
    collision = overflow
    for w in words:
        wmin = masked_min(w, _U64MAX)
        wmax = masked_max(w, np.uint64(0))
        bad = xp.logical_and(wmin != _U64MAX, wmin != wmax)
        collision = xp.logical_or(collision, xp.any(bad))

    # representative row per group -> key output (G tiny gathers)
    rep = masked_min(idx, np.int64(capacity))
    has = rep < capacity
    repc = xp.clip(rep, 0, capacity - 1)
    group_alive = xp.arange(G, dtype=np.int32) < num_groups
    key_cols = []
    for v in keys:
        kv = bk.take_colv(xp, v, repc)
        key_cols.append(kv.with_validity(
            xp.logical_and(kv.validity, xp.logical_and(has, group_alive))))

    result_cols = []
    for fn, bufs in zip(agg_fns, projections):
        reduced = []
        for spec, b in zip(fn.buffer_specs(), bufs):
            reduced.append(_onehot_reduce_buffer(
                xp, spec, b, E, idx, capacity, masked_min, masked_max,
                masked_sum))
        if evaluate:
            out = fn.evaluate(xp, reduced)
            result_cols.append(out.with_validity(
                xp.logical_and(out.validity, group_alive)))
        else:
            result_cols.extend(
                r.with_validity(xp.logical_and(r.validity, group_alive))
                for r in reduced)
    return key_cols, result_cols, num_groups, collision


def _onehot_reduce_buffer(xp, spec, b: ColV, E, idx, capacity: int,
                          masked_min, masked_max, masked_sum):
    """One buffer's one-hot reduction (sum/min/max/first/last with Spark
    null + NaN semantics, mirroring _register_minmax / segment_pick)."""
    Ev = xp.logical_and(E, b.validity[:, None])
    seg_valid = xp.any(Ev, axis=0)
    if spec.kind == "sum":
        contrib = xp.where(b.validity, b.data, 0).astype(b.data.dtype)
        return ColV(b.dtype, masked_sum(contrib), seg_valid)
    if spec.kind in ("first", "last"):
        candidate = Ev if spec.ignore_nulls else E
        if spec.kind == "first":
            key = xp.min(xp.where(candidate, idx[:, None],
                                  np.int64(capacity)), axis=0)
            pick_has = key < capacity
        else:
            key = xp.max(xp.where(candidate, idx[:, None], np.int64(-1)),
                         axis=0)
            pick_has = key >= 0
        pick = xp.clip(key, 0, capacity - 1)
        out = bk.take_colv(xp, b, pick)
        return out.with_validity(xp.logical_and(pick_has, out.validity))
    # numeric/bool min-max
    npdt = np.dtype(b.data.dtype)
    if npdt == np.bool_:
        d = b.data.astype(np.int8)
        neutral = np.int8(1 if spec.kind == "min" else 0)
        m = masked_min if spec.kind == "min" else masked_max
        return ColV(b.dtype,
                    m(xp.where(b.validity, d, neutral),
                      neutral).astype(np.bool_), seg_valid)
    if np.issubdtype(npdt, np.floating):
        neutral = np.asarray(np.inf if spec.kind == "min" else -np.inf,
                             dtype=npdt)
        nan = xp.isnan(b.data)
        d = xp.where(nan, xp.asarray(np.inf, dtype=npdt), b.data)
        m = masked_min if spec.kind == "min" else masked_max
        res = m(xp.where(b.validity, d, neutral), neutral)
        saw_nan = xp.any(xp.logical_and(Ev, nan[:, None]), axis=0)
        all_nan = xp.logical_not(
            xp.any(xp.logical_and(Ev, xp.logical_not(nan)[:, None]), axis=0))
        if spec.kind == "max":
            res = xp.where(saw_nan, xp.asarray(np.nan, dtype=npdt), res)
        else:
            res = xp.where(xp.logical_and(seg_valid, all_nan),
                           xp.asarray(np.nan, dtype=npdt), res)
        return ColV(b.dtype, res, seg_valid)
    neutral = (np.iinfo(npdt).max if spec.kind == "min"
               else np.iinfo(npdt).min)
    m = masked_min if spec.kind == "min" else masked_max
    return ColV(b.dtype, m(xp.where(b.validity, b.data, neutral), neutral),
                seg_valid)


def _reduce_phase(xp, sorted_keys, fn_bufs, gids, capacity: int, sorted_alive,
                  starts, out_cap: int):
    """Representative-key pick + per-fn buffer reduction over rows sorted by
    group, for the first ``out_cap`` groups. Returns (key columns, reduced
    buffers per fn).

    numpy path: eager per-buffer segment ops, the definition of the
    semantics. Device path: every buffer's contributions register with ONE
    bk.SortedSegmentStacker, which reduces them by segmented scans and moves
    each group's first row, key and reductions, to the front by one
    compaction sort: no scatter and no gather of the batch's capacity, and a
    float sum adds a group's own rows only (a group that cancels to exactly
    0.0 keeps `HAVING sum(x) > 0` false whatever its neighbours hold)."""
    if xp is np:
        gids = np.minimum(gids, out_cap - 1)
        pick, has = bk.segment_pick(xp, xp.ones_like(sorted_alive), gids,
                                    out_cap, "first", alive=sorted_alive)
        key_cols = [_gather_key(xp, k, pick, has) for k in sorted_keys]
        reduced = [_reduce_buffers(xp, fn, bufs, gids, out_cap, sorted_alive)
                   for fn, bufs in fn_bufs]
        return key_cols, reduced

    stacker = bk.SortedSegmentStacker(xp, gids, out_cap)
    thunk_lists = [_register_reduce(xp, fn, bufs, capacity, sorted_alive,
                                    stacker)
                   for fn, bufs in fn_bufs]
    key_cols = stacker.run(sorted_keys, starts, sorted_alive)
    reduced = [[t() for t in thunks] for thunks in thunk_lists]
    return key_cols, reduced


def reduce_form(mode: str, capacity: int) -> str:
    """What an ``agg.attempt`` span says of how a grouping mode reduces at
    this capacity: ``"onehot"`` (no sorted segments), else the stacker's
    ``"scan"`` or ``"plain"``."""
    return "onehot" if mode == "onehot" else bk.segment_reduce_form(capacity)


def _gather_key(xp, k: ColV, pick, has) -> ColV:
    valid = xp.logical_and(has, k.validity[pick])
    if k.dtype is DType.STRING:
        return ColV(k.dtype, k.data[pick], valid, k.lengths[pick])
    return ColV(k.dtype, k.data[pick], valid)


def _string_rank(xp, b: ColV, kind: str, sorted_alive):
    """Shared preamble of string min/max: rank rows by byte order (the sort is
    unavoidable — strings don't reduce), sentinel-mask non-participants.
    Returns (order, masked_rank, n)."""
    participating = xp.logical_and(sorted_alive, b.validity)
    order = bk.sort_indices(xp, [(b, True, True)], participating)
    # inverse permutation = rank of each row in sorted order
    rank = bk._stable_argsort(xp, order).astype(np.int64)
    n = rank.shape[0]
    sentinel = np.int64(n + 1) if kind == "min" else np.int64(-1)
    return order, xp.where(participating, rank, sentinel), n


def _string_pick(xp, b: ColV, order, seg, n: int) -> ColV:
    """Shared tail of string min/max: reduced per-segment rank -> row pick.
    Both sentinels (n+1 for min, -1 for max) fail the bounds check."""
    has = xp.logical_and(seg >= 0, seg <= n)
    pick = order[xp.clip(seg, 0, n - 1)]
    valid = xp.logical_and(has, b.validity[pick])
    return ColV(b.dtype, b.data[pick], valid, b.lengths[pick])


def _segment_minmax_string(xp, b: ColV, gids, capacity: int, kind: str,
                           sorted_alive) -> ColV:
    """min/max over strings, eager reduction (cuDF's string minmax analog,
    built from the existing sort + segment machinery)."""
    order, masked, n = _string_rank(xp, b, kind, sorted_alive)
    seg = bk.segment_reduce(xp, masked, xp.ones(n, dtype=bool), gids,
                            capacity, kind)[0]
    return _string_pick(xp, b, order, seg, n)


def _reduce_buffers(xp, fn: AggregateFunction, bufs: Sequence[ColV], gids,
                    capacity: int, sorted_alive) -> List[ColV]:
    reduced: List[ColV] = []
    for spec, b in zip(fn.buffer_specs(), bufs):
        if b.dtype is DType.STRING and spec.kind in ("min", "max"):
            reduced.append(_segment_minmax_string(xp, b, gids, capacity,
                                                  spec.kind, sorted_alive))
        elif spec.kind in ("first", "last"):
            p2, h2 = bk.segment_pick(xp, b.validity, gids, capacity,
                                     spec.kind, alive=sorted_alive,
                                     ignore_nulls=spec.ignore_nulls)
            valid = xp.logical_and(h2, b.validity[p2])
            if b.dtype is DType.STRING:
                reduced.append(ColV(b.dtype, b.data[p2], valid, b.lengths[p2]))
            else:
                reduced.append(ColV(b.dtype, b.data[p2], valid))
        else:
            data, valid = bk.segment_reduce(xp, b.data, b.validity, gids,
                                            capacity, spec.kind)
            reduced.append(ColV(b.dtype, data, valid))
    return reduced


def _register_reduce(xp, fn: AggregateFunction, bufs: Sequence[ColV],
                     capacity: int, sorted_alive,
                     stacker: "bk.SortedSegmentStacker"):
    """Device-path reduction, phase 1: register every segment contribution
    with the stacker; returns thunks producing the reduced ColVs after
    stacker.run(). One stacked reduction per (kind, dtype) replaces the
    per-buffer segment calls of _reduce_buffers."""
    idx = xp.arange(capacity, dtype=np.int64)
    thunks = []
    for spec, b in zip(fn.buffer_specs(), bufs):
        if b.dtype is DType.STRING and spec.kind in ("min", "max"):
            thunks.append(_register_minmax_string(xp, b, spec.kind, stacker,
                                                  sorted_alive))
        elif spec.kind in ("first", "last"):
            candidate = (xp.logical_and(sorted_alive, b.validity)
                         if spec.ignore_nulls else sorted_alive)
            thunks.append(_register_pick(xp, b, spec.kind, stacker, idx,
                                         capacity, candidate))
        elif spec.kind == "sum":
            contrib = xp.where(b.validity, b.data, 0).astype(b.data.dtype)
            h = stacker.add("sum", contrib)
            hc = stacker.add("sum", b.validity.astype(np.int32))
            thunks.append(lambda b=b, h=h, hc=hc: ColV(
                b.dtype, stacker.get(h), stacker.get(hc) > 0))
        else:  # numeric min/max
            thunks.append(_register_minmax(xp, b, spec.kind, stacker))
    return thunks


def _register_pick(xp, b: ColV, kind: str,
                   stacker: "bk.SortedSegmentStacker", idx, capacity: int,
                   candidate):
    """first/last pick through the stacker: masked row-index min/max, then a
    gather of one row a group."""
    if kind == "first":
        h = stacker.add("min", xp.where(candidate, idx,
                                        np.int64(capacity + 1)))
    else:
        h = stacker.add("max", xp.where(candidate, idx, np.int64(-1)))

    def thunk(b=b, h=h):
        key = stacker.get(h)
        has = xp.logical_and(key >= 0, key < capacity)
        p2 = xp.clip(key, 0, capacity - 1)
        valid = xp.logical_and(has, b.validity[p2])
        return bk.take_colv(xp, b, p2).with_validity(valid)
    return thunk


def _register_minmax_string(xp, b: ColV, kind: str,
                            stacker: "bk.SortedSegmentStacker", sorted_alive):
    """String min/max through the stacker: the per-segment lowest/highest-
    ranked pick rides the stacked int reduction."""
    order, masked, n = _string_rank(xp, b, kind, sorted_alive)
    h = stacker.add(kind, masked)
    return lambda: _string_pick(xp, b, order, stacker.get(h), n)


def _register_minmax(xp, b: ColV, kind: str,
                     stacker: "bk.SortedSegmentStacker"):
    """Stacked numeric/bool min-max with Spark NaN ordering (mirrors
    bk._segment_minmax_jax semantics)."""
    hc = stacker.add("sum", b.validity.astype(np.int32))
    npdt = np.dtype(b.data.dtype)
    if npdt == np.bool_:
        d = b.data.astype(np.int8)
        neutral = np.int8(1 if kind == "min" else 0)
        h = stacker.add(kind, xp.where(b.validity, d, neutral))
        return lambda: ColV(b.dtype, stacker.get(h).astype(np.bool_),
                            stacker.get(hc) > 0)
    if np.issubdtype(npdt, np.floating):
        neutral = np.asarray(np.inf if kind == "min" else -np.inf, dtype=npdt)
        nan = xp.isnan(b.data)
        d = xp.where(nan, xp.asarray(np.inf, dtype=npdt), b.data)
        h = stacker.add(kind, xp.where(b.validity, d, neutral))
        hn = stacker.add("sum",
                         xp.logical_and(nan, b.validity).astype(np.int32))

        def thunk():
            res = stacker.get(h)
            nan_count = stacker.get(hn)
            valid_count = stacker.get(hc)
            if kind == "max":
                res = xp.where(nan_count > 0,
                               xp.asarray(np.nan, dtype=npdt), res)
            else:
                res = xp.where(xp.logical_and(valid_count > 0,
                                              nan_count == valid_count),
                               xp.asarray(np.nan, dtype=npdt), res)
            return ColV(b.dtype, res, valid_count > 0)
        return thunk
    neutral = (np.iinfo(npdt).max if kind == "min" else np.iinfo(npdt).min)
    h = stacker.add(kind, xp.where(b.validity, b.data, neutral))
    return lambda: ColV(b.dtype, stacker.get(h), stacker.get(hc) > 0)


def merge_aggregate(xp, key_cols: Sequence[ColV], buffer_cols: Sequence[ColV],
                    agg_fns: Sequence[AggregateFunction], num_rows, capacity: int):
    """Final mode: merge partially-aggregated buffers (after an exchange or
    all-gather) — group by keys again, combine each buffer with its own
    reduction kind (sum-of-sums, min-of-mins, first-of-firsts...), then run each
    aggregate's evaluate() (aggregate.scala Final/PartialMerge analog).

    buffer_cols: the flattened partial buffers as produced by
    group_aggregate(evaluate=False). Returns (key_cols, result_cols, num_groups).
    Always uses the exact sort ordering: inputs here are already-reduced
    partials (tiny), so the hash fast path has nothing to win.
    """
    alive = bk.alive_mask(xp, capacity, num_rows)
    key_cols = [k.with_validity(xp.logical_and(k.validity, alive))
                for k in key_cols]
    buffer_cols = [b.with_validity(xp.logical_and(b.validity, alive))
                   for b in buffer_cols]

    if key_cols:
        passes = [xp.logical_not(alive).astype(np.int8)]
        for k in key_cols:
            passes.extend(bk._key_passes(xp, k, True, True))
        sorted_all, sorted_extras = bk.sort_colvs(
            xp, passes, list(key_cols) + list(buffer_cols), [alive])
        sorted_keys = sorted_all[:len(key_cols)]
        sorted_bufs = sorted_all[len(key_cols):]
        sorted_alive = sorted_extras[0]
        starts = bk.starts_from_sorted(xp, sorted_keys, sorted_alive)
        gids = xp.clip(xp.cumsum(starts.astype(np.int32)) - 1, 0, capacity - 1)
        num_groups = xp.sum(starts).astype(np.int32)
    else:
        gids = xp.zeros(capacity, dtype=np.int32)
        num_groups = xp.asarray(np.int32(1))
        sorted_alive = alive
        sorted_keys = []
        sorted_bufs = list(buffer_cols)
        starts = None

    fn_bufs = []
    i = 0
    for fn in agg_fns:
        specs = fn.buffer_specs()
        fn_bufs.append((fn, sorted_bufs[i:i + len(specs)]))
        i += len(specs)
    out_keys, reduced_per_fn = _reduce_phase(
        xp, sorted_keys, fn_bufs, gids, capacity, sorted_alive, starts,
        capacity)

    group_alive = xp.arange(capacity, dtype=np.int32) < num_groups
    result_cols = []
    for fn, reduced in zip(agg_fns, reduced_per_fn):
        out = fn.evaluate(xp, reduced)
        result_cols.append(out.with_validity(
            xp.logical_and(out.validity, group_alive)))

    out_keys = [k.with_validity(xp.logical_and(k.validity, group_alive))
                for k in out_keys]
    return out_keys, result_cols, num_groups
