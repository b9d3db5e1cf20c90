"""Join physical operators.

Reference analogs: GpuShuffledHashJoinExec / GpuBroadcastHashJoinExec /
GpuSortMergeJoinExec->SHJ replacement (shims/spark300/GpuHashJoin.scala,
GpuShuffledHashJoinExec.scala, GpuBroadcastHashJoinExec.scala) and
GpuCartesianProductExec / GpuBroadcastNestedLoopJoinExec for the non-equi forms.

Both engines share ops/join.py's two-phase kernel; the TPU side jits each phase
per shape bucket. The build side is coalesced to a single batch exactly like the
reference's RequireSingleBatch build-side goal. A residual non-equi condition is
applied as a post-join filter (same as GpuHashJoin's joined-then-filtered flow).
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import DType, Field, Schema, bucket_capacity
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.execs.cpu_execs import (_colvs_to_host, _host_colvs,
                                              concat_host_batches)
from spark_rapids_tpu.execs.tpu_execs import (_cached_jit, _flatten,
                                              _flatten_colvs, _to_batch,
                                              _unflatten_colvs,
                                              concat_device_batches)
from spark_rapids_tpu.exprs.core import (ColV, EvalCtx, Expression,
                                         flat_len as _n_flat)
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.ops import join as jk
from spark_rapids_tpu.utils import tracing as _tracing


def legal_broadcast_sides(how: str) -> List[int]:
    """Side indices (1=right first, the cheaper default) that may legally be
    the broadcast build for this join type: an outer/preserved side cannot be
    the build side — its unmatched rows would be emitted once per stream
    partition (Spark's BuildSide legality rules). THE single source for the
    planner, host AQE, and mesh AQE."""
    sides = []
    if how in ("inner", "left", "left_semi", "left_anti", "cross"):
        sides.append(1)
    if how in ("inner", "right", "cross"):
        sides.append(0)
    return sides


def _note_drained(span, batches: Sequence[DeviceBatch], **args) -> None:
    """What a ``join.drain`` span counts: the batches drained, empty ones
    included, and their live rows."""
    span.note(batches=len(batches), rows=sum(b.num_rows for b in batches),
              **args)


def _drain(side: str, batches: Iterator[DeviceBatch]) -> List[DeviceBatch]:
    """One side of a join pulled to its end, in one ``join.drain`` span:
    the child's scans, stages, uploads and joins, which run inside it."""
    with _tracing.span("join.drain", _tracing.LAYER_EXEC,
                       {"side": side}) as span:
        out = list(batches)
        if span is not None:
            _note_drained(span, out)
    return out


def _eval_keys(xp, colvs, capacity, smax, key_exprs) -> List[ColV]:
    ectx = EvalCtx(xp, colvs, capacity, smax)
    return [e.eval(ectx) for e in key_exprs]


class _HashJoinBase(PhysicalExec):
    #: join output size depends on key multiplicity, which no static
    #: estimate captures — None keeps downstream consumers honest
    #: (size_estimate contract, tests/test_out_of_core.py audit)
    size_estimate_none_reason = ("join output multiplicity is unknown "
                                 "without key statistics")

    def __init__(self, left: PhysicalExec, right: PhysicalExec, how: str,
                 left_keys: Tuple[Expression, ...],
                 right_keys: Tuple[Expression, ...], output: Schema,
                 condition: Optional[Expression] = None,
                 build_side: str = "right"):
        super().__init__((left, right), output)
        if how not in jk.JOIN_KINDS:
            raise ValueError(f"unsupported join type {how}")
        if build_side not in ("left", "right"):
            raise ValueError(f"invalid build side {build_side}")
        self.how = how
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.condition = condition
        #: which side is materialized as the build table. For the broadcast
        #: variants the planner wraps this child in a BroadcastExchange; Spark's
        #: BuildSide restrictions apply (an outer side cannot be broadcast).
        self.build_side = build_side

    @property
    def includes_right_columns(self) -> bool:
        return self.how not in ("left_semi", "left_anti")


class CpuHashJoinExec(_HashJoinBase):
    def execute(self, ctx: ExecContext) -> Iterator[HostBatch]:
        lb = concat_host_batches(list(self.children[0].execute(ctx)),
                                 self.children[0].output)
        rb = concat_host_batches(list(self.children[1].execute(ctx)),
                                 self.children[1].output)
        l_cols = _host_colvs(lb)
        r_cols = _host_colvs(rb)
        S, B = max(lb.num_rows, 1), max(rb.num_rows, 1)
        l_cols = [_pad_np(v, S) for v in l_cols]
        r_cols = [_pad_np(v, B) for v in r_cols]
        l_alive = np.arange(S) < lb.num_rows
        r_alive = np.arange(B) < rb.num_rows
        with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
            lk = _eval_keys(np, l_cols, S, ctx.string_max_bytes, self.left_keys)
            rk = _eval_keys(np, r_cols, B, ctx.string_max_bytes, self.right_keys)
            sized = jk.join_size(np, lk, rk, l_alive, r_alive, self.how)
            total = int(sized["total"])
            out_cap = max(total, 1)
            lrow, lvalid, rrow, rvalid, _ = jk.join_gather(
                np, sized, S, B, out_cap, self.how)
            r_out = r_cols if self.includes_right_columns else []
            out_cols = jk.gather_join_output(np, l_cols, r_out, lrow, lvalid,
                                             rrow, rvalid)
            n = total
            if self.condition is not None:
                ectx = EvalCtx(np, out_cols, out_cap, ctx.string_max_bytes)
                pred = self.condition.eval(ectx)
                keep = np.logical_and(
                    np.logical_and(np.asarray(pred.data, dtype=bool),
                                   np.asarray(pred.validity)),
                    np.arange(out_cap) < total)
                out_cols, nn = bk.compact(np, keep, out_cols, total)
                n = int(nn)
        out = _colvs_to_host(self.output, out_cols, n)
        self.count_output(n)
        yield out


def _pad_np(v: ColV, cap: int) -> ColV:
    n = v.data.shape[0]
    if n == cap:
        return v
    pad = cap - n
    data = np.concatenate([v.data, np.zeros((pad,) + v.data.shape[1:],
                                            v.data.dtype)])
    validity = np.concatenate([v.validity, np.zeros(pad, bool)])
    lengths = (np.concatenate([v.lengths, np.zeros(pad, np.int32)])
               if v.lengths is not None else None)
    return ColV(v.dtype, data, validity, lengths)


class TpuShuffledHashJoinExec(_HashJoinBase):
    """Equi-join on device; both phases jitted per shape bucket."""

    is_device = True

    #: both sides resident + the gather output while the join runs. On the
    #: DEVICE class only: the footprint contract measures HBM, and a CPU
    #: fallback join never reads a grace hint (plan/footprint.py)
    working_set_factor = 3.0

    def working_set_estimate(self):
        sizes = [c.size_estimate() for c in self.children]
        if any(s is None for s in sizes):
            return None
        return int(sum(sizes) * self.working_set_factor)

    #: set by plan/encoded.mark_encoded_domain: equi-join key pairs whose
    #: both sides kept their dictionary encoding match on int32 indices —
    #: directly when the sides share a dictionary stream, via a k_l x k_r
    #: device remap otherwise (exprs/encoded.dict_remap)
    encoded_domain_ok = False

    #: different-dictionary remaps above this k_l * k_r stay decoded (the
    #: equality matrix would no longer be trivially small)
    _REMAP_CELLS_CAP = 1 << 22

    def _encoded_key_pairs(self, ctx: ExecContext, lb: DeviceBatch,
                           rb: DeviceBatch):
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.exprs import encoded as ed
        from spark_rapids_tpu.exprs.core import BoundReference
        if not (self.encoded_domain_ok
                and ctx.conf.get(cfg.ENCODED_DOMAIN)):
            return ()
        lspecs = {s.ordinal: s for s in cenc.enc_specs_of(lb)}
        rspecs = {s.ordinal: s for s in cenc.enc_specs_of(rb)}
        pairs = []
        for pos, (lk, rk) in enumerate(zip(self.left_keys,
                                           self.right_keys)):
            if not (isinstance(lk, BoundReference)
                    and isinstance(rk, BoundReference)):
                continue
            ls, rs = lspecs.get(lk.ordinal), rspecs.get(rk.ordinal)
            if ls is None or rs is None or ls.dtype != rs.dtype:
                continue
            if ls.dtype.is_floating:
                continue      # float equality semantics stay on decoded data
            le = lb.columns[lk.ordinal].encoding
            re_ = rb.columns[rk.ordinal].encoding
            same = le.token is not None and le.token == re_.token
            if not same and ls.k * rs.k > self._REMAP_CELLS_CAP:
                continue
            pairs.append(ed.EncJoinKey(pos, ls, rs, same))
        return tuple(pairs)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.memory import grace
        left = self.children[0].execute(ctx)
        right = self.children[1].execute(ctx)
        ooc = (grace.controller_for(self, ctx, "join",
                                    self.left_keys + self.right_keys)
               if self.left_keys else None)
        if ooc is None:
            yield from self._single_pass(ctx, _drain("left", left),
                                         _drain("right", right))
            return
        with _tracing.span("join.drain", _tracing.LAYER_EXEC,
                           {"side": "both"}) as span:
            mode, payload = ooc.stage_two(left, right, self.left_keys,
                                          self.right_keys)
            if span is not None:
                span.note(mode=mode)
                if mode == "inline":
                    lbatches, rbatches = payload
                    _note_drained(span, lbatches + rbatches,
                                  left_batches=len(lbatches),
                                  right_batches=len(rbatches))
        if mode == "inline":
            yield from self._single_pass(ctx, payload[0], payload[1])
            return
        yield from self._grace_execute(ctx, ooc, payload[0], payload[1])

    def _grace_execute(self, ctx: ExecContext, ooc, lparts,
                       rparts) -> Iterator[DeviceBatch]:
        """Grace hash join: both sides partitioned by the SAME depth-salted
        hash of their join keys, so every key's rows (and null-key outer
        rows — nulls hash to one constant) meet inside exactly one
        partition pair; per-pair single-pass joins union to the global
        result. A pair still over budget re-partitions both sides with a
        deeper salt, unless the split proved degenerate (one indivisible
        key group on both sides — deeper salts cannot separate it)."""
        try:
            degenerate = lparts.degenerate and rparts.degenerate
            for pid in range(lparts.n):
                ctx.check_cancelled()
                nbytes = lparts.bytes_of(pid) + rparts.bytes_of(pid)
                if nbytes == 0:
                    continue
                if not degenerate and ooc.should_recurse(nbytes,
                                                         lparts.depth):
                    # drain() feeds each side's re-split one piece at a
                    # time — the over-budget pair is never whole on device
                    lsub = ooc.partition(lparts.drain(pid), self.left_keys,
                                         depth=lparts.depth + 1)
                    rsub = ooc.partition(rparts.drain(pid), self.right_keys,
                                         depth=rparts.depth + 1)
                    yield from self._grace_execute(ctx, ooc, lsub, rsub)
                else:
                    lbatches = lparts.take(pid)
                    rbatches = rparts.take(pid)
                    if lbatches or rbatches:
                        yield from self._single_pass(ctx, lbatches,
                                                     rbatches)
        finally:
            lparts.close()
            rparts.close()

    def _single_pass(self, ctx: ExecContext, lbatches,
                     rbatches) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.exprs import encoded as ed
        from spark_rapids_tpu.utils import metrics as mt
        smax = ctx.string_max_bytes
        lschema = self.children[0].output
        rschema = self.children[1].output
        lb = concat_device_batches(lbatches, lschema, smax)
        rb = concat_device_batches(rbatches, rschema, smax)
        S, B = lb.capacity, rb.capacity

        enc_pairs = self._encoded_key_pairs(ctx, lb, rb)
        l_used = tuple(p.left for p in enc_pairs)
        r_used = tuple(p.right for p in enc_pairs)

        key1 = ("join_size", self.how, self.left_keys, self.right_keys,
                enc_pairs, lschema, rschema, S, B, smax)

        def build1(how=self.how, lkeys=self.left_keys, rkeys=self.right_keys,
                   lschema=lschema, rschema=rschema, S=S, B=B, smax=smax,
                   enc_pairs=enc_pairs, l_used=l_used, r_used=r_used):
            nl = _n_flat(lschema)
            nr = _n_flat(rschema)

            def fn(l_rows, r_rows, *flat):
                l_cols = _unflatten_colvs(lschema, flat[:nl])
                r_cols = _unflatten_colvs(rschema, flat[nl:nl + nr])
                l_alive = jnp.arange(S, dtype=np.int32) < l_rows
                r_alive = jnp.arange(B, dtype=np.int32) < r_rows
                lk = _eval_keys(jnp, l_cols, S, smax, lkeys)
                rk = _eval_keys(jnp, r_cols, B, smax, rkeys)
                if enc_pairs:
                    rest = list(flat[nl + nr:])
                    nle = sum(4 if s.is_string else 3 for s in l_used)
                    l_enc = cenc.unflatten_encodings(jnp, l_used,
                                                     rest[:nle])
                    r_enc = cenc.unflatten_encodings(jnp, r_used,
                                                     rest[nle:])
                    for p in enc_pairs:
                        lv = l_enc[p.left.ordinal]
                        rv = r_enc[p.right.ordinal]
                        l_validity = lk[p.pos].validity
                        r_validity = rk[p.pos].validity
                        if p.same_token:
                            r_idx = rv.indices
                        else:
                            remap = ed.dict_remap(jnp, lv.values, rv.values,
                                                  p.left.k, lv.k_real,
                                                  rv.k_real)
                            r_idx = jnp.take(remap, rv.indices, axis=0)
                        from spark_rapids_tpu.columnar.dtypes import DType
                        from spark_rapids_tpu.exprs.core import ColV
                        lk[p.pos] = ColV(DType.INT, lv.indices, l_validity)
                        rk[p.pos] = ColV(DType.INT, r_idx, r_validity)
                sized = jk.join_size(jnp, lk, rk, l_alive, r_alive, how)
                return (sized["emit_counts"], sized["emit_offsets"],
                        sized["total"], sized["border"], sized["start_b"],
                        sized["matches_l"])
            return fn

        fn1 = _cached_jit(key1, build1)
        flat_in = _flatten(lb) + _flatten(rb)
        enc_flat = (list(cenc.flatten_encodings(lb, l_used))
                    + list(cenc.flatten_encodings(rb, r_used)))
        if enc_pairs:
            mt.TRANSFER_METRICS[mt.TRANSFER_ENCODED_DOMAIN_OPS].add(1)
        (emit_counts, emit_offsets, total, border, start_b,
         matches_l) = fn1(np.int32(lb.num_rows), np.int32(rb.num_rows),
                          *flat_in, *enc_flat)
        n_out = int(total)
        out_cap = bucket_capacity(n_out)

        key2 = ("join_gather", self.how, lschema, rschema, S, B, out_cap,
                self.condition, self.includes_right_columns, smax)

        def build2(how=self.how, lschema=lschema, rschema=rschema, S=S, B=B,
                   out_cap=out_cap, cond=self.condition,
                   inc_right=self.includes_right_columns, smax=smax):
            nl = _n_flat(lschema)

            def fn(emit_counts, emit_offsets, total, border, start_b,
                   matches_l, *flat):
                l_cols = _unflatten_colvs(lschema, flat[:nl])
                r_cols = _unflatten_colvs(rschema, flat[nl:])
                sized = dict(emit_counts=emit_counts,
                             emit_offsets=emit_offsets, total=total,
                             border=border, start_b=start_b,
                             matches_l=matches_l)
                lrow, lvalid, rrow, rvalid, _ = jk.join_gather(
                    jnp, sized, S, B, out_cap, how)
                r_out = r_cols if inc_right else []
                out_cols = jk.gather_join_output(jnp, l_cols, r_out, lrow,
                                                 lvalid, rrow, rvalid)
                n = total
                if cond is not None:
                    ectx = EvalCtx(jnp, out_cols, out_cap, smax)
                    pred = cond.eval(ectx)
                    keep = jnp.logical_and(
                        jnp.logical_and(pred.data, pred.validity),
                        jnp.arange(out_cap, dtype=np.int64) < total)
                    out_cols, n = bk.compact(jnp, keep, out_cols, total)
                return tuple(_flatten_colvs(out_cols)) + (n,)
            return fn

        fn2 = _cached_jit(key2, build2)
        res = fn2(emit_counts, emit_offsets, total, border, start_b,
                  matches_l, *flat_in)
        n = int(res[-1])
        out = _to_batch(self.output, res[:-1], n)
        self.count_output(n)
        yield out



class CpuSortMergeJoinExec(CpuHashJoinExec):
    """Spark's SortMergeJoinExec shape (sorted children required by
    EnsureRequirements). Never produced by this repo's frontend — it enters
    through imported Catalyst plans (plan/catalyst_import.py). Executes as
    a hash join (identical equi-join results); the overrides engine
    replaces it with the TPU shuffled-hash join and DROPS the join-key
    sorts, the reference's GpuSortMergeJoinExec behavior
    (shims/spark300/GpuSortMergeJoinExec.scala, conf
    spark.rapids.tpu.sql.replaceSortMergeJoin.enabled)."""


class CpuBroadcastHashJoinExec(CpuHashJoinExec):
    """Equi-join whose build child is a BroadcastExchange; the stream side
    keeps its partitioning, so the join runs once per stream partition against
    the one cached build batch (GpuBroadcastHashJoinExec analog,
    shims/spark300/GpuBroadcastHashJoinExec.scala)."""


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Same device kernel as the shuffled join; the build side arrives
    replicated (broadcast) rather than hash-partitioned. In distributed
    execution the build child is all-gathered across the mesh instead of
    exchanged (GpuBroadcastHashJoinExec analog)."""


class _NestedLoopMixin:
    """Brute-force joins evaluate the cross-product kernel, then apply the
    condition as a filter (how == 'inner' with condition c is equivalent to
    cross + filter(c))."""

    def __init__(self, left: PhysicalExec, right: PhysicalExec, how: str,
                 output: Schema, condition: Optional[Expression] = None,
                 build_side: str = "right"):
        if how not in ("inner", "cross"):
            raise ValueError(
                f"nested-loop/cartesian joins support inner/cross, not {how}")
        super().__init__(left, right, "cross", (), (), output, condition,
                         build_side)
        self.join_type = how


class CpuNestedLoopJoinExec(_NestedLoopMixin, CpuHashJoinExec):
    """Broadcast nested-loop join (GpuBroadcastNestedLoopJoinExec analog,
    execution/GpuBroadcastNestedLoopJoinExec.scala, disabled by default per
    GpuOverrides.scala:1688-1691): the build child is a BroadcastExchange, the
    stream side stays partitioned."""


class TpuBroadcastNestedLoopJoinExec(_NestedLoopMixin, TpuShuffledHashJoinExec):
    pass


class CpuCartesianProductExec(_NestedLoopMixin, CpuHashJoinExec):
    """Cartesian product (GpuCartesianProductExec analog, disabled by
    default). Both sides are coalesced to single partitions by
    EnsureRequirements."""


class TpuCartesianProductExec(_NestedLoopMixin, TpuShuffledHashJoinExec):
    pass
