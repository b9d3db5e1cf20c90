"""TPU physical operators.

Reference analogs are the Gpu*Exec operators (basicPhysicalOperators.scala:66
GpuProjectExec, :127 GpuFilterExec, aggregate.scala:227 GpuHashAggregateExec,
GpuSortExec.scala:50, limit.scala, GpuCoalesceBatches.scala) — but instead of one
cuDF JNI call per op, each exec traces its ENTIRE pipeline (expression evaluation,
masking, compaction/sort/segment reduction) into one jitted XLA program per
(operator-config, schema, capacity-bucket) key. Logical row counts cross the jit
boundary as traced scalars and sync to the host once per batch.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import (DType, Field, Schema,
                                              bucket_capacity,
                                              width_scaled_estimate as _width_scaled)
from spark_rapids_tpu.columnar.host import HostBatch, HostColumn
from spark_rapids_tpu.execs.base import ExecContext, LeafExec, PhysicalExec
from spark_rapids_tpu.execs.evaluator import (eval_exprs_device, output_schema)
from spark_rapids_tpu.exprs.core import (ColV, EvalCtx, Expression, flat_len,
                                         flatten_colvs, unflatten_colvs)
from spark_rapids_tpu.exprs.misc import Alias, SortOrder
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.ops.aggregate import group_aggregate

from spark_rapids_tpu.serving.program_cache import (global_program_cache,
                                                    named_jit)
from spark_rapids_tpu.utils import tracing as _tracing

_PROGRAM_CACHE = global_program_cache()
#: legacy alias for the serving cache's program table: tests introspect its
#: keys (recompile guards) and clear it between modules for heap pressure
_JIT_CACHE: Dict[Tuple, "jax.stages.Wrapped"] = _PROGRAM_CACHE._programs


def _flatten(batch: DeviceBatch) -> List:
    flat = []
    for c in batch.columns:
        flat.append(c.data)
        flat.append(c.validity)
        if c.lengths is not None:
            flat.append(c.lengths)
    return flat


_unflatten_colvs = unflatten_colvs
_flatten_colvs = flatten_colvs


def _to_batch(schema: Schema, flat, num_rows: int) -> DeviceBatch:
    """Wrap kernel outputs as a batch, shrinking to the row count's capacity
    bucket when the kernel produced far fewer rows than its input capacity
    (selective filters, aggregates): downstream programs then compile and run
    at the small shape, and downloads move only live buckets."""
    cap = flat[0].shape[0] if flat else 0
    target = bucket_capacity(num_rows)
    shrink = target < cap
    cols, i = [], 0
    for f in schema:
        step = 3 if f.dtype is DType.STRING else 2
        parts = [flat[i + k] for k in range(step)]
        if shrink:
            parts = [a[:target] for a in parts]
        cols.append(DeviceColumn(f.dtype, *parts) if step == 3
                    else DeviceColumn(f.dtype, parts[0], parts[1]))
        i += step
    return DeviceBatch(schema, tuple(cols), num_rows)


def _cached_jit(key, builder):
    """One compiled program per key, shared ACROSS QUERIES: keys carry the
    operator config + schema (dtype signature) + capacity bucket, so any
    query hitting the same plan shape reuses the program (serving/
    program_cache.py: hit/miss/disk-warm accounting, in-flight build
    latch, LRU bound, per-query attribution). The program is named by the
    key's leading string, its kind (``named_jit``)."""
    return _PROGRAM_CACHE.get_or_build(
        key, lambda: named_jit(key[0], builder()))


def concat_device_batches(batches: List[DeviceBatch], schema: Schema,
                          string_max_bytes: int = 256) -> DeviceBatch:
    """Concatenate batches into one (GpuCoalesceBatches / Table.concatenate
    analog). Row offsets are host-static, so this is plain slicing + concat that
    XLA lowers to device copies; result re-bucketed."""
    batches = [b for b in batches if b.num_rows > 0]
    if not batches:
        return DeviceBatch.empty(schema, string_max_bytes)
    # a mesh-sharded input would silently collapse onto one device through
    # XLA's implicit resharding — refuse; the explicit boundaries are
    # MeshGatherExec (collective gather) / scatter_device_batch (reshard)
    from spark_rapids_tpu.parallel.placement import assert_unsharded
    assert_unsharded(batches, "concat_device_batches")
    if len(batches) == 1:
        return batches[0]
    # the dispatch of eager slices and concatenates, through no program
    # cache; not awaited
    with _tracing.span("batch.concat", _tracing.LAYER_EXEC) as sp:
        total = sum(b.num_rows for b in batches)
        cap = bucket_capacity(total)
        cols = []
        nd = 0      # eager device calls issued, counted as issued
        for ci, f in enumerate(schema):
            datas, valids, lens, bit_parts = [], [], [], []
            # the f64 bit sibling survives only when EVERY contributor carries
            # one (upload-time doubles); device-computed doubles have none and
            # a partial sibling would desynchronize from the data
            carry_bits = (f.dtype is DType.DOUBLE
                          and all(b.columns[ci].bits is not None
                                  for b in batches))
            # the dictionary encoding survives when every contributor carries
            # one from the SAME dictionary stream (DictionaryUnifier token):
            # dictionaries are then prefix-compatible, so the concatenated
            # index vector stays valid against the largest contributor's
            # dictionary — encoded-domain operators keep working after coalesce
            encs = [b.columns[ci].encoding for b in batches]
            carry_enc = (all(e is not None and e.token is not None
                             for e in encs)
                         and len({e.token for e in encs}) == 1)
            idx_parts = []
            for b in batches:
                c = b.columns[ci]
                datas.append(c.data[:b.num_rows])
                valids.append(c.validity[:b.num_rows])
                if c.lengths is not None:
                    lens.append(c.lengths[:b.num_rows])
                if carry_bits:
                    bit_parts.append(c.bits[:b.num_rows])
                if carry_enc:
                    idx_parts.append(c.encoding.indices[:b.num_rows])
            if f.dtype is DType.STRING:
                from spark_rapids_tpu.ops.strings import pad_width
                W = max(d.shape[-1] for d in datas)
                datas = [pad_width(jnp, d, W) for d in datas]
            data = jnp.concatenate(datas, axis=0)
            validity = jnp.concatenate(valids, axis=0)
            bits = jnp.concatenate(bit_parts, axis=0) if carry_bits else None
            pad = cap - total
            if pad:
                pad_shape = (pad,) + data.shape[1:]
                data = jnp.concatenate(
                    [data, jnp.zeros(pad_shape, data.dtype)], axis=0)
                validity = jnp.concatenate(
                    [validity, jnp.zeros(pad, bool)], axis=0)
                if bits is not None:
                    bits = jnp.concatenate(
                        [bits, jnp.zeros(pad, bits.dtype)], axis=0)
            # a slice a part, a concatenate a joined vector, and with a pad
            # its zeros and one more concatenate
            nd += (len(datas) + len(valids) + len(lens) + len(bit_parts)
                   + len(idx_parts)
                   + (3 if pad else 1) * (2 + carry_bits + carry_enc
                                          + (f.dtype is DType.STRING)))
            enc = None
            if carry_enc:
                from spark_rapids_tpu.columnar.encoding import DictEncoding
                indices = jnp.concatenate(idx_parts, axis=0)
                if pad:
                    indices = jnp.concatenate(
                        [indices, jnp.zeros(pad, indices.dtype)], axis=0)
                big = max(encs, key=lambda e: (e.k, e.k_real))
                enc = DictEncoding(indices, big.values, big.k_real, big.lengths,
                                   big.token)
            if f.dtype is DType.STRING:
                lengths = jnp.concatenate(lens, axis=0)
                if pad:
                    lengths = jnp.concatenate(
                        [lengths, jnp.zeros(pad, lengths.dtype)], axis=0)
                cols.append(DeviceColumn(f.dtype, data, validity, lengths,
                                         encoding=enc))
            else:
                cols.append(DeviceColumn(f.dtype, data, validity, bits=bits,
                                         encoding=enc))
        if sp is not None:
            sp.note(batches=len(batches), rows=total, columns=len(cols),
                    dispatches=nd)
    return DeviceBatch(schema, tuple(cols), total)


# ---------------------------------------------------------------- transitions
class HostToDeviceExec(PhysicalExec):
    """Upload transition (GpuRowToColumnarExec / HostColumnarToGpu analog).

    Directly over an in-memory scan, the upload is cached across actions
    (scan_cache) so repeated queries on the same DataFrame skip the
    host->device transfer."""

    is_device = True

    def __init__(self, child: PhysicalExec):
        super().__init__((child,), child.output)

    def size_estimate(self) -> Optional[int]:
        return self.children[0].size_estimate()   # transition: same rows

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.columnar.transfer import upload_table_conf
        from spark_rapids_tpu.execs.cpu_execs import CpuLocalScanExec
        child = self.children[0]
        if (isinstance(child, CpuLocalScanExec)
                and ctx.conf.get(cfg.SCAN_CACHE_ENABLED)):
            if ctx.partition_id != 0:
                return
            from spark_rapids_tpu.memory.scan_cache import (derived_budget,
                                                            get_cache)
            cache = get_cache(derived_budget(ctx.conf))
            smax = ctx.string_max_bytes
            # per-key latch: concurrent queries missing on the same table
            # share ONE upload instead of each paying the host link
            b = cache.get_or_put(
                child.table, smax,
                lambda: upload_table_conf(child.table, smax, ctx.conf,
                                          device=ctx.device),
                cancel_check=ctx.check_cancelled)
            child.count_output(b.num_rows)
            self.count_output(b.num_rows)
            yield b
            return
        for hb in child.execute(ctx):
            ctx.check_cancelled()   # before each upload: the costliest step
            table = hb.to_arrow() if isinstance(hb, HostBatch) else hb
            b = upload_table_conf(table, ctx.string_max_bytes, ctx.conf,
                                  device=ctx.device)
            self.count_output(b.num_rows)
            yield b


class DeviceToHostExec(PhysicalExec):
    """Download transition (GpuColumnarToRowExec analog)."""

    is_device = False

    def __init__(self, child: PhysicalExec):
        super().__init__((child,), child.output)

    def size_estimate(self) -> Optional[int]:
        return self.children[0].size_estimate()   # transition: same rows

    def execute(self, ctx: ExecContext) -> Iterator[HostBatch]:
        for db in self.children[0].execute(ctx):
            ctx.check_cancelled()   # before each download
            hb = HostBatch.from_arrow(db.to_arrow(), ctx.string_max_bytes)
            self.count_output(hb.num_rows)
            yield hb


# ---------------------------------------------------------------- leaf / simple
class TpuRangeExec(LeafExec):
    is_device = True

    def __init__(self, start: int, end: int, step: int):
        super().__init__(Schema([Field("id", DType.LONG, nullable=False)]))
        self.start, self.end, self.step = start, end, step

    def size_estimate(self) -> Optional[int]:
        rows = max(0, -(-(self.end - self.start) // self.step))
        return rows * 9      # 8B id + validity byte

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        if ctx.partition_id != 0:
            return
        n = max(0, -(-(self.end - self.start) // self.step))
        cap = bucket_capacity(n)
        data = self.start + jnp.arange(cap, dtype=jnp.int64) * self.step
        validity = jnp.arange(cap, dtype=jnp.int32) < n
        self.count_output(n)
        yield DeviceBatch(self.output,
                          (DeviceColumn(DType.LONG, data, validity),), n)


class TpuProjectExec(PhysicalExec):
    is_device = True

    def __init__(self, exprs: Tuple[Expression, ...], child: PhysicalExec):
        super().__init__((child,), output_schema(exprs))
        self.exprs = exprs

    def size_estimate(self) -> Optional[int]:
        return _width_scaled(self.children[0], self.output)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for batch in self.children[0].execute(ctx):
            out = eval_exprs_device(self.exprs, batch, ctx.string_max_bytes,
                                    {"partition_id": ctx.partition_id})
            self.count_output(out.num_rows)
            yield out


class TpuFilterExec(PhysicalExec):
    is_device = True

    #: set by plan/encoded.mark_encoded_domain: the child chain can deliver
    #: batches whose columns still carry their dictionary encoding, so
    #: single-column predicates may evaluate on the k dictionary slots and
    #: gather (exprs/encoded.py) instead of scanning n decoded rows
    encoded_domain_ok = False

    def __init__(self, condition: Expression, child: PhysicalExec):
        super().__init__((child,), child.output)
        self.condition = condition

    def size_estimate(self) -> Optional[int]:
        return self.children[0].size_estimate()   # upper bound (no stats)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.exprs import encoded as ed
        from spark_rapids_tpu.utils import metrics as um
        schema = self.output
        use_enc = (self.encoded_domain_ok
                   and ctx.conf.get(cfg.ENCODED_DOMAIN))
        for batch in self.children[0].execute(ctx):
            cap = batch.capacity
            cond, used = self.condition, ()
            if use_enc:
                specs = cenc.enc_specs_of(batch)
                if specs:
                    cond, used = ed.rewrite_predicate(self.condition, specs)
            key = ("filter", cond, used, schema, cap, ctx.string_max_bytes)

            def build(cond=cond, used=used, schema=schema, cap=cap,
                      smax=ctx.string_max_bytes):
                nflat = flat_len(schema)

                def fn(num_rows, *flat):
                    colvs = _unflatten_colvs(schema, flat[:nflat])
                    ectx = EvalCtx(jnp, colvs, cap, smax)
                    if used:
                        ectx.encodings = cenc.unflatten_encodings(
                            jnp, used, flat[nflat:])
                    pred = cond.eval(ectx)
                    alive = jnp.arange(cap, dtype=np.int32) < num_rows
                    keep = jnp.logical_and(
                        jnp.logical_and(pred.data, pred.validity), alive)
                    if keep.ndim == 0:
                        keep = jnp.broadcast_to(keep, (cap,))
                        keep = jnp.logical_and(keep, alive)
                    out_cols, n = bk.compact(jnp, keep, colvs, num_rows)
                    return tuple(_flatten_colvs(out_cols)) + (n,)
                return fn

            fn = _cached_jit(key, build)
            res = fn(np.int32(batch.num_rows), *_flatten(batch),
                     *cenc.flatten_encodings(batch, used))
            if used:
                um.TRANSFER_METRICS[um.TRANSFER_ENCODED_DOMAIN_OPS].add(1)
            # justified sync: the engine's designed one-scalar-per-batch
            # download — the logical row count must reach the host to pick
            # the output capacity bucket (see module docstring)
            n = int(res[-1])  # tpu-lint: disable=R002
            out = _to_batch(schema, res[:-1], n)
            self.count_output(n)
            yield out


class TpuHashAggregateExec(PhysicalExec):
    """Grouped aggregation; may carry a fused upstream filter predicate
    (``pre_filter``) folded into the alive-mask, so the filtered rows never
    materialize (the whole-stage-fusion analog of Spark's codegen collapsing
    Filter into HashAggregate)."""

    is_device = True

    #: set by plan/encoded.mark_encoded_domain: grouping keys that are
    #: plain references to encoded columns group on the int32 dictionary
    #: indices (unlocking the sort-free one-hot path even for string keys)
    #: and materialize decoded key values only for the surviving groups
    encoded_domain_ok = False

    #: peak device bytes per input byte while the aggregation runs (input
    #: batch + the grouping sort passes + compacted output), the planner's
    #: footprint contract and the runtime pressure check (memory/grace.py)
    working_set_factor = 3.0

    def __init__(self, grouping: Tuple[Expression, ...],
                 aggregates: Tuple[Expression, ...], child: PhysicalExec,
                 output: Schema, pre_filter: Optional[Expression] = None):
        super().__init__((child,), output)
        self.grouping = grouping
        self.aggregates = aggregates
        self.pre_filter = pre_filter

    def size_estimate(self) -> Optional[int]:
        # output groups never exceed input rows: the child's estimate is an
        # upper bound, scaled by the output/input row-width ratio
        return _width_scaled(self.children[0], self.output)

    def working_set_estimate(self) -> Optional[int]:
        sz = self.children[0].size_estimate()
        return None if sz is None else int(sz * self.working_set_factor)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.memory import grace
        source = self.children[0].execute(ctx)
        ooc = (grace.controller_for(self, ctx, "agg", self.grouping)
               if self.grouping else None)
        if ooc is None:
            yield from self._single_pass(ctx, list(source))
            return
        mode, payload = ooc.stage(source, self.grouping)
        if mode == "inline":
            yield from self._single_pass(ctx, payload)
            return
        yield from self._grace_execute(ctx, ooc, payload)

    def _grace_execute(self, ctx: ExecContext, ooc,
                       parts) -> Iterator[DeviceBatch]:
        """Grace recursion: every partition holds complete key groups
        (hash-routed), so the per-partition single-pass results union to
        the global aggregation; oversized partitions re-partition with a
        deeper hash salt until they fit, the depth bound stops them, or a
        split proves degenerate (one indivisible key group)."""
        try:
            degenerate = parts.degenerate
            for pid in parts.nonempty():
                ctx.check_cancelled()
                if not degenerate and ooc.should_recurse(
                        parts.bytes_of(pid), parts.depth):
                    # drain() feeds the re-split one piece at a time, so
                    # the over-budget partition is never whole on device
                    sub = ooc.partition(parts.drain(pid), self.grouping,
                                        depth=parts.depth + 1)
                    yield from self._grace_execute(ctx, ooc, sub)
                else:
                    batches = parts.take(pid)
                    if batches:
                        yield from self._single_pass(ctx, batches)
        finally:
            parts.close()

    def _single_pass(self, ctx: ExecContext,
                     child_batches) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.exprs import encoded as ed
        from spark_rapids_tpu.utils import metrics as um
        batch = concat_device_batches(child_batches, self.children[0].output,
                                      ctx.string_max_bytes)
        cap = batch.capacity
        schema = self.children[0].output
        fns = tuple(a.c if isinstance(a, Alias) else a for a in self.aggregates)

        grouping, pre_filter = self.grouping, self.pre_filter
        subs: Dict[int, "cenc.EncSpec"] = {}
        used: Tuple = ()
        if self.encoded_domain_ok and ctx.conf.get(cfg.ENCODED_DOMAIN):
            specs = cenc.enc_specs_of(batch)
            if specs:
                grouping, subs, used_g = ed.rewrite_grouping(self.grouping,
                                                             specs)
                used_p: Tuple = ()
                if pre_filter is not None:
                    pre_filter, used_p = ed.rewrite_predicate(pre_filter,
                                                              specs)
                merged = {s.ordinal: s for s in tuple(used_g) + tuple(used_p)}
                used = tuple(sorted(merged.values(),
                                    key=lambda s: s.ordinal))

        def build(mode):
            def make(keys_=grouping, fns=fns, schema=schema, cap=cap,
                     smax=ctx.string_max_bytes, mode=mode,
                     pre=pre_filter, used=used, subs=tuple(subs.items())):
                nflat = flat_len(schema)

                def fn(num_rows, *flat):
                    colvs = _unflatten_colvs(schema, flat[:nflat])
                    ectx = EvalCtx(jnp, colvs, cap, smax)
                    if used:
                        ectx.encodings = cenc.unflatten_encodings(
                            jnp, used, flat[nflat:])
                    mask = None
                    if pre is not None:
                        p = pre.eval(ectx)
                        mask = jnp.logical_and(p.data, p.validity)
                        if mask.ndim == 0:
                            mask = jnp.broadcast_to(mask, (cap,))
                    res = group_aggregate(jnp, ectx, keys_, fns, num_rows,
                                          cap, grouping=mode,
                                          extra_mask=mask)
                    key_cols, res_cols, num_groups = res[:3]
                    key_cols = list(key_cols)
                    for j, spec in subs:
                        # late materialization: only the surviving groups'
                        # key values decode (k-bounded gather)
                        key_cols[j] = ed.materialize_key(ectx, spec,
                                                         key_cols[j])
                    tail = ((num_groups, res[3]) if mode in ("hash", "onehot")
                            else (num_groups,))
                    return tuple(_flatten_colvs(
                        list(key_cols) + list(res_cols))) + tail
                return fn
            return make

        # fastest grouping first: the sort-free one-hot path (bounded group
        # count, exact overflow/collision flag), then hash-ordered grouping
        # (one variadic sort), then the exact lexsort — each escalation only
        # on a flagged run
        # subs is keyed: it decides which key columns materialize from the
        # encoded domain inside the trace, and ``used`` alone does not pin
        # it — the predicate can contribute specs to used without touching
        # the grouping rewrite (R016)
        key = ("agg", grouping, fns, pre_filter, used, tuple(subs.items()),
               schema, cap, ctx.string_max_bytes)
        from spark_rapids_tpu.ops.aggregate import (grouping_modes,
                                                    reduce_form)
        modes = grouping_modes(grouping, fns)
        enc_flat = cenc.flatten_encodings(batch, used)
        if used:
            um.TRANSFER_METRICS[um.TRANSFER_ENCODED_DOMAIN_OPS].add(1)
        res = None
        for mode in modes:
            fn = _cached_jit(key + (mode,), build(mode))
            fast = mode in ("hash", "onehot")
            # one span per attempted mode, from the program's call to the
            # host's read of the flag (or, on the attempt that is kept, of
            # the group count): both reads were here before the span
            with _tracing.span("agg.attempt", _tracing.LAYER_EXEC) as attempt:
                res = fn(np.int32(batch.num_rows), *_flatten(batch),
                         *enc_flat)
                # justified sync: the escalation flag must be read on host
                # to decide whether the faster grouping's result is exact or
                # the next mode runs — one scalar per attempted mode, not
                # per batch
                flagged = (fast and bool(self.grouping)
                           and bool(res[-1]))  # tpu-lint: disable=R002
                if not flagged:
                    n = int(res[-2] if fast else res[-1])
                if attempt is not None:
                    attempt.note(mode=mode, flagged=flagged, capacity=cap,
                                 keys=len(self.grouping),
                                 reduce=reduce_form(mode, cap),
                                 **({} if flagged else {"groups": n}))
            if not flagged:
                break
        out = _to_batch(self.output, res[:-2] if fast else res[:-1], n)
        self.count_output(n)
        yield out


class TpuSortExec(PhysicalExec):
    is_device = True

    #: input + the variadic sort's key passes + sorted output
    working_set_factor = 3.0

    def __init__(self, orders: Tuple[SortOrder, ...], child: PhysicalExec):
        super().__init__((child,), child.output)
        self.orders = orders

    def size_estimate(self) -> Optional[int]:
        return self.children[0].size_estimate()   # a sort is a permutation

    def working_set_estimate(self) -> Optional[int]:
        sz = self.children[0].size_estimate()
        return None if sz is None else int(sz * self.working_set_factor)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.memory import grace
        source = self.children[0].execute(ctx)
        ooc = grace.controller_for(self, ctx, "sort", (),
                                   orders=self.orders)
        if ooc is None:
            yield from self._single_pass(ctx, list(source))
            return
        mode, payload = ooc.stage(source, (), orders=self.orders)
        if mode == "inline":
            yield from self._single_pass(ctx, payload)
            return
        yield from self._grace_execute(ctx, ooc, payload)

    def _grace_execute(self, ctx: ExecContext, ooc,
                       parts) -> Iterator[DeviceBatch]:
        """External sort by order-preserving range partitioning (the
        device-friendly external merge: sampled bounds split the key space,
        ties share a partition, and the bound-ordered emission of
        per-partition stable sorts IS the merged output — bit-identical to
        the single-pass stable sort). Skewed partitions re-partition on
        their OWN resampled bounds until they fit, the depth bound stops
        them, or a split proves degenerate (one indivisible key run)."""
        try:
            degenerate = parts.degenerate
            for pid in parts.nonempty():
                ctx.check_cancelled()
                sub = None
                if not degenerate and ooc.should_recurse(
                        parts.bytes_of(pid), parts.depth):
                    # drain() feeds the re-split piece-wise; bounds resample
                    # from the drained prefix (a nonempty pid has live rows,
                    # so the sample cannot come back empty)
                    sub = ooc.partition(parts.drain(pid), (),
                                        depth=parts.depth + 1,
                                        orders=self.orders)
                if sub is not None:
                    yield from self._grace_execute(ctx, ooc, sub)
                else:
                    batches = parts.take(pid)
                    if batches:
                        yield from self._single_pass(ctx, batches)
        finally:
            parts.close()

    def _single_pass(self, ctx: ExecContext, batches) -> Iterator[DeviceBatch]:
        batch = concat_device_batches(batches, self.output, ctx.string_max_bytes)
        if batch.num_rows == 0:
            yield batch
            return
        cap = batch.capacity
        schema = self.output
        key = ("sort", self.orders, schema, cap, ctx.string_max_bytes)

        def build(orders=self.orders, schema=schema, cap=cap,
                  smax=ctx.string_max_bytes):
            def fn(num_rows, *flat):
                colvs = _unflatten_colvs(schema, flat)
                ectx = EvalCtx(jnp, colvs, cap, smax)
                alive = bk.alive_mask(jnp, cap, num_rows)
                # dead rows last, then the order keys — ONE variadic sort
                # carrying every column (no per-column gathers)
                passes = [jnp.logical_not(alive).astype(np.int8)]
                for o in orders:
                    passes.extend(bk._key_passes(jnp, o.child.eval(ectx),
                                                 o.ascending, o.nulls_first))
                out_cols, _ = bk.sort_colvs(jnp, passes, colvs)
                return tuple(_flatten_colvs(out_cols))
            return fn

        fn = _cached_jit(key, build)
        res = fn(np.int32(batch.num_rows), *_flatten(batch))
        out = _to_batch(schema, res, batch.num_rows)
        self.count_output(out.num_rows)
        yield out


class TpuLimitExec(PhysicalExec):
    """Limit = shrink the logical row count; padding invariants handled by
    invalidating rows >= n (no data movement at all on device)."""

    is_device = True

    def __init__(self, n: int, child: PhysicalExec):
        super().__init__((child,), child.output)
        self.n = n

    def size_estimate(self) -> Optional[int]:
        from spark_rapids_tpu.columnar.dtypes import limit_size_estimate
        return limit_size_estimate(self.children[0], self.output, self.n)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        remaining = self.n
        for batch in self.children[0].execute(ctx):
            if remaining <= 0:
                break
            take = min(remaining, batch.num_rows)
            remaining -= take
            if take == batch.num_rows:
                self.count_output(take)
                yield batch
                continue
            cols = []
            alive = jnp.arange(batch.capacity, dtype=np.int32) < take
            for c in batch.columns:
                cols.append(DeviceColumn(c.dtype, c.data,
                                         jnp.logical_and(c.validity, alive),
                                         c.lengths))
            self.count_output(take)
            yield DeviceBatch(batch.schema, tuple(cols), take)


class TpuUnionExec(PhysicalExec):
    is_device = True

    def __init__(self, left: PhysicalExec, right: PhysicalExec):
        super().__init__((left, right), left.output)

    def size_estimate(self) -> Optional[int]:
        from spark_rapids_tpu.columnar.dtypes import union_size_estimate
        return union_size_estimate(self.children)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for child in self.children:
            yield from child.execute(ctx)


class TpuCoalesceBatchesExec(PhysicalExec):
    """Concatenate small batches toward the target size
    (GpuCoalesceBatches.scala:502 analog; TargetSize goal)."""

    is_device = True

    def __init__(self, child: PhysicalExec, target_bytes: int = 1 << 31,
                 require_single: bool = False):
        super().__init__((child,), child.output)
        self.target_bytes = target_bytes
        self.require_single = require_single

    def size_estimate(self) -> Optional[int]:
        return self.children[0].size_estimate()   # concat: same rows

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for out in coalesce_batches(self.children[0].execute(ctx),
                                    self.output, self.target_bytes,
                                    self.require_single,
                                    ctx.string_max_bytes):
            self.count_output(out.num_rows)
            yield out


def coalesce_batches(source: Iterator[DeviceBatch], schema: Schema,
                     target_bytes: int, require_single: bool,
                     string_max_bytes: int) -> Iterator[DeviceBatch]:
    """The accumulate-until-target concat loop, shared by
    TpuCoalesceBatchesExec and the fused-stage coalesce absorption
    (execs/fused_execs.py) so the flush/require_single semantics cannot
    drift between the two."""
    pending: List[DeviceBatch] = []
    pending_bytes = 0
    for batch in source:
        pending.append(batch)
        pending_bytes += batch.device_size_bytes
        if not require_single and pending_bytes >= target_bytes:
            yield concat_device_batches(pending, schema, string_max_bytes)
            pending, pending_bytes = [], 0
    if pending or require_single:
        yield concat_device_batches(pending, schema, string_max_bytes)
