"""Whole-stage-fused physical operators.

The WholeStageCodegenExec analog for the TPU engine (plan/fusion.py builds
these): a maximal chain of fusable execs between pipeline breakers —
Project / Filter / Expand / CoalesceBatches, plus the partial-aggregate
fold — collapses into ONE operator whose entire chain traces into a SINGLE
jitted XLA program. A filter inside the chain becomes a mask threaded
through the downstream expression evaluation with ONE compaction at the
stage boundary, so no intermediate DeviceBatch is ever built in HBM
between the fused operators (Flare's whole-pipeline compilation result;
Theseus' minimize-intermediate-materialization argument).

Two shapes:

- ``FusedStageExec`` — streaming chains. The chain is normalized at plan
  time into *variants*: each variant is (output expressions, predicate)
  composed over the STAGE INPUT schema by reference substitution (an
  Expand multiplies variants, one per projection list). Execution
  evaluates every variant inside one cached program per (variants,
  encodings, schema, capacity bucket) key — the fused plan-signature key,
  routed through the cross-query serving ProgramCache with the pow2 shape
  buckets preserved (R007 discipline).
- ``FusedAggregateStageExec`` — a chain terminated by a hash aggregate
  (the partial-aggregate fold): filter predicates land in ``pre_filter``
  and projections substitute into the grouping/aggregate expressions, so
  the aggregation program itself is the stage's single program. Inherits
  the aggregate's whole execution pipeline including the encoded-domain
  grouping rewrite and the one-hot/hash/lexsort escalation.

Encoded-domain composition (PR 4): the composed predicate is over the
stage INPUT schema, so when the child chain preserves dictionary
encodings (plan/encoded.py marks ``encoded_domain_ok``) the predicate is
rewritten per batch to evaluate on the k dictionary slots and gather —
fusion does not knock a filter off the encoded domain. Placement (PR 5):
fused stages are placement-agnostic like every other exec; they never
read ``ctx.placement`` and the plan-time flag rides the base class.
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.execs import tpu_execs as te
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.execs.evaluator import colv_to_column
from spark_rapids_tpu.exprs.core import (ColV, EvalCtx, Expression, flat_len,
                                         flatten_colvs, unflatten_colvs)
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.serving.program_cache import named_jit

#: per-stage metric: operators collapsed into this stage
FUSED_OPS = "fusedOps"
#: per-stage metric: intermediate batches that never materialized in HBM
#: (one per interior operator output the unfused chain would have built)
FUSED_BATCHES_SAVED = "batchesNotMaterialized"

#: one variant of a fused stage: (output expressions, optional predicate),
#: both composed over the stage input schema. A chain without Expand has
#: exactly one variant; each Expand projection list multiplies them.
Variant = Tuple[Tuple[Expression, ...], Optional[Expression]]


class FusedStageExec(PhysicalExec):
    """A fused streaming chain: one cached XLA program evaluates every
    variant's expressions AND its filter mask over each input batch, with a
    single end-of-stage compaction — the interior operators' batches never
    exist."""

    is_device = True

    #: set by plan/encoded.mark_encoded_domain: the child chain can deliver
    #: dictionary-encoded batches, so the composed predicate may evaluate
    #: on the k dictionary slots and gather (exprs/encoded.py)
    encoded_domain_ok = False

    #: 1-based whole-stage id, assigned by plan/fusion.py after the pass
    #: (display only — never part of a program-cache key)
    stage_id = 0

    def __init__(self, fused_ops: Tuple[Tuple[str, Schema], ...],
                 variants: Tuple[Variant, ...],
                 coalesce: Optional[Tuple[int, bool]],
                 child: PhysicalExec, output: Schema,
                 saved_per_batch: int = 0):
        super().__init__((child,), output)
        self.fused_ops = tuple(fused_ops)      # (name, schema), top-down
        self.variants = tuple(variants)
        self.coalesce = coalesce               # (target_bytes, require_single)
        self.saved_per_batch = saved_per_batch
        self.metrics[FUSED_OPS].add(len(self.fused_ops))

    @property
    def has_predicate(self) -> bool:
        return any(pred is not None for _, pred in self.variants)

    def size_estimate(self) -> Optional[int]:
        if len(self.variants) > 1:
            return None     # an Expand multiplies output rows per variant
        # narrowing chain: the child's estimate is an upper bound
        return self.children[0].size_estimate()

    # ---- plan display ------------------------------------------------------
    def tree_string(self, indent: int = 0, analyze: bool = False) -> str:
        from spark_rapids_tpu.utils import tracing as _tracing
        tag = ""
        if self.placement is not None:
            from spark_rapids_tpu.parallel.placement import placement_label
            tag = f" @{placement_label(self.placement)}"
        lines = []
        for i, (name, schema) in enumerate(self.fused_ops):
            # observed stats and the adaptive tag attach to the stage HEAD
            # (the fused interior never materializes, so per-interior-op
            # rows do not exist)
            obs = _tracing.analyze_annotation(self) if analyze and i == 0 \
                else ""
            atag = (f" [adaptive: {self.adaptive_tag}]"
                    if self.adaptive_tag and i == 0 else "")
            lines.append("  " * (indent + i)
                         + f"*({self.stage_id}) {name} [{schema}]{tag}{atag}{obs}")
        lines.append(self.children[0].tree_string(
            indent + len(self.fused_ops), analyze=analyze))
        return "\n".join(lines)

    # ---- execution ---------------------------------------------------------
    def _coalesced(self, source, ctx: ExecContext):
        """Batch-boundary half of a fused CoalesceBatches: concatenation runs
        on the RAW stage input (content-equivalent — every fused op is
        row-wise, so op(concat(b)) == concat(op(b)) for the live rows;
        plan/fusion._compose refuses the shapes where that is not enough:
        require_single above a real op, and any coalesce with Expand)."""
        target_bytes, require_single = self.coalesce
        return te.coalesce_batches(source, self.children[0].output,
                                   target_bytes, require_single,
                                   ctx.string_max_bytes)

    def _rewrite_encoded(self, batch: DeviceBatch, use_enc: bool):
        """Per-batch encoded-domain rewrite of every variant predicate;
        returns (variants, used EncSpecs)."""
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.exprs import encoded as ed
        variants = self.variants
        if not use_enc:
            return variants, ()
        specs = cenc.enc_specs_of(batch)
        if not specs:
            return variants, ()
        merged = {}
        out = []
        for exprs, pred in variants:
            if pred is not None:
                pred, used = ed.rewrite_predicate(pred, specs)
                for s in used:
                    merged[s.ordinal] = s
            out.append((exprs, pred))
        if not merged:
            return variants, ()
        return tuple(out), tuple(sorted(merged.values(),
                                        key=lambda s: s.ordinal))

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.columnar import encoding as cenc
        from spark_rapids_tpu.utils import metrics as um
        in_schema = self.children[0].output
        out_schema = self.output
        smax = ctx.string_max_bytes
        use_enc = (self.encoded_domain_ok and ctx.conf.get(cfg.ENCODED_DOMAIN))
        # partition-scoped eval attrs (SparkPartitionID etc.), part of the
        # program key exactly like eval_exprs_device's ctx_attrs
        attrs = (("partition_id", ctx.partition_id),)
        nflat_in = flat_len(in_schema)
        nflat_out = flat_len(out_schema)

        def make(variants, used, cap):
            """The whole stage as ONE traced function: every variant's
            expressions evaluate over the input columns, the variant's
            composed predicate (if any) becomes the keep-mask of a single
            compact — interior operator outputs exist only as XLA values."""
            def fn(num_rows, *flat):
                colvs = unflatten_colvs(in_schema, flat[:nflat_in])
                ectx = EvalCtx(jnp, colvs, cap, smax)
                for k, v in attrs:
                    setattr(ectx, k, v)
                if used:
                    ectx.encodings = cenc.unflatten_encodings(
                        jnp, used, flat[nflat_in:])
                outs = []
                for exprs, pred in variants:
                    ovals = []
                    for e, f in zip(exprs, out_schema):
                        v = e.eval(ectx)
                        data, validity, lengths = colv_to_column(
                            v, jnp, cap, smax)
                        ovals.append(ColV(f.dtype, data, validity, lengths))
                    if pred is not None:
                        p = pred.eval(ectx)
                        alive = jnp.arange(cap, dtype=np.int32) < num_rows
                        keep = jnp.logical_and(p.data, p.validity)
                        if keep.ndim == 0:
                            keep = jnp.broadcast_to(keep, (cap,))
                        keep = jnp.logical_and(keep, alive)
                        ovals, n = bk.compact(jnp, keep, ovals, num_rows)
                    else:
                        n = num_rows
                    outs.extend(flatten_colvs(ovals))
                    outs.append(n)
                return tuple(outs)
            return named_jit("stage", fn)

        source = self.children[0].execute(ctx)
        if self.coalesce is not None:
            source = self._coalesced(source, ctx)
        for batch in source:
            ctx.check_cancelled()
            cap = batch.capacity
            variants, used = self._rewrite_encoded(batch, use_enc)
            # out_schema is keyed: the traced fn zips each variant's
            # expressions against the output fields, so two stages sharing
            # (variants, in_schema) but projecting different output dtypes
            # must not share a program (R016)
            key = ("stage", variants, used, in_schema, out_schema, cap,
                   smax, attrs)
            fn = self.cached_program(key, lambda: make(variants, used, cap))
            res = fn(np.int32(batch.num_rows), *te._flatten(batch),
                     *cenc.flatten_encodings(batch, used))
            if used:
                um.TRANSFER_METRICS[um.TRANSFER_ENCODED_DOMAIN_OPS].add(1)
            self.metrics[FUSED_BATCHES_SAVED].add(self.saved_per_batch)
            i = 0
            for _ in self.variants:
                flat = list(res[i:i + nflat_out])
                # justified sync: the engine's designed one-scalar-per-batch
                # download — the logical row count must reach the host to
                # pick the output capacity bucket (see tpu_execs docstring)
                n = int(res[i + nflat_out])
                i += nflat_out + 1
                out = te._to_batch(out_schema, flat, n)
                self.count_output(n)
                yield out


class FusedAggregateStageExec(te.TpuHashAggregateExec):
    """A fused stage terminated by a hash aggregate: the folded filters ride
    ``pre_filter`` and folded projections are substituted into the grouping/
    aggregate expressions, so the inherited aggregation program IS the
    stage's single fused program (same expression trees — and therefore the
    same program-cache keys — as the fuse_device_ops fold when fusion is
    off, which is what makes fused vs unfused bit-identical)."""

    stage_id = 0

    def __init__(self, grouping, aggregates, child, output,
                 pre_filter=None, fused_ops: Tuple[Tuple[str, Schema], ...] = ()):
        super().__init__(grouping, aggregates, child, output,
                         pre_filter=pre_filter)
        self.fused_ops = tuple(fused_ops)   # folded ops below the aggregate
        self.metrics[FUSED_OPS].add(len(self.fused_ops) + 1)

    def tree_string(self, indent: int = 0, analyze: bool = False) -> str:
        from spark_rapids_tpu.utils import tracing as _tracing
        tag = ""
        if self.placement is not None:
            from spark_rapids_tpu.parallel.placement import placement_label
            tag = f" @{placement_label(self.placement)}"
        if self.adaptive_tag:
            tag += f" [adaptive: {self.adaptive_tag}]"
        if analyze:
            tag += _tracing.analyze_annotation(self)
        # the folded ops are NOT rendered (their expressions live inside the
        # aggregate now — same display contract as the fuse_device_ops fold)
        lines = ["  " * indent
                 + f"*({self.stage_id}) TpuHashAggregateExec "
                   f"[{self.output}]{tag}"]
        lines.append(self.children[0].tree_string(indent + 1,
                                                  analyze=analyze))
        return "\n".join(lines)

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.utils.metrics import NUM_OUTPUT_BATCHES
        child = self.children[0]
        before = child.metrics[NUM_OUTPUT_BATCHES].value
        try:
            yield from super().execute(ctx)
        finally:
            # in finally so an early generator close (limit above the
            # aggregate, cancellation) still accounts the elided batches
            inputs = child.metrics[NUM_OUTPUT_BATCHES].value - before
            # each folded op would have materialized one batch per input
            # batch; wrappers that don't count fall back to one input batch
            self.metrics[FUSED_BATCHES_SAVED].add(
                max(int(inputs), 1) * len(self.fused_ops))
