"""Planner pass: whole-stage fusion of device exec chains.

Collapses maximal chains of fusable execs between pipeline breakers into
``FusedStageExec`` / ``FusedAggregateStageExec`` (execs/fused_execs.py) so
the whole chain compiles into ONE XLA program — ROADMAP item 5, grounded
in Flare's whole-pipeline compilation result (PAPERS.md): with the link
pipelined (PR 3) and the bytes shrunk (PR 4), the remaining per-query
waste is the full columnar batch every exec boundary materializes in HBM
plus its kernel round-trip.

Fusable: TpuProjectExec, TpuFilterExec, TpuExpandExec,
TpuCoalesceBatchesExec, and a terminating partial TpuHashAggregateExec
(the pre_filter/substitution fold — shared with plan/overrides.
fuse_device_ops so fused and unfused plans build IDENTICAL aggregate
expression trees and therefore identical program-cache keys). Everything
else is a pipeline breaker and ends the stage: exchanges, sorts, joins,
limits, unions, caches, scans/transitions, and mesh boundaries (under
``sql.mesh.enabled`` the pass is a no-op — mesh_rewrite pattern-matches
the unfused exec types, the same contract as insert_pipeline and
mark_encoded_domain; fused stages themselves stay placement-agnostic).

Chains are normalized by REFERENCE SUBSTITUTION into per-variant
(output expressions, predicate) pairs over the stage input schema:
projections substitute into downstream expressions, filters AND into the
stage predicate (the mask threaded through the fused program), Expand
projection lists multiply variants, and CoalesceBatches moves to the
stage input (row-wise ops commute with concatenation). Operators carrying
non-deterministic expressions (rand, monotonically_increasing_id) break
the chain — substitution would duplicate or re-order their draws.

Gated by ``sql.fusion.enabled`` / bounded by ``sql.fusion.maxOps``.
"""
from __future__ import annotations

import itertools
from typing import List, Optional, Tuple

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs import tpu_execs as te
from spark_rapids_tpu.execs.base import PhysicalExec
from spark_rapids_tpu.execs.expand_execs import TpuExpandExec
from spark_rapids_tpu.execs.fused_execs import (FUSED_BATCHES_SAVED,
                                                FusedAggregateStageExec,
                                                FusedStageExec, Variant)
from spark_rapids_tpu.exprs.core import BoundReference, Expression
from spark_rapids_tpu.exprs.misc import Alias
from spark_rapids_tpu.exprs.predicates import And

_CHAIN_TYPES = (te.TpuProjectExec, te.TpuFilterExec, TpuExpandExec,
                te.TpuCoalesceBatchesExec)


def _node_exprs(node: PhysicalExec) -> Tuple[Expression, ...]:
    if isinstance(node, te.TpuProjectExec):
        return tuple(node.exprs)
    if isinstance(node, te.TpuFilterExec):
        return (node.condition,)
    if isinstance(node, TpuExpandExec):
        return tuple(x for p in node.projections for x in p)
    return ()


def _fusable(node: PhysicalExec) -> bool:
    from spark_rapids_tpu.plan.overrides import _has_nondeterministic
    return (isinstance(node, _CHAIN_TYPES) and len(node.children) == 1
            and not any(_has_nondeterministic(e) for e in _node_exprs(node)))


def _identity_exprs(schema) -> Tuple[Expression, ...]:
    return tuple(BoundReference(i, f.dtype, f.nullable, f.name)
                 for i, f in enumerate(schema))


def _strip_alias(exprs) -> List[Expression]:
    return [a.c if isinstance(a, Alias) else a for a in exprs]


def _compose(ops: List[PhysicalExec], child: PhysicalExec, max_variants: int
             ) -> Optional[Tuple[Tuple[Variant, ...],
                                 Optional[Tuple[int, bool]]]]:
    """Normalize a top-down op chain into variants over ``child.output``.
    Returns None when the chain cannot be composed soundly — including
    when Expand fan-out exceeds ``max_variants``: every variant traces
    into the ONE stage program, so a wide cube/grouping-sets Expand would
    rebuild exactly the enormous-program hazard ``sql.fusion.maxOps``
    exists to bound."""
    from spark_rapids_tpu.plan.overrides import _substitute_refs
    variants: List[Variant] = [(_identity_exprs(child.output), None)]
    coalesce: Optional[Tuple[int, bool]] = None
    seen_real_op = False
    for node in reversed(ops):                      # bottom-up
        if isinstance(node, te.TpuCoalesceBatchesExec):
            if node.require_single and seen_real_op:
                # a require_single coalesce concats exactly what reaches it;
                # moving it below a filter/project would concat the RAW
                # input — the whole unfiltered table in one HBM batch when
                # the chain is selective. Not composable.
                return None
            if coalesce is None:
                coalesce = (node.target_bytes, node.require_single)
            else:
                coalesce = (min(coalesce[0], node.target_bytes),
                            coalesce[1] or node.require_single)
            continue
        seen_real_op = True
        new_variants: List[Variant] = []
        for exprs, pred in variants:
            repl = _strip_alias(exprs)
            if isinstance(node, te.TpuProjectExec):
                new_variants.append((
                    tuple(_substitute_refs(e, repl) for e in node.exprs),
                    pred))
            elif isinstance(node, te.TpuFilterExec):
                cond = _substitute_refs(node.condition, repl)
                new_variants.append(
                    (exprs, cond if pred is None else And(pred, cond)))
            else:                                   # TpuExpandExec
                for plist in node.projections:
                    new_variants.append((
                        tuple(_substitute_refs(e, repl) for e in plist),
                        pred))
        variants = new_variants
        if len(variants) > max_variants:
            return None
    if coalesce is not None and len(variants) > 1:
        # coalesce + Expand don't compose: unfused emits variant batches
        # interleaved per ARRIVING batch (b1v1, b1v2, b2v1, ...) while the
        # concat-first fused form would emit per-variant over the combined
        # input (b12v1, b12v2) — same rows, different ORDER, and fusion's
        # contract is bit-identity order included (a require_single
        # coalesce additionally must emit ONE batch, not one per variant)
        return None
    return tuple(variants), coalesce


def _saved_per_input_batch(ops: List[PhysicalExec]) -> int:
    """Intermediate batches the unfused chain would materialize per stage-
    program input batch: one per interior NON-coalesce operator output (an
    Expand multiplies the batches every op above it sees). A fused
    CoalesceBatches is excluded — its concat batch still materializes as
    the stage input (FusedStageExec._coalesced), so counting it as saved
    would overstate ``fusion_stats``' saved-batch count."""
    real = [n for n in ops
            if not isinstance(n, te.TpuCoalesceBatchesExec)]
    batches, saved = 1, 0
    for i, node in enumerate(reversed(real)):       # bottom-up
        if isinstance(node, TpuExpandExec):
            batches *= max(len(node.projections), 1)
        if i < len(real) - 1:                       # interior op output
            saved += batches
    return saved


def _op_display(ops) -> Tuple[Tuple[str, object], ...]:
    return tuple((type(n).__name__, n.output) for n in ops)


def _fold_aggregate(node: te.TpuHashAggregateExec, max_ops: int
                    ) -> Optional[FusedAggregateStageExec]:
    """The partial-aggregate fold as a fused stage (same substitution the
    fuse_device_ops pass applies when fusion is off, plus CoalesceBatches
    absorption — the aggregate concatenates its input anyway)."""
    from spark_rapids_tpu.plan.overrides import fold_aggregate_chain
    grouping, aggs, pre, child, folded = fold_aggregate_chain(
        node, te.TpuFilterExec, te.TpuProjectExec,
        coalesce_cls=te.TpuCoalesceBatchesExec, max_ops=max_ops)
    if not folded:
        return None
    return FusedAggregateStageExec(grouping, aggs, child, node.output,
                                   pre_filter=pre,
                                   fused_ops=_op_display(folded))


def fuse_stages(plan: PhysicalExec, conf: TpuConf) -> PhysicalExec:
    """The pass. Runs on the converted plan BEFORE transitions/pipeline
    insertion (chains exist as adjacent device execs there) and before
    fuse_device_ops (which then handles the CPU engine's fold plus device
    aggregates when fusion is off)."""
    if not conf.get(cfg.FUSION_ENABLED) or conf.get(cfg.MESH_ENABLED):
        return plan
    max_ops = max(2, conf.get(cfg.FUSION_MAX_OPS))

    def rec(node: PhysicalExec) -> PhysicalExec:
        if isinstance(node, te.TpuHashAggregateExec) and \
                not isinstance(node, FusedAggregateStageExec):
            folded = _fold_aggregate(node, max_ops)
            if folded is not None:
                node = folded
        elif _fusable(node):
            ops: List[PhysicalExec] = []
            cur = node
            while _fusable(cur) and len(ops) < max_ops:
                ops.append(cur)
                cur = cur.children[0]
            if len(ops) >= 2:
                composed = _compose(ops, cur, max_ops)
                if composed is not None:
                    variants, coalesce = composed
                    node = FusedStageExec(
                        _op_display(ops), variants, coalesce, cur,
                        ops[0].output,
                        saved_per_batch=_saved_per_input_batch(ops))
        return node.with_children([rec(c) for c in node.children])

    out = rec(plan)
    counter = itertools.count(1)
    for nd in iter_plan(out):
        if isinstance(nd, (FusedStageExec, FusedAggregateStageExec)):
            nd.stage_id = next(counter)             # display metadata
    return out


# ---------------------------------------------------------------- inspection
def iter_plan(plan: PhysicalExec):
    yield plan
    for c in plan.children:
        yield from iter_plan(c)


def fused_stages(plan: PhysicalExec) -> List[PhysicalExec]:
    return [n for n in iter_plan(plan)
            if isinstance(n, (FusedStageExec, FusedAggregateStageExec))]


def fusion_stats(plan: PhysicalExec) -> dict:
    """Static per-plan fusion accounting (introspection)."""
    stages = fused_stages(plan)
    ops = [len(s.fused_ops) + (1 if isinstance(s, FusedAggregateStageExec)
                               else 0) for s in stages]
    return {
        "fused_stages": len(stages),
        "fused_ops": sum(ops),
        "ops_per_fused_stage": (round(sum(ops) / len(ops), 3) if ops
                                else 0.0),
    }


def fused_batches_not_materialized(plan: PhysicalExec) -> int:
    """Executed-plan metric total: intermediate batches fusion elided."""
    return sum(s.metrics[FUSED_BATCHES_SAVED].value
               for s in fused_stages(plan))
