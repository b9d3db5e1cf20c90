"""Planner pass: mark operators that may execute on the encoded domain.

The compressed columnar path (columnar/encoding.py) delivers scan batches
whose columns still carry their dictionary encoding. This pass walks the
FINAL physical plan (after conversion, transitions, and pipeline insertion)
and flags the filter/aggregate/join execs whose input chain can actually
deliver such batches — so the runtime rewrite (exprs/encoded.py) only ever
runs where an encoding can exist, and ``explain`` can report how many
operators were planned onto the encoded domain.

The flag is an upper bound, not a promise: the exec still checks each
batch's columns at runtime (per-column fallback when an encoding did not
survive upload or a coalesce of unrelated dictionary streams dropped it).
"""
from __future__ import annotations

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs import tpu_execs as te
from spark_rapids_tpu.execs.base import PhysicalExec


def _preserves_encoding(node: PhysicalExec) -> bool:
    """Can this subtree yield batches with surviving dictionary encodings?
    Sources: device file scans (the parquet page reader) and upload
    transitions (user tables may hold pa.DictionaryArray columns).
    Pass-through: the pipeline wrapper, coalesce (concat carries same-token
    encodings), and unions of sources. Everything else rebuilds columns
    through kernels, which drops the encoded form."""
    from spark_rapids_tpu.execs.pipeline import PipelinedExec
    if getattr(node, "is_file_scan", False) and node.is_device:
        return True
    if isinstance(node, te.HostToDeviceExec):
        return True
    try:
        from spark_rapids_tpu.execs.cache_execs import TpuCachedScanExec
        if isinstance(node, TpuCachedScanExec):
            return True
    except ImportError:     # pragma: no cover - cache execs always present
        pass
    if isinstance(node, (PipelinedExec, te.TpuCoalesceBatchesExec,
                         te.TpuUnionExec)):
        return any(_preserves_encoding(c) for c in node.children)
    return False


def mark_encoded_domain(plan: PhysicalExec, conf: TpuConf) -> PhysicalExec:
    """Set ``encoded_domain_ok`` on every eligible operator; returns the
    plan (mutated in place — the flag is execution metadata, not plan
    structure). No-op when sql.encodedDomain.enabled is off or the plan
    runs under a mesh (mesh execs have their own sharded programs)."""
    if not conf.get(cfg.ENCODED_DOMAIN) or conf.get(cfg.MESH_ENABLED):
        return plan
    from spark_rapids_tpu.execs.fused_execs import FusedStageExec
    from spark_rapids_tpu.execs.join_execs import TpuShuffledHashJoinExec

    def walk(node: PhysicalExec) -> None:
        for c in node.children:
            walk(c)
        if isinstance(node, (te.TpuFilterExec, te.TpuHashAggregateExec)):
            # incl. FusedAggregateStageExec: the fused partial aggregate
            # keeps the inherited encoded-domain grouping/pre-filter rewrite
            if _preserves_encoding(node.children[0]):
                node.encoded_domain_ok = True
        elif isinstance(node, FusedStageExec) and node.has_predicate:
            # a fused chain's composed predicate is over the stage INPUT
            # schema, so it rewrites onto dictionary indices exactly like a
            # standalone filter's would
            if _preserves_encoding(node.children[0]):
                node.encoded_domain_ok = True
        elif isinstance(node, TpuShuffledHashJoinExec):
            if any(_preserves_encoding(c) for c in node.children):
                node.encoded_domain_ok = True

    walk(plan)
    return plan


def count_encoded_domain(plan: PhysicalExec) -> int:
    """Operators planned onto the encoded domain (introspection)."""
    n = 0

    def walk(node: PhysicalExec) -> None:
        nonlocal n
        if getattr(node, "encoded_domain_ok", False):
            n += 1
        for c in node.children:
            walk(c)

    walk(plan)
    return n
