"""Physical-plan rewrite: single-device TPU operators -> mesh SPMD operators.

Runs after TpuOverrides (the GpuOverrides analog) when
``spark.rapids.tpu.sql.mesh.enabled`` is set: every maximal device subtree
over supported operators is lowered onto the session mesh, with
scatter/gather transitions at the boundaries. This is the step the reference
gets from Spark's task scheduler + RapidsShuffleInternalManager (distributing
the plan over executors); here distribution is a plan property, and the
exchanges are XLA collectives.

Lowering rules:
- upload transitions become mesh scatters; download boundaries gather;
- project/filter/sort/limit/union/exchange run per shard (ICI repartition
  where rows must move);
- hash aggregation is partial-per-shard, then either all-gather + replicated
  merge (small groupings, each shard keeping a slice) or a hash repartition
  of the partials + per-shard merge (large groupings) — mesh in, mesh out,
  so post-aggregation subtrees stay distributed;
- shuffled hash joins repartition both sides by key hash over the mesh;
  broadcast hash joins replicate the build batch;
- expand/generate run per shard (no movement); windows hash-repartition by
  their partition keys then evaluate per shard; writes emit one part file
  per shard through the shared commit protocol; range partitioning
  repartitions by sampled bounds;
- unsupported operators (unpartitioned windows, nested-loop join forms)
  fall back to single-device execution behind a gather — correctness first,
  with the boundary explicit in the plan.
"""
from __future__ import annotations

import logging
from typing import Optional

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs import tpu_execs as te
from spark_rapids_tpu.execs.base import PhysicalExec
from spark_rapids_tpu.execs import mesh_execs as me


def _is_mesh(node: PhysicalExec) -> bool:
    return getattr(node, "is_mesh", False)


def mesh_rewrite(plan: PhysicalExec, conf: TpuConf) -> PhysicalExec:
    """Lower device subtrees onto the session mesh (no-op when disabled or
    fewer than 2 devices).

    The collective mesh is clipped to ONE ICI domain (sql.mesh.requireIci):
    in-mesh all_to_all / all-gather exchanges ride the interconnect only;
    crossing a slice/process boundary (DCN) is the job of the
    fault-tolerant TCP shuffle stack (shuffle/tcp.py + retry/checksums),
    not of an XLA collective."""
    if not conf.get(cfg.MESH_ENABLED):
        return plan
    import jax
    from spark_rapids_tpu.parallel import placement as pl
    from spark_rapids_tpu.parallel.mesh import make_mesh
    devs = list(jax.devices())
    if conf.get(cfg.MESH_REQUIRE_ICI):
        devs = pl.largest_ici_group(devs)
    asked = conf.get(cfg.MESH_NUM_DEVICES)
    n = min(asked or len(devs), len(devs))
    if asked > n:
        logging.getLogger(__name__).warning(
            "%s=%d but one ICI domain here holds %d device(s); %s",
            cfg.MESH_NUM_DEVICES.key, asked, len(devs),
            f"sharding over {n}" if n >= 2
            else "keeping the single-device plan")
    if n < 2:
        return plan
    mesh = make_mesh(n, devices=devs)
    return _rewrite(plan, mesh, conf)


def _gathered(node: PhysicalExec, mesh) -> PhysicalExec:
    """Adapt a mesh producer for a consumer that needs DeviceBatch."""
    if isinstance(node, me.MeshScatterExec):
        # scatter-then-gather is a plain upload: collapse the round trip
        return te.HostToDeviceExec(node.children[0])
    if isinstance(node, me.MeshFileScatterExec):
        # a gathered file scan is just the chunked single-device scan
        scan = node.children[0]
        return (scan if getattr(scan, "is_device", False)
                else te.HostToDeviceExec(scan))
    if isinstance(node, me.MeshFromDeviceExec):
        return node.children[0]
    if isinstance(node, me.MeshWriteFilesExec):
        return node  # produces no rows; nothing to gather
    return me.MeshGatherExec(node, mesh) if _is_mesh(node) else node


def _meshed(node: PhysicalExec, mesh) -> Optional[PhysicalExec]:
    """Adapt a node for a consumer that needs MeshBatch: mesh producers pass
    through; single-device producers are scattered; host producers (CPU
    execs) return None (caller decides)."""
    if _is_mesh(node):
        return node
    if getattr(node, "is_device", False):
        return me.MeshFromDeviceExec(node, mesh)
    return None


def _rewrite(node: PhysicalExec, mesh, conf=None) -> PhysicalExec:
    from spark_rapids_tpu.execs.exchange_execs import (HashPartitioning,
                                                       RoundRobinPartitioning,
                                                       TpuBroadcastExchangeExec,
                                                       TpuShuffleExchangeExec)
    from spark_rapids_tpu.execs.join_execs import (_NestedLoopMixin,
                                                   TpuBroadcastHashJoinExec,
                                                   TpuShuffledHashJoinExec)

    kids = [_rewrite(c, mesh, conf) for c in node.children]

    # ---- scans --------------------------------------------------------------
    if getattr(node, "is_file_scan", False) and getattr(node, "is_device",
                                                        False):
        # device file scan: shard-local reads straight onto the mesh, with
        # the row-group -> shard split decided HERE at plan time
        return me.MeshFileScatterExec(node, mesh,
                                      me.plan_scan_shards(node, mesh, conf))

    # ---- transitions --------------------------------------------------------
    if isinstance(node, te.HostToDeviceExec):
        if getattr(kids[0], "is_file_scan", False):
            return me.MeshFileScatterExec(
                kids[0], mesh, me.plan_scan_shards(kids[0], mesh, conf))
        return me.MeshScatterExec(kids[0], mesh)
    if isinstance(node, te.DeviceToHostExec):
        return te.DeviceToHostExec(_gathered(kids[0], mesh))

    # ---- pass-through / drop ------------------------------------------------
    if isinstance(node, te.TpuCoalesceBatchesExec) and _is_mesh(kids[0]):
        return kids[0]

    # ---- row-parallel -------------------------------------------------------
    if isinstance(node, te.TpuProjectExec) and _is_mesh(kids[0]):
        return me.MeshProjectExec(node.exprs, kids[0], mesh)
    if isinstance(node, te.TpuFilterExec) and _is_mesh(kids[0]):
        return me.MeshFilterExec(node.condition, kids[0], mesh)

    # ---- expand/generate ----------------------------------------------------
    from spark_rapids_tpu.execs.expand_execs import TpuExpandExec
    from spark_rapids_tpu.execs.generate_execs import TpuGenerateExec
    if isinstance(node, TpuExpandExec) and _is_mesh(kids[0]):
        cls = (me.MeshGenerateExec if isinstance(node, TpuGenerateExec)
               else me.MeshExpandExec)
        return cls(node.projections, kids[0], node.output, mesh)

    # ---- window -------------------------------------------------------------
    from spark_rapids_tpu.execs.window_execs import TpuWindowExec
    from spark_rapids_tpu.exprs.misc import Alias
    if isinstance(node, TpuWindowExec) and _is_mesh(kids[0]):
        first = (node.wexprs[0].c if isinstance(node.wexprs[0], Alias)
                 else node.wexprs[0])
        if first.part_keys:
            return me.MeshWindowExec(node.wexprs, kids[0], mesh)
        # unpartitioned window: one global frame — single device, like
        # Spark's single-partition requirement (falls through to gather)

    # ---- writes -------------------------------------------------------------
    from spark_rapids_tpu.io.write_exec import TpuWriteFilesExec
    if isinstance(node, TpuWriteFilesExec) and _is_mesh(kids[0]):
        return me.MeshWriteFilesExec(node.spec, kids[0], mesh)

    # ---- aggregation --------------------------------------------------------
    if isinstance(node, te.TpuHashAggregateExec) and _is_mesh(kids[0]):
        return me.MeshHashAggregateExec(node.grouping, node.aggregates,
                                        kids[0], node.output, mesh,
                                        node.pre_filter)

    # ---- joins --------------------------------------------------------------
    if isinstance(node, _NestedLoopMixin):
        pass  # brute-force forms stay single-device (fall through to gather)
    elif isinstance(node, TpuBroadcastHashJoinExec):
        bi = 0 if node.build_side == "left" else 1
        si = 1 - bi
        build = kids[bi]
        if isinstance(build, TpuBroadcastExchangeExec):
            build = build.with_children([_gathered(build.children[0], mesh)])
        smesh = _meshed(kids[si], mesh)
        if smesh is not None:
            ordered = [None, None]
            ordered[bi], ordered[si] = build, smesh
            return me.MeshBroadcastHashJoinExec(
                ordered[0], ordered[1], node.how, node.left_keys,
                node.right_keys, node.output, mesh, node.condition,
                node.build_side)
        kids = list(kids)
        kids[bi] = build
    elif isinstance(node, TpuShuffledHashJoinExec):
        lm = _meshed(kids[0], mesh)
        rm = _meshed(kids[1], mesh)
        if lm is not None and rm is not None and (
                _is_mesh(kids[0]) or _is_mesh(kids[1])):
            return me.MeshShuffledHashJoinExec(
                lm, rm, node.how, tuple(node.left_keys),
                tuple(node.right_keys), node.output, mesh, node.condition,
                node.build_side)

    # ---- sort/limit/union ---------------------------------------------------
    if isinstance(node, te.TpuSortExec) and _is_mesh(kids[0]):
        from spark_rapids_tpu.execs.exchange_execs import RangePartitioning
        pre = (isinstance(kids[0], me.MeshShuffleExchangeExec)
               and isinstance(kids[0].partitioning, RangePartitioning)
               and tuple(kids[0].partitioning.orders) == tuple(node.orders))
        return me.MeshSortExec(node.orders, kids[0], mesh,
                               pre_partitioned=pre)
    if isinstance(node, te.TpuLimitExec) and _is_mesh(kids[0]):
        return me.MeshLimitExec(node.n, kids[0], mesh)
    if isinstance(node, te.TpuUnionExec) and (
            _is_mesh(kids[0]) or _is_mesh(kids[1])):
        lm = _meshed(kids[0], mesh)
        rm = _meshed(kids[1], mesh)
        if lm is not None and rm is not None:
            return me.MeshUnionExec(lm, rm, mesh)

    # ---- exchanges ----------------------------------------------------------
    if isinstance(node, TpuShuffleExchangeExec) and _is_mesh(kids[0]):
        from spark_rapids_tpu.execs.exchange_execs import RangePartitioning
        part = node.partitioning
        if isinstance(part, (HashPartitioning, RoundRobinPartitioning,
                             RangePartitioning)):
            return me.MeshShuffleExchangeExec(part, kids[0], mesh)
        return me.MeshGatherExec(kids[0], mesh)
    if isinstance(node, TpuBroadcastExchangeExec):
        return node.with_children([_gathered(kids[0], mesh)])

    # ---- everything else: gather mesh children ------------------------------
    new_kids = [_gathered(c, mesh) for c in kids]
    if all(a is b for a, b in zip(new_kids, node.children)):
        return node
    return node.with_children(new_kids)
