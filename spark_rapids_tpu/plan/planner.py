"""Logical -> CPU physical planning, with expression binding.

The stand-in for Spark's SparkPlanner: produces the CPU physical plan that
TpuOverrides then rewrites. Expressions are bound to child-output ordinals here
(GpuBindReferences analog) so both engines evaluate ordinal references.
"""
from __future__ import annotations

from typing import Tuple

from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs import cpu_execs as ce
from spark_rapids_tpu.execs.base import PhysicalExec
from spark_rapids_tpu.exprs.core import Expression, bind_expression
from spark_rapids_tpu.exprs.misc import Alias, SortOrder
from spark_rapids_tpu.io.parquet import CpuParquetScanExec
from spark_rapids_tpu.plan import logical as lp


def plan_physical(plan: lp.LogicalPlan, conf: TpuConf,
                  note=None) -> PhysicalExec:
    """Column pruning + plan + EnsureRequirements (distribution requirements
    are satisfied by inserting single-partition exchanges, Spark's
    EnsureRequirements role). ``note``: the ``plan`` span's, told how far the
    pruning engaged."""
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.plan.pruning import prune_columns
    # before the UDF compiler: it binds UDF arguments to ordinals of the
    # child's schema, which must be the narrowed one
    plan, scan_columns, kept = prune_columns(plan)
    if note is not None:
        note(scan_columns=scan_columns, scan_columns_kept=kept)
    if conf.get(cfg.UDF_COMPILER_ENABLED):
        from spark_rapids_tpu.udf import compile_plan_udfs
        plan = compile_plan_udfs(plan)
    plan = _resolve_input_file_meta(plan)
    return ensure_requirements(_plan_node(plan, conf))


def _resolve_input_file_meta(plan: lp.LogicalPlan) -> lp.LogicalPlan:
    """When any expression references input-file metadata
    (InputFileName/BlockStart/BlockLength), flip every file scan below to
    emit the hidden per-file columns; binding then resolves the markers to
    those columns (GpuInputFileBlock.scala riding the scan's metadata)."""
    import dataclasses
    from spark_rapids_tpu.exprs.core import Expression
    from spark_rapids_tpu.exprs.misc import _InputFileMeta

    def expr_has(e: Expression) -> bool:
        if isinstance(e, _InputFileMeta):
            return True
        return any(expr_has(c) for c in e.children)

    def any_exprs(obj, depth=0) -> bool:
        if isinstance(obj, Expression):
            return expr_has(obj)
        if depth > 3:
            return False
        if isinstance(obj, (tuple, list)):
            return any(any_exprs(x, depth + 1) for x in obj)
        if dataclasses.is_dataclass(obj) and not isinstance(
                obj, (lp.LogicalPlan, type)):
            return any(any_exprs(getattr(obj, f.name), depth + 1)
                       for f in dataclasses.fields(obj))
        return False

    def node_uses_meta(node: lp.LogicalPlan) -> bool:
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, lp.LogicalPlan):
                continue
            if any_exprs(v):
                return True
        return any(node_uses_meta(c) for c in node.children)

    if not node_uses_meta(plan):
        return plan

    from spark_rapids_tpu.exprs.core import UnresolvedAttribute
    from spark_rapids_tpu.exprs.literals import Literal
    from spark_rapids_tpu.exprs.misc import Alias, INPUT_FILE_META_SPEC
    meta_cols = tuple(n for n, _d, _v in INPUT_FILE_META_SPEC)

    def with_default_meta(child: lp.LogicalPlan) -> lp.LogicalPlan:
        """Union branches without a file scan get Spark's defaults ('' / -1,
        InputFileBlockHolder's initial state) so branch schemas align."""
        exprs = [Alias(UnresolvedAttribute(n), n)
                 for n in child.schema().names()]
        exprs.extend(Alias(Literal(default, dtype), name)
                     for name, dtype, default in INPUT_FILE_META_SPEC)
        return lp.Project(tuple(exprs), child)

    def flip(node: lp.LogicalPlan) -> lp.LogicalPlan:
        if isinstance(node, lp.FileScan):
            return dataclasses.replace(node, with_file_meta=True)
        kids = [flip(c) for c in node.children]
        extended = False
        if isinstance(node, lp.Project):
            # thread the hidden columns THROUGH intervening projections so
            # metadata above a select()/withColumn() still resolves
            have = set(kids[0].schema().names())
            mine = {e.name_hint for e in node.exprs}
            passthrough = tuple(
                Alias(UnresolvedAttribute(n), n) for n in meta_cols
                if n in have and n not in mine)
            if passthrough:
                node = dataclasses.replace(
                    node, exprs=tuple(node.exprs) + passthrough)
                extended = True
        if isinstance(node, lp.Union):
            # every branch must agree on the hidden columns
            if any(meta_cols[0] in k.schema().names() for k in kids):
                kids = [k if meta_cols[0] in k.schema().names()
                        else with_default_meta(k) for k in kids]
        if not extended and all(
                a is b for a, b in zip(kids, node.children)):
            return node
        return lp.with_children(node, kids)

    out = flip(plan)
    # the hidden columns must never surface in user-visible output (they
    # exist only for the markers to bind against): strip any that reached
    # the root — incl. join-duplicate renames (__input_file_name_1 ...)
    root_names = out.schema().names()
    visible = [n for n in root_names if not n.startswith("__input_file_")]
    if len(visible) != len(root_names):
        out = lp.Project(tuple(Alias(UnresolvedAttribute(n), n)
                               for n in visible), out)
    return out


def ensure_requirements(plan: PhysicalExec) -> PhysicalExec:
    from spark_rapids_tpu.execs.exchange_execs import (
        BroadcastExchangeExecBase, CpuBroadcastExchangeExec,
        CpuShuffleExchangeExec, RangePartitioning, SinglePartitioning)
    from spark_rapids_tpu.execs.join_execs import (CpuBroadcastHashJoinExec,
                                                   CpuHashJoinExec,
                                                   CpuNestedLoopJoinExec)
    from spark_rapids_tpu.execs.window_execs import CpuWindowExec
    single_required = (ce.CpuHashAggregateExec, ce.CpuLimitExec,
                       CpuHashJoinExec, CpuWindowExec)

    def fix(node: PhysicalExec) -> PhysicalExec:
        if isinstance(node, (CpuBroadcastHashJoinExec, CpuNestedLoopJoinExec)):
            # broadcast distribution on the build side only; the stream side
            # keeps its partitioning (BroadcastDistribution requirement)
            bi = 0 if node.build_side == "left" else 1
            build = node.children[bi]
            if not isinstance(build, BroadcastExchangeExecBase):
                new_children = list(node.children)
                new_children[bi] = CpuBroadcastExchangeExec(build)
                return node.with_children(new_children)
            return node
        if isinstance(node, ce.CpuSortExec):
            # global sort over partitioned input = range exchange +
            # per-partition sort (Spark's SortExec + RangePartitioning shape;
            # downstream consumers read partitions in order)
            child = node.children[0]
            if child.num_partitions > 1:
                exchange = CpuShuffleExchangeExec(
                    RangePartitioning(child.num_partitions, node.orders), child)
                return node.with_children([exchange])
            return node
        if not isinstance(node, single_required):
            return node
        new_children = [
            CpuShuffleExchangeExec(SinglePartitioning(), c)
            if c.num_partitions > 1 else c for c in node.children]
        if all(a is b for a, b in zip(new_children, node.children)):
            return node
        return node.with_children(new_children)

    return plan.transform_up(fix)


def _plan_node(plan: lp.LogicalPlan, conf: TpuConf) -> PhysicalExec:
    if isinstance(plan, lp.LocalRelation):
        return ce.CpuLocalScanExec(plan.table, conf.string_max_bytes)
    if isinstance(plan, lp.Range):
        return ce.CpuRangeExec(plan.start, plan.end, plan.step)
    if isinstance(plan, lp.CachedRelation):
        from spark_rapids_tpu.execs.cache_execs import CpuCachedScanExec
        return CpuCachedScanExec(plan.entry, plan.schema())
    if isinstance(plan, lp.FileScan):
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.io.datasource import PartitionedFile
        files = plan.files or tuple(PartitionedFile(p) for p in plan.paths)
        scan_schema = plan.schema()   # + hidden input-file meta when asked
        if plan.fmt == "parquet":
            return CpuParquetScanExec(
                files, scan_schema, plan.partition_schema, plan.filters,
                conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS),
                conf.get(cfg.MAX_READER_BATCH_SIZE_BYTES))
        if plan.fmt == "csv":
            from spark_rapids_tpu.io.csv import CpuCsvScanExec
            return CpuCsvScanExec(files, scan_schema, dict(plan.options),
                                  plan.partition_schema)
        if plan.fmt == "orc":
            from spark_rapids_tpu.io.orc import CpuOrcScanExec
            return CpuOrcScanExec(
                files, scan_schema, plan.partition_schema, plan.filters,
                conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS),
                conf.get(cfg.MAX_READER_BATCH_SIZE_BYTES))
        raise ValueError(f"unsupported format {plan.fmt}")
    if isinstance(plan, lp.WriteFiles):
        from spark_rapids_tpu.io.write_exec import CpuWriteFilesExec
        return CpuWriteFilesExec(plan.spec, _plan_node(plan.child, conf))
    if isinstance(plan, lp.Filter) and isinstance(plan.child, lp.FileScan) \
            and plan.child.fmt in ("parquet", "orc"):
        # predicate pushdown: pushable conjuncts clip parquet row groups; the
        # Filter itself stays as the exact row-level net (Spark keeps both too)
        from dataclasses import replace
        from spark_rapids_tpu.io.datasource import is_pushable, split_conjuncts
        pushed = tuple(c for c in split_conjuncts(plan.condition)
                       if is_pushable(c))
        if pushed:
            scan = replace(plan.child,
                           filters=plan.child.filters + pushed)
            plan = lp.Filter(plan.condition, scan)
        child = _plan_node(plan.child, conf)
        return ce.CpuFilterExec(bind_expression(plan.condition, child.output),
                                child)
    if isinstance(plan, lp.Project):
        child = _plan_node(plan.child, conf)
        cs = child.output
        bound = tuple(_named(bind_expression(e, cs), e) for e in plan.exprs)
        return ce.CpuProjectExec(bound, child)
    if isinstance(plan, lp.Filter):
        child = _plan_node(plan.child, conf)
        return ce.CpuFilterExec(bind_expression(plan.condition, child.output), child)
    if isinstance(plan, lp.Aggregate):
        child = _plan_node(plan.child, conf)
        cs = child.output
        grouping = tuple(bind_expression(e, cs) for e in plan.grouping)
        aggs = tuple(_named(bind_expression(e, cs), e) for e in plan.aggregates)
        return ce.CpuHashAggregateExec(grouping, aggs, child, plan.schema())
    if isinstance(plan, lp.Sort):
        child = _plan_node(plan.child, conf)
        orders = tuple(
            SortOrder(bind_expression(o.child, child.output), o.ascending,
                      o.nulls_first) for o in plan.orders)
        return ce.CpuSortExec(orders, child)
    if isinstance(plan, lp.Expand):
        from spark_rapids_tpu.execs.expand_execs import CpuExpandExec
        child = _plan_node(plan.child, conf)
        projs = tuple(tuple(bind_expression(e, child.output) for e in p)
                      for p in plan.projections)
        return CpuExpandExec(projs, child, plan.schema())
    if isinstance(plan, lp.Generate):
        from spark_rapids_tpu.execs.generate_execs import (
            CpuGenerateExec, generate_projections)
        child = _plan_node(plan.child, conf)
        elements = tuple(bind_expression(e, child.output)
                         for e in plan.elements)
        out = plan.schema()
        projs = generate_projections(child.output, elements, plan.pos, out)
        return CpuGenerateExec(projs, child, out)
    if isinstance(plan, lp.Window):
        from spark_rapids_tpu.execs.window_execs import CpuWindowExec
        child = _plan_node(plan.child, conf)
        bound = tuple(_named(bind_expression(e, child.output), e)
                      for e in plan.wexprs)
        return CpuWindowExec(bound, child)
    if isinstance(plan, lp.Limit):
        return ce.CpuLimitExec(plan.n, _plan_node(plan.child, conf))
    if isinstance(plan, lp.Union):
        return ce.CpuUnionExec(_plan_node(plan.left, conf),
                               _plan_node(plan.right, conf))
    if isinstance(plan, lp.Join):
        from spark_rapids_tpu.columnar.dtypes import DType
        from spark_rapids_tpu.execs.join_execs import CpuHashJoinExec
        from spark_rapids_tpu.exprs.cast import Cast
        left = _plan_node(plan.left, conf)
        right = _plan_node(plan.right, conf)
        lkeys = [bind_expression(e, left.output) for e in plan.left_keys]
        rkeys = [bind_expression(e, right.output) for e in plan.right_keys]
        # Catalyst-style key coercion: both sides of each key pair must share a
        # type or equal keys can land in different sort groups
        for i, (lk, rk) in enumerate(zip(lkeys, rkeys)):
            ct = DType.common_type(lk.dtype(), rk.dtype())
            if lk.dtype() != ct:
                lkeys[i] = Cast(lk, ct)
            if rk.dtype() != ct:
                rkeys[i] = Cast(rk, ct)
        out_schema = plan.schema()
        cond = (bind_expression(plan.condition, out_schema)
                if plan.condition is not None else None)
        if cond is not None and plan.how != "inner":
            # post-join filtering is only equivalent to a join condition for
            # inner joins (the reference's tagJoin has the same restriction)
            raise NotImplementedError(
                f"join conditions are only supported for inner joins, not "
                f"{plan.how}")
        return _select_join(left, right, plan.how, tuple(lkeys), tuple(rkeys),
                            out_schema, cond, conf)
    if isinstance(plan, lp.Repartition):
        from spark_rapids_tpu.execs.exchange_execs import (
            CpuShuffleExchangeExec, HashPartitioning, RoundRobinPartitioning)
        child = _plan_node(plan.child, conf)
        if plan.keys:
            keys = tuple(bind_expression(e, child.output) for e in plan.keys)
            part = HashPartitioning(plan.num_partitions, keys)
        else:
            part = RoundRobinPartitioning(plan.num_partitions)
        return CpuShuffleExchangeExec(part, child)
    raise NotImplementedError(f"no physical plan for {type(plan).__name__}")


def _select_join(left: PhysicalExec, right: PhysicalExec, how: str,
                 lkeys: Tuple[Expression, ...], rkeys: Tuple[Expression, ...],
                 out_schema: Schema, cond, conf: TpuConf) -> PhysicalExec:
    """Join strategy selection (Spark JoinSelection role): broadcast hash join
    when a legal build side's estimated size is under the threshold, shuffled
    hash join otherwise; keyless joins become broadcast nested-loop or
    cartesian product."""
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.execs.join_execs import (CpuBroadcastHashJoinExec,
                                                   CpuCartesianProductExec,
                                                   CpuHashJoinExec,
                                                   CpuNestedLoopJoinExec)
    threshold = conf.get(cfg.BROADCAST_JOIN_THRESHOLD)

    def broadcastable(side: PhysicalExec) -> bool:
        sz = side.size_estimate()
        return sz is not None and sz <= threshold

    from spark_rapids_tpu.execs.join_execs import legal_broadcast_sides
    _sides = legal_broadcast_sides(how)
    can_build_right = 1 in _sides
    can_build_left = 0 in _sides
    if not lkeys:
        if how not in ("inner", "cross"):
            raise NotImplementedError(
                f"{how} join requires join keys (no nested-loop form)")
        if can_build_right and broadcastable(right):
            return CpuNestedLoopJoinExec(left, right, how, out_schema, cond,
                                         build_side="right")
        if can_build_left and broadcastable(left):
            return CpuNestedLoopJoinExec(left, right, how, out_schema, cond,
                                         build_side="left")
        return CpuCartesianProductExec(left, right, how, out_schema, cond)
    if can_build_right and broadcastable(right):
        return CpuBroadcastHashJoinExec(left, right, how, lkeys, rkeys,
                                        out_schema, cond, build_side="right")
    if can_build_left and broadcastable(left):
        return CpuBroadcastHashJoinExec(left, right, how, lkeys, rkeys,
                                        out_schema, cond, build_side="left")
    return CpuHashJoinExec(left, right, how, lkeys, rkeys, out_schema, cond)


def _named(bound: Expression, original: Expression) -> Expression:
    """Preserve the user-facing name through binding."""
    if isinstance(bound, Alias):
        return bound
    name = original.name_hint
    return Alias(bound, name)
