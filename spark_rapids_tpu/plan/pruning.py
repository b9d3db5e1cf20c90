"""Planner pass: column pruning over the logical plan.

The role Catalyst's ``ColumnPruning`` fills for the reference, which gets
narrow ``readSchema``s and ``Project``s from Spark's optimized plan for
free: every operator carries only the columns something above it reads.

Top-down, each node is told which of its output columns its parent needs
(the root needs all of its own); bottom-up the plan is rebuilt. A file scan
is narrowed in place, through its ``read_schema`` (which the parquet, ORC
and CSV readers turn into the columns they decode). Any other node whose
output is wider than what is needed of it, under a parent that would carry
the surplus along (filter, sort, limit, repartition, join, window,
generate), gets a ``Project`` of plain attribute references on top. An
in-memory table is never narrowed itself: the scan cache keys the uploaded
batch by the table's identity, so the resident batch stays whole and shared
by every query, and the ``Project`` above it selects.

Where the pass cannot reason it asks for every column, which is the plan as
it was: a node type it does not know, a ``Union`` (positional), a
``WriteFiles``, a ``Join`` whose sides share a column name (the ``name_1``
renaming of ``lp.Join.schema`` depends on both sides' columns), and any
node whose expressions hold an ordinal reference. It changes no row count,
no expression and no order of evaluation.
"""
from __future__ import annotations

from dataclasses import is_dataclass, replace
from typing import (Dict, FrozenSet, Iterable, Optional, Sequence, Tuple)

from spark_rapids_tpu.columnar.dtypes import Schema, row_width
from spark_rapids_tpu.exprs.core import (BoundReference, Expression,
                                         UnresolvedAttribute)
from spark_rapids_tpu.plan import logical as lp

#: a node's output columns that its parent needs; None: all of them
Required = Optional[FrozenSet[str]]

#: output = the child's columns (a window's: plus its own), so a wider
#: child costs them a sort, a gather or an exchange per column; beside each,
#: the expressions it reads on top of what passes through
_CARRIES = {
    lp.Filter: lambda n: (n.condition,),
    lp.Sort: lambda n: n.orders,
    lp.Limit: lambda n: (),
    lp.Repartition: lambda n: n.keys,
    lp.Window: lambda n: n.wexprs,
    lp.Generate: lambda n: n.elements,
}
#: output = what their expressions compute: the child owes what those read,
#: whatever the parent asked for
_COMPUTES = {
    lp.Project: lambda n: n.exprs,
    lp.Aggregate: lambda n: tuple(n.grouping) + tuple(n.aggregates),
    lp.Expand: lambda n: tuple(e for p in n.projections for e in p),
}


def prune_columns(plan: lp.LogicalPlan) -> Tuple[lp.LogicalPlan, int, int]:
    """The pass. Returns the rebuilt plan, the columns the plan's scans
    have between them, and how many of those leave the scan (or the
    ``Project`` directly above it)."""
    pruner = _Pruner()
    out = pruner.prune(plan, None, False)
    return out, pruner.scan_columns, pruner.scan_columns_kept


def _references(exprs: Iterable[Optional[Expression]]) -> Required:
    """The column names the expressions read; None (every column) when one
    of them holds a reference by ordinal, which a narrower child would
    shift."""
    names = set()
    stack = [e for e in exprs if e is not None]
    while stack:
        e = stack.pop()
        if isinstance(e, BoundReference):
            return None
        if isinstance(e, UnresolvedAttribute):
            names.add(e.name)
        stack.extend(e.children)
    return frozenset(names)


def _union(*sets: Required) -> Required:
    return None if None in sets else frozenset().union(*sets)


def _kept(names: Sequence[str], required: FrozenSet[str], schema
          ) -> Tuple[str, ...]:
    """The required ones of ``names``, in their order; never none of them,
    so that a bare ``count(*)`` still has rows to count: the narrowest
    column of ``schema()`` (called for that alone) stands in."""
    keep = tuple(n for n in names if n in required)
    if not keep and names:
        keep = (min(schema(), key=lambda f: row_width(Schema([f]))).name,)
    return keep


class _Pruner:
    def __init__(self):
        self.scan_columns = 0
        self.scan_columns_kept = 0
        #: id(node) -> (node, its output names), so that no node's
        #: ``schema()`` binds every expression below it once per ancestor
        self._names: Dict[int, Tuple[lp.LogicalPlan, Tuple[str, ...]]] = {}

    def names(self, node: lp.LogicalPlan) -> Tuple[str, ...]:
        if id(node) in self._names:
            return self._names[id(node)][1]
        if isinstance(node, (lp.Filter, lp.Sort, lp.Limit, lp.Repartition)):
            got = self.names(node.child)
        elif isinstance(node, (lp.Project, lp.Aggregate)):
            got = tuple(e.name_hint for e in _COMPUTES[type(node)](node))
        elif isinstance(node, lp.Join) and not (
                set(self.names(node.left)) & set(self.names(node.right))):
            got = self.names(node.left)
            if node.how not in ("left_semi", "left_anti"):
                got = got + self.names(node.right)
        else:
            got = tuple(node.schema().names())
        self._names[id(node)] = (node, got)
        return got

    def prune(self, node: lp.LogicalPlan, required: Required,
              carried: bool) -> lp.LogicalPlan:
        """Rebuild ``node`` to give at least its ``required`` output
        columns. ``carried``: the parent passes them through, so a surplus
        is cut off with a ``Project``."""
        if isinstance(node, lp.FileScan):
            out = self._narrow_scan(node, required)
            self.scan_columns += len(node.read_schema)
            self.scan_columns_kept += len(out.read_schema)
            return out      # as narrow as it gets: nothing to put on top
        out = self._rebuild(node, required) if node.children else node
        if node.children and (required is None or not carried):
            return out
        names = self.names(out)
        keep = names if required is None else _kept(names, required,
                                                    out.schema)
        if not node.children:
            self.scan_columns += len(names)
            self.scan_columns_kept += len(keep)
        if carried and len(keep) < len(names):
            out = lp.Project(tuple(UnresolvedAttribute(n) for n in keep), out)
        return out

    @staticmethod
    def _narrow_scan(scan: lp.FileScan, required: Required) -> lp.FileScan:
        """Required columns in file order; partition columns stay (they
        cost no read), and so does one data column at least."""
        if required is None:
            return scan
        parts = {f.name for f in scan.partition_schema}
        data = [f for f in scan.read_schema if f.name not in parts]
        keep = parts.union(_kept([f.name for f in data], required,
                                 lambda: data))
        if len(keep) == len(scan.read_schema):
            return scan
        return replace(scan, read_schema=Schema(
            [f for f in scan.read_schema if f.name in keep]))

    def _rebuild(self, node: lp.LogicalPlan, required: Required
                 ) -> lp.LogicalPlan:
        if type(node) in _CARRIES:
            child = self.prune(node.child, _union(
                required, _references(_CARRIES[type(node)](node))), True)
        elif type(node) in _COMPUTES:
            child = self.prune(
                node.child, _references(_COMPUTES[type(node)](node)), False)
        elif isinstance(node, lp.Join):
            return self._rebuild_join(node, required)
        else:               # Union, WriteFiles, a node the pass does not know
            return _with_children(node, [self.prune(c, None, False)
                                         for c in node.children])
        return node if child is node.child else replace(node, child=child)

    def _rebuild_join(self, node: lp.Join, required: Required
                      ) -> lp.LogicalPlan:
        left = frozenset(self.names(node.left))
        right = frozenset(self.names(node.right))
        above = _union(required, _references((node.condition,)))
        if (left & right) or above is None:
            lreq = rreq = None
        else:
            # distinct names: an output column, and a name the condition
            # reads from the joined schema, belongs to exactly one side
            lreq = _union(above & left, _references(node.left_keys))
            rreq = _union(above & right, _references(node.right_keys))
        return _with_children(node, [self.prune(node.left, lreq, True),
                                     self.prune(node.right, rreq, True)])


def _with_children(node: lp.LogicalPlan, kids: Sequence[lp.LogicalPlan]
                   ) -> lp.LogicalPlan:
    if all(a is b for a, b in zip(kids, node.children)) \
            or not is_dataclass(node):
        return node
    return lp.with_children(node, kids)
