"""Logical plan nodes produced by the DataFrame API.

The stand-in for Catalyst's optimized logical plan: the session plans these into a
CPU physical plan (the "Spark CPU plan"), which the overrides engine then rewrites
onto the TPU (plan/overrides.py) — preserving the reference's architecture where
acceleration is a *physical plan* rewrite, not a frontend.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields, replace
from typing import Optional, Sequence, Tuple

import pyarrow as pa

from spark_rapids_tpu.columnar.dtypes import DType, Field, Schema
from spark_rapids_tpu.exprs.core import Expression
from spark_rapids_tpu.exprs.misc import Alias, SortOrder


class LogicalPlan:
    @property
    def children(self) -> Tuple["LogicalPlan", ...]:
        return ()

    def schema(self) -> Schema:
        raise NotImplementedError


def with_children(node: LogicalPlan, kids: Sequence[LogicalPlan]
                  ) -> LogicalPlan:
    """``node`` (a dataclass) with its child plans replaced by ``kids``, in
    the order of its fields, which is the order of ``children``."""
    it = iter(kids)
    reps = {}
    for f in fields(node):
        v = getattr(node, f.name)
        if isinstance(v, LogicalPlan):
            reps[f.name] = next(it)
        elif isinstance(v, tuple) and v and all(
                isinstance(x, LogicalPlan) for x in v):
            reps[f.name] = tuple(next(it) for _ in v)
    return replace(node, **reps)


@dataclass
class LocalRelation(LogicalPlan):
    table: pa.Table

    def schema(self) -> Schema:
        return Schema.from_pa(self.table.schema)


@dataclass
class Range(LogicalPlan):
    start: int
    end: int
    step: int = 1

    def schema(self) -> Schema:
        return Schema([Field("id", DType.LONG, nullable=False)])


@dataclass
class FileScan(LogicalPlan):
    fmt: str                      # parquet | csv | orc
    paths: Tuple[str, ...]
    read_schema: Schema           # full schema incl partition columns
    options: Tuple[Tuple[str, str], ...] = ()
    filters: Tuple[Expression, ...] = ()   # pushed-down predicates
    #: hive-partition discovery results (io.datasource.PartitionedFile)
    files: Tuple = ()
    partition_schema: Schema = field(default_factory=lambda: Schema([]))
    #: emit hidden per-file metadata columns (set by the planner when the
    #: query references input_file_name()/block exprs — GpuInputFileBlock)
    with_file_meta: bool = False

    def schema(self) -> Schema:
        if not self.with_file_meta:
            return self.read_schema
        from spark_rapids_tpu.exprs.misc import INPUT_FILE_META_SPEC
        return Schema(list(self.read_schema.fields) + [
            Field(name, dtype, False)
            for name, dtype, _default in INPUT_FILE_META_SPEC])


@dataclass
class WriteFiles(LogicalPlan):
    """V1 write command (GpuDataWritingCommandExec / InsertIntoHadoopFsRelation
    analog). Produces no rows."""
    spec: object                  # io.write_exec.WriteSpec
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        return Schema([])


@dataclass
class Project(LogicalPlan):
    exprs: Tuple[Expression, ...]   # named via Alias or attribute name
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        from spark_rapids_tpu.exprs.core import bind_expression
        cs = self.child.schema()
        fields = []
        for e in self.exprs:
            b = bind_expression(e, cs)
            fields.append(Field(e.name_hint, b.dtype(), b.nullable()))
        return Schema(fields)


@dataclass
class Filter(LogicalPlan):
    condition: Expression
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        return self.child.schema()


@dataclass
class Aggregate(LogicalPlan):
    grouping: Tuple[Expression, ...]
    aggregates: Tuple[Expression, ...]   # Alias(AggregateFunction) entries
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        from spark_rapids_tpu.exprs.core import bind_expression
        cs = self.child.schema()
        fields = []
        for e in self.grouping:
            b = bind_expression(e, cs)
            fields.append(Field(e.name_hint, b.dtype(), b.nullable()))
        for e in self.aggregates:
            b = bind_expression(e, cs)
            fields.append(Field(e.name_hint, b.dtype(), b.nullable()))
        return Schema(fields)


@dataclass
class Sort(LogicalPlan):
    orders: Tuple[SortOrder, ...]
    child: LogicalPlan
    is_global: bool = True

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        return self.child.schema()


@dataclass
class Limit(LogicalPlan):
    n: int
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        return self.child.schema()


@dataclass
class Union(LogicalPlan):
    left: LogicalPlan
    right: LogicalPlan

    @property
    def children(self):
        return (self.left, self.right)

    def schema(self) -> Schema:
        return self.left.schema()


@dataclass
class Join(LogicalPlan):
    left: LogicalPlan
    right: LogicalPlan
    how: str                       # inner | left | right | full | left_semi | left_anti | cross
    left_keys: Tuple[Expression, ...] = ()
    right_keys: Tuple[Expression, ...] = ()
    condition: Optional[Expression] = None

    @property
    def children(self):
        return (self.left, self.right)

    def schema(self) -> Schema:
        lf = list(self.left.schema().fields)
        rf = list(self.right.schema().fields)
        if self.how in ("left_semi", "left_anti"):
            return Schema(lf)
        if self.how in ("left", "full"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        if self.how in ("right", "full"):
            lf = [Field(f.name, f.dtype, True) for f in lf]
        names = set()
        out = []
        for f in lf + rf:
            name = f.name
            i = 0
            while name in names:
                i += 1
                name = f"{f.name}_{i}"
            names.add(name)
            out.append(Field(name, f.dtype, f.nullable))
        return Schema(out)


@dataclass
class Expand(LogicalPlan):
    """Each input row becomes one output row PER projection list (Spark's
    Expand, used by rollup/cube/grouping sets). All projection lists align on
    slot count, names, and types."""
    projections: Tuple[Tuple[Expression, ...], ...]
    names: Tuple[str, ...]
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        from spark_rapids_tpu.exprs.core import bind_expression
        cs = self.child.schema()
        fields = []
        for i, name in enumerate(self.names):
            slot = [bind_expression(p[i], cs) for p in self.projections]
            dt = next((b.dtype() for b in slot if b.dtype() is not DType.NULL),
                      DType.NULL)
            nullable = any(b.nullable() or b.dtype() is DType.NULL for b in slot)
            fields.append(Field(name, dt, nullable))
        return Schema(fields)


@dataclass
class Generate(LogicalPlan):
    """Explode/posexplode of a created array (Spark's Generate; reference
    GpuGenerateExec scope): child columns ++ [pos] ++ [col], one output row per
    array element per input row."""
    elements: Tuple[Expression, ...]
    pos: bool
    col_name: str
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        from spark_rapids_tpu.exprs.core import bind_expression
        cs = self.child.schema()
        fields = list(cs.fields)
        if self.pos:
            fields.append(Field("pos", DType.INT, nullable=False))
        bound = [bind_expression(e, cs) for e in self.elements]
        dt = DType.NULL
        for b in bound:
            et = b.dtype()
            if et is not DType.NULL:
                dt = et if dt is DType.NULL else DType.common_type(dt, et)
        nullable = any(b.nullable() or b.dtype() is DType.NULL for b in bound)
        fields.append(Field(self.col_name, dt, nullable))
        return Schema(fields)


@dataclass
class Window(LogicalPlan):
    """Window computation: child columns ++ one window column per expression.
    All wexprs share one (partition, order) sort spec (the API groups them)."""
    wexprs: Tuple[Expression, ...]   # Alias(WindowExpression) entries
    child: LogicalPlan

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        from spark_rapids_tpu.exprs.core import bind_expression
        cs = self.child.schema()
        fields = list(cs.fields)
        for e in self.wexprs:
            b = bind_expression(e, cs)
            fields.append(Field(e.name_hint, b.dtype(), b.nullable()))
        return Schema(fields)


@dataclass
class Repartition(LogicalPlan):
    num_partitions: int
    child: LogicalPlan
    keys: Tuple[Expression, ...] = ()   # empty = round robin

    @property
    def children(self):
        return (self.child,)

    def schema(self) -> Schema:
        return self.child.schema()


@dataclass
class CachedRelation(LogicalPlan):
    """A subtree replaced by its cached materialization (InMemoryRelation
    analog — Spark's CacheManager swaps matching subtrees for the cached
    plan; the reference accelerates scanning the cached columnar data,
    HostColumnarToGpu.scala:222). ``entry`` is a memory.df_cache.CachedData;
    identity equality on it is intended — two CachedRelations are the same
    relation iff they reference the same cache entry."""
    entry: object

    def schema(self) -> Schema:
        return self.entry.logical.schema()
