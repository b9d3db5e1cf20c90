"""DataFrame + session frontend.

The user-facing API a Spark user lands on: DataFrames build logical plans; an
action (collect/count/to_pandas) plans the CPU physical plan, runs TpuOverrides
to rewrite supported subtrees onto the TPU, and executes. ``explain()`` surfaces
the will-run/fallback report like spark.rapids.sql.explain.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Union

import pyarrow as pa

from spark_rapids_tpu.api.column import Column, _expr
from spark_rapids_tpu.columnar.dtypes import DType, Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.exprs import (Alias, Coalesce, SortOrder,
                                    UnresolvedAttribute)
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.plan.planner import plan_physical


def _to_expr(c: Union[str, Column]):
    return UnresolvedAttribute(c) if isinstance(c, str) else c.expr


def _extract_generators(exprs, child: lp.LogicalPlan):
    """Pull an Explode/PosExplode out of a projection list into a Generate node
    beneath it (Catalyst's ExtractGenerator analog). At most one generator per
    select, like Spark."""
    from spark_rapids_tpu.exprs.generators import Explode
    hits = [i for i, e in enumerate(exprs)
            if isinstance(e.c if isinstance(e, Alias) else e, Explode)]
    if not hits:
        return exprs, child
    if len(hits) > 1:
        raise ValueError("only one generator (explode/posexplode) is allowed "
                         "per select")
    i = hits[0]
    e = exprs[i]
    alias = e.name if isinstance(e, Alias) else None
    gen = e.c if isinstance(e, Alias) else e
    col_name = alias or "col"
    node = lp.Generate(gen.child_array.items, gen.with_position, col_name,
                       child)
    refs = [UnresolvedAttribute(col_name)]
    if gen.with_position:
        refs.insert(0, UnresolvedAttribute("pos"))
    out = list(exprs)
    out[i:i + 1] = refs
    return tuple(out), node


def _extract_windows(exprs, child: lp.LogicalPlan):
    """Pull WindowExpressions out of a projection list into Window nodes
    beneath it (Catalyst's ExtractWindowExpressions analog). Expressions
    sharing a (partition, order) spec land in one Window node."""
    from spark_rapids_tpu.exprs.windows import WindowExpression
    pulled = []
    counter = [0]
    taken = {f.name for f in child.schema()}

    def fresh_name() -> str:
        while True:
            name = f"_we{counter[0]}"
            counter[0] += 1
            if name not in taken:
                taken.add(name)
                return name

    def strip(e):
        if isinstance(e, WindowExpression):
            name = fresh_name()
            pulled.append(Alias(e, name))
            return UnresolvedAttribute(name)
        return e.map_children(strip)

    new_exprs = tuple(strip(e) for e in exprs)
    if not pulled:
        return exprs, child
    groups = {}
    for a in pulled:
        groups.setdefault(a.c.sort_spec_key(), []).append(a)
    node = child
    for aliases in groups.values():
        node = lp.Window(tuple(aliases), node)
    return new_exprs, node


class Row(dict):
    """Collected row: dict with attribute access (pyspark Row analog)."""

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name) from None

    def __repr__(self):
        inner = ", ".join(f"{k}={v!r}" for k, v in self.items())
        return f"Row({inner})"


def _show_cell(v, width: int) -> str:
    s = "null" if v is None else str(v)
    if width and len(s) > width:
        # pyspark: plain cut below 4 chars, ellipsis otherwise
        s = s[:width] if width < 4 else s[:width - 3] + "..."
    return s


def _null_safe_set_op(left: "DataFrame", right: "DataFrame",
                      mode: str) -> "DataFrame":
    """SQL set-operation semantics (distinct rows, nulls compare equal,
    positional columns like Spark): tag each side, union, group by every
    column — group keys dedup with null==null natively — and keep groups
    by which sides contributed."""
    from spark_rapids_tpu.api import functions as F
    names = left.schema().names()
    if len(names) != len(right.schema().names()):
        raise ValueError(
            f"set operation column-count mismatch: {names} vs "
            f"{right.schema().names()}")
    la = left.dropDuplicates().withColumn("__setf", F.lit(1))
    rb = (right.toDF(*names).dropDuplicates()
          .withColumn("__setf", F.lit(2)))
    agg = (la.union(rb).groupBy(*names)
           .agg(F.min("__setf").alias("__mn"),
                F.max("__setf").alias("__mx")))
    if mode == "intersect":
        agg = agg.filter((F.col("__mn") == 1) & (F.col("__mx") == 2))
    else:                                   # subtract / EXCEPT
        agg = agg.filter(F.col("__mx") == 1)
    return agg.select(*names)


class DataFrame:
    def __init__(self, logical: lp.LogicalPlan, session: "TpuSession"):
        self._plan = logical
        self.session = session

    # ---- transformations -----------------------------------------------------
    def select(self, *cols: Union[str, Column]) -> "DataFrame":
        exprs = tuple(_to_expr(c) for c in cols)
        exprs, child = _extract_generators(exprs, self._plan)
        exprs, child = _extract_windows(exprs, child)
        return DataFrame(lp.Project(exprs, child), self.session)

    def withColumn(self, name: str, c: Column) -> "DataFrame":
        # a replaced column keeps its position (pyspark semantics)
        exprs = []
        replaced = False
        for f in self._plan.schema():
            if f.name == name:
                exprs.append(Alias(c.expr, name))
                replaced = True
            else:
                exprs.append(UnresolvedAttribute(f.name))
        if not replaced:
            exprs.append(Alias(c.expr, name))
        out, child = _extract_generators(tuple(exprs), self._plan)
        out, child = _extract_windows(out, child)
        return DataFrame(lp.Project(out, child), self.session)

    def filter(self, cond: Column) -> "DataFrame":
        return DataFrame(lp.Filter(cond.expr, self._plan), self.session)

    where = filter

    def groupBy(self, *cols: Union[str, Column]) -> "GroupedData":
        return GroupedData(self, tuple(_to_expr(c) for c in cols))

    def rollup(self, *cols: Union[str, Column]) -> "GroupedData":
        return GroupedData(self, tuple(_to_expr(c) for c in cols), "rollup")

    def cube(self, *cols: Union[str, Column]) -> "GroupedData":
        return GroupedData(self, tuple(_to_expr(c) for c in cols), "cube")

    def agg(self, *cols: Column) -> "DataFrame":
        return GroupedData(self, ()).agg(*cols)

    def sort(self, *cols: Union[str, Column]) -> "DataFrame":
        orders = []
        for c in cols:
            e = _to_expr(c)
            orders.append(e if isinstance(e, SortOrder) else SortOrder(e, True, True))
        return DataFrame(lp.Sort(tuple(orders), self._plan), self.session)

    orderBy = sort

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(lp.Limit(n, self._plan), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(lp.Union(self._plan, other._plan), self.session)

    unionAll = union

    def join(self, other: "DataFrame", on: Union[str, List],
             how: str = "inner") -> "DataFrame":
        """USING-style join: key columns appear once in the output (from the
        left side, the right side for right joins, coalesced for full).

        ``on`` may also contain ``(left_name, right_name)`` pairs for keys
        named differently on each side; those keep both columns in the output
        (the ``df1.c1 == df2.c2`` pyspark form)."""
        how = {"leftsemi": "left_semi", "semi": "left_semi",
               "leftanti": "left_anti", "anti": "left_anti",
               "leftouter": "left", "rightouter": "right",
               "outer": "full", "fullouter": "full"}.get(how, how)
        if isinstance(on, Column):
            # pyspark's df.join(other, df.a == other.b) equality form:
            # conjunctions of EqualTo over plain column refs become key
            # pairs; anything else needs the explicit pair form (list(on)
            # on a Column would loop forever through getItem)
            on = _column_condition_to_pairs(on.expr)
        raw = [on] if isinstance(on, str) else list(on)
        if any(isinstance(k, tuple) for k in raw):
            if not all(isinstance(k, tuple) for k in raw):
                # a string key promises USING dedup/coalesce, which the
                # pair form does not do — mixing would silently change the
                # shared key's output semantics
                raise ValueError(
                    "join keys must be all strings (USING semantics) or all "
                    "(left, right) pairs; use ('k', 'k') for same-named keys "
                    "in the pair form")
            pairs = raw
            lkeys = tuple(UnresolvedAttribute(a) for a, _ in pairs)
            rkeys = tuple(UnresolvedAttribute(b) for _, b in pairs)
            return DataFrame(
                lp.Join(self._plan, other._plan, how, lkeys, rkeys),
                self.session)
        keys = raw
        lkeys = tuple(UnresolvedAttribute(k) for k in keys)
        rkeys = tuple(UnresolvedAttribute(k) for k in keys)
        joined = lp.Join(self._plan, other._plan, how, lkeys, rkeys)
        if how in ("left_semi", "left_anti"):
            return DataFrame(joined, self.session)
        out = joined.schema()
        left_n = len(self._plan.schema())
        right_schema = other._plan.schema()
        right_key_out = {out[left_n + right_schema.index_of(k)].name: k
                         for k in keys}
        exprs = []
        for i, f in enumerate(out):
            if i < left_n:
                if f.name in keys:
                    from spark_rapids_tpu.exprs import Coalesce
                    if how == "full":
                        rname = out[left_n + right_schema.index_of(f.name)].name
                        exprs.append(Alias(Coalesce(
                            (UnresolvedAttribute(f.name),
                             UnresolvedAttribute(rname))), f.name))
                    elif how == "right":
                        rname = out[left_n + right_schema.index_of(f.name)].name
                        exprs.append(Alias(UnresolvedAttribute(rname), f.name))
                    else:
                        exprs.append(UnresolvedAttribute(f.name))
                else:
                    exprs.append(UnresolvedAttribute(f.name))
            elif f.name not in right_key_out:
                exprs.append(UnresolvedAttribute(f.name))
        return DataFrame(lp.Project(tuple(exprs), joined), self.session)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(lp.Join(self._plan, other._plan, "cross", (), ()),
                         self.session)

    def repartition(self, n: int, *cols: Union[str, Column]) -> "DataFrame":
        return DataFrame(
            lp.Repartition(n, self._plan, tuple(_to_expr(c) for c in cols)),
            self.session)

    def drop(self, *names: str) -> "DataFrame":
        keep = [UnresolvedAttribute(f.name) for f in self._plan.schema()
                if f.name not in names]
        return DataFrame(lp.Project(tuple(keep), self._plan), self.session)

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = tuple(Alias(UnresolvedAttribute(f.name), new)
                      if f.name == old else UnresolvedAttribute(f.name)
                      for f in self._plan.schema())
        return DataFrame(lp.Project(exprs, self._plan), self.session)

    def createOrReplaceTempView(self, name: str) -> None:
        """Register this DataFrame for SQL access via session.sql()."""
        self.session.register_view(name, self)

    def distinct(self) -> "DataFrame":
        return self.dropDuplicates()

    def dropDuplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        """Distinct via group-by (Spark plans distinct the same way). With a
        subset, the remaining columns keep one arbitrary row per key (pyspark
        semantics), taken with first()."""
        from spark_rapids_tpu.exprs import First
        all_names = [f.name for f in self._plan.schema()]
        names = subset or all_names
        grouping = tuple(UnresolvedAttribute(n) for n in names)
        rest = tuple(Alias(First(UnresolvedAttribute(n), False), n)
                     for n in all_names if n not in names)
        agg = DataFrame(lp.Aggregate(grouping, rest, self._plan), self.session)
        if not rest:
            return agg
        # restore the original column order
        return agg.select(*all_names)

    # ---- row-level conveniences (pyspark user surface) -----------------------
    def _rows(self) -> List["Row"]:
        table = self.collect()
        cols = [table.column(i).to_pylist() for i in range(table.num_columns)]
        names = table.column_names
        return [Row(zip(names, vals)) for vals in zip(*cols)] if cols else []

    def take(self, n: int) -> List["Row"]:
        return self.limit(n)._rows()

    def head(self, n: Optional[int] = None):
        """head() -> first Row or None; head(n) -> list of Rows (pyspark)."""
        if n is None:
            rows = self.take(1)
            return rows[0] if rows else None
        return self.take(n)

    def first(self):
        return self.head()

    def show(self, n: int = 20, truncate: Union[bool, int] = True) -> None:
        """Print the first n rows formatted as pyspark does."""
        width = 20 if truncate is True else (0 if truncate is False
                                             else int(truncate))
        table = self.limit(n).collect()
        names = table.column_names
        cols = [[_show_cell(v, width) for v in table.column(i).to_pylist()]
                for i in range(table.num_columns)]
        widths = [max([len(nm)] + [len(v) for v in col])
                  for nm, col in zip(names, cols)]
        sep = "+" + "+".join("-" * w for w in widths) + "+"
        print(sep)
        print("|" + "|".join(nm.rjust(w) for nm, w in zip(names, widths))
              + "|")
        print(sep)
        for r in range(table.num_rows):
            print("|" + "|".join(cols[i][r].rjust(widths[i])
                                 for i in range(len(names))) + "|")
        print(sep)

    def printSchema(self) -> None:
        lines = ["root"]
        for f in self.schema():
            lines.append(f" |-- {f.name}: {f.dtype.value} "
                         f"(nullable = {str(f.nullable).lower()})")
        print("\n".join(lines))

    def describe(self, *cols: str) -> "DataFrame":
        """count/mean/stddev/min/max per column, values stringified in a
        'summary' table (pyspark describe). One aggregation pass."""
        from spark_rapids_tpu.api import functions as F
        schema = self.schema()
        names = list(cols) or [f.name for f in schema
                               if f.dtype.is_numeric
                               or f.dtype is DType.STRING]
        stat_fns = {"count": F.count, "mean": F.avg, "stddev": F.stddev,
                    "min": F.min, "max": F.max}
        aggs = []
        for nm in names:
            dt = schema[schema.index_of(nm)].dtype
            for stat, fn in stat_fns.items():
                if stat in ("mean", "stddev") and not dt.is_numeric:
                    continue
                aggs.append(fn(nm).alias(f"{stat}__{nm}"))
        out = self.agg(*aggs).collect()
        vals = {c: out.column(c)[0].as_py() for c in out.column_names}
        stats = []
        for stat in stat_fns:
            row = {"summary": stat}
            for nm in names:
                v = vals.get(f"{stat}__{nm}")
                row[nm] = None if v is None else str(v)
            stats.append(row)
        return self.session.create_dataframe(pa.Table.from_pylist(stats))

    def sample(self, withReplacement=None, fraction=None, seed=None
               ) -> "DataFrame":
        """Bernoulli sample WITHOUT replacement (rand(seed) < fraction).
        Accepts both pyspark call forms: sample(fraction[, seed]) and
        sample(withReplacement, fraction[, seed])."""
        from spark_rapids_tpu.api import functions as F
        if not isinstance(withReplacement, bool) and \
                withReplacement is not None:
            # sample(fraction[, seed]) form: shift arguments, but keep a
            # keyword seed= that was passed alongside a positional fraction
            withReplacement, fraction, seed = (
                None, withReplacement,
                fraction if fraction is not None else seed)
        if withReplacement:
            raise NotImplementedError(
                "sample(withReplacement=True) is not supported")
        if fraction is None:
            raise TypeError("sample() needs a fraction")
        if seed is None:
            # pyspark draws a fresh random seed per unseeded call
            import random
            seed = random.randint(0, 2**31 - 1)
        return self.filter(F.rand(int(seed)) < float(fraction))

    def toDF(self, *names: str) -> "DataFrame":
        cur = self.schema().names()
        if len(names) != len(cur):
            raise ValueError(f"toDF needs {len(cur)} names, got {len(names)}")
        exprs = tuple(Alias(UnresolvedAttribute(o), n)
                      for o, n in zip(cur, names))
        return DataFrame(lp.Project(exprs, self._plan), self.session)

    def withColumnsRenamed(self, mapping: Dict[str, str]) -> "DataFrame":
        exprs = tuple(Alias(UnresolvedAttribute(f.name),
                            mapping.get(f.name, f.name))
                      for f in self.schema())
        return DataFrame(lp.Project(exprs, self._plan), self.session)

    def unionByName(self, other: "DataFrame",
                    allowMissingColumns: bool = False) -> "DataFrame":
        from spark_rapids_tpu.api import functions as F
        mine = self.schema().names()
        theirs = other.schema().names()
        if allowMissingColumns:
            all_names = mine + [n for n in theirs if n not in mine]

            def null_as(schema, n):
                # typed null (Spark casts the null literal to the peer type)
                dt = schema[schema.index_of(n)].dtype
                return F.lit(None).cast(dt.value).alias(n)

            left = self.select(*[F.col(n) if n in mine
                                 else null_as(other.schema(), n)
                                 for n in all_names])
            right = other.select(*[F.col(n) if n in theirs
                                   else null_as(self.schema(), n)
                                   for n in all_names])
            return left.union(right)
        if set(mine) != set(theirs):
            raise ValueError(
                f"unionByName column mismatch: {mine} vs {theirs}")
        return self.union(other.select(*mine))

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in both (SQL INTERSECT: nulls compare
        equal, Spark semantics)."""
        return _null_safe_set_op(self, other, "intersect")

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of self absent from other (SQL EXCEPT)."""
        return _null_safe_set_op(self, other, "subtract")

    def exceptAll(self, other: "DataFrame") -> "DataFrame":
        raise NotImplementedError(
            "exceptAll (bag semantics) is not supported; use subtract() "
            "for SQL EXCEPT (distinct) semantics")

    def dropna(self, how: str = "any", thresh: Optional[int] = None,
               subset: Optional[List[str]] = None) -> "DataFrame":
        """pyspark na.drop: NaN counts as null for float columns
        (AtLeastNNonNulls, the expression Spark plans for dropna)."""
        from spark_rapids_tpu.exprs import AtLeastNNonNulls
        if how not in ("any", "all"):
            raise ValueError(f"how must be 'any' or 'all', got {how!r}")
        names = subset or self.schema().names()
        need = thresh if thresh is not None else (
            len(names) if how == "any" else 1)
        cond = AtLeastNNonNulls(
            need, tuple(UnresolvedAttribute(n) for n in names))
        return DataFrame(lp.Filter(cond, self._plan), self.session)

    def fillna(self, value, subset: Optional[List[str]] = None
               ) -> "DataFrame":
        from spark_rapids_tpu.api import functions as F
        schema = self.schema()
        names = subset or [f.name for f in schema]
        by_col = value if isinstance(value, dict) else {n: value
                                                        for n in names}
        exprs = []
        for f in schema:
            v = by_col.get(f.name)
            compatible = v is not None and (
                (f.dtype.is_numeric and isinstance(v, (int, float))
                 and not isinstance(v, bool))
                or (f.dtype is DType.STRING and isinstance(v, str))
                or (f.dtype is DType.BOOLEAN and isinstance(v, bool)))
            if compatible:
                src: Any = UnresolvedAttribute(f.name)
                if f.dtype.is_floating and isinstance(v, (int, float)):
                    # pyspark na.fill also replaces NaN in float columns
                    from spark_rapids_tpu.exprs import NaNvl
                    src = NaNvl(src, F.lit(float(v)).expr)
                filled = Coalesce((src, F.lit(v).expr))
                if f.dtype.is_numeric and isinstance(v, float):
                    # Spark casts the result BACK to the column type, so a
                    # double fill never widens an integer column
                    from spark_rapids_tpu.exprs.cast import Cast
                    filled = Cast(filled, f.dtype)
                exprs.append(Alias(filled, f.name))
            else:
                exprs.append(UnresolvedAttribute(f.name))
        return DataFrame(lp.Project(tuple(exprs), self._plan), self.session)

    # ---- caching -------------------------------------------------------------
    def cache(self) -> "DataFrame":
        """Mark this DataFrame's plan for caching (lazy, like Spark): the
        first action materializes its batches into the spillable device
        store; later plans containing this subtree scan the cache."""
        return self.persist()

    def persist(self, storage_level: Optional[str] = None) -> "DataFrame":
        # every Spark storage level lands in the same tiered store here:
        # DEVICE first, spilling host->disk under pressure
        self.session.cache_manager.add(self._plan)
        return self

    def unpersist(self, blocking: bool = False) -> "DataFrame":
        self.session.cache_manager.remove(self._plan)
        return self

    @property
    def is_cached(self) -> bool:
        return self.session.cache_manager.lookup(self._plan) is not None

    # ---- actions -------------------------------------------------------------
    def _trace_scope(self):
        """Tracer activation for one action under trace.enabled (nesting
        counts, so the root span's scope and the action driver's compose),
        else a no-op."""
        import contextlib
        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.utils import tracing as _tracing
        if not self.session.conf.get(_cfg.TRACE_ENABLED):
            return contextlib.nullcontext()
        _tracing.TRACER.configure(
            self.session.conf.get(_cfg.TRACE_BUFFER_SPANS))
        return _tracing.TRACER.activate()

    def _executed_plan(self, prepared=None) -> PhysicalExec:
        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.utils import tracing as _tracing
        with _tracing.span("plan", _tracing.LAYER_PLAN) as sp:
            logical = (prepared if prepared is not None
                       else self.session.cache_manager.prepare(self._plan))
            cpu_plan = plan_physical(logical, self.session.conf,
                                     note=sp.note if sp is not None else None)
            overrides = TpuOverrides(self.session.conf)
            final = overrides.apply(cpu_plan)
            if self.session.conf.get(_cfg.MESH_ENABLED):
                from spark_rapids_tpu.plan.mesh_rewrite import mesh_rewrite
                final = mesh_rewrite(final, self.session.conf)
            self.session.last_explain = overrides.last_explain
            self.session.last_plan = final
            if sp is not None:
                execs = list(_iter_execs(final))
                sp.note(execs=len(execs),
                        cpu_execs=sum(type(nd).__name__.startswith("Cpu")
                                      for nd in execs))
        return final

    def _run_partitions(self, final: PhysicalExec,
                        capture_device: bool = False, query=None,
                        publish_trace: bool = True) -> List:
        """Execute and collect per-partition results as arrow tables. With
        ``capture_device`` (cache materialization), a single-process plan
        whose root is the download transition instead returns the raw
        DeviceBatches — the cache stores them without a device->host->device
        round trip."""
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        from spark_rapids_tpu import config as _cfg
        # cluster + adaptive compose: the stage scheduler coalesces reduce
        # tasks from observed MapStatus sizes (parallel/cluster.py
        # _coalesce_stage_reads — the GpuCustomShuffleReaderExec role)
        if (self.session.conf.get(_cfg.CLUSTER_EXECUTORS) >= 1
                and not self.session.conf.get(_cfg.MESH_ENABLED)):
            from spark_rapids_tpu.parallel.cluster import cluster_scheduler_for
            from spark_rapids_tpu.utils.metrics import (recompute_delta,
                                                        recompute_snapshot)
            # the cluster driver is the only executor of lineage recomputes,
            # and it returns before the single-process metrics block below —
            # snapshot around the run so a query served through the stage
            # scheduler still records its fault-recovery story
            recompute_before = recompute_snapshot()
            tables = cluster_scheduler_for(self.session).run(final)
            if tables is not None:
                if self.session.conf.get(_cfg.METRICS_ENABLED):
                    snap = {"shuffle": recompute_delta(recompute_before)}
                    if query is not None:
                        query.record_exec_metrics(snap)
                    self.session.last_metrics = snap
                if query is not None:
                    for t in tables:
                        query.emit_batch(t)
                return tables
            # plan not stageable (CPU exchanges): single-process fallback
        # spark.rapids.tpu.trace.enabled: structured span tracing for the
        # whole action (utils/tracing.py — per-exec spans, transfer/memory/
        # serving layers, EXPLAIN ANALYZE and the Chrome export) plus the
        # action-level jax.profiler range (NVTX analog); when metrics are
        # on, per-operator counters land in session.last_metrics
        import contextlib
        import time as _time
        from spark_rapids_tpu.utils import tracing as _tracing
        from spark_rapids_tpu.utils.metrics import (action_depth_scope,
                                                    adaptive_delta,
                                                    adaptive_snapshot,
                                                    memory_delta,
                                                    memory_snapshot,
                                                    recompute_delta,
                                                    recompute_snapshot,
                                                    serving_delta,
                                                    serving_snapshot,
                                                    transfer_delta,
                                                    transfer_snapshot)
        # the query's own work outside the action is under query.* spans,
        # the action's outside its children under action.*: their self time
        # is what no span holds (docs/observability.md)
        with _tracing.span("query.prepare", _tracing.LAYER_ACTION):
            dm = DeviceManager.initialize(self.session.conf)
            cleanups: List = []
            tables = []
            trace = self.session.conf.get(_cfg.TRACE_ENABLED)
            trace_scope = self._trace_scope()
            transfer_before = transfer_snapshot()
            memory_before = memory_snapshot()
            serving_before = serving_snapshot()
            recompute_before = recompute_snapshot()
            adaptive_before = adaptive_snapshot()
            # stable node ordinals: the span/EXPLAIN-ANALYZE key (pre-order,
            # matching the f"{i}:{name}" keys of session.last_metrics)
            for i, nd in enumerate(_iter_execs(final)):
                nd.plan_id = i
            tenant = query.tenant if query is not None else "default"
            cancel = query.check_cancelled if query is not None else None
            # one stack for the action-scoped contexts (depth attribution +
            # tracer activation): entered before the admission wait so the
            # wait is traced, unwound in the finally below even when a
            # cleanup fn raises — a stuck activation would leave the
            # process-wide tracer on for every later query
            scopes = contextlib.ExitStack()
            depth_holder = scopes.enter_context(action_depth_scope())
            scopes.enter_context(trace_scope)
            trace_mark = _tracing.TRACER.mark()
            t_wall = _time.perf_counter()
            t_admit = _time.perf_counter()
        try:
            # the action span (profiler range tpu-sql-action) opens first,
            # so the admission wait is a child inside it; then the
            # device-admission throttle for the whole task (GpuSemaphore
            # analog), fair-shared by tenant; a cancelled query blocked on
            # admission unwinds here instead of waiting for a permit
            with contextlib.ExitStack() as action:
                action.enter_context(_tracing.span(
                    "action", _tracing.LAYER_ACTION,
                    profile=_tracing.ACTION_RANGE))
                with _tracing.span("serving.admission_wait",
                                   _tracing.LAYER_SERVING,
                                   {"tenant": tenant} if trace else None):
                    action.enter_context(dm.semaphore.held(
                        tenant=tenant, cancel_check=cancel))
                if query is not None:
                    query.note_admission_wait(_time.perf_counter() - t_admit)
                if self.session.conf.get(_cfg.ADAPTIVE_ENABLED) and \
                        not any(getattr(nd, "is_mesh", False)
                                for nd in _iter_execs(final)):
                    # mesh operators adapt inside their execs (observed
                    # sizes precede every exchange program); the host-side
                    # stage rewrite runs whenever the plan actually stayed
                    # on host exchanges (incl. mesh.enabled on one device)
                    from spark_rapids_tpu.plan.adaptive import adaptive_rewrite
                    stage_ctx = ExecContext(self.session.conf, partition_id=0,
                                            num_partitions=1,
                                            device_manager=dm,
                                            cleanups=cleanups, query=query)
                    final = adaptive_rewrite(final, stage_ctx)
                    self.session.last_plan = final
                    for i, nd in enumerate(_iter_execs(final)):
                        nd.plan_id = i      # rewritten plan: fresh ordinals
                from spark_rapids_tpu.execs.tpu_execs import DeviceToHostExec
                if (capture_device and isinstance(final, DeviceToHostExec)
                        and not any(getattr(nd, "is_mesh", False)
                                    for nd in _iter_execs(final))):
                    final = final.children[0]   # keep batches device-resident
                    for p in range(final.num_partitions):
                        ctx = ExecContext(self.session.conf, partition_id=p,
                                          num_partitions=final.num_partitions,
                                          device_manager=dm, cleanups=cleanups,
                                          query=query)
                        for b in final.execute(ctx):
                            ctx.check_cancelled()
                            tables.append(b)
                    return tables
                stream = (
                    isinstance(final, DeviceToHostExec)
                    and self.session.conf.get(_cfg.TRANSFER_STREAMING_COLLECT)
                    and not any(getattr(nd, "is_mesh", False)
                                for nd in _iter_execs(final)))
                if stream:
                    # streaming collect: each result batch's D2H starts the
                    # moment its program is dispatched (copy_to_host_async)
                    # and overlaps the remaining compute; at most
                    # transfer.maxInflight downloads are outstanding, and
                    # batch order is preserved by resolving in FIFO order
                    from spark_rapids_tpu.columnar.transfer import \
                        start_download
                    child = final.children[0]
                    max_inflight = self.session.conf.get(
                        _cfg.TRANSFER_MAX_INFLIGHT)
                    pending: List = []
                    for p in range(final.num_partitions):
                        ctx = ExecContext(self.session.conf, partition_id=p,
                                          num_partitions=final.num_partitions,
                                          device_manager=dm,
                                          cleanups=cleanups, query=query)
                        for db in child.execute(ctx):
                            ctx.check_cancelled()
                            final.count_output(db.num_rows)
                            with _tracing.span("action.download_dispatch",
                                               _tracing.LAYER_ACTION):
                                pending.append(start_download(db))
                            while len(pending) > max_inflight:
                                t = pending.pop(0).result()
                                tables.append(t)
                                # streaming partial results: each batch
                                # reaches the serving stream the moment
                                # its async D2H resolves — before the
                                # final batch exists
                                if query is not None:
                                    query.emit_batch(t)
                    for pd_ in pending:
                        t = pd_.result()
                        tables.append(t)
                        if query is not None:
                            query.emit_batch(t)
                else:
                    for p in range(final.num_partitions):
                        ctx = ExecContext(self.session.conf, partition_id=p,
                                          num_partitions=final.num_partitions,
                                          device_manager=dm,
                                          cleanups=cleanups, query=query)
                        for b in final.execute(ctx):
                            ctx.check_cancelled()
                            with _tracing.span("download.to_arrow",
                                               _tracing.LAYER_TRANSFER):
                                t = b.to_arrow()
                            tables.append(t)
                            if query is not None:
                                query.emit_batch(t)
        finally:
            try:
                with _tracing.span("query.cleanup", _tracing.LAYER_ACTION):
                    for fn in cleanups:
                        fn()
            finally:
                self.session.last_action_wall_s = (_time.perf_counter()
                                                   - t_wall)
                scopes.close()
            if self.session.conf.get(_cfg.METRICS_ENABLED):
                with _tracing.span("query.metrics",
                                   _tracing.LAYER_ACTION) as sp:
                    # build the whole snapshot FIRST, then publish with ONE
                    # attribute store: two interleaved actions used to mutate
                    # the shared dict after assignment, so a reader could see
                    # the other query's half-written metrics. The per-query
                    # handle is the first-class record; the session global
                    # stays as a last-action alias for compatibility.
                    snap = {f"{i}:{nd.name}": nd.metrics.snapshot()
                            for i, nd in enumerate(_iter_execs(final))}
                    if sp is not None:
                        sp.note(execs=len(snap))
                    # host-link story for the whole action, incl. derived GB/s
                    # (process-global counters: under concurrent queries the
                    # per-action delta includes overlapping queries' traffic)
                    snap["transfer"] = transfer_delta(transfer_before)
                    # out-of-core story for the action: pressure events, grace
                    # partitions, recursion peak, bytes spilled per tier. The
                    # recursion peak is the ACTION-SCOPED maximum (thread/
                    # query-bound attribution, not the shared re-armed global
                    # whose concurrent-overlap misattribution PR 11 documented)
                    snap["memory"] = memory_delta(memory_before,
                                                  recursion_peak=(
                                                      depth_holder.peak))
                    # serving story: wire bytes/batches streamed, preemptions,
                    # footprint-admission rejections over the action's window
                    snap["serving"] = serving_delta(serving_before)
                    # fault-recovery story for the action: lineage-scoped stage
                    # recomputes the cluster driver ran (and escalations to the
                    # failover path) while this action was collecting
                    snap["shuffle"] = recompute_delta(recompute_before)
                    # adaptive story: runtime rewrites this action's AQE pass
                    # applied (skew splits, coalesced partitions, broadcast
                    # switches, re-fused stages)
                    snap["adaptive"] = adaptive_delta(adaptive_before)
                    if query is not None:
                        query.record_exec_metrics(snap)
                    self.session.last_metrics = snap
            if trace and publish_trace:
                self._publish_trace(trace_mark)
        return tables

    def _publish_trace(self, mark: int) -> None:
        """The span window since ``mark``: kept on the session for
        introspection and exported per trace.export.path (the file is
        rewritten per action — last-action semantics)."""
        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.utils import tracing as _tracing
        records = _tracing.TRACER.since(mark)
        self.session.last_trace = records
        export = self.session.conf.get(_cfg.TRACE_EXPORT_PATH)
        if export:
            _tracing.export_chrome(
                records, export,
                metadata={"action_wall_s": round(
                    self.session.last_action_wall_s, 6)})

    def collect(self) -> pa.Table:
        return self._collect()

    def _collect(self, query=None, final: Optional[PhysicalExec] = None
                 ) -> pa.Table:
        """collect() with serving context: ``query`` is the QueryHandle a
        scheduler worker is driving (cancellation checkpoints, fair-share
        tenant, per-query metric snapshot); ``final`` reuses an already-
        planned physical tree."""
        import contextlib
        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.utils import tracing as _tracing
        trace_mark = _tracing.TRACER.mark()
        try:
            with contextlib.ExitStack() as scopes:
                if query is None:
                    # embedded: the query's span tree opens here (a served
                    # query's root is the scheduler worker's)
                    scopes.enter_context(self._trace_scope())
                    scopes.enter_context(_tracing.span(
                        "query", _tracing.LAYER_QUERY, profile=False))
                if final is None:
                    final = self._executed_plan()
                tables = self._run_partitions(final, query=query,
                                              publish_trace=False)
                with _tracing.span("query.schema", _tracing.LAYER_ACTION):
                    schema = self._plan.schema().to_pa()
                if not tables:
                    return schema.empty_table()
                with _tracing.span("result.concat",
                                   _tracing.LAYER_TRANSFER):
                    return pa.concat_tables(tables)
        finally:
            # after the root has closed, so the window holds the whole tree
            if self.session.conf.get(_cfg.TRACE_ENABLED):
                self._publish_trace(trace_mark)

    def to_pandas(self):
        return self.collect().to_pandas()

    toPandas = to_pandas

    def count(self) -> int:
        from spark_rapids_tpu.api.functions import count
        return self.agg(count().alias("count")).collect().column(0)[0].as_py()

    def schema(self) -> Schema:
        return self._plan.schema()

    @property
    def columns(self) -> List[str]:
        return self._plan.schema().names()

    def explain(self, print_out: bool = True) -> str:
        # substitute cached subtrees (no materialization: explain is free)
        logical = self.session.cache_manager.substitute(self._plan)
        cpu_plan = plan_physical(logical, self.session.conf)
        overrides = TpuOverrides(self.session.conf)
        final = overrides.apply(cpu_plan)
        text = overrides.last_explain + "\n\nPhysical plan:\n" + final.tree_string()
        if print_out:
            print(text)
        return text

    def write_parquet(self, path: str, compression: str = "snappy") -> None:
        from spark_rapids_tpu.io.parquet import write_parquet
        write_parquet(self.collect(), path, compression)

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)


class DataFrameWriter:
    """df.write API (DataFrameWriter analog) driving the columnar write path
    (GpuDataWritingCommandExec / GpuFileFormatWriter)."""

    def __init__(self, df: DataFrame):
        self._df = df
        self._mode = "error"
        self._partition_by: List[str] = []
        self._options: Dict[str, str] = {}

    def mode(self, m: str) -> "DataFrameWriter":
        m = {"errorifexists": "error", "default": "error"}.get(m.lower(),
                                                               m.lower())
        if m not in ("error", "overwrite", "append", "ignore"):
            raise ValueError(f"unknown save mode {m!r}")
        self._mode = m
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    partition_by = partitionBy

    def option(self, k: str, v) -> "DataFrameWriter":
        self._options[k] = str(v)
        return self

    def _save(self, fmt: str, path: str):
        from spark_rapids_tpu.io.write_exec import WriteSpec
        from spark_rapids_tpu.io.write_exec import CpuWriteFilesExec
        max_records = int(self._options.get("maxRecordsPerFile", "0"))
        opts = tuple((k, v) for k, v in self._options.items()
                     if k != "maxRecordsPerFile")
        spec = WriteSpec(fmt, path, self._mode, tuple(self._partition_by),
                         opts, max_records)
        df = DataFrame(lp.WriteFiles(spec, self._df._plan), self._df.session)
        final = df._executed_plan()
        df._run_partitions(final)
        # surface write stats from whichever engine ran the command
        from spark_rapids_tpu.execs.mesh_execs import MeshWriteFilesExec
        for node in _iter_execs(final):
            if isinstance(node, (CpuWriteFilesExec, MeshWriteFilesExec)):
                return node.stats
        return None

    def parquet(self, path: str):
        return self._save("parquet", path)

    def orc(self, path: str):
        return self._save("orc", path)

    def csv(self, path: str):
        return self._save("csv", path)


def _column_condition_to_pairs(e) -> List[tuple]:
    """EqualTo conjunctions over column refs -> [(left_name, right_name)...];
    raises a clear TypeError for anything richer."""
    from spark_rapids_tpu.exprs.predicates import And, EqualTo
    from spark_rapids_tpu.exprs.core import BoundReference

    def name_of(x):
        if isinstance(x, UnresolvedAttribute):
            return x.name
        if isinstance(x, BoundReference) and x.ref_name:
            return x.ref_name
        return None

    if isinstance(e, And):
        return (_column_condition_to_pairs(e.l)
                + _column_condition_to_pairs(e.r))
    if isinstance(e, EqualTo):
        a, b = name_of(e.l), name_of(e.r)
        if a and b:
            return [(a, b)]
    raise TypeError(
        "join(on=Column) supports only equality conjunctions of plain "
        "columns (df.a == other.b [& ...]); use string keys or "
        "(left, right) pairs otherwise")


def _iter_execs(plan: PhysicalExec):
    yield plan
    for c in plan.children:
        yield from _iter_execs(c)


def _tree_has(e, cls) -> bool:
    if isinstance(e, cls):
        return True
    return any(_tree_has(c, cls) for c in e.children)


def _null_safe_zero(dt):
    """A valid stand-in value of the key's type for coalescing null keys; rows
    are disambiguated by the paired isnull flag, so the value itself is
    arbitrary."""
    import datetime
    from spark_rapids_tpu.columnar.dtypes import DType
    if dt is DType.STRING:
        return ""
    if dt is DType.BOOLEAN:
        return False
    if dt is DType.DATE:
        return datetime.date(1970, 1, 1)
    if dt is DType.TIMESTAMP:
        return datetime.datetime(1970, 1, 1)
    if dt.is_floating:
        return 0.0
    return 0


def _null_safe_key_join(left: "DataFrame", right: "DataFrame",
                        keynames: List[str]) -> "DataFrame":
    """Inner join on keys where null keys match each other (eqNullSafe): each
    key joins as the pair (coalesce(k, zero), isnull(k)). The right side's key
    and helper columns are dropped afterwards."""
    from spark_rapids_tpu.api import functions as F
    lschema = left.schema()
    pairs = []
    drop_after = []
    for j, kn in enumerate(keynames):
        dt = lschema[lschema.index_of(kn)].dtype
        zero = F.lit(_null_safe_zero(dt))
        lv, ln = f"__jl{j}_v", f"__jl{j}_n"
        rv, rn = f"__jr{j}_v", f"__jr{j}_n"
        rk = f"__jr{j}_k"
        left = (left.withColumn(lv, F.coalesce(F.col(kn), zero))
                .withColumn(ln, F.col(kn).isNull()))
        right = (right.withColumnRenamed(kn, rk)
                 .withColumn(rv, F.coalesce(F.col(rk), zero))
                 .withColumn(rn, F.col(rk).isNull()))
        pairs += [(lv, rv), (ln, rn)]
        drop_after += [lv, ln, rv, rn, rk]
    return left.join(right, pairs).drop(*drop_after)


class GroupedData:
    def __init__(self, df: DataFrame, grouping, mode: str = "groupby"):
        self._df = df
        self._grouping = grouping
        self._mode = mode
        self._pivot: Optional[tuple] = None

    def pivot(self, col_name: str, values: Optional[List] = None
              ) -> "GroupedData":
        """Spark pivot: one output column per pivot value. With no values
        list, the distinct pivot values are queried first (exactly what
        Spark does, which is why it recommends passing them)."""
        if self._mode != "groupby":
            raise NotImplementedError("pivot with rollup/cube")
        if values is None:
            vals = (self._df.select(col_name).distinct().collect()
                    .column(0).to_pylist())
            values = sorted([v for v in vals if v is not None],
                            key=lambda v: (str(type(v)), v))
            if any(v is None for v in vals):
                values.insert(0, None)      # Spark's 'null' pivot column
        g = GroupedData(self._df, self._grouping)
        g._pivot = (col_name, list(values))
        return g

    def agg(self, *cols: Column) -> DataFrame:
        if self._pivot is not None:
            return self._pivot_agg(cols)
        return self._agg_impl(cols)

    def _pivot_agg(self, cols) -> DataFrame:
        """Pivot lowering (Catalyst's single-aggregation pivot shape):
        each aggregate becomes one conditional aggregate per pivot value —
        agg(when(p == v, child)) AS <v>[_<aggname>]."""
        from spark_rapids_tpu.api import functions as F
        from spark_rapids_tpu.exprs.core import Expression
        pcol, values = self._pivot
        from spark_rapids_tpu.exprs.aggregates import (AggregateFunction,
                                                       DistinctAgg)
        aggs = []
        for v in values:
            for c in cols:
                e = c.expr
                name_suffix = None
                if isinstance(e, Alias):
                    name_suffix = e.name
                    e = e.c
                if not isinstance(e, AggregateFunction):
                    raise NotImplementedError(
                        "pivot aggregates must be plain aggregate "
                        "functions (optionally aliased), e.g. sum(col)")

                # rewrite the aggregate's input to when(p == v, input);
                # a null pivot value matches with isNull (Spark's 'null'
                # pivot column)
                def gate(child: Expression, v=v) -> Expression:
                    match = (F.col(pcol).isNull() if v is None
                             else F.col(pcol) == F.lit(v))
                    return (F.when(match, Column(child))
                            .otherwise(F.lit(None))).expr

                if isinstance(e, DistinctAgg):
                    # gate INSIDE the distinct wrapper so the rewrite in
                    # _distinct_agg still sees an aggregate at the top
                    gated = DistinctAgg(e.inner.map_children(gate))
                else:
                    gated = e.map_children(gate)
                base = "null" if v is None else str(v)
                name = (base if len(cols) == 1 and name_suffix is None
                        else f"{base}_{name_suffix or e.name_hint}")
                aggs.append(Column(Alias(gated, name)))
        return GroupedData(self._df, self._grouping).agg(*aggs)

    def _agg_impl(self, cols) -> DataFrame:
        from spark_rapids_tpu.exprs import DistinctAgg
        aggs = []
        for i, c in enumerate(cols):
            e = c.expr
            if not isinstance(e, Alias):
                e = Alias(e, e.name_hint)
            aggs.append(e)
        if any(isinstance(a.c, DistinctAgg) for a in aggs):
            if self._mode != "groupby":
                raise NotImplementedError(
                    "distinct aggregates are not supported with rollup/cube")
            return self._distinct_agg(aggs)
        for a in aggs:
            if _tree_has(a.c, DistinctAgg):
                raise NotImplementedError(
                    "distinct aggregate must be a top-level aggregate "
                    "expression (optionally aliased)")
        if self._mode != "groupby":
            return self._grouping_sets_agg(tuple(aggs))
        return DataFrame(
            lp.Aggregate(self._grouping, tuple(aggs), self._df._plan),
            self._df.session)

    def _distinct_agg(self, aggs) -> DataFrame:
        """Rewrite an aggregation containing DISTINCT aggregates into
        dedup-then-aggregate subplans recombined on the grouping keys — the
        join-based form of Spark's RewriteDistinctAggregates (the reference GPU
        plugin falls back to CPU for these; here both engines run the rewrite).

        Each distinct agg becomes: select(keys, child) -> dropDuplicates ->
        groupBy(keys).agg(inner). Group sets are identical across subplans (every
        subplan sees every input row), so an inner join on the keys recombines
        them; keys are joined null-safely (coalesce + isnull flag pairs, the
        standard eqNullSafe lowering) because a group key may be null."""
        from spark_rapids_tpu.exprs import DistinctAgg
        df = self._df
        keys = list(self._grouping)
        keynames = [k.name_hint for k in keys]
        out_names = [a.name_hint for a in aggs]
        if len(set(keynames + out_names)) != len(keynames) + len(out_names):
            raise ValueError(
                "duplicate output names in a DISTINCT aggregation: "
                f"{keynames + out_names!r} — alias the colliding columns")

        # Subplans are recombined BY NAME, so keys and agg outputs get
        # generated unique names (__gk{i}/__da{i}); user-facing names come
        # back only in the final select.
        gk = [f"__gk{i}" for i in range(len(keys))]
        da = [f"__da{i}" for i in range(len(aggs))]
        key_aliases = tuple(Alias(k, g) for k, g in zip(keys, gk))

        regular = [(i, a) for i, a in enumerate(aggs)
                   if not isinstance(a.c, DistinctAgg)]
        for _, a in regular:
            if _tree_has(a.c, DistinctAgg):
                raise NotImplementedError(
                    "distinct aggregate must be a top-level aggregate "
                    "expression (optionally aliased)")
        parts: List[DataFrame] = []
        if regular:
            parts.append(GroupedData(df, key_aliases).agg(
                *[Column(Alias(a.c, da[i])) for i, a in regular]))
        for i, a in enumerate(aggs):
            if not isinstance(a.c, DistinctAgg):
                continue
            inner = a.c.inner
            vname = f"__dv{i}"
            sel = [Column(ka) for ka in key_aliases]
            sel.append(Column(Alias(inner.child, vname)))
            dd = df.select(*sel).dropDuplicates()
            rebuilt = inner.map_children(
                lambda _e: UnresolvedAttribute(vname))
            grouping = tuple(UnresolvedAttribute(g) for g in gk)
            parts.append(GroupedData(dd, grouping).agg(
                Column(Alias(rebuilt, da[i]))))

        result = parts[0]
        for p in parts[1:]:
            result = (_null_safe_key_join(result, p, gk) if gk
                      else result.crossJoin(p))
        final = [Column(Alias(UnresolvedAttribute(g), kn))
                 for g, kn in zip(gk, keynames)]
        final += [Column(Alias(UnresolvedAttribute(d), on))
                  for d, on in zip(da, out_names)]
        return result.select(*final)

    def _grouping_sets_agg(self, aggs) -> DataFrame:
        """rollup/cube via Expand (Spark's Expand + grouping-id plan shape):
        each row replicates once per grouping set with rolled-up keys nulled;
        grouping by (expanded keys, grouping id) keeps real nulls distinct
        from rolled-up nulls; a final projection drops the internal columns."""
        from spark_rapids_tpu.columnar.dtypes import DType
        from spark_rapids_tpu.exprs import Literal
        keys = list(self._grouping)
        n = len(keys)
        if self._mode == "rollup":
            # (all keys), (all but last), ..., (none)
            masks = [[j < n - i for j in range(n)] for i in range(n + 1)]
        else:  # cube: every subset
            masks = [[not ((i >> (n - 1 - j)) & 1) for j in range(n)]
                     for i in range(2 ** n)]
        cs = self._df._plan.schema()
        kn = [f"_gset{i}" for i in range(n)]
        names = tuple(f.name for f in cs) + tuple(kn) + ("_gid",)
        projections = []
        for mask in masks:
            gid = 0
            row = [UnresolvedAttribute(f.name) for f in cs]
            for j, (e, inc) in enumerate(zip(keys, mask)):
                row.append(e if inc else Literal(None, DType.NULL))
                if not inc:
                    gid |= 1 << (n - 1 - j)
            row.append(Literal(gid, DType.INT))
            projections.append(tuple(row))
        expand = lp.Expand(tuple(projections), names, self._df._plan)
        grouping = tuple(UnresolvedAttribute(k) for k in kn) + (
            UnresolvedAttribute("_gid"),)
        agg = lp.Aggregate(grouping, aggs, expand)
        final = tuple(
            Alias(UnresolvedAttribute(k), keys[i].name_hint)
            for i, k in enumerate(kn)
        ) + tuple(UnresolvedAttribute(a.name_hint) for a in aggs)
        return DataFrame(lp.Project(final, agg), self._df.session)

    def count(self) -> DataFrame:
        from spark_rapids_tpu.api.functions import count
        return self.agg(count().alias("count"))

    def _simple(self, fname, *cols) -> DataFrame:
        from spark_rapids_tpu.api import functions as F
        fn = getattr(F, fname)
        names = cols or [f.name for f in self._df._plan.schema()
                         if f.dtype.is_numeric]
        return self.agg(*[fn(n).alias(f"{fname}({n})") for n in names])

    def sum(self, *cols) -> DataFrame:
        return self._simple("sum", *cols)

    def avg(self, *cols) -> DataFrame:
        return self._simple("avg", *cols)

    def min(self, *cols) -> DataFrame:
        return self._simple("min", *cols)

    def max(self, *cols) -> DataFrame:
        return self._simple("max", *cols)


class DataFrameReader:
    def __init__(self, session: "TpuSession"):
        self.session = session
        self._options: Dict[str, str] = {}

    def option(self, k: str, v) -> "DataFrameReader":
        self._options[k] = str(v)
        return self

    def _scan(self, fmt: str, paths, infer_schema) -> DataFrame:
        """Discover hive partitions, then full read schema = data schema from
        the first file ++ partition columns."""
        from spark_rapids_tpu.columnar.dtypes import Field as SField
        from spark_rapids_tpu.io.datasource import discover_partitioned_files
        files, pschema = discover_partitioned_files(paths, fmt)
        if not files:
            raise FileNotFoundError(f"no {fmt} files under {paths}")
        data_schema = infer_schema(files[0].path)
        full = Schema(list(data_schema.fields)
                      + [SField(f.name, f.dtype, f.nullable) for f in pschema])
        return DataFrame(lp.FileScan(fmt, tuple(paths), full,
                                     tuple(self._options.items()),
                                     files=files, partition_schema=pschema),
                         self.session)

    def parquet(self, *paths: str) -> DataFrame:
        import pyarrow.parquet as pq
        return self._scan("parquet", paths,
                          lambda p: Schema.from_pa(pq.read_schema(p)))

    def csv(self, *paths: str, schema: Optional[Schema] = None) -> DataFrame:
        from spark_rapids_tpu.io.csv import infer_csv_schema
        return self._scan(
            "csv", paths,
            lambda p: schema or infer_csv_schema(p, self._options))

    def orc(self, *paths: str) -> DataFrame:
        import pyarrow.orc as po
        return self._scan("orc", paths,
                          lambda p: Schema.from_pa(po.ORCFile(p).schema))


class TpuSession:
    """SparkSession analog wired to the TPU accelerator (SQLPlugin +
    RapidsDriverPlugin role: holds the conf, applies the overrides rule)."""

    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        from spark_rapids_tpu.memory.df_cache import CacheManager
        self.conf = TpuConf(conf or {})
        self.last_explain: str = ""
        self.last_plan: Optional[PhysicalExec] = None
        #: per-operator metric snapshots of the LAST action, filled when
        #: spark.rapids.tpu.metrics.enabled (SQLMetrics reporting analog).
        #: Under concurrent serving this is a last-writer-wins alias —
        #: read QueryHandle.exec_metrics for a specific query's snapshot.
        self.last_metrics: Dict[str, Dict[str, int]] = {}
        #: wall-clock seconds of the last action (EXPLAIN ANALYZE header)
        self.last_action_wall_s: float = 0.0
        #: span window of the last TRACED action (trace.enabled) — the
        #: records export_chrome() writes; last-writer-wins like
        #: last_metrics (per-query spans live on the QueryHandle)
        self.last_trace: list = []
        self._views: Dict[str, DataFrame] = {}
        #: guards the view table: concurrent serve.register handlers (the
        #: transport worker pool) register views while SQL planning reads
        #: them (R012)
        self._views_lock = threading.Lock()
        self.cache_manager = CacheManager(self)
        self._scheduler = None
        self._scheduler_lock = threading.Lock()

    def clear_cache(self) -> None:
        """Drop every cached DataFrame (spark.catalog.clearCache analog)."""
        self.cache_manager.clear()

    clearCache = clear_cache

    def explain_analyze(self, print_out: bool = False) -> str:
        """EXPLAIN ANALYZE of the LAST action: the physical plan annotated
        with each node's OBSERVED rows / batches / wall / self time / spill
        (Spark-UI style). Requires the action to have run with
        ``trace.enabled`` — without it the tree renders without stats.
        Per-node self times sum (within driver slack) to the action wall."""
        if self.last_plan is None:
            raise RuntimeError("no action has run yet")
        text = (f"== Physical plan with observed stats "
                f"(action wall {self.last_action_wall_s:.3f}s) ==\n"
                + self.last_plan.tree_string(analyze=True))
        if print_out:
            print(text)
        return text

    # ---- concurrent serving -----------------------------------------------
    @property
    def scheduler(self):
        """The session's query scheduler (serving/scheduler.py), created on
        first use with the session's serving.* conf."""
        with self._scheduler_lock:
            if self._scheduler is None:
                from spark_rapids_tpu.serving.scheduler import \
                    SessionScheduler
                self._scheduler = SessionScheduler(self)
            return self._scheduler

    def submit(self, query, tenant: str = "default",
               timeout: Optional[float] = None, label: Optional[str] = None):
        """Submit a DataFrame or SQL string for concurrent execution;
        returns a QueryHandle immediately (state QUEUED). ``handle.
        result()`` blocks for the collected table; ``handle.cancel()``
        requests cooperative cancellation; per-query metrics live in
        ``handle.snapshot()`` / ``handle.exec_metrics``."""
        return self.scheduler.submit(query, tenant=tenant, timeout=timeout,
                                     label=label)

    # ---- SQL frontend -----------------------------------------------------
    def table(self, name: str) -> "DataFrame":
        with self._views_lock:
            try:
                return self._views[name.lower()]
            except KeyError:
                raise KeyError(
                    f"table or view not found: {name}") from None

    def register_view(self, name: str, df: "DataFrame") -> None:
        with self._views_lock:
            self._views[name.lower()] = df

    def sql(self, query: str) -> "DataFrame":
        """Run a SQL query over registered temp views (the role Catalyst's
        parser/analyzer plays for the reference — its benchmark suites feed
        raw SQL, TpcdsLikeSpark.scala:30)."""
        from spark_rapids_tpu.sql.parser import parse_sql
        from spark_rapids_tpu.sql.planner import SqlPlanner
        stmt = parse_sql(query)
        df, _names = SqlPlanner(self).plan(stmt)
        return df

    @staticmethod
    def builder() -> "TpuSessionBuilder":
        return TpuSessionBuilder()

    def create_dataframe(self, data, schema: Optional[Sequence[str]] = None
                         ) -> DataFrame:
        if isinstance(data, pa.Table):
            table = data
        elif hasattr(data, "to_dict") and hasattr(data, "columns"):  # pandas
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, dict):
            table = pa.table(data)
        else:  # rows
            import pandas as pd
            table = pa.Table.from_pandas(pd.DataFrame(data, columns=schema),
                                         preserve_index=False)
        return DataFrame(lp.LocalRelation(table), self)

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None, step: int = 1
              ) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(lp.Range(start, end, step), self)

    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    def set_conf(self, key: str, value) -> None:
        self.conf = self.conf.with_overrides({key: value})


class TpuSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, Any] = {}

    def config(self, key: str, value) -> "TpuSessionBuilder":
        self._conf[key] = value
        return self

    def getOrCreate(self) -> TpuSession:
        return TpuSession(self._conf)
