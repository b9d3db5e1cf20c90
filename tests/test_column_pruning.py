"""Column pruning (plan/pruning.py): what each node type asks of its child,
where the pass keeps every column, and that the pruned plans of the
benchmark's queries read the columns their text names and give the answers
the unpruned plans give. CPU, SF0.01."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import gen_tables
from benchmark.queries import q1, q3, q6, q18
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar.dtypes import DType, Field, Schema
from spark_rapids_tpu.exprs.core import BoundReference, UnresolvedAttribute
from spark_rapids_tpu.exprs.misc import Alias, SortOrder
from spark_rapids_tpu.exprs.predicates import EqualTo, GreaterThan
from spark_rapids_tpu.exprs.literals import Literal
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.plan.planner import (_plan_node, ensure_requirements,
                                           plan_physical)
from spark_rapids_tpu.plan.pruning import prune_columns
from spark_rapids_tpu.serving.program_cache import global_program_cache
from spark_rapids_tpu.testing import assert_tables_equal
from spark_rapids_tpu.utils.metrics import TRANSFER_METRICS

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false"}
TRACE = {"spark.rapids.tpu.trace.enabled": "true"}

col = UnresolvedAttribute


def _rel(*names):
    return lp.LocalRelation(pa.table(
        {n: np.arange(4, dtype=np.int64) for n in names}))


def _scan(*names, partitions=()):
    schema = Schema([Field(n, DType.LONG, True) for n in names])
    return lp.FileScan("parquet", ("/nowhere",), schema,
                       partition_schema=Schema(
                           [f for f in schema if f.name in partitions]))


def _gt(name):
    return GreaterThan(col(name), Literal(1, DType.LONG))


def _project_of(node, *names):
    """``node`` is the pass's Project of exactly these plain references."""
    assert isinstance(node, lp.Project), node
    assert all(isinstance(e, UnresolvedAttribute) for e in node.exprs)
    assert [e.name for e in node.exprs] == list(names)
    return node.child


# --------------------------------------------------------------- the rules
def test_the_root_keeps_all_of_its_columns():
    plan = lp.Filter(_gt("a"), _rel("a", "b", "c"))
    out, have, kept = prune_columns(plan)
    assert out is plan and (have, kept) == (3, 3)


def test_project_and_aggregate_ask_for_what_their_expressions_read():
    rel = _rel("a", "b", "c", "d")
    for top in (lp.Project((col("a"),), lp.Filter(_gt("b"), rel)),
                lp.Aggregate((col("a"),), (), lp.Filter(_gt("b"), rel))):
        out, have, kept = prune_columns(top)
        assert (have, kept) == (4, 2)
        # nothing between the narrowing parent and the filter: a Project
        # there would drop what the parent does not read anyway
        assert isinstance(out.child, lp.Filter)
        assert _project_of(out.child.child, "a", "b") is rel


def test_a_computing_parent_directly_over_a_table_inserts_nothing():
    plan = lp.Project((Alias(col("a"), "x"),), _rel("a", "b"))
    out, have, kept = prune_columns(plan)
    assert out is plan and (have, kept) == (2, 1)


@pytest.mark.parametrize("carrier", [
    lambda child: lp.Filter(_gt("b"), child),
    lambda child: lp.Sort((SortOrder.asc(col("b")),), child),
    lambda child: lp.Repartition(4, child, (col("b"),)),
], ids=["filter", "sort", "repartition"])
def test_a_carrier_asks_for_the_parents_columns_and_its_own(carrier):
    rel = _rel("a", "b", "c")
    out, _, kept = prune_columns(lp.Project((col("a"),), carrier(rel)))
    assert kept == 2
    assert _project_of(out.child.child, "a", "b") is rel


def test_limit_asks_for_what_the_parent_asks():
    rel = _rel("a", "b", "c")
    out, _, kept = prune_columns(lp.Project((col("c"),), lp.Limit(2, rel)))
    assert kept == 1
    assert _project_of(out.child.child, "c") is rel


def test_a_filter_whose_column_nothing_above_reads_is_projected_away():
    rel = _rel("a", "b", "c")
    plan = lp.Sort((SortOrder.asc(col("a")),),
                   lp.Limit(3, lp.Project(
                       (col("a"),), lp.Sort((SortOrder.asc(col("a")),),
                                            lp.Filter(_gt("b"), rel)))))
    out, _, _ = prune_columns(plan)
    inner_sort = out.child.child.child
    below = _project_of(inner_sort.child, "a")       # over the filter
    assert isinstance(below, lp.Filter)
    assert _project_of(below.child, "a", "b") is rel


def test_join_asks_each_side_for_its_keys_and_the_parents_share():
    left, right = _rel("a", "b", "c", "d"), _rel("x", "y", "z")
    join = lp.Join(left, right, "inner", (col("a"),), (col("x"),))
    out, have, kept = prune_columns(lp.Project((col("b"), col("y")), join))
    assert (have, kept) == (7, 4)
    assert _project_of(out.child.left, "a", "b") is left
    assert _project_of(out.child.right, "x", "y") is right
    # the condition's names count as the parent's
    cond = lp.Join(left, right, "inner", (col("a"),), (col("x"),),
                   EqualTo(col("c"), col("z")))
    out, _, kept = prune_columns(lp.Project((col("b"),), cond))
    assert kept == 5
    assert _project_of(out.child.left, "a", "b", "c") is left
    assert _project_of(out.child.right, "x", "z") is right


def test_a_semi_join_asks_its_right_side_for_keys_only():
    left, right = _rel("a", "b"), _rel("x", "y", "z")
    join = lp.Join(left, right, "left_semi", (col("a"),), (col("x"),))
    out, _, kept = prune_columns(lp.Project((col("b"),), join))
    assert kept == 3 and out.child.left is left
    assert _project_of(out.child.right, "x") is right


def test_a_join_whose_keys_nothing_above_reads_is_projected():
    left, right = _rel("a", "b"), _rel("x", "y")
    join = lp.Join(left, right, "inner", (col("a"),), (col("x"),))
    out, _, _ = prune_columns(
        lp.Project((col("b"),), lp.Filter(_gt("y"), join)))
    assert isinstance(_project_of(out.child.child, "b", "y"), lp.Join)


def test_window_generate_and_expand():
    from spark_rapids_tpu.exprs.windows import RowNumber, WindowExpression
    rel = _rel("a", "b", "c", "d")
    w = lp.Window((Alias(WindowExpression(
        RowNumber(), (col("a"),), (SortOrder.asc(col("b")),)), "rn"),), rel)
    out, _, kept = prune_columns(lp.Project((col("rn"), col("c")), w))
    assert kept == 3
    assert _project_of(out.child.child, "a", "b", "c") is rel

    g = lp.Generate((col("a"), col("b")), False, "e", rel)
    out, _, kept = prune_columns(lp.Project((col("e"), col("d")), g))
    assert kept == 3
    assert _project_of(out.child.child, "a", "b", "d") is rel

    null = Literal(None, DType.LONG)
    x = lp.Expand(((col("a"), col("b")), (col("a"), null)), ("a", "b"),
                  lp.Filter(_gt("c"), rel))
    out, _, kept = prune_columns(x)
    assert kept == 3
    assert _project_of(out.child.child, "a", "b", "c") is rel


# ------------------------------------------- where every column is kept
class _Unknown(lp.LogicalPlan):
    def __init__(self, child):
        self.child = child

    @property
    def children(self):
        return (self.child,)

    def schema(self):
        return self.child.schema()


@pytest.mark.parametrize("build", [
    lambda rel: lp.Project((col("a"),), _Unknown(lp.Filter(_gt("a"), rel))),
    lambda rel: lp.Project((col("a"),), lp.Union(
        lp.Filter(_gt("a"), rel), lp.Filter(_gt("a"), rel))),
    lambda rel: lp.WriteFiles(object(), lp.Filter(_gt("a"), rel)),
    # b on both sides: the right one leaves the join as b_1
    lambda rel: lp.Project((col("a"),), lp.Join(
        lp.Filter(_gt("a"), rel), lp.Filter(_gt("b"), _rel("b", "k")),
        "inner", (col("a"),), (col("k"),))),
    # a reference by ordinal, as the UDF compiler leaves them
    lambda rel: lp.Project((col("a"),), lp.Filter(GreaterThan(
        BoundReference(1, DType.LONG, True, "b"), Literal(1, DType.LONG)),
        rel)),
], ids=["unknown", "union", "write", "duplicate-names", "ordinal"])
def test_where_the_pass_cannot_reason_nothing_is_narrowed(build):
    rel = _rel("a", "b", "c")
    plan = build(rel)
    out, have, kept = prune_columns(plan)
    assert have == kept

    def projects(node):
        return isinstance(node, lp.Project) + sum(
            projects(c) for c in node.children)
    assert projects(out) == projects(plan)


def test_below_a_conservative_node_the_pass_goes_on():
    rel = _rel("a", "b", "c")
    inner = lp.Project((col("a"),), lp.Filter(_gt("b"), rel))
    out, have, kept = prune_columns(lp.Union(inner, inner))
    assert (have, kept) == (6, 4)
    assert _project_of(out.left.child.child, "a", "b") is rel


# ---------------------------------------------------------------- file scans
def test_a_file_scan_is_narrowed_in_file_order_and_keeps_its_partitions():
    scan = _scan("a", "b", "c", "p", partitions=("p",))
    out, have, kept = prune_columns(
        lp.Project((col("c"), col("a")), lp.Filter(_gt("c"), scan)))
    narrowed = out.child.child
    assert isinstance(narrowed, lp.FileScan)       # nothing inserted
    assert narrowed.read_schema.names() == ["a", "c", "p"]
    assert (have, kept) == (4, 3)
    assert narrowed.partition_schema is scan.partition_schema


def test_a_bare_count_keeps_one_narrow_data_column():
    schema = Schema([Field("s", DType.STRING, True),
                     Field("d", DType.DOUBLE, True),
                     Field("i", DType.INT, True),
                     Field("p", DType.LONG, True)])
    scan = lp.FileScan("parquet", ("/nowhere",), schema,
                       partition_schema=Schema([schema[3]]))
    from spark_rapids_tpu.exprs.aggregates import Count
    out, have, kept = prune_columns(lp.Aggregate(
        (), (Alias(Count((Literal(1, DType.INT),)), "n"),), scan))
    assert out.child.read_schema.names() == ["i", "p"]
    assert (have, kept) == (4, 2)


# ------------------------------------------------- the benchmark's queries
@pytest.fixture(scope="module")
def tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, 2**31 + 29)


@pytest.fixture(scope="module")
def session():
    return TpuSession(CONF)


def _collect_unpruned(df):
    """The same logical plan through the planner without the pass."""
    conf = df.session.conf
    final = TpuOverrides(conf).apply(
        ensure_requirements(_plan_node(df._plan, conf)))
    return pa.concat_tables(df._run_partitions(final, publish_trace=False))


# Q18 scans lineitem twice, two columns each time
@pytest.mark.parametrize("query,have,kept", [(q1, 16, 7), (q6, 16, 4),
                                             (q3, 33, 10), (q18, 49, 10)],
                         ids=["q1", "q6", "q3", "q18"])
def test_the_benchmarks_queries_keep_the_columns_they_name(
        query, have, kept, tables, session):
    dfs = {n: session.createDataFrame(t) for n, t in tables.items()}
    df = query.build(dfs)
    _, scan_columns, scan_columns_kept = prune_columns(df._plan)
    assert (scan_columns, scan_columns_kept) == (have, kept)
    assert_tables_equal(_collect_unpruned(df), df.collect())
    assert not any(type(n).__name__.startswith("Cpu")
                   and type(n).__name__ != "CpuLocalScanExec"
                   for n in _execs(session.last_plan))


def _execs(plan):
    yield plan
    for c in plan.children:
        yield from _execs(c)


def test_the_plan_span_says_how_far_the_pass_engaged(tables):
    session = TpuSession({**CONF, **TRACE})
    dfs = {n: session.createDataFrame(t) for n, t in tables.items()}
    q3.build(dfs).collect()
    (plan,) = [r for r in session.last_trace if r.name == "plan"]
    assert plan.args["scan_columns"] == 33
    assert plan.args["scan_columns_kept"] == 10


@pytest.fixture(scope="module")
def lineitem_file(tables, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pruning") / "lineitem.parquet")
    pq.write_table(tables["lineitem"], path)
    return path


def _uploaded():
    return TRANSFER_METRICS.snapshot()["transfer.upload_bytes"]


def test_a_parquet_scan_of_q6_reads_four_columns(lineitem_file, tables,
                                                 session, monkeypatch):
    asked = []
    real = pq.ParquetFile.read_row_group

    def spy(self, i, columns=None, **kw):
        asked.append(tuple(columns))
        return real(self, i, columns=columns, **kw)

    monkeypatch.setattr(pq.ParquetFile, "read_row_group", spy)
    real_iter = pq.ParquetFile.iter_batches

    def spy_iter(self, *a, columns=None, **kw):
        asked.append(tuple(columns))
        return real_iter(self, *a, columns=columns, **kw)

    monkeypatch.setattr(pq.ParquetFile, "iter_batches", spy_iter)
    q6_names = {"l_quantity", "l_extendedprice", "l_discount", "l_shipdate"}
    narrow = q6.build({"lineitem": session.read.parquet(lineitem_file)})
    before = _uploaded()
    got = narrow.collect()
    uploaded = _uploaded() - before
    scans = [n for n in _execs(session.last_plan)
             if getattr(n, "is_file_scan", False)]
    assert [set(s.output.names()) for s in scans] == [q6_names]
    assert asked and all(set(c) <= q6_names for c in asked)
    assert_tables_equal(
        q6.build({"lineitem": session.createDataFrame(tables["lineitem"])}
                 ).collect(), got)
    # ... and ships them only: the whole file is several times that
    before = _uploaded()
    session.read.parquet(lineitem_file).collect()
    assert uploaded * 3 < _uploaded() - before


def test_a_bare_count_over_a_parquet_scan(lineitem_file, tables, session):
    df = session.read.parquet(lineitem_file)
    assert df.count() == tables["lineitem"].num_rows
    (scan,) = [n for n in _execs(session.last_plan)
               if getattr(n, "is_file_scan", False)]
    assert len(scan.output) == 1


def test_input_file_name_above_a_pruned_parquet_scan(lineitem_file, session):
    df = (session.read.parquet(lineitem_file)
          .filter(F.col("l_quantity") < 2)
          .select("l_orderkey", F.input_file_name().alias("f")))
    out = df.collect()
    assert out.column_names == ["l_orderkey", "f"]
    assert set(out.column("f").to_pylist()) == {lineitem_file}
    (scan,) = [n for n in _execs(session.last_plan)
               if getattr(n, "is_file_scan", False)]
    assert [n for n in scan.output.names()
            if not n.startswith("__input_file_")] == ["l_orderkey",
                                                      "l_quantity"]


def test_a_csv_scan_reads_the_pruned_columns(tmp_path, session):
    path = os.path.join(tmp_path, "t.csv")
    with open(path, "w") as f:
        f.write("a,b,c\n1,2,x\n3,4,y\n")
    df = session.read.option("header", "true").csv(path)
    assert df.filter(F.col("a") > 1).select("c").collect().to_pydict() == {
        "c": ["y"]}
    assert df.count() == 2


def test_a_narrower_query_still_reads_the_cached_relation(tables, session):
    base = session.createDataFrame(tables["orders"]).filter(
        F.col("o_shippriority") == 0)
    base.cache()
    try:
        base.count()                       # materializes
        got = base.filter(F.col("o_totalprice") > 1000.0).select(
            "o_orderkey").collect()
        plan = session.last_plan.tree_string()
        assert "CachedScanExec" in plan, plan
        want = tables["orders"].filter(
            pa.compute.greater(tables["orders"].column("o_totalprice"),
                               1000.0)).select(["o_orderkey"])
        assert_tables_equal(want, got, ignore_order=True)
    finally:
        base.unpersist()


def test_two_column_sets_over_one_table_share_one_scan_cache_entry(tables):
    from spark_rapids_tpu.memory import scan_cache
    session = TpuSession(CONF)
    table = pa.table({n: tables["orders"].column(n)
                      for n in tables["orders"].column_names})  # a new identity
    df = session.createDataFrame(table)
    before = _uploaded()
    a = df.filter(F.col("o_totalprice") > 1000.0).select("o_orderkey").collect()
    first = _uploaded() - before
    before = _uploaded()
    b = df.filter(F.col("o_shippriority") == 0).select("o_custkey",
                                                       "o_orderdate").collect()
    assert first > 0 and _uploaded() - before == 0
    assert a.num_rows and b.num_rows
    entries = [k for k in scan_cache.peek_cache()._entries
               if k[0] == id(table)]
    assert len(entries) == 1
    # the resident batch is the whole table
    batch = scan_cache.peek_cache().get(table, session.conf.string_max_bytes)
    assert batch.schema.names() == table.column_names


def test_a_projection_of_plain_references_calls_no_program(tables):
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    from spark_rapids_tpu.execs.evaluator import eval_exprs_device
    batch = DeviceBatch.from_arrow(tables["customer"].slice(0, 100), 64)
    refs = [BoundReference(i, f.dtype, f.nullable, f.name)
            for i, f in enumerate(batch.schema)]
    exprs = (Alias(refs[6], "segment"), refs[0])
    cache = global_program_cache()
    calls = lambda: cache.stats()["hits"] + cache.stats()["misses"]  # noqa: E731
    before = calls()
    out = eval_exprs_device(exprs, batch, 64)
    assert calls() == before
    assert out.schema.names() == ["segment", "c_custkey"]
    assert out.columns[0] is batch.columns[6]
    assert out.columns[1] is batch.columns[0]
    assert out.num_rows == 100
    # and through the exec, in a whole query: Sort(Project(table)) plans a
    # TpuProjectExec that stands alone
    session = TpuSession(CONF)
    df = session.createDataFrame(tables["customer"])
    narrow = df.select("c_custkey", "c_acctbal").sort("c_acctbal").limit(5)
    narrow.collect()                               # programs compiled
    assert "TpuProjectExec" in session.last_plan.tree_string()
    before = calls()
    narrow.collect()
    with_project = calls() - before
    before = calls()
    df.sort("c_acctbal").limit(5).collect()
    df.sort("c_acctbal").limit(5).collect()
    assert with_project <= (calls() - before) // 2


def test_plan_physical_is_where_the_pass_runs(tables, session):
    df = q6.build({"lineitem": session.createDataFrame(tables["lineitem"])})
    noted = {}
    plan_physical(df._plan, session.conf, note=noted.update)
    assert noted == {"scan_columns": 16, "scan_columns_kept": 4}
