"""TPC-H Q3 over three parquet tables, the deployment of
``tpch_sf1_parquet.join``: ``session.read.parquet`` of one directory a table,
the engine against the benchmark's plain numpy reference on the same Arrow
tables, and the ``join.drain`` spans of the two joins, whose children are the
file scans. CPU, SF0.01; the chip's cell is ``tpch_sf1_parquet.join``
(benchmark/)."""
import datetime
import os

import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import correct
from benchmark.datagen import gen_tables
from benchmark.queries import q3
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.utils import tracing

#: the confs of benchmark/configs/tpch_sf1_parquet_q3.json, traced; both joins
#: shuffled, as SF1 plans the join with lineitem (at SF0.01 every side would
#: be broadcast, and a broadcast side arrives as one batch)
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false",
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.sql.broadcastJoinThreshold.bytes": "1"}
NO_OOC = {"spark.rapids.tpu.memory.outOfCore.enabled": "false"}
TABLES = ("customer", "orders", "lineitem")
#: rows a row group: one group a table, or three to four
GROUP_ROWS = {"one": None,
              "several": {"customer": 500, "orders": 5_000,
                          "lineitem": 20_000}}


@pytest.fixture(scope="module")
def tables():
    return gen_tables(list(TABLES), 0.01, 2**31 + 38)


@pytest.fixture(scope="module", params=list(GROUP_ROWS))
def files(request, tables, tmp_path_factory):
    """(one directory a table, row groups a table)."""
    root = tmp_path_factory.mktemp(f"q3_{request.param}")
    groups = {}
    for name, table in tables.items():
        os.mkdir(root / name)
        path = root / name / "part-0.parquet"
        sizes = GROUP_ROWS[request.param]
        pq.write_table(table, path,
                       row_group_size=sizes and sizes[name])
        groups[name] = pq.ParquetFile(path).num_row_groups
    return {n: str(root / n) for n in TABLES}, groups


def _collect(dirs, conf=()):
    session = TpuSession({**CONF, **dict(conf)})
    dfs = {n: session.read.parquet(d) for n, d in dirs.items()}
    return session, q3.build(dfs).collect()


def _drains(session):
    """The last collect's ``join.drain`` spans, outermost first."""
    records = list(session.last_trace)
    by_id = {r.span_id: r for r in records}

    def depth(r):
        n = 0
        while r.parent_id is not None:
            r = by_id[r.parent_id]
            n += r.name == "join.drain"
        return n

    return sorted((r for r in records if r.name == "join.drain"), key=depth)


def _scanned(session, table):
    """The batches the scan of ``table`` emitted, by its exec span."""
    (scan,) = [r for r in session.last_trace
               if r.name == "TpuParquetScanExec"
               and r.args["rows"] == table.num_rows]
    return scan.args["batches"]


def _kept(tables):
    """Rows through each of Q3's three filters, counted on the Arrow
    tables."""
    cutoff = datetime.date(1995, 3, 15)
    return (pc.sum(pc.equal(tables["customer"]["c_mktsegment"],
                            "BUILDING")).as_py(),
            pc.sum(pc.less(tables["orders"]["o_orderdate"], cutoff)).as_py(),
            pc.sum(pc.greater(tables["lineitem"]["l_shipdate"],
                              cutoff)).as_py())


def test_q3_over_three_files_is_the_references(files, tables):
    """The plan reads each table from its directory, the answer is the
    reference's under its limits, and the join over two scans concatenated
    one batch a row group on each side."""
    dirs, groups = files
    session, got = _collect(dirs)
    plan = session.last_plan.tree_string()
    assert plan.count("TpuParquetScanExec") == 3
    assert "Cpu" not in plan and "HostToDeviceExec" not in plan
    ok, numbers = correct.judge({"q3": [got]}, tables, 0)
    assert ok, numbers
    inner = _drains(session)[-1]
    assert inner.args["mode"] == "inline"
    assert inner.args["left_batches"] == groups["customer"]
    assert inner.args["right_batches"] == groups["orders"]
    # the join's own concatenations: a side of several batches each
    concats = sorted(r.args["batches"] for r in session.last_trace
                     if r.name == "batch.concat")
    assert concats == sorted(g for g in groups.values() if g > 1)


@pytest.mark.parametrize("conf", [{}, NO_OOC], ids=["staged", "drained"])
def test_the_drains_count_what_the_scans_emitted(files, tables, conf):
    """One span a join for both sides where the out-of-core controller
    stages them, one a side without it; either way the inner join drained
    what the customer and orders scans emitted, the outer one lineitem's
    scan and the inner join's one batch."""
    session, _ = _collect(files[0], conf)
    drains = _drains(session)
    for r in drains:
        assert r.cat == tracing.LAYER_EXEC
        assert {"side", "batches", "rows"} <= set(r.args)
    customer, orders, lineitem = (_scanned(session, tables[n])
                                  for n in TABLES)
    kept_c, kept_o, kept_l = _kept(tables)
    # the inner join lies below the outer one: the higher plan id
    joined = max((r for r in session.last_trace
                  if r.name == "TpuShuffledHashJoinExec"),
                 key=lambda r: r.plan_id).args["rows"]
    if conf:
        assert [r.args["side"] for r in drains] == [
            "left", "right", "left", "right"]
        assert "mode" not in drains[0].args
        top_l, top_r, inner_l, inner_r = drains
        assert [(r.args["batches"], r.args["rows"]) for r in drains] == [
            (1, joined), (lineitem, kept_l), (customer, kept_c),
            (orders, kept_o)]
        # the inner join's drains lie inside the outer join's left one
        assert all(top_l.ts_ns <= r.ts_ns and r.ts_ns + r.dur_ns
                   <= top_l.ts_ns + top_l.dur_ns for r in (inner_l, inner_r))
    else:
        top, inner = drains
        assert [top.args["side"], top.args["mode"]] == ["both", "inline"]
        assert (top.args["left_batches"], top.args["right_batches"],
                top.args["batches"], top.args["rows"]) == (
            1, lineitem, 1 + lineitem, joined + kept_l)
        assert (inner.args["left_batches"], inner.args["right_batches"],
                inner.args["batches"], inner.args["rows"]) == (
            customer, orders, customer + orders, kept_c + kept_o)
        assert top.ts_ns <= inner.ts_ns


def test_without_tracing_no_drain_is_recorded(files):
    session, _ = _collect(files[0], {"spark.rapids.tpu.trace.enabled":
                                     "false"})
    assert not [r for r in session.last_trace if r.name == "join.drain"]
