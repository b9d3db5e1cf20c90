"""TPC-H Q1 over ``lineitem.repartition(8, "l_orderkey")``, the deployment of
``tpch_sf1_exchange``: the answer against a plain numpy Q1 written here, the
partitioning against a plain reference of what a hash partitioning promises,
what the ``exchange.map`` / ``exchange.split`` / ``exchange.fetch`` spans say
of one query, and that a session which runs the query again and again builds
nothing new and leaves nothing in the shuffle catalog. CPU, SF0.01; the
chip's cell is ``tpch_sf1_exchange.repartition`` (benchmark/), and the
placement of every row at SF1 on the chip is ``chip_smoke.py``'s exchange
leg."""
import collections
import datetime

import numpy as np
import pytest

from benchmark.datagen import gen_tables
from benchmark.queries import q1_repart
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api.functions import col, spark_partition_id
from spark_rapids_tpu.memory.device_manager import DeviceManager
from spark_rapids_tpu.serving.program_cache import global_program_cache
from spark_rapids_tpu.utils import tracing

#: the confs of benchmark/configs/tpch_sf1_exchange.json
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false"}
TRACE = {"spark.rapids.tpu.trace.enabled": "true"}
KERNEL_MODE = "spark.rapids.tpu.shuffle.kernel.mode"
#: the device engine with the reorder kernel off (the sort path, what the
#: CPU backend takes at the default ``auto``) and interpreted (the kernel
#: path the TPU takes), and the CPU engine
ENGINES = {"sort": {KERNEL_MODE: "off"},
           "kernel": {KERNEL_MODE: "interpret"},
           "cpu": {"spark.rapids.tpu.sql.enabled": "false"}}
SEEDS = (2**31 + 34, 34)
PARTITIONS = q1_repart.PARTITIONS
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def lineitem(request):
    return gen_tables(["lineitem"], 0.01, request.param)["lineitem"]


def _session(engine, extra=()):
    return TpuSession({**CONF, **ENGINES[engine], **dict(extra)})


def _plain_q1(table):
    """Q1 in plain numpy: {(flag, status): (four sums, three averages,
    count)}, float64 sums in the table's order."""
    c = {n: table.column(n).to_numpy(zero_copy_only=False)
         for n in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")}
    flag = np.asarray(table.column("l_returnflag").to_pylist())
    status = np.asarray(table.column("l_linestatus").to_pylist())
    ship = np.asarray(table.column("l_shipdate").to_pylist())
    keep = ship <= datetime.date(1998, 9, 2)
    disc_price = c["l_extendedprice"] * (1 - c["l_discount"])
    charge = disc_price * (1 + c["l_tax"])
    out = {}
    for key in sorted(set(zip(flag[keep], status[keep]))):
        sel = keep & (flag == key[0]) & (status == key[1])
        n = int(sel.sum())
        sums = [float(v[sel].sum()) for v in
                (c["l_quantity"], c["l_extendedprice"], disc_price, charge)]
        avgs = [sums[0] / n, sums[1] / n, float(c["l_discount"][sel].sum()) / n]
        out[key] = (sums, avgs, n)
    return out


@pytest.mark.parametrize("engine", list(ENGINES))
def test_q1_over_the_repartition_is_plain_q1(lineitem, engine):
    session = _session(engine)
    got = q1_repart.build(
        {"lineitem": session.createDataFrame(lineitem)}).collect()
    want = _plain_q1(lineitem)
    rows = got.to_pylist()
    # order by l_returnflag, l_linestatus; no row lost, none doubled
    assert [(r["l_returnflag"], r["l_linestatus"]) for r in rows] \
        == list(want)
    for r in rows:
        sums, avgs, n = want[(r["l_returnflag"], r["l_linestatus"])]
        assert r["count_order"] == n
        np.testing.assert_allclose([r[c] for c in SUMS], sums, rtol=1e-12)
        np.testing.assert_allclose([r[c] for c in AVGS], avgs, rtol=1e-12)
    plan = session.last_plan.tree_string()
    if engine == "cpu":
        assert "CpuShuffleExchangeExec" in plan
    else:
        assert plan.count("TpuShuffleExchangeExec") == 2    # hash, single
        assert "CpuShuffleExchangeExec" not in plan


def _placement(lineitem, engine):
    """{(l_orderkey, l_linenumber): partition} of the repartitioned table
    (the pair is lineitem's primary key), with its collected row count."""
    session = _session(engine)
    t = (session.createDataFrame(lineitem)
         .repartition(PARTITIONS, "l_orderkey")
         .select(col("l_orderkey"), col("l_linenumber"),
                 spark_partition_id().alias("p"))).collect()
    rows = list(zip(t.column("l_orderkey").to_pylist(),
                    t.column("l_linenumber").to_pylist(),
                    t.column("p").to_pylist()))
    return {(k, ln): p for k, ln, p in rows}, len(rows)


@pytest.fixture(scope="module")
def placements(lineitem):
    return {engine: _placement(lineitem, engine) for engine in ENGINES}


@pytest.mark.parametrize("engine", list(ENGINES))
def test_every_row_is_in_one_partition_and_a_key_in_one(lineitem, placements,
                                                        engine):
    placed, collected = placements[engine]
    # every row of the table came back, and once: the rows collected are
    # as many as the table's and as many as their distinct primary keys
    assert collected == len(placed) == lineitem.num_rows
    assert set(placed) == set(zip(lineitem.column("l_orderkey").to_pylist(),
                                  lineitem.column("l_linenumber").to_pylist()))
    by_key = collections.defaultdict(set)
    for (key, _), p in placed.items():
        assert 0 <= p < PARTITIONS
        by_key[key].add(p)
    assert all(len(parts) == 1 for parts in by_key.values())
    # a hash spreads 15,000 orders over 8 partitions: none stays empty
    assert len({p for parts in by_key.values() for p in parts}) == PARTITIONS


@pytest.mark.parametrize("engine", ["kernel", "cpu"])
def test_the_engines_and_kernel_modes_place_alike(placements, engine):
    """Row for row, so partition sizes too: the sort path, the interpreted
    reorder kernel and the CPU engine compute one partitioning."""
    assert placements[engine][0] == placements["sort"][0]
    sizes = collections.Counter(placements[engine][0].values())
    assert sizes == collections.Counter(placements["sort"][0].values())


def _query_spans(session):
    """The last collect's records and its exchange spans by name, each shown
    to hang under the collect's one ``query`` root."""
    records = list(session.last_trace)
    by_id = {r.span_id: r for r in records}
    (root,) = [r for r in records
               if r.name == "query" and r.parent_id is None]
    spans = collections.defaultdict(list)
    for r in records:
        if not r.name.startswith("exchange."):
            continue
        assert r.cat == tracing.LAYER_SHUFFLE
        top = r
        while top.parent_id is not None:
            top = by_id[top.parent_id]
        assert top is root
        spans[r.name].append(r)
    return records, spans


@pytest.mark.parametrize("engine", ["sort", "kernel"])
def test_a_query_leaves_the_exchanges_spans(lineitem, engine):
    session = _session(engine, TRACE)
    dfs = {"lineitem": session.createDataFrame(lineitem)}
    q1_repart.build(dfs).collect()
    records, spans = _query_spans(session)
    execs = [r for r in records if r.name == "TpuShuffleExchangeExec"]
    # one map per exchange of the plan: the hash repartition, and the single
    # exchange the planner puts under the aggregate
    by_kind = {m.args["partitioning"]: m for m in spans["exchange.map"]}
    assert sorted(by_kind) == ["hash", "single"]
    assert len(spans["exchange.map"]) == len({r.plan_id for r in execs}) == 2
    hashed, single = by_kind["hash"], by_kind["single"]
    assert hashed.args["partitions"] == PARTITIONS
    assert hashed.args["rows"] == lineitem.num_rows
    assert hashed.args["pieces"] == PARTITIONS
    # 8 columns at stored widths: an int64, four doubles, a date and two
    # strings of 8 bytes, each with its validity byte, a string its length
    assert hashed.args["bytes"] == lineitem.num_rows * (
        (8 + 1) + 4 * (8 + 1) + (4 + 1) + 2 * (8 + 1 + 4))
    splits = spans["exchange.split"]
    under = collections.defaultdict(list)
    for s in splits:
        under[s.parent_id].append(s)
        assert s.args["widenings"] >= 0
        assert 0 < s.args["rows"] <= s.args["cap"]
    (split,) = under[hashed.span_id]
    assert split.args["path"] == engine
    assert split.args["rows"] == hashed.args["rows"]
    assert (hashed.args["kernel_batches"], hashed.args["sort_batches"]) \
        == ((1, 0) if engine == "kernel" else (0, 1))
    # the single exchange passes each of the eight filtered pieces through
    assert [s.args["path"] for s in under[single.span_id]] \
        == ["single"] * PARTITIONS
    assert single.args["kernel_batches"] == single.args["sort_batches"] == 0
    assert sum(s.args["rows"] for s in under[single.span_id]) \
        == single.args["rows"] <= lineitem.num_rows
    moved = [s for s in splits if s.args["path"] != "single"]
    assert len(moved) == sum(m.args["kernel_batches"] + m.args["sort_batches"]
                             for m in spans["exchange.map"])
    # the reduce side: one fetch a block, the eight partitions of the hash
    # exchange (one block each) and then the eight blocks of the single
    # partition above, each closed before its consumer ran
    fetches = spans["exchange.fetch"]
    by_exec = collections.defaultdict(list)
    for r in fetches:
        by_exec[r.plan_id].append(r)
    assert sorted(r.args["partition"] for r in by_exec[hashed.plan_id]) \
        == list(range(PARTITIONS))
    assert [r.args["partition"] for r in by_exec[single.plan_id]] \
        == [0] * PARTITIONS
    assert sorted(r.args["map_id"] for r in by_exec[single.plan_id]) \
        == list(range(PARTITIONS))
    assert sum(r.args["rows"] for r in fetches) \
        == hashed.args["rows"] + single.args["rows"]
    assert all(r.args["bytes"] > 0 for r in fetches)
    assert len(fetches) == 2 * PARTITIONS
    assert set(by_exec) == {r.plan_id for r in execs}
    # closed before the consumer ran: the stage's program over a fetched
    # batch starts after that batch's fetch has ended, and no span lies
    # under a fetch
    assert not [r for r in records
                if r.parent_id in {f.span_id for f in fetches}]
    stages = sorted((r for r in records if r.name == "program.stage"),
                    key=lambda r: r.ts_ns)
    hash_fetches = sorted(by_exec[hashed.plan_id], key=lambda r: r.ts_ns)
    assert len(stages) == len(hash_fetches)
    for fetch, stage in zip(hash_fetches, stages):
        assert fetch.ts_ns + fetch.dur_ns <= stage.ts_ns
    # the aggregate over the eight batches concatenates them once, under
    # its own exec span
    (concat,) = [r for r in records if r.name == "batch.concat"]
    assert by_id_name(records, concat.parent_id) == "TpuHashAggregateExec"
    assert concat.args["batches"] == PARTITIONS
    assert concat.args["rows"] == single.args["rows"]
    assert concat.args["dispatches"] > concat.args["columns"] * PARTITIONS
    if engine == "kernel":
        # the consolidation program, one call a piece, through the wrapper
        # that gives every cached program its span
        consols = [r for r in records if r.name == "program.pconsol"]
        assert len(consols) == PARTITIONS
        assert {by_id_name(records, r.parent_id) for r in consols} \
            == {"exchange.split"}


def by_id_name(records, span_id):
    return next(r.name for r in records if r.span_id == span_id)


def test_without_tracing_the_exchange_leaves_no_span_and_no_program(lineitem):
    """The spans add no program call: a traced and an untraced run of the
    query call the program cache equally often."""
    calls = {}
    for name, extra in (("traced", TRACE), ("plain", {})):
        session = _session("kernel", extra)
        dfs = {"lineitem": session.createDataFrame(lineitem)}
        q1_repart.build(dfs).collect()              # builds what is missing
        before = global_program_cache().stats()
        mark = tracing.TRACER.mark()
        q1_repart.build(dfs).collect()
        after = global_program_cache().stats()
        calls[name] = (after["hits"] + after["misses"]
                       - before["hits"] - before["misses"])
        if name == "plain":
            assert not tracing.TRACER.since(mark)
    assert calls["plain"] == calls["traced"] > 0


@pytest.mark.parametrize("engine", ["sort", "kernel"])
def test_later_runs_build_nothing_and_leave_no_block(lineitem, engine):
    session = _session(engine)
    dfs = {"lineitem": session.createDataFrame(lineitem)}
    first = q1_repart.build(dfs).collect()
    shuffle = DeviceManager.get()._exchange_shuffle_env.shuffle_catalog
    for _ in range(2):
        before = global_program_cache().stats()["misses"]
        again = q1_repart.build(dfs).collect()
        assert global_program_cache().stats()["misses"] == before
        assert again.equals(first)
        assert not shuffle._blocks and not shuffle._by_shuffle
