"""TPC-H Q18, the deployment of ``tpch_sf1_highcard``: the engine against the
benchmark's plain reference under the reference's own limits, and what the
``agg.attempt`` spans say of the aggregate's grouping ladder (onehot, hash,
sort), on the single-device and on the mesh aggregate. CPU, SF0.01; the
chip's cell is ``tpch_sf1_highcard.q18`` (benchmark/)."""
import numpy as np
import pytest

from benchmark import correct
from benchmark.datagen import gen_tables
from benchmark.queries import q1, q6, q18
from benchmark.reference import q18 as q18_reference
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.execs import tpu_execs
from spark_rapids_tpu.ops import aggregate
from spark_rapids_tpu.utils import tracing

#: the confs of benchmark/configs/tpch_sf1_highcard.json
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.tpu.sql.hasNans": "false"}
TRACE = {"spark.rapids.tpu.trace.enabled": "true"}
MESH4 = {"spark.rapids.tpu.sql.mesh.enabled": "true",
         "spark.rapids.tpu.sql.mesh.numDevices": "4"}
SEEDS = (2**31 + 33, 32)
#: at SF0.01 the validation parameter leaves a row or two; 150 leaves more
#: than the limit's 100, so that the top-N and its order are tested
QUANTITIES = (300, 150)


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def seeded(request):
    tables = gen_tables(["customer", "orders", "lineitem"], 0.01,
                        request.param)
    session = TpuSession(CONF)
    return tables, {n: session.createDataFrame(t) for n, t in tables.items()}


@pytest.fixture(scope="module")
def tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, SEEDS[0])


def _traced(tables, conf=()):
    session = TpuSession({**CONF, **TRACE, **dict(conf)})
    return session, {n: session.createDataFrame(t)
                     for n, t in tables.items()}


def _attempts(session):
    """The last collect's ``agg.attempt`` spans, oldest first, each shown to
    hang under the collect's one ``query`` root."""
    records = list(session.last_trace)
    by_id = {r.span_id: r for r in records}
    (root,) = [r for r in records
               if r.name == "query" and r.parent_id is None]
    attempts = [r for r in records if r.name == "agg.attempt"]
    for r in attempts:
        top = r
        while top.parent_id is not None:
            top = by_id[top.parent_id]
        assert top is root
        assert r.cat == tracing.LAYER_EXEC
    return attempts


def _ladder(attempts, keys):
    return [(r.args["mode"], r.args["flagged"]) for r in attempts
            if r.args["keys"] == keys]


def _distinct_orderkeys(tables):
    return len(np.unique(tables["lineitem"].column("l_orderkey").to_numpy()))


@pytest.fixture
def small_group_cap(monkeypatch):
    """``GROUP_CAP`` under the first aggregate's group count. It is read when
    a program is traced, so programs built before and under the patch go."""
    tpu_execs._JIT_CACHE.clear()
    monkeypatch.setattr(aggregate, "GROUP_CAP", 1024)
    yield 1024
    tpu_execs._JIT_CACHE.clear()


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_q18_is_the_references_answer(seeded, quantity):
    tables, dfs = seeded
    ref = q18_reference.answer(tables, quantity=quantity)
    assert q18_reference.tied_rows(tables, quantity) == 0
    assert ref.num_rows == 100 if quantity == 150 else 0 < ref.num_rows < 100
    got = q18.build(dfs, quantity).collect()
    mismatches, gap = correct.compare(got, ref, q18_reference.EXACT)
    assert mismatches == 0
    assert gap <= q18_reference.REL_GAP_LIMIT
    # on this backend a double is a double: o_totalprice comes back bit for
    # bit, which the TPU's pair of float32 cannot do (reference/q18.py)
    assert got.column("o_totalprice") == ref.column("o_totalprice")
    _, control = correct.compare(
        q18_reference.answer(tables, "float32", quantity), ref,
        q18_reference.EXACT)
    assert control > 100 * q18_reference.REL_GAP_LIMIT    # by o_totalprice


def test_a_collect_of_q18_leaves_the_ladders_attempts(tables):
    session, dfs = _traced(tables)
    q18.build(dfs, 150).collect()
    attempts = _attempts(session)
    # the first aggregate: a quarter of lineitem's rows are groups, the
    # one-hot path is tried and lost, the hash path's 65,536 hold them here
    assert _ladder(attempts, 1) == [("onehot", True), ("hash", False)]
    lost, kept = [r for r in attempts if r.args["keys"] == 1]
    assert "groups" not in lost.args
    assert kept.args["groups"] == _distinct_orderkeys(tables)
    assert kept.args["capacity"] == lost.args["capacity"] \
        >= tables["lineitem"].num_rows
    # the last one: every order that passed the HAVING is a group
    assert _ladder(attempts, 5) == [("onehot", True), ("hash", False)]
    assert attempts[-1].args["groups"] == q18_reference.ranked(
        tables, "float64", 150).num_rows > aggregate.ONEHOT_CAP


def test_over_group_cap_hash_is_lost_too_and_sort_answers(
        tables, small_group_cap):
    assert _distinct_orderkeys(tables) > small_group_cap
    session, dfs = _traced(tables)
    got = q18.build(dfs, 150).collect()
    attempts = _attempts(session)
    assert _ladder(attempts, 1) == [("onehot", True), ("hash", True),
                                    ("sort", False)]
    assert [r.args.get("groups") for r in attempts if r.args["keys"] == 1] \
        == [None, None, _distinct_orderkeys(tables)]
    ref = q18_reference.answer(tables, quantity=150)
    assert correct.compare(got, ref, q18_reference.EXACT) == (0, 0.0)


def test_q1_leaves_one_onehot_attempt_and_discards_nothing(tables):
    session, dfs = _traced(tables)
    got = q1.build(dfs).collect()
    (attempt,) = _attempts(session)
    assert (attempt.args["mode"], attempt.args["flagged"]) == ("onehot",
                                                              False)
    assert attempt.args["groups"] == got.num_rows == 4
    assert attempt.args["keys"] == 2
    assert attempt.args["reduce"] == "onehot"


def _reduces(attempts, keys):
    return [(r.args["mode"], r.args["reduce"]) for r in attempts
            if r.args["keys"] == keys]


def test_the_spans_say_which_form_the_reduction_took(tables, small_group_cap):
    """Q18's first aggregate: ``hash`` and ``sort`` both reduce their sorted
    rows by scans (the one-hot path has no sorted segments to reduce); the
    last one, a row or two here and some 70 at SF1, stays under the least
    capacity of the scans (the plain form, which
    tests/test_dense_segment_reduce.py drives). Q6 has no keys: one
    segment, scanned."""
    session, dfs = _traced(tables)
    q18.build(dfs).collect()
    attempts = _attempts(session)
    assert _reduces(attempts, 1) == [("onehot", "onehot"), ("hash", "scan"),
                                     ("sort", "scan")]
    assert attempts[0].args["capacity"] >= 2048
    assert _reduces(attempts, 5) == [("onehot", "onehot")]
    assert attempts[-1].args["capacity"] < 2048
    q6.build(dfs).collect()
    (attempt,) = _attempts(session)
    assert (attempt.args["mode"], attempt.args["reduce"],
            attempt.args["keys"]) == ("hash", "scan", 0)
    assert attempt.args["capacity"] >= 2048


def test_without_tracing_the_ladder_leaves_nothing(seeded):
    _, dfs = seeded
    mark = tracing.TRACER.mark()
    q18.build(dfs).collect()
    assert not tracing.TRACER.since(mark)


def _first_aggregate(dfs):
    return (dfs["lineitem"].groupBy("l_orderkey")
            .agg(F.sum("l_quantity").alias("qty")).sort("l_orderkey"))


@pytest.mark.parametrize("kept", ["hash", "sort"])
def test_the_mesh_aggregate_leaves_the_same_spans(
        tables, eight_devices, request, kept):
    """Q18's first aggregate as the partial aggregate of four shards: the
    same ladder, one flag for the whole mesh; ``groups`` sums the shards'
    partial groups, so an order split over two shards counts twice."""
    ladder = [("onehot", True), ("hash", False)]
    if kept == "sort":
        request.getfixturevalue("small_group_cap")
        ladder = [("onehot", True), ("hash", True), ("sort", False)]
    session, dfs = _traced(tables, MESH4)
    got = _first_aggregate(dfs).collect()
    assert "MeshHashAggregateExec" in session.last_plan.tree_string()
    attempts = _attempts(session)
    assert _ladder(attempts, 1) == ladder
    assert _reduces(attempts, 1) == [("onehot", "onehot")] + [
        (mode, "scan") for mode, _ in ladder[1:]]
    distinct = _distinct_orderkeys(tables)
    assert distinct <= attempts[-1].args["groups"] <= distinct + 3
    assert all("groups" not in r.args for r in attempts[:-1])
    lkey = tables["lineitem"].column("l_orderkey").to_numpy()
    first = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    assert got.column("l_orderkey").to_pylist() == lkey[first].tolist()
    assert got.column("qty").to_pylist() == np.add.reduceat(
        tables["lineitem"].column("l_quantity").to_numpy(), first).tolist()
