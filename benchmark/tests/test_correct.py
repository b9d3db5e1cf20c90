"""``correct``: the control fails it, and so does each fault a cell can have,
planted under the harness while the rest of a run is driven as it stands
(at SF0.01, on whatever backend jax finds; the look for a chip is skipped)."""
import time

import pyarrow as pa
import pyarrow.compute as pc
import pytest

from benchmark import correct, harness, manifest
from benchmark.datagen import gen_tables

SCANAGG = "tpch_sf1_session.scanagg"
JOIN = "tpch_sf1_session.join"
SERVED = "tpch_sf1_server.short_openloop"
PARQUET = "tpch_sf1_parquet.scanagg"


def _run(cell, seed=11):
    result, numbers, _ = harness.run_cell(cell, seed, 2.0, False,
                                          time.perf_counter(), scale=0.01,
                                          need_tpu=False)
    return result, {n["name"]: n for n in numbers}


@pytest.fixture(scope="module")
def tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, 2**31 + 11)


def test_the_control_fails_every_query(tables):
    """The reference in float32, the precision below the configuration's
    float64, put in the program's place: over the limit for every query."""
    for qid, (miss, gap) in correct.control_gaps(
            tables, ["q1", "q6", "q3"]).items():
        limit = correct.load_reference(qid).REL_GAP_LIMIT
        assert miss > 0 or gap > 3 * limit, (qid, miss, gap)


def test_the_reference_agrees_with_itself_and_a_wrong_shape_is_counted(tables):
    ref = correct.load_reference("q1")
    answer = ref.answer(tables)
    assert correct.compare(answer, answer, ref.EXACT) == (0, 0.0)
    miss, gap = correct.compare(answer.slice(1), answer, ref.EXACT)
    assert miss > 0 and gap == correct.GAP_WHEN_INCOMPARABLE


@pytest.mark.parametrize("cell", [SCANAGG, JOIN, SERVED, PARQUET])
def test_a_sound_run_is_correct(cell):
    result, numbers = _run(cell)
    assert result["correct"] is True, numbers
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) >= {"setup_s"}


def _scaled(column, factor):
    def alter(table):
        i = table.column_names.index(column)
        return table.set_column(i, column, pc.multiply(
            table.column(column), pa.scalar(factor, pa.float64())))
    return alter


def _one_more(column):
    def alter(table):
        i = table.column_names.index(column)
        return table.set_column(i, column, pc.add(table.column(column), 1))
    return alter


@pytest.mark.parametrize("cell,qid,alter,number", [
    # an answer altered where it is produced: a float by 1e-8 of itself ...
    (SCANAGG, "q6", _scaled("revenue", 1 + 1e-8), "q6.rel_gap"),
    (SERVED, "q1", _scaled("sum_charge", 1 + 1e-8), "q1.rel_gap"),
    (PARQUET, "q6", _scaled("revenue", 1 + 1e-8), "q6.rel_gap"),
    # ... a count by one, a key by one
    (SCANAGG, "q1", _one_more("count_order"), "q1.exact_mismatch"),
    (PARQUET, "q1", _one_more("count_order"), "q1.exact_mismatch"),
    (JOIN, "q3", _one_more("l_orderkey"), "q3.exact_mismatch"),
    # ... a row dropped
    (JOIN, "q3", lambda t: t.slice(1), "q3.exact_mismatch"),
])
def test_an_altered_answer_is_not_correct(monkeypatch, cell, qid, alter,
                                          number):
    sound = harness.run_query

    def altered(st, q, record=None):
        table = sound(st, q, record)
        return alter(table) if q == qid else table

    monkeypatch.setattr(harness, "run_query", altered)
    result, numbers = _run(cell)
    assert result["correct"] is False
    assert not correct.holds(numbers[number]), numbers[number]


def test_half_of_the_rows_left_out_is_not_correct(monkeypatch):
    """The program is given half of lineitem; the reference all of it."""
    from spark_rapids_tpu.api import TpuSession
    whole = TpuSession.createDataFrame

    def half(self, table, *args, **kwargs):
        return whole(self, table.slice(0, table.num_rows // 2),
                     *args, **kwargs)

    monkeypatch.setattr(TpuSession, "createDataFrame", half)
    result, numbers = _run(SCANAGG)
    assert result["correct"] is False
    assert not correct.holds(numbers["q1.exact_mismatch"])   # count_order
    assert not correct.holds(numbers["q6.rel_gap"])


def test_half_of_the_rows_left_out_of_the_file_is_not_correct(monkeypatch):
    """The file the program scans holds half of lineitem; the reference is
    computed on the table from the seed, all of it."""
    import pyarrow.parquet as pq
    whole = pq.write_table

    def half(table, where, *args, **kwargs):
        return whole(table.slice(0, table.num_rows // 2), where,
                     *args, **kwargs)

    monkeypatch.setattr(pq, "write_table", half)
    result, numbers = _run(PARQUET)
    assert result["correct"] is False
    assert not correct.holds(numbers["q1.exact_mismatch"])   # count_order
    assert not correct.holds(numbers["q6.rel_gap"])


@pytest.mark.parametrize("cell", [SCANAGG, PARQUET])
def test_an_operator_on_the_cpu_engine_is_not_correct(monkeypatch, cell):
    """The answer is right but the device path did not make it."""
    real = manifest.config_file

    def on_cpu(mf, name):
        config = real(mf, name)
        return {**config, "confs": {**config["confs"],
                                    "spark.rapids.tpu.sql.enabled": "false"}}

    monkeypatch.setattr(manifest, "config_file", on_cpu)
    result, numbers = _run(cell)
    assert result["correct"] is False
    assert numbers["cpu_execs"]["value"] > 0
    assert correct.holds(numbers["q1.rel_gap"])
