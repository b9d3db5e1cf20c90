"""Q18 and the cell ``tpch_sf1_highcard.q18``: each fault of PERF.md section
2 planted in an answer is not ``correct``, the float32 control fails (and what
the TPU's float64 does to a double does not), the two
readers of the ``agg.attempt`` span on hand-made records, and the manifest
and a rehearsal of the cell on the CPU. At SF0.01 the validation parameter
leaves a row or two, so the faults are planted at ``quantity=150``, which
leaves more than the limit's hundred."""
import functools
import types

import numpy as np
import pyarrow as pa
import pytest

from benchmark import correct, manifest, readers, rehearse, run, spans
from benchmark.datagen import gen_tables
from benchmark.queries import q18
from benchmark.reference import q18 as reference

CELL = "tpch_sf1_highcard.q18"
QUANTITY = 150


@pytest.fixture(scope="module")
def tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, 2**31 + 33)


def _engine(tables, quantity=QUANTITY):
    from spark_rapids_tpu.api import TpuSession
    mf = manifest.load()
    session = TpuSession(manifest.config_file(mf, "tpch_sf1_highcard")["confs"])
    dfs = {n: session.createDataFrame(t) for n, t in tables.items()}
    return q18.build(dfs, quantity).collect()


@pytest.fixture(scope="module")
def sound(tables):
    return _engine(tables)


@pytest.fixture
def judge(tables, monkeypatch):
    """``correct.judge`` of one Q18 answer, reference at ``QUANTITY``."""
    monkeypatch.setattr(reference, "answer", functools.partial(
        reference.answer, quantity=QUANTITY))

    def judged(answer):
        ok, numbers = correct.judge({"q18": [answer]}, tables, 0)
        return ok, {n["name"]: n for n in numbers}
    return judged


def _set(table, column, values):
    return table.set_column(table.column_names.index(column), column,
                            pa.array(values, type=table.column(column).type))


def _numpy(table, column):
    return table.column(column).to_numpy().copy()


def _scaled(column):
    def alter(table):
        return _set(table, column, _numpy(table, column) * (1 + 1e-8))
    return alter


def _last_bits(table):
    """What the TPU's float64, a pair of float32, does to a double."""
    price = _numpy(table, "o_totalprice")
    hi = price.astype(np.float32)
    lo = (price - hi).astype(np.float32)
    return _set(table, "o_totalprice", hi.astype(np.float64) + lo)


def _key_off_by_one(table):
    keys = _numpy(table, "o_orderkey")
    keys[3] += 1
    return _set(table, "o_orderkey", keys)


def _two_rows_swapped(table):
    order = np.arange(table.num_rows)
    order[[40, 41]] = 41, 40
    return table.take(pa.array(order))


def test_the_sound_answer_is_correct(sound, judge):
    assert sound.num_rows == reference.LIMIT
    ok, numbers = judge(sound)
    assert ok, numbers
    assert numbers["q18.exact_mismatch"]["value"] == 0
    assert numbers["q18.rel_gap"]["value"] == 0.0   # sums of whole numbers


@pytest.mark.parametrize("alter,number", [
    (_scaled("sum_qty"), "q18.rel_gap"),
    (_scaled("o_totalprice"), "q18.rel_gap"),
    (_key_off_by_one, "q18.exact_mismatch"),
    (lambda t: t.slice(1), "q18.exact_mismatch"),
    (_two_rows_swapped, "q18.exact_mismatch"),
], ids=["sum_by_1e-8", "totalprice_by_1e-8", "key_off_by_one", "row_dropped",
        "two_rows_swapped"])
def test_an_altered_answer_is_not_correct(sound, judge, alter, number):
    ok, numbers = judge(alter(sound))
    assert ok is False
    assert not correct.holds(numbers[number]), numbers[number]


def test_a_double_held_as_two_float32_is_still_correct(sound, judge):
    """o_totalprice is compared by its relative gap because the TPU cannot
    return it bit for bit (reference/q18.py): the last bits may differ."""
    altered = _last_bits(sound)
    assert altered.column("o_totalprice") != sound.column("o_totalprice")
    ok, numbers = judge(altered)
    assert ok, numbers
    assert 0 < numbers["q18.rel_gap"]["value"] < 2.0 ** -46


def test_half_of_lineitem_left_out_is_not_correct(tables, judge):
    """The program is given half of lineitem; the reference all of it."""
    li = tables["lineitem"]
    ok, numbers = judge(_engine({**tables,
                                 "lineitem": li.slice(0, li.num_rows // 2)}))
    assert ok is False
    assert not correct.holds(numbers["q18.exact_mismatch"])


@pytest.mark.parametrize("quantity", [300, QUANTITY])
def test_the_control_fails_by_o_totalprice(tables, monkeypatch, quantity):
    """Every sum_qty is a small whole number, exact in float32 as well: the
    control fails by the double that is passed through, and only by it."""
    monkeypatch.setattr(reference, "answer", functools.partial(
        reference.answer, quantity=quantity))
    (miss, gap), = correct.control_gaps(tables, ["q18"]).values()
    assert miss == 0 and gap > 100 * reference.REL_GAP_LIMIT
    whole = reference.answer(tables)
    control = reference.answer(tables, "float32")
    assert control.column("o_totalprice") != whole.column("o_totalprice")
    assert control.drop(["o_totalprice"]) == whole.drop(["o_totalprice"])


def test_rows_tied_on_both_sort_keys_are_counted(tables):
    assert reference.tied_rows(tables, QUANTITY) == 0
    orders = tables["orders"]
    same = _set(orders, "o_totalprice", np.full(orders.num_rows, 1000.0))
    same = _set(same, "o_orderdate", np.full(orders.num_rows, 9000, np.int32))
    tied = {**tables, "orders": same}
    assert reference.tied_rows(tied, QUANTITY) == reference.LIMIT
    # and the reference then orders them by o_orderkey
    keys = _numpy(reference.answer(tied, quantity=QUANTITY), "o_orderkey")
    assert np.all(keys[1:] > keys[:-1])


# ------------------------------------------------- the two metrics' readers
MS = 1_000_000


def _window(queries_attempts):
    """Span records of a window: per query a list of (mode, flagged, ms)."""
    records, ids = [], iter(range(1, 10_000))
    for attempts in queries_attempts:
        root_id = next(ids)
        kids = []
        exec_id = next(ids)
        for mode, flagged, ms in attempts:
            kids.append(types.SimpleNamespace(
                name="agg.attempt", dur_ns=ms * MS, span_id=next(ids),
                parent_id=exec_id,
                args={"mode": mode, "flagged": flagged, "capacity": 8,
                      "keys": 1, **({} if flagged else {"groups": 3})}))
        records += kids
        records.append(types.SimpleNamespace(
            name="TpuHashAggregateExec", dur_ns=900 * MS, span_id=exec_id,
            parent_id=root_id, args={"rows": 3}))
        records.append(types.SimpleNamespace(
            name="query", dur_ns=1000 * MS, span_id=root_id, parent_id=None,
            args=None))
    for seq, r in enumerate(records):
        r.seq = seq
    return records


def _read(name, records, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    return readers.read(name, manifest.metric_file(name),
                        {"queries": queries})


ATTEMPTS = "agg_attempts_per_query.collect"
DISCARDED = "agg_discarded_s_per_query.collect"


def test_the_readers_count_attempts_and_sum_the_discarded(monkeypatch):
    q18ish = [("onehot", True, 200), ("hash", True, 100), ("sort", False, 300),
              ("onehot", True, 20), ("hash", False, 10)]
    warm_up = [("onehot", True, 90_000)] * 3
    records = _window([warm_up, q18ish, q18ish])
    assert _read(ATTEMPTS, records, 2, monkeypatch) == 5
    assert _read(DISCARDED, records, 2, monkeypatch) == pytest.approx(0.32)


def test_kept_attempts_alone_read_zero_discarded(monkeypatch):
    q1, q6 = [("onehot", False, 30)], [("hash", False, 7)]
    records = _window([q1, q6, q1, q6])
    assert _read(ATTEMPTS, records, 4, monkeypatch) == 1
    assert _read(DISCARDED, records, 4, monkeypatch) == 0.0


def test_no_span_is_no_reading(monkeypatch):
    records = _window([[], []])             # a program from before the span
    assert _read(ATTEMPTS, records, 2, monkeypatch) is None
    assert _read(DISCARDED, records, 2, monkeypatch) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)   # no tracer at all
    for name in (ATTEMPTS, DISCARDED):
        assert readers.read(name, manifest.metric_file(name),
                            {"queries": 2}) is None


# ----------------------------------------------- the manifest and the cell
def test_the_manifest_is_valid_and_lists_the_cell(capsys):
    mf = manifest.load()
    assert run.main(["--validate"]) == 0
    assert f"valid: {len(mf['workloads'])} cells" in capsys.readouterr().out
    entry = manifest.workload_entry(mf, CELL)
    assert (entry["config"], entry["chips"]) == ("tpch_sf1_highcard", 1)
    config = manifest.config_file(mf, entry["config"])
    session = manifest.config_file(mf, "tpch_sf1_session")
    assert config["confs"] == session["confs"]
    assert config["schema"] == session["schema"]
    assert (config["scale_factor"], config["reduced"]) == (1.0, [])
    assert manifest.tables_named(["q18"], config["schema"]) == [
        "customer", "orders", "lineitem"]
    ours = {m["name"] for m in manifest.metrics_of(mf, CELL, "per_layer")}
    joins = {m["name"] for m in manifest.metrics_of(
        mf, "tpch_sf1_session.join", "per_layer")}
    # every metric of both resident one-chip cells, nothing join lacks
    shared = {m["name"] for m in mf["per_layer"]
              if {"tpch_sf1_session.scanagg", "tpch_sf1_session.join"}
              <= set(m["workloads"])}
    assert shared <= ours <= joins and {ATTEMPTS, DISCARDED} <= ours
    assert [m["name"] for m in manifest.metrics_of(mf, CELL, "end_to_end")] \
        == ["setup_s", "query_wall_s"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace, capsys):
    assert rehearse.main(["--workload", CELL, "--trace", str(trace)]) == 0
    out = capsys.readouterr()
    assert "rehearsal: ok" in out.out
    if trace:
        assert ATTEMPTS in out.err and DISCARDED in out.err
