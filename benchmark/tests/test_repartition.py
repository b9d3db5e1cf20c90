"""Q1 over ``lineitem.repartition(8, "l_orderkey")`` and the cell
``tpch_sf1_exchange.repartition``: faults of an exchange planted at SF0.01
(a row lost, a row doubled, a float altered) are not ``correct``, the float32
control fails, the five readers of the ``exchange.map`` / ``exchange.split``
spans on hand-made records, and the manifest and a rehearsal of the cell on
the CPU."""
import types

import numpy as np
import pyarrow as pa
import pytest

from benchmark import correct, manifest, readers, rehearse, run, spans
from benchmark.datagen import gen_tables
from benchmark.queries import q1_repart
from benchmark.reference import q1 as q1_reference
from benchmark.reference import q1_repart as reference

CELL = "tpch_sf1_exchange.repartition"
CONFIG = "tpch_sf1_exchange"
QUERY = "q1_repart"


@pytest.fixture(scope="module")
def tables():
    return gen_tables(["lineitem"], 0.01, 2**31 + 34)


def _engine(tables):
    from spark_rapids_tpu.api import TpuSession
    session = TpuSession(manifest.config_file(manifest.load(), CONFIG)["confs"])
    dfs = {n: session.createDataFrame(t) for n, t in tables.items()}
    return q1_repart.build(dfs).collect()


@pytest.fixture(scope="module")
def sound(tables):
    return _engine(tables)


def _judge(answer, tables):
    ok, numbers = correct.judge({QUERY: [answer]}, tables, 0)
    return ok, {n["name"]: n for n in numbers}


def test_the_reference_is_q1s(tables):
    assert reference.answer is q1_reference.answer
    assert (reference.EXACT, reference.REL_GAP_LIMIT) \
        == (q1_reference.EXACT, q1_reference.REL_GAP_LIMIT)


def test_the_sound_answer_is_correct(sound, tables):
    ok, numbers = _judge(sound, tables)
    assert ok, numbers
    assert numbers[f"{QUERY}.exact_mismatch"]["value"] == 0
    assert numbers[f"{QUERY}.rel_gap"]["value"] < 1e-13


def _without_a_row(li):
    """One row of one partition's worth gone: what an exchange that lost a
    row would hand the query."""
    at = li.num_rows // 3
    return pa.concat_tables([li.slice(0, at), li.slice(at + 1)])


def _with_a_row_doubled(li):
    at = 2 * li.num_rows // 3
    return pa.concat_tables([li.slice(0, at + 1), li.slice(at)])


@pytest.mark.parametrize("alter", [_without_a_row, _with_a_row_doubled],
                         ids=["row_lost", "row_doubled"])
def test_a_row_lost_or_doubled_is_not_correct(tables, alter):
    """The program gets the altered table, the reference the whole one:
    ``count_order`` is off by one and the sums by a row's worth."""
    ok, numbers = _judge(_engine({"lineitem": alter(tables["lineitem"])}),
                         tables)
    assert ok is False
    assert numbers[f"{QUERY}.exact_mismatch"]["value"] == 1
    assert not correct.holds(numbers[f"{QUERY}.rel_gap"])


@pytest.mark.parametrize("column", ["sum_charge", "avg_disc"])
def test_a_float_altered_by_1e_8_is_not_correct(sound, tables, column):
    values = sound.column(column).to_numpy().copy()
    values[2] *= 1 + 1e-8
    altered = sound.set_column(sound.column_names.index(column), column,
                               pa.array(values))
    ok, numbers = _judge(altered, tables)
    assert ok is False
    assert numbers[f"{QUERY}.exact_mismatch"]["value"] == 0
    assert not correct.holds(numbers[f"{QUERY}.rel_gap"])


def test_the_float32_control_fails_rel_gap(tables):
    (miss, gap), = correct.control_gaps(tables, [QUERY]).values()
    assert miss == 0 and gap > 100 * reference.REL_GAP_LIMIT


# ------------------------------------------------ the five metrics' readers
MS = 1_000_000
SECONDS = "exchange_s_per_query.collect"
MEGABYTES = "exchange_mb_per_query.collect"
KERNEL_SHARE = "exchange_kernel_share.collect"
WIDENINGS = "exchange_widenings_per_query.collect"
HBM_SHARE = "exchange_split_hbm_share.collect"
FIVE = (SECONDS, MEGABYTES, KERNEL_SHARE, WIDENINGS, HBM_SHARE)
PEAKS = {"hbm_bytes_per_s": 819e9}


def _span(ids, name, parent, ms, **args):
    return types.SimpleNamespace(name=name, dur_ns=int(ms * MS),
                                 span_id=next(ids), parent_id=parent,
                                 args=args or None)


def _window(queries):
    """Span records of a window. A query: a list of exchanges, each
    (partitioning, map ms, bytes, [(path, widenings, split ms), ...])."""
    records, ids = [], iter(range(1, 100_000))
    for exchanges in queries:
        root = _span(ids, "query", None, 1000)
        for partitioning, ms, nbytes, split in exchanges:
            exec_span = _span(ids, "TpuShuffleExchangeExec", root.span_id, ms)
            mapped = _span(ids, "exchange.map", exec_span.span_id, ms,
                           partitioning=partitioning, partitions=8, rows=10,
                           bytes=nbytes, pieces=8, kernel_batches=1,
                           sort_batches=0)
            records += [_span(ids, "exchange.split", mapped.span_id, s_ms,
                              path=path, widenings=w, rows=10, cap=16)
                        for path, w, s_ms in split]
            records += [mapped, exec_span]
        records.append(root)
    for seq, r in enumerate(records):
        r.seq = seq
    return records


def _read(name, records, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    return readers.read(name, manifest.metric_file(name),
                        {"queries": queries, "peaks": PEAKS})


#: the cell's query: a hash exchange of 400 MB whose kernel ran twice, and
#: the single exchange above the filter, which moves nothing
HASHED = ("hash", 300, 400_000_000, [("kernel", 1, 100)])
SINGLE = ("single", 500, 300_000_000, [("single", 0, 0.01)] * 8)


def test_the_readers_read_the_repartitioning_exchanges_only(monkeypatch):
    warm_up = [("hash", 90_000, 400_000_000, [("kernel", 1, 80_000)]), SINGLE]
    records = _window([warm_up, [HASHED, SINGLE], [HASHED, SINGLE]])
    assert _read(SECONDS, records, 2, monkeypatch) == pytest.approx(0.3)
    assert _read(MEGABYTES, records, 2, monkeypatch) == pytest.approx(400.0)
    assert _read(KERNEL_SHARE, records, 2, monkeypatch) == 100.0
    assert _read(WIDENINGS, records, 2, monkeypatch) == 1.0
    # 2 x 400 MB over 819 GB/s is 0.977 ms of the split's 100
    assert _read(HBM_SHARE, records, 2, monkeypatch) == pytest.approx(
        100 * 2 * 400e6 / 819e9 / 0.1)


def test_a_decline_to_the_sort_shows_in_the_share(monkeypatch):
    declined = ("hash", 700, 400_000_000, [("sort", 0, 600)])
    rr = ("roundrobin", 200, 100_000_000,
          [("kernel", 0, 40), ("encoded", 0, 50)])
    records = _window([[HASHED, SINGLE], [declined, rr]])
    assert _read(KERNEL_SHARE, records, 2, monkeypatch) == 50.0
    assert _read(WIDENINGS, records, 2, monkeypatch) == 0.5
    assert _read(SECONDS, records, 2, monkeypatch) == pytest.approx(0.6)
    assert _read(MEGABYTES, records, 2, monkeypatch) == pytest.approx(450.0)


def test_no_widening_reads_zero(monkeypatch):
    records = _window([[("hash", 300, 4_000, [("kernel", 0, 100)])]])
    assert _read(WIDENINGS, records, 1, monkeypatch) == 0.0


@pytest.mark.parametrize("queries", [
    [[], []],                       # a program from before the spans
    [[SINGLE], [SINGLE]],           # only exchanges that move nothing
], ids=["no_span", "single_only"])
def test_nothing_to_read_is_no_reading(monkeypatch, queries):
    records = _window(queries)
    for name in FIVE:
        assert _read(name, records, 2, monkeypatch) is None, name
    monkeypatch.setattr(spans, "_ring", lambda: None)   # no tracer at all
    for name in FIVE:
        assert readers.read(name, manifest.metric_file(name),
                            {"queries": 2, "peaks": PEAKS}) is None


def test_a_share_over_100_fails_the_run(monkeypatch):
    # 400 MB read and written in 0.5 ms: faster than HBM
    records = _window([[("hash", 1, 400_000_000, [("kernel", 0, 0.5)])]])
    with pytest.raises(RuntimeError, match="> 100 %"):
        _read(HBM_SHARE, records, 1, monkeypatch)


def test_without_peaks_the_share_is_not_read(monkeypatch):
    """A rehearsal's device is in no table of peaks."""
    records = _window([[HASHED]])
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    assert readers.read(HBM_SHARE, manifest.metric_file(HBM_SHARE),
                        {"queries": 1, "peaks": None}) is None


# ----------------------------------------------- the manifest and the cell
def test_the_manifest_is_valid_and_lists_the_cell(capsys):
    mf = manifest.load()
    assert run.main(["--validate"]) == 0
    assert f"valid: {len(mf['workloads'])} cells" in capsys.readouterr().out
    entry = manifest.workload_entry(mf, CELL)
    assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    config = manifest.config_file(mf, CONFIG)
    session = manifest.config_file(mf, "tpch_sf1_session")
    assert config["confs"] == session["confs"]      # no switch of its own
    assert config["schema"] == session["schema"]
    assert (config["scale_factor"], config["reduced"]) == (1.0, [])
    assert manifest.tables_named([QUERY], config["schema"]) == ["lineitem"]
    ours = {m["name"] for m in manifest.metrics_of(mf, CELL, "per_layer")}
    q18 = {m["name"] for m in manifest.metrics_of(
        mf, "tpch_sf1_highcard.q18", "per_layer")}
    assert q18 | set(FIVE) <= ours
    for m in mf["per_layer"]:
        if m["name"] in FIVE or m["name"] in ours - q18:
            assert m["workloads"] == [CELL]
            assert (m["layer"], m["moves"], m["source"]) == (
                "Exchange; Mesh", "query_wall_s", "program_span")
    assert [m["name"] for m in manifest.metrics_of(mf, CELL, "end_to_end")] \
        == ["setup_s", "query_wall_s"]


def test_the_text_names_the_eight_exchanged_columns(tables):
    from benchmark import least_bytes
    named = least_bytes.referenced(manifest.query_sql(QUERY), tables)
    assert named == {"lineitem": [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace, capsys):
    assert rehearse.main(["--workload", CELL, "--trace", str(trace)]) == 0
    out = capsys.readouterr()
    assert "rehearsal: ok" in out.out
    if trace:
        # the CPU backend takes the sort path and knows no HBM peak
        for name in (SECONDS, MEGABYTES, KERNEL_SHARE, WIDENINGS):
            assert name in out.err
        assert HBM_SHARE not in out.err
