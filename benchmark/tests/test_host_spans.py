"""The eight per-layer metrics of PR 36 (``benchmark/host_spans.py``): seven
read the leaf spans of the parquet scan's host pipeline, of the action's own
work, of ``batch.concat`` and of ``exchange.fetch`` from the window's trees;
``idle_named_share.collect`` reads the traced slice's listed idle gaps. The
values by hand on synthetic rings and gap lists; no reading on a ring from
before the spans; 0 where the program has them and the window holds none."""
import itertools
import types

import pytest

from benchmark import host_spans, manifest, readers, spans

MS = 1_000_000
COLLECT = ["tpch_sf1_session.scanagg", "tpch_sf1_session.join",
           "tpch_sf1_parquet.scanagg", "tpch_sf1_mesh4.join",
           "tpch_sf1_highcard.q18", "tpch_sf1_exchange.repartition"]
PARQUET = ["tpch_sf1_parquet.scanagg"]
#: name -> (unit, source, layer, cells, the spans its file names)
METRICS = {
    "scan_decode_s_per_query.collect": (
        "s/query", "program_span", "Host link", PARQUET,
        ["scan.chunk_decode"]),
    "scan_arrow_read_s_per_query.collect": (
        "s/query", "program_span", "Host link", PARQUET,
        ["scan.arrow_read"]),
    "scan_decoded_mb_per_query.collect": (
        "MB/query", "program_span", "Host link", PARQUET,
        ["scan.chunk_decode", "scan.arrow_read"]),
    "scan_wait_s_per_query.collect": (
        "s/query", "program_span", "Host link", PARQUET, ["scan.wait"]),
    "action_overhead_ms_per_query.collect": (
        "ms/query", "program_span", "Entry points", COLLECT,
        ["query.", "action."]),
    "batch_concat_ms_per_query.collect": (
        "ms/query", "program_span", "Operators (XLA)",
        [c for c in COLLECT if "mesh4" not in c], ["batch.concat"]),
    "exchange_fetch_ms_per_query.collect": (
        "ms/query", "program_span", "Exchange; Mesh",
        ["tpch_sf1_exchange.repartition"], ["exchange.fetch"]),
    "idle_named_share.collect": (
        "%", "device_trace", "Device", COLLECT, None),
}
SPAN_METRICS = [n for n, m in METRICS.items() if m[1] == "program_span"]


def _span(ids, name, parent, ms, **args):
    return types.SimpleNamespace(name=name, dur_ns=int(ms * MS),
                                 span_id=next(ids), parent_id=parent,
                                 args=args or None)


def _window(queries, blocks=True):
    """Span records of a window. A query: a list of (name, ms, args) under
    its root; ``blocks``: the program opens ``query.prepare`` in every
    query, as every program with these spans does."""
    records, ids = [], itertools.count(1)
    for leaves in queries:
        root = _span(ids, "query", None, 1000)
        if blocks:
            records.append(_span(ids, "query.prepare", root.span_id, 0.25))
        action = _span(ids, "action", root.span_id, 900)
        for name, ms, args in leaves:
            parent = root if name.startswith("query.") else action
            records.append(_span(ids, name, parent.span_id, ms, **args))
        records += [action, root]
    for seq, r in enumerate(records):
        r.seq = seq
    return records


def _read(name, records, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    return readers.read(name, manifest.metric_file(name), {"queries": queries})


#: two queries of a parquet scan over an exchange: what each leaves
SCAN_QUERY = [
    ("scan.chunk_decode", 300, dict(form="dict", decoded_bytes=8_000_000)),
    ("scan.chunk_decode", 100, dict(form="mixed", decoded_bytes=4_000_000)),
    ("scan.chunk_decode", 200, dict(form="declined",
                                    decoded_bytes=8_000_000)),
    ("scan.arrow_read", 400, dict(columns=["a"], rows=10, bytes=10_000_000)),
    ("scan.wait", 700, {}),
    ("scan.wait", 250, {}),
    ("action.download_dispatch", 5, {}),
    ("query.metrics", 0.5, dict(execs=5)),
    ("query.schema", 0.25, {}),
    ("batch.concat", 30, dict(batches=8, rows=10, columns=7, dispatches=176)),
    ("exchange.fetch", 1.5, dict(partition=0, map_id=0, rows=5, bytes=40)),
    ("exchange.fetch", 0.5, dict(partition=1, map_id=0, rows=5, bytes=40)),
]
BY_HAND = {
    "scan_decode_s_per_query.collect": 0.6,             # declined included
    "scan_arrow_read_s_per_query.collect": 0.4,
    "scan_decoded_mb_per_query.collect": 22.0,          # declined left out
    "scan_wait_s_per_query.collect": 0.95,
    "action_overhead_ms_per_query.collect": 6.0,        # with query.prepare
    "batch_concat_ms_per_query.collect": 30.0,
    "exchange_fetch_ms_per_query.collect": 2.0,
}


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_the_value_by_hand(name, monkeypatch):
    warm_up = [("scan.wait", 9000, {}), ("batch.concat", 9000, {})]
    records = _window([warm_up, SCAN_QUERY, SCAN_QUERY])
    assert _read(name, records, 2, monkeypatch) == pytest.approx(BY_HAND[name])
    # half the window without the spans: the sum over both queries
    records = _window([SCAN_QUERY, []])
    expected = (BY_HAND[name] + (0.25 if "overhead" in name else 0)) / 2
    assert _read(name, records, 2, monkeypatch) == pytest.approx(expected)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_ring_from_before_the_spans_is_no_reading(name, monkeypatch):
    """The parent's ring: roots and actions, none of the new names."""
    records = _window([[], []], blocks=False)
    assert _read(name, records, 2, monkeypatch) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)   # no tracer at all
    assert readers.read(name, manifest.metric_file(name),
                        {"queries": 2}) is None
    # fewer roots than queries: no window
    assert _read(name, _window([SCAN_QUERY]), 2, monkeypatch) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_window_without_the_span_reads_zero(name, monkeypatch):
    """The program has the spans (every query opened ``query.prepare``) and
    the window none of this metric's: ``scanagg`` concatenates nothing."""
    value = _read(name, _window([[], []]), 2, monkeypatch)
    assert value == (0.25 if "overhead" in name else 0.0)


def test_the_action_and_the_root_are_not_their_own_blocks(monkeypatch):
    records = _window([[("action.download_dispatch", 3, {})]])
    assert {r.name for r in records} >= {"query", "action"}
    assert _read("action_overhead_ms_per_query.collect", records, 1,
                 monkeypatch) == pytest.approx(3.25)


#: the ten longest gaps of a traced slice, as ``reduce.py`` lists them
#: (ledger, PR 35, with its names back in the trace's form)
LEDGER_GAPS = {
    "tpch_sf1_parquet.scanagg": ([
        ["TpuParquetScanExec#3", 1.6292236510000002],
        ["TpuParquetScanExec#4", 1.351056017],
        ["PipelinedExec(depth=2)#2", 0.32800202700000003],
        ["PipelinedExec(depth=2)#3", 0.305694834],
        ["FusedAggregateStageExec#2", 0.0615560269999995],
        ["upload.stage#4", 0.04375859999999975],
        ["FusedAggregateStageExec#1", 0.027729701999999856],
        ["upload.stage#3", 0.025214804999999958],
        ["tpu-sql-action", 0.013216710000000001],
        ["agg.attempt#1", 0.001977235]], 1.9),
    "tpch_sf1_exchange.repartition": ([
        ["TpuHashAggregateExec#2", 1.4203068510000763],
        ["exchange.map#3", 0.2712011410000001],
        ["tpu-sql-action", 0.20029039600000037],
        ["between queries", 0.141096277],
        ["exchange.split#5", 0.09822008799999411],
        ["FusedStageExec#4", 0.07833194299999229],
        ["program.agg#2", 0.03100302099999996],
        ["agg.attempt#2", 0.02419795699999918],
        ["plan#0", 0.009889807],
        ["TpuShuffleExchangeExec#5", 0.008423913]], 20),
    "tpch_sf1_session.scanagg": ([
        ["tpu-sql-action", 0.7546254010000253],
        ["plan#0", 0.3931825190000001],
        ["between queries", 0.340028712],
        ["agg.attempt#2", 0.14785263299999205],
        ["agg.attempt#1", 0.08240066299998917],
        ["FusedAggregateStageExec#1", 0.048533833999999894],
        ["FusedAggregateStageExec#2", 0.027870709000000004],
        ["TpuSortExec#1", 0.01400208],
        ["result.concat#0", 0.013401516],
        ["program.sort#1", 2.50269999999996e-05]], 43),
}


def _share(gaps):
    name = "idle_named_share.collect"
    return readers.read(name, manifest.metric_file(name),
                        {"trace": {"idle_gaps": gaps}})


@pytest.mark.parametrize("cell", sorted(LEDGER_GAPS))
def test_idle_named_share_of_the_ledgers_lists(cell):
    gaps, percent = LEDGER_GAPS[cell]
    digits = 1 if percent < 10 else 0
    assert round(_share(gaps), digits) == percent


def test_idle_named_share_by_hand():
    assert _share([["scan.chunk_decode#3", 3.0], ["TpuSortExec#1", 1.0],
                   ["between queries", 50.0]]) == 75.0
    assert _share([["tpu-sql-action", 2.0], ["TpuSortExec#1", 1.0]]) == 0.0
    assert _share([["plan#0", 2.0]]) == 100.0
    # nothing but the time between queries, no gap, no trace: no reading
    assert _share([["between queries", 5.0]]) is None
    assert _share([]) is None
    name = "idle_named_share.collect"
    assert readers.read(name, manifest.metric_file(name),
                        {"trace": None}) is None


def test_the_manifest_lists_the_eight_last():
    mf = manifest.load()
    manifest.validate(mf)
    by_name = {m["name"]: m for m in mf["per_layer"]}
    assert set(METRICS) <= set(by_name)
    for name, (unit, source, layer, cells, names) in METRICS.items():
        entry = dict(by_name[name])
        # the cells each was written for, and any a later cell added
        assert set(cells) <= set(entry.pop("workloads"))
        assert entry == {"name": name, "unit": unit,
                         "better": "higher" if source == "device_trace"
                         else "lower", "source": source, "layer": layer,
                         "moves": "query_wall_s"}
        assert manifest.metric_file(name).get("spans") == names
    # every cell that lists a metric prints it in a traced run
    for cell in COLLECT:
        listed = {m["name"] for m in manifest.metrics_of(mf, cell,
                                                         "per_layer")}
        assert {n for n, m in METRICS.items() if cell in m[3]} <= listed


def test_the_mark_is_a_span_the_program_opens():
    """``query.prepare`` is what tells a program with these spans from one
    before them: it is a span site of ``_run_partitions``."""
    import inspect

    from spark_rapids_tpu.api.dataframe import DataFrame
    assert f'"{host_spans.MARK}"' in inspect.getsource(
        DataFrame._run_partitions)
