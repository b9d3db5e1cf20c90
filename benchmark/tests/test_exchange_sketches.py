"""``exchange_sketches_per_query.collect`` (PR 35): the key-sketch programs
a repartitioning exchange's map side dispatched, read from the ``sketches``
arg of its ``exchange.map`` span. The span records are made as
``test_repartition.py`` makes them."""
import pytest

from benchmark import manifest, readers, spans
from benchmark.tests.test_repartition import CELL, _span

NAME = "exchange_sketches_per_query.collect"


def _window(queries):
    """Span records of a window. A query: a list of exchanges, each
    (partitioning, sketches or None for a program from before the arg)."""
    records, ids = [], iter(range(1, 100_000))
    for exchanges in queries:
        root = _span(ids, "query", None, 1000)
        for partitioning, sketches in exchanges:
            exec_span = _span(ids, "TpuShuffleExchangeExec", root.span_id, 300)
            args = dict(partitioning=partitioning, partitions=8, rows=10,
                        bytes=760, pieces=8, kernel_batches=1, sort_batches=0)
            if sketches is not None:
                args["sketches"] = sketches
            records += [_span(ids, "exchange.map", exec_span.span_id, 300,
                              **args), exec_span]
        records.append(root)
    for seq, r in enumerate(records):
        r.seq = seq
    return records


def _read(records, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (records, 0))
    return readers.read(NAME, manifest.metric_file(NAME), {"queries": queries})


def test_one_sketch_a_batch_reads_one(monkeypatch):
    """The cell's query: the hash exchange sketched its one batch once;
    the planner's single exchange is left out, whatever it notes."""
    warm_up = [("hash", 1), ("single", 0)]
    query = [("hash", 1), ("single", 5)]
    records = _window([warm_up, query, query])
    assert _read(records, 2, monkeypatch) == 1.0


def test_the_parents_eight_would_read_eight(monkeypatch):
    records = _window([[("hash", 8), ("single", 0)]])
    assert _read(records, 1, monkeypatch) == 8.0


def test_zero_is_a_reading(monkeypatch):
    """Round robin and range exchanges sketch nothing."""
    records = _window([[("roundrobin", 0), ("single", 0)],
                       [("range", 0), ("single", 0)]])
    assert _read(records, 2, monkeypatch) == 0.0


def test_several_exchanges_and_batches_add_up(monkeypatch):
    records = _window([[("hash", 3), ("roundrobin", 0), ("hash", 1)],
                       [("hash", 2)]])
    assert _read(records, 2, monkeypatch) == 3.0


@pytest.mark.parametrize("queries", [
    [[], []],                                     # a program without spans
    [[("hash", None), ("single", None)]] * 2,     # the parent's: no arg
    [[("single", 0)], [("single", 0)]],           # nothing repartitioned
], ids=["no_span", "no_arg", "single_only"])
def test_nothing_to_read_is_no_reading(monkeypatch, queries):
    assert _read(_window(queries), 2, monkeypatch) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)   # no tracer at all
    assert readers.read(NAME, manifest.metric_file(NAME),
                        {"queries": 2}) is None


def test_the_manifest_lists_it_for_the_cell():
    mf = manifest.load()
    (entry,) = [m for m in mf["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "1/query", "better": "lower",
                     "source": "program_span", "layer": "Exchange; Mesh",
                     "moves": "query_wall_s", "workloads": [CELL]}
    assert NAME in {m["name"] for m in manifest.metrics_of(mf, CELL,
                                                           "per_layer")}
    assert manifest.metric_file(NAME)["spans"] == ["exchange.map"]
