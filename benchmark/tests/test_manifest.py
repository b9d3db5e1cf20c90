"""--validate: the real manifest passes; PR 22's mistake and its kin fail."""
import copy

import pytest

from benchmark import manifest, run


@pytest.fixture
def mf():
    return copy.deepcopy(manifest.load())


def _metric(mf, name):
    return next(m for m in mf["per_layer"] + mf["end_to_end"]
                if m["name"] == name)


def _drop_cells_of(mf, config):
    mf["workloads"] = [w for w in mf["workloads"] if w["config"] != config]


def test_the_committed_manifest_is_valid(mf, capsys):
    assert manifest.problems_of(mf) == []
    assert run.main(["--validate"]) == 0
    assert (f"valid: {len(mf['workloads'])} cells, "
            f"{len(mf['end_to_end'])} end-to-end and "
            f"{len(mf['per_layer'])} per-layer metrics"
            in capsys.readouterr().out)


def test_pr22_mistake_is_refused(mf):
    # a served-only metric left attached to a cell that does not report
    # the end-to-end metric it moves
    _metric(mf, "queue_wait_p50_ms.served")["workloads"].append(
        "tpch_sf1_session.scanagg")
    bad = manifest.problems_of(mf)
    assert any("queue_wait_p50_ms.served is reported on workload "
               "tpch_sf1_session.scanagg, where latency_p50_s" in b
               for b in bad)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(mf)


@pytest.mark.parametrize("breach,expected", [
    (lambda mf: _metric(mf, "first_call_s").pop("workloads"),
     "has no workloads list"),
    (lambda mf: mf.update(run_seconds=52), "run_seconds"),
    (lambda mf: mf.update(run_seconds=5), "run_seconds"),
    (lambda mf: _metric(mf, "query_wall_s").update(unit="s per query"),
     "unit"),
    (lambda mf: mf["workloads"][0].update(name="bad name"), "name"),
    (lambda mf: mf["configs"][0].update(source="x" * 201), "source"),
    (lambda mf: _drop_cells_of(mf, "tpch_sf1_server"), "has no cell"),
    (lambda mf: _drop_cells_of(mf, "tpch_sf1_parquet"), "has no cell"),
    (lambda mf: _metric(mf, "first_call_s").update(why="because"), "keys"),
    (lambda mf: _metric(mf, "latency_p90_s").update(bound=0.5), "bound"),
    (lambda mf: _metric(mf, "upload_mb_setup").update(moves="nothing"),
     "no end-to-end metric"),
])
def test_breaches_are_found(mf, breach, expected):
    breach(mf)
    assert any(expected in b for b in manifest.problems_of(mf))


def test_a_traced_cell_prints_exactly_the_metrics_that_list_it(mf):
    for cell in [w["name"] for w in mf["workloads"]]:
        names = {m["name"] for m in manifest.metrics_of(mf, cell, "per_layer")}
        assert names == {m["name"] for m in mf["per_layer"]
                         if cell in m["workloads"]}
        suffix = ".served" if "server" in cell else ".collect"
        other = ".collect" if suffix == ".served" else ".served"
        assert not any(n.endswith(other) for n in names)
