"""The four-chip deployment ``tpch_sf1_mesh4`` and its cell: the files are
whole and differ from their single-chip siblings only in the layout; a
rehearsal on four host devices runs the mesh plan and is ``correct``; on one
device the same cell silently keeps the single-device plan, and what tells;
the four readers the cell brings, on hand-made rings and traces."""
import itertools
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest, readers, spans

CELL = "tpch_sf1_mesh4.join"
SIBLING = "tpch_sf1_session.join"
NEW = ("mesh_exchange_s_per_query.collect",
       "mesh_exchange_mb_per_query.collect",
       "mesh_exchange_ici_share.collect",
       "hbm_roofline_share_mesh.collect")
MS = 1_000_000


# ----------------------------------------------------------------- the files
def test_the_configuration_and_the_cell_are_valid():
    mf = manifest.load()
    assert manifest.problems_of(mf) == []
    entry = manifest.workload_entry(mf, CELL)
    assert entry["chips"] == 4 and entry["config"] == "tpch_sf1_mesh4"
    assert [w["name"] for w in mf["workloads"] if w["chips"] == 4] == [CELL]
    (config,) = [c for c in mf["configs"] if c["name"] == "tpch_sf1_mesh4"]
    assert config["reduced"] == []


def test_the_cell_differs_from_its_sibling_only_in_the_layout():
    mf = manifest.load()
    mesh4 = manifest.config_file(mf, "tpch_sf1_mesh4")
    single = manifest.config_file(mf, "tpch_sf1_session")
    assert mesh4["confs"] == {
        **single["confs"],
        "spark.rapids.tpu.sql.mesh.enabled": "true",
        "spark.rapids.tpu.sql.mesh.numDevices": "4"}
    for key in ("benchmark", "scale_factor", "schema", "rows_at_sf1",
                "reduced", "entry", "tables_made"):
        assert mesh4[key] == single[key], key
    assert manifest.tables_from(mesh4) == "memory"
    assert "mesh of 4 devices" in mesh4["guarantees"]["placement"]
    traffic = lambda cell: {k: v for k, v in
                            manifest.workload_file(cell).items()
                            if k not in ("config", "why")}
    assert traffic(CELL) == traffic(SIBLING)


def test_the_cell_reports_what_the_issue_lists():
    mf = manifest.load()
    names = {m["name"] for m in manifest.metrics_of(mf, CELL, "per_layer")}
    assert set(NEW) <= names
    # every metric it prints moves one of the cell's own end-to-end metrics
    assert {m["moves"] for m in manifest.metrics_of(mf, CELL, "per_layer")
            } <= {"setup_s", "query_wall_s"}
    assert not names & {"hbm_roofline_share.collect",
                        "scan_pull_s_per_query.collect",
                        "link_encoded_share.collect"}
    for m in mf["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["layer"] == "Exchange; Mesh"
            assert m["moves"] == "query_wall_s"
    assert {m["name"] for m in manifest.metrics_of(mf, CELL, "end_to_end")
            } == {"setup_s", "query_wall_s"}


# ------------------------------------------------------------- the rehearsal
REHEARSAL = """
import json, sys, time
from benchmark import harness
result, numbers, _ = harness.run_cell(%r, 2**31 + 28, 2.0, True,
                                      time.perf_counter(), scale=0.01,
                                      need_tpu=False)
from spark_rapids_tpu.utils.tracing import TRACER
names = [r.name for r in TRACER.since(0)]
print(json.dumps({"correct": result["correct"], "failed": result["failed"],
                  "metrics": sorted(result["metrics"]),
                  "device": result["device"],
                  "compared": result["compared"],
                  "exchanges": names.count("mesh.exchange"),
                  "scatters": names.count("mesh.scatter")}))
""" % CELL


def _rehearse(devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    done = subprocess.run([sys.executable, "-c", REHEARSAL],
                          capture_output=True, text=True, timeout=600,
                          cwd=manifest.ROOT, env=env)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_rehearsal_on_four_devices_runs_the_mesh_plan_and_is_correct():
    out = _rehearse(4)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    assert out["compared"]["cpu_execs"] == [0, 0]
    assert out["compared"]["q3.exact_mismatch"] == [0, 0]
    mf = manifest.load()
    listed = {m["name"] for m in mf["per_layer"] if CELL in m["workloads"]}
    assert set(out["metrics"]) <= listed
    # the program's spans and counters read on any backend; the device's
    # trace and memory do not, and are left out rather than zero
    assert {"mesh_exchange_s_per_query.collect",
            "mesh_exchange_mb_per_query.collect",
            "mesh_exchange_ici_share.collect", "upload_mb_setup",
            "upload_mb_per_query.collect",
            "compiles_in_window.collect"} <= set(out["metrics"])
    assert "hbm_roofline_share_mesh.collect" not in out["metrics"]
    assert out["exchanges"] > 0 and out["scatters"] > 0


def test_on_one_device_the_cell_keeps_the_single_device_plan_and_what_tells():
    """``mesh_rewrite`` returns the plan unchanged under two devices, and
    ``correct`` cannot tell: the answer is the same. ``device.count`` on the
    result line tells (a measured run fails before that: ``check_device``),
    and so does a traced run without the exchange metrics."""
    out = _rehearse(1)
    assert out["correct"] is True
    assert out["device"]["count"] == 1
    assert out["exchanges"] == 0 and out["scatters"] == 0
    assert not set(out["metrics"]) & set(NEW)


# ---------------------------------------------------------------- the readers
class Ring:
    """Records as the program's ring holds them: everything under a root is
    numbered before the root, which closes last."""

    def __init__(self):
        self.trees, self._ids = [], itertools.count(1)

    def add(self, name, dur_ms, parent=None, args=None):
        rec = types.SimpleNamespace(
            name=name, dur_ns=int(dur_ms * MS), span_id=next(self._ids),
            parent_id=parent.span_id if parent else None, args=args,
            self_ns=0, seq=None)
        if parent is None:
            self.trees.append([])
        self.trees[-1].append(rec)
        return rec

    def close(self):
        order = [r for tree in self.trees for r in tree[1:] + tree[:1]]
        for seq, r in enumerate(order):
            r.seq = seq
        return order


def mesh_query(ring, exchanges):
    """``exchanges``: [(move ms, max_shard_bytes, wire_bytes)]."""
    root = ring.add("query", 3000)
    action = ring.add("action", 2900, root)
    for move_ms, most, wire in exchanges:
        join = ring.add("MeshShuffledHashJoinExec", move_ms + 20, action)
        ex = ring.add("mesh.exchange", move_ms + 10, join,
                      {"op": "mjoin_lpart", "max_shard_bytes": most,
                       "wire_bytes": wire, "bytes": 3 * most})
        ring.add("mesh.exchange.count", 5, ex)
        ring.add("mesh.exchange.move", move_ms, ex)
    return root


def _read(name, ring, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (ring.close(), 0))
    return readers.read(name, manifest.metric_file(name),
                        {"queries": queries})


def test_the_exchange_readers_sum_over_the_window(monkeypatch):
    ring = Ring()
    mesh_query(ring, [(999, 1, 1)])                       # warm-up's
    mesh_query(ring, [(100, 200e6, 500e6), (300, 600e6, 1500e6)])
    mesh_query(ring, [(200, 400e6, 1000e6)])
    assert _read(NEW[0], ring, 2, monkeypatch) == pytest.approx(
        (0.110 + 0.310 + 0.210) / 2)
    assert _read(NEW[1], ring, 2, monkeypatch) == pytest.approx(3000 / 2)
    # least time over ICI: 1200e6 B / 200e9 B/s = 6 ms of the 600 ms moving
    assert _read(NEW[2], ring, 2, monkeypatch) == pytest.approx(1.0)


def test_an_exchange_faster_than_the_interconnect_fails_the_run(monkeypatch):
    ring = Ring()
    mesh_query(ring, [(1, 400e6, 1000e6)])      # 2 ms of ICI in a 1 ms span
    with pytest.raises(RuntimeError, match="> 100 %"):
        _read(NEW[2], ring, 1, monkeypatch)
    assert _read(NEW[1], ring, 1, monkeypatch) == pytest.approx(1000)


@pytest.mark.parametrize("name", NEW[:3])
def test_a_window_without_exchanges_reads_nothing(name, monkeypatch):
    """The parent's program has no such span, and a single-device plan
    records none: no reading, no error."""
    ring = Ring()
    root = ring.add("query", 100)
    ring.add("program.filter", 10, ring.add("action", 90, root))
    assert _read(name, ring, 1, monkeypatch) is None
    monkeypatch.setattr(spans, "_ring", lambda: None)
    assert readers.read(name, manifest.metric_file(name),
                        {"queries": 1}) is None


def _trace(chips, busy_s, least_bytes):
    return {"trace": {"queries": 2, "busy_s": busy_s, "window_s": 6.0,
                      "chips": chips, "least_bytes": least_bytes},
            "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("chips", [1, 4])
def test_the_mesh_roofline_divides_by_the_chips_of_the_trace(chips):
    read = lambda ctx: readers.read(NEW[3], manifest.metric_file(NEW[3]), ctx)
    share = read(_trace(chips, 2.0, 819e9))
    assert share == pytest.approx(50.0 / chips)
    if chips == 1:   # then it is the single-chip reader's number
        assert share == pytest.approx(
            readers.trace_hbm_roofline(_trace(1, 2.0, 819e9)))
    # what one chip's peak would call 200 % is 50 % of four
    with pytest.raises(RuntimeError, match="> 100 %"):
        read(_trace(chips, 1.0, chips * 2 * 819e9))
    assert read({"trace": None, "peaks": {"hbm_bytes_per_s": 819e9}}) is None
    old = _trace(chips, 2.0, 819e9)
    del old["trace"]["chips"]
    assert read(old) is None
