"""The run command's contract: no chip, no result; the result line's shape."""
import json
import subprocess
import sys
import time

import pytest

from benchmark import harness, manifest


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    with pytest.raises(SystemExit, match="needs a TPU"):
        harness.check_device(1, need_tpu=True)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tpch_sf1_session.scanagg", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=manifest.ROOT, env={**__import__("os").environ,
                                "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_an_unknown_device_kind_is_an_error_not_a_default(monkeypatch):
    monkeypatch.setattr(manifest, "peaks", lambda: {})
    device, peaks = harness.check_device(1, need_tpu=False)
    assert peaks is None and device["platform"] == "cpu"


def test_a_traced_result_holds_only_metrics_that_list_the_cell():
    result, numbers, _ = harness.run_cell(
        "tpch_sf1_server.short_openloop", 2**31 + 7, 2.0, True,
        time.perf_counter(), scale=0.01, need_tpu=False)
    mf = manifest.load()
    listed = {m["name"] for m in mf["per_layer"]
              if "tpch_sf1_server.short_openloop" in m["workloads"]}
    assert set(result["metrics"]) <= listed
    # the counters and the generator's own clock read on any backend; the
    # device's trace and memory do not, and are left out rather than zero
    assert {"compiles_in_window.served", "gen_late_p90_ms.served",
            "queue_wait_p50_ms.served", "first_call_s",
            "upload_mb_setup"} <= set(result["metrics"])
    assert "device_idle_share.served" not in result["metrics"]
    assert result["metrics"]["compiles_in_window.served"]["value"] == 0
    assert list(result)[:3] == ["correct", "attempted", "failed"]
    assert list(result)[-1] == "compared"
    json.loads(json.dumps(result))      # plain JSON
    assert all(isinstance(v, list) and len(v) == 2
               for v in result["compared"].values())
