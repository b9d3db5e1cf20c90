"""Tooling tests: shim loader, api-validation parity, config doc generation
(ShimLoader / ApiValidation / RapidsConf.help analog coverage)."""
import pathlib

from spark_rapids_tpu import api_validation, config, shims


def test_shim_loader_picks_provider():
    s = shims.get()
    assert isinstance(s, shims.JaxShims)
    import jax
    assert type(s).version_match(jax.__version__)


def test_shim_provider_selection_logic():
    assert shims.PROVIDERS == [shims.Jax05PlusShims]    # exactly one
    assert shims.Jax05PlusShims.version_match("0.9.0")
    assert not shims.Jax05PlusShims.version_match("0.4.30")


def test_shim_loader_rejects_unserved_jax(monkeypatch):
    import jax
    monkeypatch.setattr(shims, "_ACTIVE", None)
    monkeypatch.setattr(jax, "__version__", "0.4.30")
    import pytest
    with pytest.raises(RuntimeError, match="no shim provider"):
        shims.get()


def test_shim_rng_and_mesh_work():
    import jax
    s = shims.get()
    key = s.prng_key(7)
    v = jax.random.uniform(key, (3,))
    assert v.shape == (3,)
    assert s.tree_map(lambda x: x + 1, {"a": 1})["a"] == 2
    m = s.make_mesh(jax.devices()[:1], ("data",))
    assert m.axis_names == ("data",)


def test_exec_constructor_parity():
    """ApiValidation.scala analog: every Cpu/Tpu exec pair must agree on
    constructor parameters (conversion rules copy fields across)."""
    problems = api_validation.validate()
    assert not problems, "\n".join(problems)
    assert len(api_validation.exec_pairs()) >= 15


def test_config_docs_current():
    """docs/configs.md must match the registry (the reference regenerates
    docs/configs.md from RapidsConf and CI diffs it)."""
    path = pathlib.Path(__file__).resolve().parent.parent / "docs" / "configs.md"
    assert path.exists(), "run: python -m spark_rapids_tpu.config docs/configs.md"
    assert path.read_text() == config.generate_docs(), (
        "docs/configs.md is stale; regenerate with "
        "python -m spark_rapids_tpu.config docs/configs.md")


# ---------------------------------------------------------------- device set-up
_REPO = pathlib.Path(__file__).resolve().parent.parent


def _run_py(args, env_overrides):
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(_REPO),
                **env_overrides})
    return subprocess.run([sys.executable, *args], env=env, cwd=str(_REPO),
                          capture_output=True, text=True, timeout=300)


_PRINT_CACHE_DIR = ["-c", "import jax, spark_rapids_tpu.device; "
                          "print(jax.config.jax_compilation_cache_dir)"]


def test_compile_cache_env_var_wins(tmp_path):
    r = _run_py(_PRINT_CACHE_DIR, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)


def test_compile_cache_defaults_to_checkout():
    r = _run_py(_PRINT_CACHE_DIR, {})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(_REPO / ".jax_cache")


def test_chip_smoke_refuses_cpu_backend():
    """Without a TPU the smoke fails at the device check: non-zero exit, no
    data generated, no summary printed."""
    r = _run_py(["chip_smoke.py"], {})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "data:" not in r.stdout and '"ok"' not in r.stdout


def test_chip_smoke_last_line_is_the_result_object(monkeypatch, capsys):
    """The chip check reads the last stdout line: one JSON object with exactly
    "ok" and "device" {"platform", "kind", "count"}. The smoke's own summary
    goes on the line before it. Legs are stubbed; only main's output is run."""
    import json
    import sys
    monkeypatch.syspath_prepend(str(_REPO))
    import chip_smoke
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: device)
    monkeypatch.setattr(chip_smoke, "collect_leg",
                        lambda *a: ({3: None}, {"q3": 0.0}))
    monkeypatch.setattr(chip_smoke, "exchange_leg", lambda *a: {})
    monkeypatch.setattr(chip_smoke, "served_leg", lambda *a: {})
    monkeypatch.setattr(chip_smoke, "mesh_leg", lambda *a: {})
    chip_smoke.main(["--scale", "0.001"])
    sys.modules.pop("chip_smoke", None)
    *_, summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert summary.startswith("summary: ")
    record = json.loads(summary.removeprefix("summary: "))
    assert record["ok"] is True and record["claim"] is None
    assert record["mesh"] in ("ok", "not run")
