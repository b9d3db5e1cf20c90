"""Test configuration: force JAX onto 8 virtual CPU devices.

Tests must not require the real TPU chip; multi-device sharding logic is exercised
on a virtual CPU mesh (mirrors how the driver dry-runs multichip compilation).
This must run before jax is imported anywhere.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Free compiled-executable memory between test modules: one process
    accumulates thousands of XLA programs across the suite, and LLVM
    compiles near the end of the run can die under that heap pressure.
    The persistent on-disk cache keeps recompiles cheap."""
    yield
    import jax
    jax.clear_caches()
    from spark_rapids_tpu.execs import tpu_execs, evaluator
    tpu_execs._JIT_CACHE.clear() if hasattr(tpu_execs, "_JIT_CACHE") else None
    evaluator._JIT_CACHE.clear()
