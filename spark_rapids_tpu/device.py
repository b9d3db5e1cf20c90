"""Central jax runtime setup.

Every module that touches jax imports it through here so process-wide settings are
applied exactly once:

- ``jax_enable_x64``: Spark's LONG/DOUBLE semantics require true 64-bit arithmetic;
  jax's default 32-bit mode silently truncates. On TPU, int64 is natively supported
  and float64 is compiler-emulated — correctness first, with an opt-in
  ``variableFloatAgg``-style downgrade path for perf-critical double math later.
"""
from __future__ import annotations

import os
import sys

import jax

jax.config.update("jax_enable_x64", True)

# Deep traces (the fused shuffle kernel: jit -> pjit -> pallas, with x64
# promotion wrappers on every op) legitimately exceed CPython's default
# 1000-frame limit during tracing.
sys.setrecursionlimit(max(sys.getrecursionlimit(), 20000))

# Persistent XLA compilation cache: compiled executables survive process
# restarts. JAX_COMPILATION_CACHE_DIR, when set, is jax's own setting and
# wins untouched; otherwise the cache lives at one fixed path in the
# checkout, shared by every process and every run of it.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import jax.numpy as jnp  # noqa: E402,F401


def default_device():
    return jax.devices()[0]


def device_count() -> int:
    return jax.device_count()
