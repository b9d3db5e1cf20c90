"""Version shims: adapters over JAX API drift.

Reference analogs: SparkShims trait (SparkShims.scala:58-127 — ~25 methods
abstracting Spark API drift across 3.0.0/3.0.1/3.1.0/Databricks) and
ShimLoader (ShimLoader.scala:33-60 — ServiceLoader picking the provider whose
version_match accepts the runtime version). The reference's drift surface is
Spark; this framework's is JAX, whose public API moved repeatedly across the
0.4 -> 0.5+ line (new-style PRNG keys, jax.tree namespace, jax.make_mesh).
Every version-sensitive call in the engine routes through ``get()`` so
supporting a new JAX release means one new provider class, exactly like
adding a shims/sparkXYZ module in the reference.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


class JaxShims:
    """Provider interface (SparkShims trait analog). Subclasses pin the
    version range they serve and override what drifted there."""

    @staticmethod
    def version_match(version: str) -> bool:
        raise NotImplementedError

    # ---- RNG ------------------------------------------------------------------
    def prng_key(self, seed: int):
        """New-style typed PRNG key (jax.random.key, 0.4.16+)."""
        import jax
        return jax.random.key(seed)

    # ---- trees ----------------------------------------------------------------
    def tree_map(self, fn, tree):
        """jax.tree.map (0.4.25+); older releases only had
        jax.tree_util.tree_map."""
        import jax
        return jax.tree.map(fn, tree)

    # ---- meshes ---------------------------------------------------------------
    def make_mesh(self, devices: Sequence, axis_names):
        """Build a Mesh over explicit devices (stable across versions; routed
        through the shim so a future Mesh-API change lands in one place)."""
        from jax.sharding import Mesh
        return Mesh(np.array(devices), axis_names)

    def shard_map(self, f, mesh, in_specs, out_specs, check_vma=False):
        """Top-level jax.shard_map (promoted from experimental in 0.5+);
        ``check_vma`` is the 0.5+ name of the replication check flag."""
        import jax
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)

    # ---- dtype bit tricks -----------------------------------------------------
    def bitcast(self, arr, dtype):
        import jax
        return jax.lax.bitcast_convert_type(arr, dtype)


class Jax05PlusShims(JaxShims):
    """0.5.x and later (including the 0.9 line this image ships)."""

    @staticmethod
    def version_match(version: str) -> bool:
        major, minor = _parse(version)
        return (major, minor) >= (0, 5)


#: registration order = match priority (ShimLoader's provider list)
PROVIDERS: List[type] = [Jax05PlusShims]

_ACTIVE: Optional[JaxShims] = None


def _parse(version: str):
    parts = version.split(".")
    try:
        return int(parts[0]), int(parts[1])
    except (ValueError, IndexError):
        return (0, 0)


def get() -> JaxShims:
    """The provider matching the runtime jax version (ShimLoader.getShims
    analog); raises if no provider claims it, like the reference's
    'Could not find Spark Shim Loader' error."""
    global _ACTIVE
    if _ACTIVE is None:
        import jax
        version = jax.__version__
        for cls in PROVIDERS:
            if cls.version_match(version):
                _ACTIVE = cls()
                break
        else:
            raise RuntimeError(
                f"no shim provider matches jax {version}; supported: "
                f"{[c.__name__ for c in PROVIDERS]}")
    return _ACTIVE
