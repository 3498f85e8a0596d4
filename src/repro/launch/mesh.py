"""Device setup for the entry points: meshes and the compile cache.

Kept as functions (never module-level constants or import-time calls) so
importing this module never touches jax device state — device count is
locked at first jax init, and only launch/dryrun.py forces the
512-host-device placeholder.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/mesh.py.
# A fixed path, because the cache directory is part of the cache key.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def make_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """A device mesh whose axes are all `AxisType.Auto`.

    `jax.make_mesh` defaults to Explicit axes, which
    `with_sharding_constraint(P("data"))` refuses; the round engines'
    sharding constraints and shard_map regions are written for Auto axes.
    Every mesh in the repo comes from here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 (data, model) per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (tests/benches see 1 device)."""
    return make_mesh((1, 1), ("data", "model"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    this sets no other path. Otherwise the cache goes to the fixed
    `.jax_cache/` at the checkout root. Called by the entry points'
    mains, never on import.

    The key holds the programs' metadata: the ``fl.*`` named scopes a
    profiler trace reads live only there, so a program whose scopes moved
    must not be served the executable of the old ones."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
