"""Fused Pallas kernels for the flat round engine, with pure-jnp oracles
in `ref.py`."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None) -> bool:
    """Resolve a kernel wrapper's `interpret` flag.

    None (every wrapper's default) runs the Pallas interpreter on any
    backend but a TPU, and compiles the kernel with Mosaic on a TPU. An
    explicit bool wins: compile-only tests pass False to lower for a
    described chip from a CPU process."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
