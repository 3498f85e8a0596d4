"""Jitted tree-level wrappers over the Pallas kernels.

These mirror `repro.core.treemath` but stream through the fused kernels —
used by the kernel benchmarks. Trees are flattened leaf-by-leaf and the per-leaf
partial statistics are combined, so no concatenated copy of the parameter
vector is ever materialized.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import grad_dot, weighted_agg

PyTree = Any


def tree_dot_and_norms(a: PyTree, b: PyTree, *, interpret=None):
    dots, nas, nbs = [], [], []
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        d, na, nb = grad_dot.grad_dot_stats(x, y, interpret=interpret)
        dots.append(d)
        nas.append(na)
        nbs.append(nb)
    return (
        jnp.sum(jnp.stack(dots)),
        jnp.sum(jnp.stack(nas)),
        jnp.sum(jnp.stack(nbs)),
    )


def tree_weighted_sum(stacked: PyTree, w: jax.Array, *, interpret=None):
    """sum_k w[k] * tree[k] for leaves with leading K axis."""

    def leaf(x):
        K = x.shape[0]
        y = weighted_agg.weighted_agg(w, x.reshape(K, -1), interpret=interpret)
        return y.reshape(x.shape[1:]).astype(x.dtype)

    return jax.tree.map(leaf, stacked)

