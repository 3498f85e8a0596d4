"""Pure-jnp references for the Pallas kernels.

`benchmarks.run --only kernels` times these under `jax.jit` as the XLA
baseline, and `flash_attention` is the attention kernel's test oracle.
Dots run at HIGHEST precision, so on a TPU a reference is not itself a
reduced-precision MXU pass. The reduction kernels' parity tests compare
against float64 oracles instead (`tests/_oracle.py`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def grad_dot_stats(a: jax.Array, b: jax.Array):
    af = a.reshape(-1).astype(jnp.float32)
    bf = b.reshape(-1).astype(jnp.float32)
    return (jnp.dot(af, bf, precision=_HI), jnp.dot(af, af, precision=_HI),
            jnp.dot(bf, bf, precision=_HI))


def weighted_agg(w: jax.Array, x: jax.Array):
    return jnp.sum(
        w.astype(jnp.float32)[:, None] * x.astype(jnp.float32), axis=0
    ).astype(x.dtype)


def round_stats(x: jax.Array, g: jax.Array, mask: jax.Array | None = None):
    """(dots (K,), sqnorms (K,), sqg ()) over x (K, N), g (N,)."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    if mask is not None:
        mf = mask.astype(jnp.float32)
        xf = xf * mf[None]
        gf = gf * mf
    return (jnp.dot(xf, gf, precision=_HI), jnp.sum(xf * xf, axis=1),
            jnp.dot(gf, gf, precision=_HI))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True):
    """Naive softmax attention oracle. q/k/v (BH, T, d)."""
    T = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32)).astype(q.dtype)
