"""Common neural-net building blocks (pure functional, no framework).

Conventions:
  * params are nested dicts of jnp arrays;
  * init_* functions take a PRNG key and return a param subtree;
  * apply functions are pure: (params, x, ...) -> y;
  * compute dtype follows the input; norm statistics and softmax in f32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------- norms


def norm_init(d: int, kind: str, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def norm_apply(p: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def groupnorm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    """LayerNorm within each head: x (..., H, hd)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------- MLPs


def mlp_init(key, d: int, d_ff: int, kind: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype),
        }
    if kind in ("gelu", "relu_sq"):
        return {
            "w_up": dense_init(k1, d, d_ff, dtype),
            "w_down": dense_init(k2, d_ff, d, dtype),
        }
    raise ValueError(kind)


def mlp_apply(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif kind == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    elif kind == "relu_sq":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    else:
        raise ValueError(kind)
    return h @ p["w_down"]


# ----------------------------------------------------------------- RoPE


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def yarn_mscale(scale: float, m: float) -> float:
    """YaRN's attention temperature factor 0.1 m ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies: the plain RoPE frequencies blended
    with the same over `yarn.factor` through a linear ramp between the
    correction dims that `beta_fast` and `beta_slow` rotations give at
    the original context length."""
    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the plain frequency is kept
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                 yarn=None):
    """positions (..., T) int -> cos/sin (..., T, head_dim//2) f32; with
    `yarn` (a `config.YarnConfig`) the YaRN frequencies, and cos/sin
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    if yarn is None:
        freqs, scale = rope_freqs(head_dim, theta), 1.0
    else:
        freqs = yarn_inv_freq(head_dim, theta, yarn)
        scale = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        freqs, jnp.float32)
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return scale * jnp.cos(ang), scale * jnp.sin(ang)


def mrope_cos_sin(positions: jax.Array, head_dim: int, theta: float, sections):
    """Qwen2-VL multimodal RoPE [arXiv:2409.12191].

    positions: (3, B, T) — temporal / height / width position streams.
    sections: split of head_dim//2 among the three streams.
    Returns cos/sin of shape (B, T, head_dim//2).
    """
    assert positions.shape[0] == 3
    cos3, sin3 = rope_cos_sin(positions, head_dim, theta)  # (3,B,T,hd/2)
    secs = np.cumsum(np.asarray(sections))[:-1]
    cos_parts = jnp.split(cos3, secs, axis=-1)
    sin_parts = jnp.split(sin3, secs, axis=-1)
    cos = jnp.concatenate([cos_parts[i][i] for i in range(3)], axis=-1)
    sin = jnp.concatenate([sin_parts[i][i] for i in range(3)], axis=-1)
    return cos, sin


def rope_apply(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, T, H, hd); cos/sin (B, T, hd//2) or (T, hd//2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


# ----------------------------------------------------------------- loss


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array, mask=None) -> jax.Array:
    """Mean token cross-entropy, f32. logits (..., V), labels (...) int.

    The label pick uses a masked reduction rather than take_along_axis: a
    gather over a vocab-sharded logits tensor makes GSPMD all-gather the
    full (B, T, V) array, while select+reduce stays shard-local.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    picked = jnp.where(vocab_iota == labels[..., None], logits, 0.0)
    ll = jnp.sum(picked, axis=-1)
    nll = logz - ll
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
