"""GQA/MQA attention with RoPE, sliding-window option, and KV-cache decode.

Cache layout (per layer): {"k": (B, S, G, hd), "v": (B, S, G, hd)} with S =
max_len for full attention or S = window for the sliding-window ring buffer.
Keys are stored *already rotated*; decode only rotates the query.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e30


def attn_init(key, cfg, d_in: int | None = None) -> dict:
    d = d_in or cfg.d_model
    hd = cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.jdtype
    return {
        "wq": layers.dense_init(kq, d, cfg.num_heads * hd, dt),
        "wk": layers.dense_init(kk, d, cfg.num_kv_heads * hd, dt),
        "wv": layers.dense_init(kv, d, cfg.num_kv_heads * hd, dt),
        "wo": layers.dense_init(ko, cfg.num_heads * hd, d, dt),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _gqa_scores(q, k, scale):
    """q (B,T,H,hd), k (B,S,G,hd) -> scores (B,G,H/G,T,S) in f32."""
    B, T, H, hd = q.shape
    G = k.shape[2]
    q = q.reshape(B, T, G, H // G, hd)
    return jnp.einsum(
        "btghe,bsge->bghts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale


def _gqa_out(probs, v):
    """probs (B,G,Hg,T,S), v (B,S,G,hd) -> (B,T,H*hd)."""
    B, G, Hg, T, S = probs.shape
    out = jnp.einsum("bghts,bsge->btghe", probs, v.astype(jnp.float32))
    return out.reshape(B, T, G * Hg * v.shape[-1])


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, NEG_INF)
    return jax.nn.softmax(scores, axis=-1)


def full_mask(T: int, S: int, causal: bool, window: int, offset: int = 0):
    """(T, S) bool mask. `offset` = absolute position of query 0 minus key 0."""
    qi = jnp.arange(T)[:, None] + offset
    kj = jnp.arange(S)[None, :]
    m = jnp.ones((T, S), bool)
    if causal:
        m &= kj <= qi
    if window:
        m &= kj > qi - window
    return m


def attn_forward(p: dict, cfg, x: jax.Array, cos, sin, *, causal: bool = True,
                 window: int = 0, return_cache: bool = False, max_len: int = 0):
    """Full-sequence attention (train / prefill).

    Returns (y, cache|None). For prefill, `max_len` sizes the cache buffer
    (>= T for full attention; ring of size `window` for SWA).

    With cfg.q_chunk > 0 the score/softmax/AV contraction is computed one
    query block at a time (lax.scan), bounding the live score tensor to
    B*H*q_chunk*T f32 instead of B*H*T^2 — the §Perf memory-term
    optimization for long-sequence training.
    """
    B, T, _ = x.shape
    hd = cfg.hd
    q = _split_heads(x @ p["wq"], cfg.num_heads, hd)
    k = _split_heads(x @ p["wk"], cfg.num_kv_heads, hd)
    v = _split_heads(x @ p["wv"], cfg.num_kv_heads, hd)
    if cos is not None:
        q = layers.rope_apply(q, cos, sin)
        k = layers.rope_apply(k, cos, sin)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q_chunk = getattr(cfg, "q_chunk", 0)
    use_flash = (
        getattr(cfg, "attention_impl", "xla") == "flash"
        and window == 0 and causal and T % 64 == 0
    )
    if use_flash:
        from repro.kernels import flash_attn

        blk = min(128, T)
        out = flash_attn.gqa_flash(q, k, v, causal=True, blk_q=blk, blk_k=blk)
        y = out.reshape(B, T, cfg.num_heads * hd).astype(x.dtype) @ p["wo"]
    elif q_chunk and T > q_chunk and T % q_chunk == 0:
        out = _chunked_attention(q, k, v, scale, causal, window, q_chunk)
        y = out.astype(x.dtype) @ p["wo"]
    else:
        scores = _gqa_scores(q, k, scale)
        if getattr(cfg, "act_constrain", False):
            from repro.models import sharding as shmod

            # keep batch on the data axes through the score tensor — GSPMD
            # otherwise un-shards it under FSDP param sharding (§Perf)
            scores = shmod.constrain(scores, "batch", "model", None, None, None)
        mask = full_mask(T, T, causal, window)
        probs = _masked_softmax(scores, mask)
        y = _gqa_out(probs, v).astype(x.dtype) @ p["wo"]

    cache = None
    if return_cache:
        S = min(window, max_len) if window else max_len
        assert S > 0
        ck = jnp.zeros((B, S, cfg.num_kv_heads, hd), k.dtype)
        cv = jnp.zeros((B, S, cfg.num_kv_heads, hd), v.dtype)
        if window and T > S:
            # ring buffer keeps the trailing `window` positions, rotated so
            # that slot = pos % S matches decode-time writes.
            tail_k, tail_v = k[:, -S:], v[:, -S:]
            shift = T % S
            tail_k = jnp.roll(tail_k, shift, axis=1)
            tail_v = jnp.roll(tail_v, shift, axis=1)
            ck, cv = tail_k, tail_v
        else:
            ck = jax.lax.dynamic_update_slice(ck, k[:, -min(T, S):], (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v[:, -min(T, S):], (0, 0, 0, 0))
        cache = {"k": ck, "v": cv}
    return y, cache


def attn_decode(p: dict, cfg, x: jax.Array, cache: dict, pos, cos, sin, *,
                window: int = 0):
    """Single-token decode. x (B,1,d); pos: scalar int32 absolute position.

    Returns (y, new_cache).
    """
    B = x.shape[0]
    hd = cfg.hd
    S = cache["k"].shape[1]
    q = _split_heads(x @ p["wq"], cfg.num_heads, hd)
    k = _split_heads(x @ p["wk"], cfg.num_kv_heads, hd)
    v = _split_heads(x @ p["wv"], cfg.num_kv_heads, hd)
    if cos is not None:
        q = layers.rope_apply(q, cos, sin)
        k = layers.rope_apply(k, cos, sin)
    slot = (pos % S) if window else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))

    scores = _gqa_scores(q, ck, 1.0 / jnp.sqrt(hd).astype(jnp.float32))  # (B,G,Hg,1,S)
    idx = jnp.arange(S)
    if window:
        valid = idx < jnp.minimum(pos + 1, S)  # ring: everything written is in-window
    else:
        valid = idx <= pos
    probs = _masked_softmax(scores, valid[None, None, None, None, :])
    y = _gqa_out(probs, cv).astype(x.dtype) @ p["wo"]
    return y, {"k": ck, "v": cv}


def _chunked_attention(q, k, v, scale, causal, window, q_chunk):
    """Query-blocked attention: scan over query chunks, full K/V visible.

    Live memory per step: (B, G, Hg, q_chunk, T) f32 scores — T/q_chunk x
    smaller than the naive path. Returns (B, T, H*hd) f32.
    """
    B, T, H, hd = q.shape
    n = T // q_chunk
    qs = jnp.moveaxis(q.reshape(B, n, q_chunk, H, hd), 1, 0)

    def body(_, xs):
        qb, i = xs
        scores = _gqa_scores(qb, k, scale)
        mask = full_mask(q_chunk, T, causal, window, offset=i * q_chunk)
        probs = _masked_softmax(scores, mask)
        return None, _gqa_out(probs, v)

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(n)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, T, H * hd)


# ------------------------------------------------------- cross-attention


def cross_attn_init(key, cfg) -> dict:
    return attn_init(key, cfg)


def cross_attn_kv(p: dict, cfg, enc: jax.Array) -> dict:
    """Precompute encoder K/V once (prefill); reused for every decode step."""
    hd = cfg.hd
    k = _split_heads(enc @ p["wk"], cfg.num_kv_heads, hd)
    v = _split_heads(enc @ p["wv"], cfg.num_kv_heads, hd)
    return {"k": k, "v": v}

def cross_attn_apply(p: dict, cfg, x: jax.Array, kv: dict) -> jax.Array:
    hd = cfg.hd
    q = _split_heads(x @ p["wq"], cfg.num_heads, hd)
    scores = _gqa_scores(q, kv["k"], 1.0 / jnp.sqrt(hd).astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, kv["v"]).astype(x.dtype) @ p["wo"]
