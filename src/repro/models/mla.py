"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

K/V are compressed into a shared latent c_kv (kv_lora_rank) plus one shared
RoPE key head. Decode runs in the *absorbed* form: the cache holds only
(c_kv, k_rope) — O(kv_lora_rank + rope_dim) bytes per token — and the
up-projections W_uk / W_uv are folded into the query/output sides. This is
what makes `long_500k` decode feasible for the 236B model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attn
from repro.models import layers
from repro.models import sharding as shmod

NEG_INF = -1e30
ATTN_SCOPE = "model.mla_attn"  # the fused attention core alone


def mla_init(key, cfg) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    dt = cfg.jdtype
    ks = jax.random.split(key, 8)
    p = {
        "wkv_a": layers.dense_init(ks[0], d, m.kv_lora_rank + m.rope_head_dim, dt),
        "kv_norm": layers.norm_init(m.kv_lora_rank, "rmsnorm", dt),
        "wk_b": layers.dense_init(ks[1], m.kv_lora_rank, H * m.nope_head_dim, dt),
        "wv_b": layers.dense_init(ks[2], m.kv_lora_rank, H * m.v_head_dim, dt),
        "wo": layers.dense_init(ks[3], H * m.v_head_dim, d, dt),
    }
    q_out = H * (m.nope_head_dim + m.rope_head_dim)
    if m.q_lora_rank:
        p["wq_a"] = layers.dense_init(ks[4], d, m.q_lora_rank, dt)
        p["q_norm"] = layers.norm_init(m.q_lora_rank, "rmsnorm", dt)
        p["wq_b"] = layers.dense_init(ks[5], m.q_lora_rank, q_out, dt)
    else:
        p["wq"] = layers.dense_init(ks[4], d, q_out, dt)
    return p


def softmax_scale(cfg) -> float:
    """(q/k head dim)^-1/2, times mscale(factor, mscale_all_dim)^2 under
    YaRN (HF `DeepseekV2Attention.softmax_scale`)."""
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= layers.yarn_mscale(cfg.yarn.factor,
                                    cfg.yarn.mscale_all_dim) ** 2
    return scale


def _queries(p, cfg, x, cos, sin):
    m = cfg.mla
    H = cfg.num_heads
    if m.q_lora_rank:
        q = layers.norm_apply(p["q_norm"], x @ p["wq_a"]) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(x.shape[0], x.shape[1], H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = layers.rope_apply(q_rope, cos, sin)
    return q_nope, q_rope


def _latents(p, cfg, x, cos, sin):
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = layers.norm_apply(p["kv_norm"], c_kv)
    k_rope = layers.rope_apply(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return c_kv, k_rope


def _einsum_core(cfg, q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal attention as f32 einsums over materialized (B, H, T, T)
    scores; under `act_constrain` the partitioner splits them by head."""
    T = q_nope.shape[1]
    s = jnp.einsum("bthe,bshe->bhts", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
    s += jnp.einsum("bthe,bse->bhts", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    if cfg.act_constrain:
        s = shmod.constrain(s, "batch", "model", None, None)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(mask, s * scale, NEG_INF), axis=-1)
    out = jnp.einsum("bhts,bshe->bthe", probs, v.astype(jnp.float32))
    if cfg.act_constrain:
        out = shmod.constrain(out, "batch", None, "model", None)
    return out


def _fused_core(q_nope, q_rope, k_nope, k_rope, v, scale):
    """The same attention as one fused causal kernel, forward and
    backward, under `ATTN_SCOPE`: q and k are the nope and RoPE parts
    side by side, the shared RoPE key broadcast over the heads. On a TPU
    each head's share of the RoPE key's gradient leaves the kernel in
    bfloat16 and the heads are summed in f32 after; the einsum core sums
    them inside one f32 accumulation."""
    B, T, H, _ = q_nope.shape
    q = jnp.concatenate([q_nope, q_rope], -1)
    k_rope = jnp.broadcast_to(k_rope[:, :, None], (B, T, H, k_rope.shape[-1]))
    k = jnp.concatenate([k_nope, k_rope], -1)

    def heads(t):  # (B, T, H, d) <-> (B, H, T, d)
        return t.transpose(0, 2, 1, 3)

    with jax.named_scope(ATTN_SCOPE):
        out = flash_attn.causal_attention(heads(q), heads(k), heads(v), scale)
    return heads(out)


def mla_forward(p: dict, cfg, x: jax.Array, cos, sin, *,
                return_cache: bool = False, max_len: int = 0):
    """Train/prefill: expanded (non-absorbed) attention over the sequence.
    The attention core runs fused (`_fused_core`) where the kernel tiles
    T and the step runs on one device, else as einsums (`_einsum_core`),
    which the partitioner can split: on a mesh (`sharding.traced_on`) or
    where `act_constrain` asks it to shard the scores."""
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _queries(p, cfg, x, cos, sin)
    c_kv, k_rope = _latents(p, cfg, x, cos, sin)
    k_nope = (c_kv @ p["wk_b"]).reshape(B, T, H, m.nope_head_dim)
    v = (c_kv @ p["wv_b"]).reshape(B, T, H, m.v_head_dim)

    scale = softmax_scale(cfg)
    if (cfg.act_constrain or shmod.step_devices() > 1
            or flash_attn.causal_blocks(T) is None):
        out = _einsum_core(cfg, q_nope, q_rope, k_nope, k_rope, v, scale)
    else:
        out = _fused_core(q_nope, q_rope, k_nope, k_rope, v, scale)
    y = out.reshape(B, T, H * m.v_head_dim).astype(x.dtype) @ p["wo"]

    cache = None
    if return_cache:
        assert max_len >= T
        ck = jnp.zeros((B, max_len, m.kv_lora_rank), c_kv.dtype)
        cr = jnp.zeros((B, max_len, m.rope_head_dim), k_rope.dtype)
        cache = {
            "ckv": jax.lax.dynamic_update_slice(ck, c_kv, (0, 0, 0)),
            "krope": jax.lax.dynamic_update_slice(cr, k_rope, (0, 0, 0)),
        }
    return y, cache


def mla_decode(p: dict, cfg, x: jax.Array, cache: dict, pos, cos, sin):
    """Absorbed single-token decode against the latent cache."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    q_nope, q_rope = _queries(p, cfg, x, cos, sin)  # (B,1,H,*)
    c_kv, k_rope = _latents(p, cfg, x, cos, sin)  # (B,1,r), (B,1,rd)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_kv, (0, pos, 0))
    krope = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, pos, 0))

    # absorb W_uk into the query: q_lat (B,1,H,r)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
    q_lat = jnp.einsum("bthe,rhe->bthr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    s = jnp.einsum("bthr,bsr->bhts", q_lat, ckv.astype(jnp.float32))
    s += jnp.einsum("bthe,bse->bhts", q_rope.astype(jnp.float32),
                    krope.astype(jnp.float32))
    scale = softmax_scale(cfg)
    valid = jnp.arange(ckv.shape[1]) <= pos
    probs = jax.nn.softmax(
        jnp.where(valid[None, None, None, :], s * scale, NEG_INF), axis=-1
    )
    out_lat = jnp.einsum("bhts,bsr->bthr", probs, ckv.astype(jnp.float32))
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bthr,rhe->bthe", out_lat, wv_b.astype(jnp.float32))
    y = out.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return y, {"ckv": ckv, "krope": krope}
