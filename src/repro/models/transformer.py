"""Unified architecture assembly for the whole model zoo.

A model is a repeating group of `len(cfg.block_pattern)` blocks, scanned
`cfg.num_pattern_groups` times with stacked parameters (bounded HLO size —
a 72-layer Jamba lowers as one 9-iteration scan over an 8-block group).

Block kinds: "attn" (GQA/MQA or MLA; + cross-attention for enc-dec),
"mamba", "rwkv". Every non-rwkv block has an FFN slot (dense MLP or MoE
according to cfg.moe_pattern); rwkv blocks embed their own channel-mix.

`cfg.first_dense_layers` leading blocks (DeepSeek's
`first_k_dense_replace`) run before the groups as a scan of their own,
with a dense MLP of width `cfg.d_ff` ("dense" in the params and, where
there are any, in the decode cache beside "blocks").

Three entry points:
  forward(..., mode="train")    -> (logits, aux_loss)
  forward(..., mode="prefill")  -> (logits, aux_loss, cache)
  decode_step(...)              -> (logits, cache)   # one token
and, for a federated client, `next_token_loss` / `next_token_eval`.

The model's parts run under `jax.named_scope`s outside the round's
"fl." ones: "model.mla" (latent attention), "model.moe" (routing,
dispatch, combine and the shared experts), "model.moe_gmm" (the grouped
expert products alone, inside "model.moe"), "model.mlp" (a dense FFN)
and "model.head" (final norm, unembedding and loss).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention, layers, mamba, mla, moe, rwkv6
from repro.models.config import ModelConfig

PyTree = Any

DENSE_KINDS = (("attn", False),)  # the leading dense blocks' pattern


# =================================================================== init


def _ffn_init(key, cfg, is_moe: bool) -> dict:
    if is_moe:
        return moe.moe_init(key, cfg)
    return layers.mlp_init(key, cfg.d_model, cfg.d_ff, cfg.mlp, cfg.jdtype)


def _block_init(key, cfg, kind: str, is_moe: bool, cross: bool) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"norm1": layers.norm_init(d, cfg.norm, cfg.jdtype)}
    if kind == "attn":
        p["mixer"] = mla.mla_init(k1, cfg) if cfg.mla else attention.attn_init(k1, cfg)
    elif kind == "mamba":
        p["mixer"] = mamba.mamba_init(k1, cfg)
    elif kind == "rwkv":
        p["mixer"] = rwkv6.rwkv_init(k1, cfg)
        p["norm2"] = layers.norm_init(d, cfg.norm, cfg.jdtype)
        return p  # rwkv block embeds its channel-mix; no separate FFN slot
    else:
        raise ValueError(kind)
    if cross:
        p["norm_cross"] = layers.norm_init(d, cfg.norm, cfg.jdtype)
        p["cross"] = attention.cross_attn_init(k4, cfg)
    p["norm2"] = layers.norm_init(d, cfg.norm, cfg.jdtype)
    p["ffn"] = _ffn_init(k2, cfg, is_moe)
    return p


def _stack_init(key, cfg, *, cross: bool, num_groups: int,
                kinds=None) -> dict:
    """Stacked block params: {"p{i}": leaves with leading G axis}."""
    kinds = kinds or cfg.layer_kinds()
    out = {}
    for i, (kind, is_moe) in enumerate(kinds):
        keys = jax.random.split(jax.random.fold_in(key, i), num_groups)
        out[f"p{i}"] = jax.vmap(
            lambda k: _block_init(k, cfg, kind, is_moe, cross)
        )(keys)
    return out


def init_params(key, cfg: ModelConfig) -> PyTree:
    ke, kb, kh, kenc = jax.random.split(key, 4)
    params = {
        "embed": layers.embed_init(ke, cfg.vocab_size, cfg.d_model, cfg.jdtype),
        "blocks": _stack_init(
            kb, cfg, cross=cfg.encoder_layers > 0, num_groups=cfg.num_pattern_groups
        ),
        "final_norm": layers.norm_init(cfg.d_model, cfg.norm, cfg.jdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(kh, cfg.d_model, cfg.vocab_size, cfg.jdtype)
    if cfg.first_dense_layers:
        params["dense"] = _stack_init(
            jax.random.fold_in(key, 4), cfg, cross=cfg.encoder_layers > 0,
            num_groups=cfg.first_dense_layers, kinds=DENSE_KINDS)
    if cfg.encoder_layers:
        # encoder is a plain full-attention stack (one group per layer pair)
        enc_groups = cfg.encoder_layers
        params["encoder"] = {
            "blocks": _stack_init(kenc, cfg, cross=False, num_groups=enc_groups),
            "final_norm": layers.norm_init(cfg.d_model, cfg.norm, cfg.jdtype),
        }
    return params


# ============================================================ positions


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """Additive sinusoidal embedding (whisper-style decoder positions)."""
    half = d // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _rope_for(cfg, batch, B, T, offset=0):
    """cos/sin for the configured rope style; None for rope_style='none'."""
    hd = cfg.mla.rope_head_dim if cfg.mla else cfg.hd
    if cfg.rope_style == "none":
        return None, None
    if cfg.rope_style == "mrope":
        pos = batch.get("positions")
        if pos is None:
            base = jnp.arange(T)[None].repeat(B, 0) + offset
            pos = jnp.broadcast_to(base[None], (3, B, T))
        return layers.mrope_cos_sin(pos, hd, cfg.rope_theta, cfg.mrope_sections)
    pos = jnp.arange(T)[None].repeat(B, 0) + offset
    return layers.rope_cos_sin(pos, hd, cfg.rope_theta, cfg.yarn)


# =============================================================== blocks


def _mixer(bp, cfg, kind, x, cos, sin, mode, cache, pos, window):
    """Dispatch one mixer. Returns (y, new_cache_or_None)."""
    if kind == "attn":
        if cfg.mla:
            with jax.named_scope("model.mla"):
                if mode == "decode":
                    return mla.mla_decode(bp["mixer"], cfg, x, cache, pos,
                                          cos, sin)
                return mla.mla_forward(
                    bp["mixer"], cfg, x, cos, sin,
                    return_cache=(mode == "prefill"), max_len=cache,
                )
        if mode == "decode":
            return attention.attn_decode(
                bp["mixer"], cfg, x, cache, pos, cos, sin, window=window
            )
        return attention.attn_forward(
            bp["mixer"], cfg, x, cos, sin, causal=True, window=window,
            return_cache=(mode == "prefill"), max_len=cache if mode == "prefill" else 0,
        )
    if kind == "mamba":
        st = cache if mode == "decode" else None
        y, ns = mamba.mamba_forward(bp["mixer"], cfg, x, st)
        return y, (ns if mode in ("prefill", "decode") else None)
    raise ValueError(kind)


def _block(bp, cfg, kind, is_moe, x, ctx, cache, mode):
    """One block. Returns (x, new_cache, aux, rows): `rows` counts the
    token-expert pairs through the held experts' products."""
    aux = jnp.zeros((), jnp.float32)
    rows = jnp.zeros((), jnp.int32)
    h = layers.norm_apply(bp["norm1"], x)
    if kind == "rwkv":
        st = cache if mode == "decode" else None
        if mode == "decode":
            y, tm_state = rwkv6.time_mix_decode(bp["mixer"], cfg, h, st)
        else:
            y, tm_state = rwkv6.time_mix(bp["mixer"], cfg, h, st)
        x = x + y
        # rwkv: channel-mix lives inside the block (own token-shift state)
        h2 = layers.norm_apply(bp["norm2"], x)
        cm_last = cache["cm_last"] if mode == "decode" else None
        y2, new_cm = rwkv6.channel_mix(bp["mixer"], h2, cm_last)
        x = x + y2
        new_cache = None
        if mode in ("prefill", "decode"):
            new_cache = dict(tm_state, cm_last=new_cm)
        return x, new_cache, aux, rows

    y, new_cache = _mixer(bp, cfg, kind, h, ctx["cos"], ctx["sin"], mode,
                          cache, ctx["pos"], ctx["window"])
    x = x + y
    if "cross" in bp:
        hc = layers.norm_apply(bp["norm_cross"], x)
        if mode == "decode":
            kv = {"k": cache["cross_k"], "v": cache["cross_v"]}
        else:
            kv = attention.cross_attn_kv(bp["cross"], cfg, ctx["enc"])
        x = x + attention.cross_attn_apply(bp["cross"], cfg, hc, kv)
        if mode == "prefill":
            new_cache = dict(new_cache or {}, cross_k=kv["k"], cross_v=kv["v"])
        elif mode == "decode":
            new_cache = dict(new_cache or {}, cross_k=cache["cross_k"],
                             cross_v=cache["cross_v"])
    hf = layers.norm_apply(bp["norm2"], x)
    if is_moe:
        with jax.named_scope("model.moe"):
            yf, aux, rows = moe.moe_apply(bp["ffn"], cfg, hf)
    else:
        with jax.named_scope("model.mlp"):
            yf = layers.mlp_apply(bp["ffn"], hf, cfg.mlp)
    return x + yf, new_cache, aux, rows


def _run_stack(blocks, cfg, x, ctx, mode, cache=None, *, encoder=False,
               kinds=None):
    """Scan the stacked groups (of `kinds`, the config's pattern by
    default). Returns (x, aux, rows, new_cache|None)."""
    kinds = DENSE_KINDS if encoder else (kinds or cfg.layer_kinds())

    def group_body(carry, xs):
        x, aux, rows = carry
        bp = xs[0] if isinstance(xs, tuple) else xs
        cache_g = xs[1] if isinstance(xs, tuple) else None
        new_cache_g = {}
        for i, (kind, is_moe) in enumerate(kinds):
            sub = bp[f"p{i}"]
            c_in = None
            if mode == "decode":
                c_in = cache_g[f"p{i}"]
            elif mode == "prefill":
                c_in = ctx["max_len"]  # scalar buffer size for cache alloc
            if encoder:
                h = layers.norm_apply(sub["norm1"], x)
                y, _ = attention.attn_forward(
                    sub["mixer"], cfg, h, ctx["cos"], ctx["sin"], causal=False
                )
                x = x + y
                hf = layers.norm_apply(sub["norm2"], x)
                x = x + layers.mlp_apply(sub["ffn"], hf, cfg.mlp)
                a = jnp.zeros((), jnp.float32)
                r = jnp.zeros((), jnp.int32)
                nc = None
            else:
                x, nc, a, r = _block(sub, cfg, kind, is_moe, x, ctx, c_in,
                                     mode)
            aux = aux + a
            rows = rows + r
            if nc is not None:
                new_cache_g[f"p{i}"] = nc
        ys = new_cache_g if new_cache_g else jnp.zeros(())
        return (x, aux, rows), ys

    carry0 = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    xs = blocks if mode != "decode" else (blocks, cache)
    body = group_body
    if mode == "train":
        # activation checkpointing per scanned group: O(G) residual stream
        # saves instead of O(G x per-layer activations) for the backward.
        body = jax.checkpoint(group_body)
    (x, aux, rows), ys = jax.lax.scan(body, carry0, xs)
    new_cache = ys if mode in ("prefill", "decode") else None
    return x, aux, rows, new_cache


def _trunk(params, cfg, x, ctx, mode, cache=None):
    """The leading dense blocks, then the scanned groups. Returns (x, aux,
    rows, new_cache|None); with dense blocks the cache is {"dense": ...,
    "blocks": ...}."""
    if not cfg.first_dense_layers:
        return _run_stack(params["blocks"], cfg, x, ctx, mode, cache)
    dense_c = cache["dense"] if mode == "decode" else None
    blocks_c = cache["blocks"] if mode == "decode" else None
    x, a1, r1, c1 = _run_stack(params["dense"], cfg, x, ctx, mode, dense_c,
                               kinds=DENSE_KINDS)
    x, a2, r2, c2 = _run_stack(params["blocks"], cfg, x, ctx, mode, blocks_c)
    new_cache = ({"dense": c1, "blocks": c2}
                 if mode in ("prefill", "decode") else None)
    return x, a1 + a2, r1 + r2, new_cache


# ================================================================ public


def embed_inputs(params, cfg, batch):
    """Token embedding + optional multimodal stub prefixes.

    Returns (x, text_offset): loss applies from text_offset onward.
    """
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    offset = 0
    if cfg.vision_prefix:
        v = batch["vision_embeds"].astype(x.dtype)  # (B, P, d) stub patches
        x = jnp.concatenate([v, x], axis=1)
        offset = v.shape[1]
    return x, offset


def forward(params, cfg: ModelConfig, batch, *, mode: str = "train",
            max_len: int = 0):
    """Full-sequence forward. mode: "train" | "prefill"."""
    x, text_offset = embed_inputs(params, cfg, batch)
    B, T = x.shape[0], x.shape[1]
    cos, sin = _rope_for(cfg, batch, B, T)
    if cfg.rope_style == "none":
        x = x + _sinusoid(jnp.arange(T), cfg.d_model).astype(x.dtype)[None]

    enc = None
    if cfg.encoder_layers:
        enc = batch["enc_embeds"].astype(x.dtype)  # stub frame embeddings
        ectx = {"cos": None, "sin": None, "pos": None, "window": 0,
                "enc": None, "max_len": 0}
        enc, _, _, _ = _run_stack(params["encoder"]["blocks"], cfg, enc,
                                  ectx, "train", encoder=True)
        enc = layers.norm_apply(params["encoder"]["final_norm"], enc)

    ctx = {"cos": cos, "sin": sin, "pos": None, "window": cfg.sliding_window,
           "enc": enc, "max_len": max(max_len, T)}
    x, aux, _, cache = _trunk(params, cfg, x, ctx, mode)
    with jax.named_scope("model.head"):
        x = layers.norm_apply(params["final_norm"], x)
        logits = unembed(params, cfg, x)
    if mode == "prefill":
        return logits, aux, cache
    return logits, aux, text_offset


def unembed(params, cfg, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def hidden_forward(params, cfg: ModelConfig, batch):
    """Forward up to the final norm, WITHOUT the unembed projection."""
    x, aux, _, text_offset = _hidden_and_rows(params, cfg, batch)
    return x, aux, text_offset


def _hidden_and_rows(params, cfg: ModelConfig, batch):
    """`hidden_forward`, with the expert-row count beside the aux loss."""
    x, text_offset = embed_inputs(params, cfg, batch)
    B, T = x.shape[0], x.shape[1]
    cos, sin = _rope_for(cfg, batch, B, T)
    if cfg.rope_style == "none":
        x = x + _sinusoid(jnp.arange(T), cfg.d_model).astype(x.dtype)[None]
    enc = None
    if cfg.encoder_layers:
        enc = batch["enc_embeds"].astype(x.dtype)
        ectx = {"cos": None, "sin": None, "pos": None, "window": 0,
                "enc": None, "max_len": 0}
        enc, _, _, _ = _run_stack(params["encoder"]["blocks"], cfg, enc,
                                  ectx, "train", encoder=True)
        enc = layers.norm_apply(params["encoder"]["final_norm"], enc)
    ctx = {"cos": cos, "sin": sin, "pos": None, "window": cfg.sliding_window,
           "enc": enc, "max_len": T}
    x, aux, rows, _ = _trunk(params, cfg, x, ctx, "train")
    with jax.named_scope("model.head"):
        x = layers.norm_apply(params["final_norm"], x)
    return x, aux, rows, text_offset


def _chunked_ce(params, cfg, x_pred, labels):
    """Cross-entropy with the unembed applied chunk-by-chunk over tokens,
    so the (B, T, V) logits never materialize (cfg.loss_chunk, §Perf)."""
    B, T, d = x_pred.shape
    L = cfg.loss_chunk
    pad = (-T) % L
    mask = jnp.concatenate([jnp.ones((B, T), jnp.float32),
                            jnp.zeros((B, pad), jnp.float32)], 1)
    if pad:
        x_pred = jnp.concatenate([x_pred, jnp.zeros((B, pad, d), x_pred.dtype)], 1)
        labels = jnp.concatenate([labels, jnp.zeros((B, pad), labels.dtype)], 1)
    n = (T + pad) // L

    def chunk(carry, xs):
        xc, yc, mc = xs  # (B, L, d), (B, L), (B, L)
        logits = unembed(params, cfg, xc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        iota = jnp.arange(logits.shape[-1], dtype=yc.dtype)
        ll = jnp.sum(jnp.where(iota == yc[..., None], logits, 0.0), axis=-1)
        return carry + jnp.sum((logz - ll) * mc), None

    def split(t):
        return jnp.moveaxis(t.reshape(B, n, L, *t.shape[2:]), 1, 0)

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
        (split(x_pred), split(labels), split(mask)),
    )
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token cross-entropy (+ MoE aux). Returns scalar f32."""
    tokens = batch["tokens"]
    if cfg.loss_chunk:
        x, aux, off = hidden_forward(params, cfg, batch)
        x_pred = x[:, off:-1] if off else x[:, :-1]
        return _chunked_ce(params, cfg, x_pred, tokens[:, 1:]) + aux
    logits, aux, off = forward(params, cfg, batch, mode="train")
    # predict tokens[1:] from positions [off .. off+T-2]
    pred = logits[:, off:-1] if off else logits[:, :-1]
    ce = layers.softmax_cross_entropy(pred, tokens[:, 1:], batch.get("loss_mask"))
    return ce + aux


def next_token_loss(params, cfg: ModelConfig, x, y):
    """Mean next-token cross-entropy of ids `x` (B, T) against their
    targets `y` (B, T), plus the MoE balance loss. Returns (loss, rows),
    `rows` the token-expert pairs through the held experts' products."""
    h, aux, rows, _ = _hidden_and_rows(params, cfg, {"tokens": x})
    with jax.named_scope("model.head"):
        ce = layers.softmax_cross_entropy(unembed(params, cfg, h), y)
    return ce + aux, rows


def next_token_eval(params, cfg: ModelConfig, x, y):
    """(correct argmax predictions, summed cross-entropy, rows) of ids
    `x` (B, T) against their targets `y`."""
    h, _, rows, _ = _hidden_and_rows(params, cfg, {"tokens": x})
    with jax.named_scope("model.head"):
        logits = unembed(params, cfg, h).astype(jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.sum(
            jnp.where(jnp.arange(logits.shape[-1]) == y[..., None], logits,
                      0.0), axis=-1)
        correct = jnp.sum((jnp.argmax(logits, axis=-1) == y).astype(jnp.int32))
    return correct, jnp.sum(nll), rows


def decode_step(params, cfg: ModelConfig, token, cache, pos, batch_extras=None):
    """One-token decode. token (B,1) int32; pos scalar int32 absolute position.

    Returns (logits (B,1,V), new_cache).
    """
    x = params["embed"][token]
    B = x.shape[0]
    if cfg.rope_style == "none":
        x = x + _sinusoid(pos[None], cfg.d_model).astype(x.dtype)[None]
        cos = sin = None
    else:
        batch = batch_extras or {}
        cos, sin = _rope_for(cfg, batch, B, 1, offset=pos)
    ctx = {"cos": cos, "sin": sin, "pos": pos, "window": cfg.sliding_window,
           "enc": None, "max_len": 0}
    x, _, _, cache = _trunk(params, cfg, x, ctx, "decode", cache)
    with jax.named_scope("model.head"):
        x = layers.norm_apply(params["final_norm"], x)
        return unembed(params, cfg, x), cache


def init_cache(cfg: ModelConfig, B: int, max_len: int) -> PyTree:
    """Zero-initialized decode cache (leaves stacked over groups)."""
    blocks = _stack_cache(cfg, B, max_len, cfg.num_pattern_groups,
                          cfg.layer_kinds())
    if not cfg.first_dense_layers:
        return blocks
    return {"dense": _stack_cache(cfg, B, max_len, cfg.first_dense_layers,
                                  DENSE_KINDS),
            "blocks": blocks}


def _stack_cache(cfg: ModelConfig, B: int, max_len: int, G: int,
                 kinds) -> PyTree:
    S = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    out = {}
    for i, (kind, _) in enumerate(kinds):
        if kind == "attn":
            if cfg.mla:
                m = cfg.mla
                c = {
                    "ckv": jnp.zeros((G, B, max_len, m.kv_lora_rank), cfg.jdtype),
                    "krope": jnp.zeros((G, B, max_len, m.rope_head_dim), cfg.jdtype),
                }
            else:
                c = {
                    "k": jnp.zeros((G, B, S, cfg.num_kv_heads, cfg.hd), cfg.jdtype),
                    "v": jnp.zeros((G, B, S, cfg.num_kv_heads, cfg.hd), cfg.jdtype),
                }
            if cfg.encoder_layers:
                c["cross_k"] = jnp.zeros(
                    (G, B, cfg.encoder_len, cfg.num_kv_heads, cfg.hd), cfg.jdtype)
                c["cross_v"] = jnp.zeros_like(c["cross_k"])
        elif kind == "mamba":
            st = mamba.init_state(cfg, B)
            c = jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, a.dtype), st)
        elif kind == "rwkv":
            st = rwkv6.init_state(cfg, B)
            c = jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, a.dtype), st)
        else:
            raise ValueError(kind)
        out[f"p{i}"] = c
    return out


# ========================================================== param count


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.key(0)
    )
    total = 0
    moe_names = ("w_gate", "w_up", "w_down")
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        n = int(np.prod(leaf.shape))
        if (
            active_only
            and cfg.moe is not None
            and "ffn" in keys
            and keys[-1] in moe_names
            and leaf.ndim == 4  # (G, E, d_in, d_out) stacked routed experts
        ):
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total
