"""Fused causal attention Pallas kernel (flash-attention, TPU target).

§Perf identified the f32 (B, H, T, T) score tensor as the dominant HBM
term for every dense arch's train/prefill: XLA materializes scores and
probs to HBM. This kernel computes one (blk_q x T) stripe at a time with
an online softmax — scores/probs never leave VMEM.

Tiling: grid = (B*H, T/blk_q). Per step the kernel holds
  q     (blk_q, d)        — 64 KiB at blk_q=128, d=128, f32
  k, v  (T, d) each       — 2 MiB at T=4096 (streamed blk_k-wise in-loop)
  acc/m/l + p (blk_q, blk_k)
comfortably inside the ~16 MiB v5e VMEM for T <= 8k; longer sequences
want a 3-D grid streaming K/V from HBM (left as the documented next step —
the q-chunked jnp path in models/attention.py already covers that regime).

Validated against ref.flash_attention (pure jnp) in interpret mode.

`causal_attention` is the other entry: causal attention fused forward AND
backward, with separate q/k and v head dims and any softmax scale (MLA's
train, eval and prefill core). It runs JAX's splash attention kernels
under a causal mask: scores and probabilities stay in VMEM in the
forward, the dkv and dq kernels alike, and tiles above the diagonal are
skipped.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, blk_k: int, scale: float,
            causal: bool):
    blk_q, d = q_ref.shape[1], q_ref.shape[2]
    T = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32) * scale
    q_pos = pl.program_id(1) * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0
    )

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * blk_k, blk_k), :].astype(jnp.float32)
        s = q @ k.T  # (blk_q, blk_k)
        if causal:
            k_pos = i * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + p @ v
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((blk_q, d), jnp.float32)
    m0 = jnp.full((blk_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, T // blk_k, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, blk_q: int = 128, blk_k: int = 128,
                    interpret=None):
    """q/k/v: (BH, T, d) (heads pre-flattened; GQA callers repeat kv).

    Returns (BH, T, d) in q's dtype. T must divide by blk_q and blk_k.

    Differentiable via custom_vjp: the forward is the fused Pallas kernel;
    the backward recomputes scores with the standard jnp formulation (a
    fused flash BACKWARD kernel is the documented next step — the forward
    is where the (T x T) HBM materialization hurts prefill/serving).
    """
    return _flash_fwd_impl(q, k, v, causal, blk_q, blk_k, interpret)


def _flash_fwd_impl(q, k, v, causal, blk_q, blk_k, interpret):
    BH, T, d = q.shape
    assert k.shape == v.shape == (BH, T, d)
    assert T % blk_q == 0 and T % blk_k == 0
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_kernel, blk_k=blk_k, scale=scale, causal=causal)
    return pl.pallas_call(
        kern,
        grid=(BH, T // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(q, k, v)


def _flash_fwd(q, k, v, causal, blk_q, blk_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, blk_q, blk_k, interpret), (q, k, v)


def _flash_bwd(causal, blk_q, blk_k, interpret, res, do):
    q, k, v = res
    T = q.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bts,btd->bsd", p, dof)
    dp = jnp.einsum("btd,bsd->bts", dof, v.astype(jnp.float32))
    ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True)) * scale
    dq = jnp.einsum("bts,bsd->btd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bts,btd->bsd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def gqa_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              interpret=None, blk_q: int = 128, blk_k: int = 128):
    """GQA convenience wrapper: q (B,T,H,hd), k/v (B,T,G,hd) -> (B,T,H,hd)."""
    B, T, H, hd = q.shape
    G = k.shape[2]
    rep = H // G
    kx = jnp.repeat(k, rep, axis=2)
    vx = jnp.repeat(v, rep, axis=2)

    def flat(t):
        return jnp.moveaxis(t, 2, 1).reshape(B * H, T, hd)

    o = flash_attention(flat(q), flat(kx), flat(vx), causal, blk_q, blk_k,
                        interpret)
    return jnp.moveaxis(o.reshape(B, H, T, hd), 1, 2)


# (q, kv) tiles of the causal kernel's forward and of its backward's dkv
# and dq kernels, swept on a v5e at T = 2,048; a sequence takes the
# largest tile of each that divides it. (Splash's fused backward, which
# computes dq inside the dkv kernel, was faster there but writes each key
# tile's share of dq in bfloat16 and sums the shares after: a rounding
# the einsum core does not make.)
CAUSAL_TILES = {"fwd": (512, 512), "dkv": (1024, 1024), "dq": (1024, 1024)}
LANES = 128  # keys lie on the lanes of a score tile


def causal_blocks(T: int) -> splash.BlockSizes | None:
    """The causal kernel's tiles at sequence length T, or None where T is
    not a multiple of 128, which the kernel cannot tile."""
    if T % LANES:
        return None
    (fq, fk), (kq, kk), (dq, dk) = (
        tuple(math.gcd(T, b) for b in CAUSAL_TILES[n])
        for n in ("fwd", "dkv", "dq"))
    return splash.BlockSizes(
        block_q=fq, block_kv=fk, block_kv_compute=fk,
        block_q_dkv=kq, block_kv_dkv=kk, block_kv_dkv_compute=kk,
        block_q_dq=dq, block_kv_dq=dk)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                     *, interpret=None) -> jax.Array:
    """softmax(scale * q k^T, causal) v, fused forward and backward.

    q, k: (B, H, T, d_qk); v: (B, H, T, d_v); returns (B, H, T, d_v) in
    q's dtype. T must be a multiple of 128 (`causal_blocks`). q is scaled
    in its own dtype; then q, k and v enter the products as XLA's default
    precision takes an f32 product's on this backend (bfloat16 on a TPU,
    the inputs' dtype elsewhere). Products accumulate in f32, and the
    running max, sum and logsumexp are f32. The backward's dkv and dq
    kernels recompute the probabilities tile by tile from the forward's
    logsumexp and accumulate dk, dv and dq in f32. The output and dq, dk,
    dv leave the kernels in the operands' dtype (bfloat16 on a TPU) and
    are cast back to q's, k's and v's: one rounding of each finished
    value, per head.
    """
    B, H, T, _ = q.shape
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else q.dtype
    mask = splash.MultiHeadMask([splash.CausalMask((T, T))] * H)
    kernel = splash.make_splash_mha(
        mask, block_sizes=causal_blocks(T), head_shards=1, q_seq_shards=1,
        interpret=interpret_mode(interpret))
    out = jax.vmap(kernel)((q * scale).astype(dtype), k.astype(dtype),
                           v.astype(dtype))
    return out.astype(q.dtype)
