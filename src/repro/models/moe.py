"""Mixture-of-Experts FFN (DeepSeek-V2 style: shared + routed, top-k).

Expert weights are stacked on a leading expert axis so the tensor-
parallel mesh axis shards *experts* (expert parallelism). Two dispatches,
picked by what the layer holds:

* all experts (`MoEConfig.held` empty): sort-based with a fixed
  per-expert capacity (drop-on-overflow), so compiled FLOPs track
  *activated* parameters (E·C ≈ tokens·top_k·cap): tokens are argsorted
  by expert id, packed into an (E, C, d) buffer, run through a
  stacked-expert grouped matmul, and combined back with their gate
  weights. Plain einsums: it vmaps over clients and shards over a mesh.
* experts named by id (`MoEConfig.held`, one chip's share of an
  expert-parallel deployment, or all of them): dropless. Every
  token-expert pair bound for an expert this layer holds is computed,
  at any imbalance. The pairs are sorted by the held
  expert they go to (pairs bound for experts held elsewhere last) into a
  static (N·top_k, d) buffer, and the grouped products (Pallas
  megablox `gmm`, one bf16 pass with f32 accumulation, as XLA's default
  precision takes an f32 product) run over the per-expert row counts:
  their grid visits only the row tiles of the held experts' groups, so
  their work follows the rows actually routed here (`jax.lax.ragged_dot`
  computed every row of the buffer on a v5e). Rows past the groups are
  zeroed on the way in and out. The layer routes over all
  `num_experts` and returns its own experts' part of the output plus
  the shared experts'; on one chip it runs without the exchange.

`moe_apply` returns the rows that went through the held experts'
products beside the output and the balance loss, the counter the
benchmark's expert roofline reads.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.models import layers

# the kernels' module (the package's own `gmm` is the wrapped function)
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

GMM_SCOPE = "model.moe_gmm"  # the grouped products alone
GMM_TILE = (512, 512, 512)  # row, contraction and output tiles


def _tiling(m: int, k: int, n: int):
    return (math.gcd(m, GMM_TILE[0]), min(k, GMM_TILE[1]),
            min(n, GMM_TILE[2]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, sizes, interpret, dtype):
    return megablox.gmm(x.astype(dtype), w.astype(dtype), sizes,
                        jnp.float32, _tiling, interpret=interpret)


def _gmm_fwd(x, w, sizes, interpret, dtype):
    xb, wb = x.astype(dtype), w.astype(dtype)
    out = megablox.gmm(xb, wb, sizes, jnp.float32, _tiling,
                       interpret=interpret)
    return out, (xb, wb, sizes)


def _gmm_bwd(interpret, dtype, res, ct):
    xb, wb, sizes = res
    cb = ct.astype(dtype)
    dx = megablox.gmm(cb, wb, sizes, jnp.float32, _tiling,
                      transpose_rhs=True, interpret=interpret)
    dw = megablox.tgmm(xb.swapaxes(0, 1), cb, sizes, jnp.float32, _tiling,
                       num_actual_groups=wb.shape[0], interpret=interpret)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped(x, w, sizes):
    """Grouped product: rows of x (m, a), in groups of `sizes`
    consecutive rows, times their group's matrix of w (G, a, b), f32;
    rows past the groups are left undefined. Operands and cotangents
    enter the products as XLA's default precision takes an f32 product's
    on this backend (bfloat16 on a TPU), accumulation and results are
    f32."""
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else x.dtype
    return _gmm(x, w, sizes, interpret_mode(None), dtype)


def moe_init(key, cfg) -> dict:
    m = cfg.moe
    d, dt = cfg.d_model, cfg.jdtype
    kr, ke, ks = jax.random.split(key, 3)
    ek = jax.random.split(ke, 3)
    f = m.d_ff_expert

    def stacked(k, a, b):
        # expert e's weights come from key e of all `num_experts`, so a
        # share holds exactly what the uncut layer holds for its ids
        kk = jax.random.split(k, m.num_experts)
        if m.held:
            kk = kk[jnp.asarray(m.held)]
        return jax.vmap(lambda q: layers.dense_init(q, a, b, dt))(kk)

    p = {
        "router": layers.dense_init(kr, d, m.num_experts, jnp.float32),
        "w_gate": stacked(ek[0], d, f),
        "w_up": stacked(ek[1], d, f),
        "w_down": stacked(ek[2], f, d),
    }
    if m.num_shared:
        p["shared"] = layers.mlp_init(ks, d, m.num_shared * f, "swiglu", dt)
    return p


def _capacity(num_tokens: int, m) -> int:
    c = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(p, m, xf):
    """Router probabilities over all `num_experts`, and each token's
    top-k gate weights and expert ids."""
    x32 = xf.astype(jnp.float32)
    if m.router_precision == "highest":
        logits = jnp.matmul(x32, p["router"],
                            precision=jax.lax.Precision.HIGHEST)
    else:
        logits = x32 @ p["router"]  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)  # (N, k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True),
                                    1e-9)
    if m.routed_scaling_factor != 1.0:
        gates = gates * m.routed_scaling_factor
    return probs, gates, eidx


def _balance_loss(m, probs, eidx, B: int, T: int):
    """The load-balance loss over the router's `num_experts` outputs."""
    E, k = m.num_experts, m.top_k
    if m.aux_loss == "seq":
        counts = jnp.zeros((B, E), jnp.float32).at[
            jnp.arange(B)[:, None], eidx.reshape(B, T * k)].add(1.0)
        ce = counts / (T * k / E)
        p_mean = jnp.mean(probs.reshape(B, T, E), axis=1)
        return m.aux_loss_weight * jnp.mean(jnp.sum(ce * p_mean, axis=1))
    f_e = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (
        B * T * k)
    p_e = jnp.mean(probs, axis=0)
    return m.aux_loss_weight * E * jnp.sum(f_e * p_e)


def _capacity_experts(p, m, xf, gates, eidx):
    N, d = xf.shape
    C = _capacity(N, m)
    E = m.num_experts
    flat_e = eidx.reshape(-1)  # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N), m.top_k)
    flat_gate = gates.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]
    pos_in_e = jnp.arange(N * m.top_k) - jnp.searchsorted(se, se, side="left")
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)  # overflow row dropped

    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[stok])
    h = buf[: E * C].reshape(E, C, d)
    # grouped swiglu over stacked experts
    with jax.named_scope(GMM_SCOPE):
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
        u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
        ye = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"]).reshape(E * C, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)

    contrib = ye[slot] * (sgate * keep).astype(ye.dtype)[:, None]
    acc_dt = jnp.dtype(m.combine_dtype)
    y = jnp.zeros((N, d), acc_dt).at[stok].add(contrib.astype(acc_dt))
    return y.astype(xf.dtype), jnp.sum(keep.astype(jnp.int32))


def _dropless_experts(p, m, xf, gates, eidx):
    """The held experts' part of the output, every pair computed."""
    N, d = xf.shape
    k = m.top_k
    H = len(m.held)
    # each routed id's slot among the held experts; H for one held elsewhere
    local = jnp.full((m.num_experts,), H, jnp.int32).at[
        jnp.asarray(m.held)].set(jnp.arange(H, dtype=jnp.int32))
    flat_l = local[eidx].reshape(-1)  # (N*k,)
    order = jnp.argsort(flat_l, stable=True)
    sizes = jnp.zeros((H,), jnp.int32).at[flat_l].add(1, mode="drop")
    rows = jnp.sum(sizes)
    # rows past the held experts' groups are not computed: keep them
    # zero, and their gradients too
    valid = (jnp.arange(N * k) < rows)[:, None]
    xs = jnp.where(valid, xf[order // k], 0.0)  # sorted by held expert
    with jax.named_scope(GMM_SCOPE):
        g = _grouped(xs, p["w_gate"], sizes)
        u = _grouped(xs, p["w_up"], sizes)
        h = jnp.where(valid, jax.nn.silu(g) * u, 0.0)
        ye = jnp.where(valid, _grouped(h, p["w_down"], sizes), 0.0)
    pair_rows = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * k, dtype=order.dtype))
    yk = ye[pair_rows].reshape(N, k, d).astype(jnp.float32)
    gk = jnp.where(flat_l.reshape(N, k) < H, gates, 0.0)
    y = jnp.sum(gk[..., None] * yk, axis=1)
    return y.astype(xf.dtype), rows


def moe_apply(p: dict, cfg, x: jax.Array):
    """x (B, T, d) -> (y, aux_loss, rows): `rows` counts the token-expert
    pairs that went through the held experts' products. Also handles
    (B, 1, d) decode."""
    m = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    probs, gates, eidx = _route(p, m, xf)
    if m.held:
        y, rows = _dropless_experts(p, m, xf, gates, eidx)
    else:
        y, rows = _capacity_experts(p, m, xf, gates, eidx)
    if "shared" in p:
        y = y + layers.mlp_apply(p["shared"], xf, "swiglu")
    aux = _balance_loss(m, probs, eidx, B, T)
    return y.reshape(B, T, d), aux, rows
