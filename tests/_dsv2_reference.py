"""Plain float32 reference of the DeepSeek-V2-Lite client model and its
cross-silo FedAdp round, for the CPU tests (`tests/test_dsv2.py`).

A copy, kept apart from the program, of the benchmark family's own
reference (`bench/families/moe_lm.py`): straightforward `jax.numpy` at
HIGHEST precision, one layer at a time under `jax.checkpoint`; each held
expert applied densely to every token under its gate mask; the round
streams its silos in two passes, keeping no delta. It follows the
program's key scheme for the init and `core.driver`'s RNG stream for the
batches, so that the two can be compared from one seed. Departures from
the published model (identical in program and reference): RoPE's
half-split layout, kv_b_proj as two matrices, the balance loss added to
the reported loss.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-7


class Numerics(NamedTuple):
    dtype: str
    precision: str


REFERENCE = Numerics("float32", "highest")
CONTROL = Numerics("bfloat16", "default")


class Setup(NamedTuple):
    """What the reference needs of the cell (hashable, for jit)."""
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    router: int
    held: tuple
    top_k: int
    shared: int
    vocab: int
    dense_layers: int
    moe_layers: int
    theta: float
    yarn: tuple  # factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim
    eps: float
    aux_alpha: float
    clients: int
    tau: int
    batch: int
    base_lr: float
    lr_decay: float
    alpha: float
    eval_chunk: int
    numerics: Numerics
    half_batch: bool = False


def _prec(s: Setup):
    return {"highest": jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT}[s.numerics.precision]


def _dense(k, a, b):
    return jax.random.normal(k, (a, b), jnp.float32) * (1.0 / np.sqrt(a))


def _mlp(k, d, f):
    k1, k2, k3 = jax.random.split(k, 3)
    return {"w_gate": _dense(k1, d, f), "w_up": _dense(k2, d, f),
            "w_down": _dense(k3, f, d)}


def _layer(s: Setup, key, moe: bool) -> dict:
    k1, k2, _, _ = jax.random.split(key, 4)
    ks = jax.random.split(k1, 8)
    h = s.heads
    mixer = {"wkv_a": _dense(ks[0], s.d, s.kv_rank + s.rope),
             "kv_norm": {"scale": jnp.ones((s.kv_rank,), jnp.float32)},
             "wk_b": _dense(ks[1], s.kv_rank, h * s.nope),
             "wv_b": _dense(ks[2], s.kv_rank, h * s.v_dim),
             "wo": _dense(ks[3], h * s.v_dim, s.d),
             "wq": _dense(ks[4], s.d, h * (s.nope + s.rope))}
    if moe:
        kr, ke, ksh = jax.random.split(k2, 3)
        ek = jax.random.split(ke, 3)
        held = jnp.asarray(s.held)

        def experts(k, a, b):
            keys = jax.random.split(k, s.router)[held]
            return jnp.stack([_dense(keys[i], a, b)
                              for i in range(len(s.held))])

        ffn = {"router": _dense(kr, s.d, s.router),
               "w_gate": experts(ek[0], s.d, s.expert_ff),
               "w_up": experts(ek[1], s.d, s.expert_ff),
               "w_down": experts(ek[2], s.expert_ff, s.d),
               "shared": _mlp(ksh, s.d, s.shared * s.expert_ff)}
    else:
        ffn = _mlp(k2, s.d, s.dense_ff)
    ones = {"scale": jnp.ones((s.d,), jnp.float32)}
    return {"norm1": ones, "mixer": mixer, "norm2": dict(ones), "ffn": ffn}


def _stack(layers):
    return jax.tree.map(lambda *a: jnp.stack(a), *layers)


@functools.partial(jax.jit, static_argnums=0)
def init_params(s: Setup, key) -> dict:
    ke, kb, kh, _ = jax.random.split(key, 4)
    kd = jax.random.fold_in(jax.random.fold_in(key, 4), 0)
    kb = jax.random.fold_in(kb, 0)
    dt = jnp.dtype(s.numerics.dtype)
    p = {"embed": jax.random.normal(ke, (s.vocab, s.d), jnp.float32) * 0.02,
         "blocks": {"p0": _stack([_layer(s, k, True) for k in
                                  jax.random.split(kb, s.moe_layers)])},
         "final_norm": {"scale": jnp.ones((s.d,), jnp.float32)},
         "lm_head": _dense(kh, s.d, s.vocab)}
    if s.dense_layers:
        p["dense"] = {"p0": _stack([_layer(s, k, False) for k in
                                    jax.random.split(kd, s.dense_layers)])}
    return jax.tree.map(lambda a: a.astype(dt), p)


def yarn_inv_freq(s: Setup) -> np.ndarray:
    factor, orig, fast, slow, _, _ = s.yarn
    dim = s.rope

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(s.theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    base = 1.0 / s.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra_share = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return base / factor * (1 - extra_share) + base * extra_share


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(s: Setup) -> float:
    factor, _, _, _, _, m_all = s.yarn
    return (s.nope + s.rope) ** -0.5 * _mscale(factor, m_all) ** 2


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, sn = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c],
                           axis=-1).astype(x.dtype)


def _attention(s: Setup, p, x, cos, sin):
    prec = _prec(s)
    B, T, _ = x.shape
    mm = functools.partial(jnp.matmul, precision=prec)
    q = mm(x, p["wq"]).reshape(B, T, s.heads, s.nope + s.rope)
    q_nope, q_rope = q[..., :s.nope], _rope(q[..., s.nope:], cos, sin)
    kv = mm(x, p["wkv_a"])
    c = _rms(kv[..., :s.kv_rank], p["kv_norm"]["scale"], s.eps)
    k_rope = _rope(kv[..., s.kv_rank:][:, :, None, :], cos, sin)[:, :, 0]
    k_nope = mm(c, p["wk_b"]).reshape(B, T, s.heads, s.nope)
    v = mm(c, p["wv_b"]).reshape(B, T, s.heads, s.v_dim)
    f32 = jnp.float32
    sc = (jnp.einsum("bthe,bshe->bhts", q_nope.astype(f32), k_nope.astype(f32),
                     precision=prec)
          + jnp.einsum("bthe,bse->bhts", q_rope.astype(f32), k_rope.astype(f32),
                       precision=prec)) * softmax_scale(s)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshe->bthe", probs, v.astype(f32), precision=prec)
    return mm(out.reshape(B, T, s.heads * s.v_dim).astype(x.dtype), p["wo"])


def _swiglu(s: Setup, p, x):
    mm = functools.partial(jnp.matmul, precision=_prec(s))
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def _moe(s: Setup, p, x):
    """(y, balance loss): the held experts' part and the shared experts."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    logits = jnp.matmul(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=_prec(s))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, s.top_k)
    n = jnp.arange(B * T)[:, None]
    gate = jnp.zeros((B * T, s.router), jnp.float32).at[n, idx].set(
        gates)[:, jnp.asarray(s.held)]  # (N, held): 0 where not routed
    prec = _prec(s)
    h = (jax.nn.silu(jnp.einsum("nd,edf->enf", xf, p["w_gate"], precision=prec))
         * jnp.einsum("nd,edf->enf", xf, p["w_up"], precision=prec))
    ye = jnp.einsum("enf,efd->end", h, p["w_down"], precision=prec)
    y = jnp.sum(gate.T[:, :, None] * ye.astype(jnp.float32), axis=0)
    y = y.astype(x.dtype) + _swiglu(s, p["shared"], xf)
    counts = jax.vmap(lambda r: jnp.zeros((s.router,), jnp.float32)
                      .at[r].add(1.0))(idx.reshape(B, T * s.top_k))
    ce = counts / (T * s.top_k / s.router)
    aux = s.aux_alpha * jnp.mean(jnp.sum(
        ce * jnp.mean(probs.reshape(B, T, s.router), axis=1), axis=1))
    return y.reshape(B, T, d), aux


def _cos_sin(s: Setup, T: int):
    ang = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(s)[None]
    factor, _, _, _, m, m_all = s.yarn
    scale = _mscale(factor, m) / _mscale(factor, m_all)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _hidden(s: Setup, params, x):
    """Final-normed hidden states of ids x (B, T), and the balance loss."""
    h = params["embed"][x]
    cos, sin = _cos_sin(s, x.shape[1])

    def layer(h, p, moe):
        h = h + _attention(s, p["mixer"], _rms(h, p["norm1"]["scale"], s.eps),
                           cos, sin)
        hf = _rms(h, p["norm2"]["scale"], s.eps)
        if moe:
            y, aux = _moe(s, p["ffn"], hf)
        else:
            y, aux = _swiglu(s, p["ffn"], hf), jnp.zeros((), jnp.float32)
        return h + y, aux

    aux = jnp.zeros((), jnp.float32)
    for name, moe in (("dense", False), ("blocks", True)):
        if name not in params:
            continue

        def body(carry, p, moe=moe):
            h, a = jax.checkpoint(functools.partial(layer, moe=moe))(
                carry[0], p)
            return (h, carry[1] + a), None

        (h, aux), _ = jax.lax.scan(body, (h, aux), params[name]["p0"])
    return _rms(h, params["final_norm"]["scale"], s.eps), aux


def _nll(s: Setup, params, h, y):
    logits = jnp.matmul(h, params["lm_head"], precision=_prec(s)).astype(
        jnp.float32)
    ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - ll, logits


def loss(s: Setup, params, x, y):
    h, aux = _hidden(s, params, x)
    nll, _ = _nll(s, params, h, y)
    return jnp.mean(nll) + aux


@functools.partial(jax.jit, static_argnums=0)
def local_train(s: Setup, params, xb, yb, lr):
    """tau SGD steps from params; (delta, mean loss)."""
    def step(p, b):
        x, y = b
        if s.half_batch:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        val, g = jax.value_and_grad(lambda q: loss(s, q, x, y))(p)
        return jax.tree.map(lambda a, b: a - lr.astype(a.dtype) * b, p, g), val

    final, losses = jax.lax.scan(step, params, (xb, yb))
    delta = jax.tree.map(lambda a, b: a - b, final, params)
    return delta, jnp.mean(losses.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 4))
def batch_rows(s: Setup, k_bat, c, size, n_max: int):
    """Silo c's rows of the round: the first tau*B of an argsort of
    uniform draws from the batching key folded with its slot."""
    u = jax.random.uniform(jax.random.fold_in(k_bat, c), (n_max,))
    u = jnp.where(jnp.arange(n_max) < size, u, jnp.inf)
    return jnp.argsort(u)[: s.tau * s.batch]


@jax.jit
def _axpy(a, x, y):
    return jax.tree.map(lambda u, v: a * u.astype(jnp.float32) + v, x, y)


@jax.jit
def _stats(d, g):
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    dot = sum(jnp.vdot(a.astype(f32), b, precision=hi)
              for a, b in zip(jax.tree.leaves(d), jax.tree.leaves(g)))
    sq = sum(jnp.vdot(a.astype(f32), a.astype(f32), precision=hi)
             for a in jax.tree.leaves(d))
    sqg = sum(jnp.vdot(b, b, precision=hi) for b in jax.tree.leaves(g))
    return dot, sq, sqg


@jax.jit
def _apply(params, num, den):
    return jax.tree.map(lambda p, n: (p.astype(jnp.float32) + n / den)
                        .astype(p.dtype), params, num)


@functools.partial(jax.jit, static_argnums=0)
def evaluate(s: Setup, params, tx, ty):
    """Next-token accuracy and mean cross-entropy of the held-out set, a
    chunk of sequences at a time."""
    n, t = tx.shape
    xs = tx.reshape(n // s.eval_chunk, s.eval_chunk, t)
    ys = ty.reshape(n // s.eval_chunk, s.eval_chunk, t)

    def one(xy):
        x, y = xy
        h, _ = _hidden(s, params, x)
        nll, logits = _nll(s, params, h, y)
        return (jnp.sum((jnp.argmax(logits, axis=-1) == y).astype(jnp.int32)),
                jnp.sum(nll))

    hits, nll = jax.lax.map(one, (xs, ys))
    return jnp.sum(hits) / (n * t), jnp.sum(nll) / (n * t)


class Data(NamedTuple):
    xs: list  # per silo (n, T) input ids, on the device
    ys: list
    sizes: np.ndarray
    tx: jax.Array
    ty: jax.Array


def reference_data(nodes, test) -> Data:
    return Data([jnp.asarray(n.x) for n in nodes],
                [jnp.asarray(n.y) for n in nodes],
                np.asarray([len(n.y) for n in nodes]),
                jnp.asarray(test.x), jnp.asarray(test.y))


def follow(s: Setup, data: Data, seed: int, rounds: int,
           eval_every: int) -> dict:
    """`rounds` reference rounds from `seed`: the initial and final
    params, the per-round loss and FedAdp weights, and the eval accuracy
    and mean loss by round."""
    k_init, rng = jax.random.split(jax.random.key(seed))
    params = init_params(s, k_init)
    params0 = jax.device_get(params)
    n_max = int(max(data.sizes))
    smoothed = np.zeros(s.clients)
    count = np.zeros(s.clients)
    sizes = data.sizes.astype(np.float64)
    psi = sizes / sizes.sum()
    losses, weights, acc, eval_loss = [], [], {}, {}
    for r in range(rounds):
        rng, _, k_bat = jax.random.split(rng, 3)
        lr = s.base_lr * jnp.float32(s.lr_decay) ** jnp.float32(r)

        def train(c):
            rows = batch_rows(s, k_bat, c, int(data.sizes[c]), n_max)
            xb = data.xs[c][rows].reshape(s.tau, s.batch, -1)
            yb = data.ys[c][rows].reshape(s.tau, s.batch, -1)
            return local_train(s, params, xb, yb, lr)

        # pass 1: g, the FedAvg delta; pass 2 trains each silo again on
        # the same batches (the same delta) for its angle and its share
        # of the weighted sum, so no delta is kept
        g = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
        round_loss = []
        for c in range(s.clients):
            d, val = train(c)
            g = _axpy(jnp.float32(psi[c]), d, g)
            round_loss.append(float(val))
            del d
        num = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
        den, w_round = 0.0, []
        for c in range(s.clients):
            d, _ = train(c)
            dot, sq, sqg = (float(v) for v in _stats(d, g))
            cos = dot / (math.sqrt(max(sq, EPS)) * math.sqrt(max(sqg, EPS)))
            theta = math.acos(min(max(cos, -1 + EPS), 1 - EPS))
            count[c] += 1
            smoothed[c] = ((count[c] - 1) * smoothed[c] + theta) / count[c]
            f = s.alpha * (1 - math.exp(-math.exp(-s.alpha * (smoothed[c] - 1))))
            w = sizes[c] * math.exp(f)
            num = _axpy(jnp.float32(w), d, num)
            den += w
            w_round.append(w)
            del d
        params = _apply(params, num, jnp.float32(den))
        del num, g
        weights.append(np.asarray(w_round) / den)
        losses.append(float(np.mean(round_loss)))
        if eval_every > 0 and (r + 1) % eval_every == 0:
            a, e = evaluate(s, params, data.tx, data.ty)
            acc[r + 1], eval_loss[r + 1] = float(a), float(e)
    return {"params0": params0, "params": jax.device_get(params),
            "loss": losses, "weights": weights, "acc": acc,
            "eval_loss": eval_loss}
