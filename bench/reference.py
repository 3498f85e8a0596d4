"""Plain reference of the federated round that the cells run.

Written from the FedAdp paper (arXiv:2012.00661, Eqs. 1 and 8-11) and the
round's documented semantics, in straightforward `jax.numpy`, with no
kernels and nothing imported from the program:

* the same RNG stream as the round driver: the seed splits into an init
  key and a driver key; each round splits the driver key into (next,
  selection, batching); a cohort below the population is the first K of
  a permutation; each client's epoch order is an argsort of uniform
  draws from the batching key folded with its population slot;
* local training: tau steps of plain SGD at lr = base_lr * decay**round
  on the mean softmax cross-entropy; the client's delta is its final
  params minus its starting params;
* the server->client broadcast: int8 per-16384-chunk symmetric
  quantization of the raveled params (of their difference to the
  previous broadcast when delta-encoded; the clients then train from the
  accumulated chain);
* the client uplink: the deltas raveled in parameter-name order into a
  (K, N) buffer, quantized symmetrically (int4 per `group_size` values,
  int8 per 16384) and dequantized;
* FedAdp: g = sum_i psi_i d_i with psi_i = D_i / sum D; theta_i =
  arccos of the clipped cosine of d_i and g; Eq. 9's running mean per
  client; Gompertz weights softmax(f(theta~) + log D); the server adds
  sum_i w_i d_i to its own, uncompressed params;
* test accuracy: the share of test images whose argmax is the label.

`Numerics` sets the dtype of params, data and training, and the matmul
precision. `REFERENCE` is float32 at HIGHEST; `CONTROL` is bfloat16, the
step below the float32 that the configurations state, which the check
must refuse. `half_batch` plants a fault: each local step takes the mean
loss over the first half of its minibatch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-7
CHUNK = 128 * 128


class Numerics(NamedTuple):
    dtype: str  # params, data, activations and updates
    precision: str  # matmul / convolution precision


REFERENCE = Numerics("float32", "highest")
CONTROL = Numerics("bfloat16", "default")


class RefState(NamedTuple):
    params: dict
    smoothed: jax.Array  # (population,) Eq. 9 running mean of theta
    count: jax.Array  # (population,) rounds each client took part in
    rng: jax.Array
    round: jax.Array
    head: jax.Array  # (N,) the clients' broadcast reconstruction


# ------------------------------------------------------------------ models


def init_params(model: str, key) -> dict:
    """The models' initialisation: N(0, 1) scaled by 1/sqrt(fan-in) for
    the CNN's kernels, by 0.01 for MLR's weights; zero biases."""
    if model == "mlr":
        return {"w": jax.random.normal(key, (784, 10), jnp.float32) * 0.01,
                "b": jnp.zeros((10,), jnp.float32)}
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv(k, cin, cout):
        return (jax.random.normal(k, (5, 5, cin, cout), jnp.float32)
                * (1.0 / jnp.sqrt(5 * 5 * cin)))

    def fc(k, a, b):
        return jax.random.normal(k, (a, b), jnp.float32) / jnp.sqrt(a)

    return {"c1": conv(k1, 1, 32), "b1": jnp.zeros((32,)),
            "c2": conv(k2, 32, 64), "b2": jnp.zeros((64,)),
            "f1": fc(k3, 3136, 512), "fb1": jnp.zeros((512,)),
            "f2": fc(k4, 512, 10), "fb2": jnp.zeros((10,))}


def apply(model: str, params: dict, x, precision) -> jax.Array:
    if model == "mlr":
        return (jnp.matmul(x.reshape(x.shape[0], -1), params["w"],
                           precision=precision) + params["b"])

    def conv(h, w, b):
        return jax.lax.conv_general_dilated(
            h, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + b

    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    h = pool(jax.nn.relu(conv(x, params["c1"], params["b1"])))
    h = pool(jax.nn.relu(conv(h, params["c2"], params["b2"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.matmul(h, params["f1"], precision=precision)
                    + params["fb1"])
    return jnp.matmul(h, params["f2"], precision=precision) + params["fb2"]


def loss(model, params, x, y, precision):
    logits = apply(model, params, x, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


# ------------------------------------------------------------- the wires


def ravel(tree: dict, lead: int = 0) -> jax.Array:
    """Leaves in parameter-name order, concatenated as f32 (the first
    `lead` axes kept)."""
    return jnp.concatenate(
        [tree[k].reshape(tree[k].shape[:lead] + (-1,)).astype(jnp.float32)
         for k in sorted(tree)], axis=lead)


def unravel(vec: jax.Array, like: dict) -> dict:
    out, at = {}, 0
    for k in sorted(like):
        size = like[k].size
        out[k] = vec[at:at + size].reshape(like[k].shape).astype(like[k].dtype)
        at += size
    return out


def quantize_roundtrip(x: jax.Array, bits: int, group: int) -> jax.Array:
    """Symmetric quantization of each row of x (k, n) in groups of `group`
    values: scale = absmax / (2**(bits-1) - 1) (1 for an all-zero group),
    q = clip(round(x / scale)), returned as q * scale."""
    k, n = x.shape
    g = -(-n // group)
    xg = jnp.pad(x, ((0, 0), (0, g * group - n))).reshape(k, g, group)
    top = 2 ** (bits - 1) - 1
    absmax = jnp.max(jnp.abs(xg), axis=2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / top, 1.0)
    q = jnp.clip(jnp.round(xg / scale), -top, top)
    return (q * scale).reshape(k, g * group)[:, :n]


def wire_roundtrip(x: jax.Array, wire: str, group_size: int) -> jax.Array:
    if wire == "f32":
        return x
    if wire == "int8":
        return quantize_roundtrip(x, 8, CHUNK)
    if wire == "int4":
        return quantize_roundtrip(x, 4, group_size)
    raise ValueError(f"no reference for wire {wire!r}")


# ------------------------------------------------------------- the round


class Setup(NamedTuple):
    """What the reference needs of the cell (hashable, for jit)."""
    model: str
    population: int
    cohort: int
    tau: int
    batch: int
    base_lr: float
    lr_decay: float
    alpha: float
    transport: str
    group_size: int
    downlink: str
    downlink_delta: bool
    numerics: Numerics
    half_batch: bool = False


def setup_from(traffic: dict, numerics: Numerics = REFERENCE,
               half_batch: bool = False) -> Setup:
    fl = traffic["fl"]
    return Setup(
        model=traffic["model"], population=fl["num_clients"],
        cohort=fl["clients_per_round"], tau=fl["local_steps"],
        batch=traffic["batch_size"], base_lr=fl["base_lr"],
        lr_decay=fl.get("lr_decay", 0.995), alpha=fl.get("alpha", 5.0),
        transport=fl.get("transport", "f32"),
        group_size=fl.get("group_size", 512),
        downlink=fl.get("downlink", "f32"),
        downlink_delta=fl.get("downlink_delta", False),
        numerics=numerics, half_batch=half_batch)


def _precision(s: Setup):
    return {"highest": jax.lax.Precision.HIGHEST,
            "default": jax.lax.Precision.DEFAULT}[s.numerics.precision]


def init_state(s: Setup, seed: int) -> RefState:
    k_init, k_drv = jax.random.split(jax.random.key(seed))
    dt = jnp.dtype(s.numerics.dtype)
    params = jax.tree.map(lambda a: a.astype(dt), init_params(s.model, k_init))
    n = sum(a.size for a in params.values())
    return RefState(params, jnp.zeros((s.population,), jnp.float32),
                    jnp.zeros((s.population,), jnp.int32), k_drv,
                    jnp.int32(0), jnp.zeros((n,), jnp.float32))


def _local(s: Setup, params, xb, yb, lr):
    prec = _precision(s)

    def step(p, b):
        x, y = b
        if s.half_batch:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        val, g = jax.value_and_grad(
            lambda q: loss(s.model, q, x, y, prec))(p)
        return {k: p[k] - lr.astype(p[k].dtype) * g[k] for k in p}, val

    final, losses = jax.lax.scan(step, params, (xb, yb))
    delta = {k: final[k] - params[k] for k in params}
    return delta, jnp.mean(losses.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=0)
def round_step(s: Setup, state: RefState, xs, ys, sizes):
    """One round; xs (population, n_max, ...) and ys (population, n_max)
    are the clients' data, `sizes` their true row counts."""
    dt = jnp.dtype(s.numerics.dtype)
    hi = jax.lax.Precision.HIGHEST
    rng, k_sel, k_bat = jax.random.split(state.rng, 3)
    if s.cohort >= s.population:
        sel = jnp.arange(s.population, dtype=jnp.int32)
    else:
        sel = jax.random.permutation(k_sel, s.population)[:s.cohort]
        sel = sel.astype(jnp.int32)
    n_max = xs.shape[1]
    count = s.tau * s.batch

    def batches(c):
        u = jax.random.uniform(jax.random.fold_in(k_bat, c), (n_max,))
        u = jnp.where(jnp.arange(n_max) < sizes[c], u, jnp.inf)
        idx = jnp.argsort(u)[:count]
        xb = xs[c][idx].reshape((s.tau, s.batch) + xs.shape[2:])
        return xb.astype(dt), ys[c][idx].reshape(s.tau, s.batch)

    xb, yb = jax.vmap(batches)(sel)
    lr = s.base_lr * jnp.float32(s.lr_decay) ** state.round.astype(jnp.float32)

    head = state.head
    start = state.params
    if s.downlink != "f32":
        vec = ravel(state.params)
        if s.downlink_delta:
            recon = wire_roundtrip((vec - head)[None], s.downlink, 0)[0]
            head = head + recon
            vec = head
        else:
            vec = wire_roundtrip(vec[None], s.downlink, 0)[0]
        start = unravel(vec, state.params)

    deltas, losses = jax.vmap(lambda x, y: _local(s, start, x, y, lr))(xb, yb)
    wire = wire_roundtrip(ravel(deltas, lead=1), s.transport, s.group_size)

    d = sizes[sel].astype(jnp.float32)
    psi = d / jnp.sum(d)
    g = jnp.matmul(psi, wire, precision=hi)
    dots = jnp.matmul(wire, g, precision=hi)
    sqs = jnp.sum(wire * wire, axis=1)
    sqg = jnp.sum(g * g)
    cos = dots / (jnp.sqrt(jnp.maximum(sqs, EPS)) * jnp.sqrt(jnp.maximum(sqg, EPS)))
    theta = jnp.arccos(jnp.clip(cos, -1.0 + EPS, 1.0 - EPS))

    count_new = state.count.at[sel].add(1)
    t = count_new[sel].astype(jnp.float32)
    sm_sel = ((t - 1.0) * state.smoothed[sel] + theta) / t
    smoothed = state.smoothed.at[sel].set(sm_sel)
    f = s.alpha * (1.0 - jnp.exp(-jnp.exp(-s.alpha * (sm_sel - 1.0))))
    w = jax.nn.softmax(f + jnp.log(d))
    agg = jnp.matmul(w, wire, precision=hi)
    upd = unravel(agg, state.params)
    params = {k: state.params[k] + upd[k] for k in state.params}
    new = RefState(params, smoothed, count_new, rng, state.round + 1, head)
    return new, {"loss": jnp.mean(losses), "weights": w, "theta": theta}


@functools.partial(jax.jit, static_argnums=0)
def accuracy(s: Setup, params, tx, ty):
    prec = _precision(s)
    dt = jnp.dtype(s.numerics.dtype)
    n = tx.shape[0]
    chunk = max(c for c in range(1, min(n, 2000) + 1) if n % c == 0)
    m = n // chunk
    xs = tx.reshape((m, chunk) + tx.shape[1:])
    ys = ty.reshape(m, chunk)

    def one(xy):
        x, y = xy
        pred = jnp.argmax(apply(s.model, params, x.astype(dt), prec), axis=-1)
        return jnp.sum((pred == y).astype(jnp.int32))

    return jnp.sum(jax.lax.map(one, (xs, ys))) / tx.shape[0]


class Data(NamedTuple):
    xs: jax.Array
    ys: jax.Array
    sizes: jax.Array
    tx: jax.Array
    ty: jax.Array


def device_data(nodes, test) -> Data:
    """The clients' data stacked (all nodes of a cell hold the same row
    count) and the test set, on the default device."""
    return Data(jnp.asarray(np.stack([n.x for n in nodes])),
                jnp.asarray(np.stack([n.y for n in nodes])),
                jnp.asarray([len(n.y) for n in nodes], jnp.int32),
                jnp.asarray(test.x), jnp.asarray(test.y))


def follow(s: Setup, data: Data, seed: int, rounds: int, eval_every: int,
           state: Optional[RefState] = None):
    """Run `rounds` reference rounds from `seed`. Returns the initial
    params, the final params and per-round host readings: loss, weights,
    and the test accuracy after each round where round % eval_every == 0
    (keyed by round)."""
    state = init_state(s, seed) if state is None else state
    params0 = jax.device_get(state.params)
    losses, weights, acc = [], [], {}
    for _ in range(rounds):
        state, m = round_step(s, state, data.xs, data.ys, data.sizes)
        r = int(state.round)
        losses.append(float(m["loss"]))
        weights.append(np.asarray(m["weights"]))
        if eval_every > 0 and r % eval_every == 0:
            acc[r] = float(accuracy(s, state.params, data.tx, data.ty))
    return {"params0": params0, "params": jax.device_get(state.params),
            "loss": losses, "weights": weights, "acc": acc}
