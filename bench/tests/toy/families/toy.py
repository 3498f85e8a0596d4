"""A toy family, for the test that adds a family through new files only
(`bench/tests/test_family.py`): `FedServer`'s `mlr` model on Gaussian
class blobs in 784 dimensions (not the image task), with a plain
reference of its own.

The reference follows the program's round from the same seed: the seed
splits into an init key and a driver key; each round splits the driver
key into (next, selection, batching); every client takes part (the
traffic's cohort is its population); a client's epoch order is an
argsort of uniform draws from the batching key folded with its slot;
tau SGD steps at lr = base_lr * decay**round on the mean softmax cross
entropy; FedAdp's angle weights (arXiv:2012.00661, Eqs. 8-11) over the
f32 deltas.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-7
DIM, CLASSES = 784, 10
HI = jax.lax.Precision.HIGHEST


class Dataset(NamedTuple):
    x: np.ndarray  # (n, DIM) float32
    y: np.ndarray  # (n,) int32


def make_data(traffic: dict):
    """Each client draws its rows from the class blobs of its own label
    set ("all" or a list of classes); the test set from all of them."""
    d = traffic["data"]
    rng = np.random.default_rng(d["seed"])
    means = rng.normal(scale=d["separation"], size=(CLASSES, DIM))

    def draw(n, labels):
        y = rng.choice(labels, size=n).astype(np.int32)
        x = means[y] + rng.normal(size=(n, DIM))
        return Dataset(x.astype(np.float32), y)

    nodes = [draw(d["samples"], np.arange(CLASSES) if c == "all" else c)
             for c in d["clients"]]
    return nodes, draw(d["num_test"], np.arange(CLASSES))


def make_server(repro, fl, traffic: dict, nodes, test, seed: int):
    return repro.FedServer(traffic["model"], fl, nodes, test,
                           batch_size=traffic["batch_size"], seed=seed)


# ------------------------------------------------------------ reference

class Setup(NamedTuple):
    clients: int
    tau: int
    batch: int
    base_lr: float
    lr_decay: float
    alpha: float


def reference_setup(traffic: dict) -> Setup:
    fl = traffic["fl"]
    if fl["clients_per_round"] != fl["num_clients"]:
        raise ValueError("the toy reference follows full participation only")
    return Setup(fl["num_clients"], fl["local_steps"], traffic["batch_size"],
                 fl["base_lr"], fl["lr_decay"], fl["alpha"])


def reference_data(nodes, test):
    return (jnp.asarray(np.stack([n.x for n in nodes])),
            jnp.asarray(np.stack([n.y for n in nodes])),
            jnp.asarray(test.x), jnp.asarray(test.y))


def _loss(p, x, y):
    logits = jnp.matmul(x, p["w"], precision=HI) + p["b"]
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)


@functools.partial(jax.jit, static_argnums=0)
def _round(s: Setup, params, smoothed, rng, rnd, xs, ys):
    rng, _, k_bat = jax.random.split(rng, 3)
    n = xs.shape[1]
    lr = s.base_lr * jnp.float32(s.lr_decay) ** rnd.astype(jnp.float32)

    def client(c, x, y):
        u = jax.random.uniform(jax.random.fold_in(k_bat, c), (n,))
        idx = jnp.argsort(u)[: s.tau * s.batch]
        xb = x[idx].reshape(s.tau, s.batch, DIM)
        yb = y[idx].reshape(s.tau, s.batch)

        def step(p, b):
            val, g = jax.value_and_grad(_loss)(p, *b)
            return jax.tree.map(lambda a, d: a - lr * d, p, g), val

        final, losses = jax.lax.scan(step, params, (xb, yb))
        delta = jnp.concatenate([(final[k] - params[k]).ravel()
                                 for k in sorted(params)])
        return delta, jnp.mean(losses)

    deltas, losses = jax.vmap(client)(jnp.arange(s.clients), xs, ys)
    psi = jnp.full((s.clients,), 1.0 / s.clients)
    g = jnp.matmul(psi, deltas, precision=HI)
    cos = jnp.matmul(deltas, g, precision=HI) / (
        jnp.sqrt(jnp.maximum(jnp.sum(deltas * deltas, axis=1), EPS))
        * jnp.sqrt(jnp.maximum(jnp.sum(g * g), EPS)))
    theta = jnp.arccos(jnp.clip(cos, -1.0 + EPS, 1.0 - EPS))
    t = (rnd + 1).astype(jnp.float32)  # every client took every round
    smoothed = ((t - 1.0) * smoothed + theta) / t
    f = s.alpha * (1.0 - jnp.exp(-jnp.exp(-s.alpha * (smoothed - 1.0))))
    w = jax.nn.softmax(f)  # equal data sizes: log D adds a constant
    agg = jnp.matmul(w, deltas, precision=HI)
    new = {"b": params["b"] + agg[:CLASSES],
           "w": params["w"] + agg[CLASSES:].reshape(DIM, CLASSES)}
    return new, smoothed, rng, jnp.mean(losses), w


def follow(s: Setup, data, seed: int, rounds: int, eval_every: int) -> dict:
    xs, ys, tx, ty = data
    k_init, rng = jax.random.split(jax.random.key(seed))
    params = {"w": jax.random.normal(k_init, (DIM, CLASSES)) * 0.01,
              "b": jnp.zeros((CLASSES,))}
    params0 = jax.device_get(params)
    smoothed = jnp.zeros((s.clients,))
    losses, weights, acc = [], [], {}
    for r in range(rounds):
        params, smoothed, rng, loss, w = _round(s, params, smoothed, rng,
                                                jnp.int32(r), xs, ys)
        losses.append(float(loss))
        weights.append(np.asarray(w))
        if eval_every > 0 and (r + 1) % eval_every == 0:
            pred = jnp.argmax(jnp.matmul(tx, params["w"], precision=HI)
                              + params["b"], axis=-1)
            acc[r + 1] = float(jnp.mean(pred == ty))
    return {"params0": params0, "params": jax.device_get(params),
            "loss": losses, "weights": weights, "acc": acc}


# ---------------------------------------------------------------- counts

def round_flops(config: dict, traffic: dict) -> int:
    """Forward and weight gradient of the DIM x CLASSES product for every
    sample trained (no input gradient: the data needs none)."""
    fl = traffic["fl"]
    samples = fl["clients_per_round"] * fl["local_steps"] * traffic[
        "batch_size"]
    return samples * 4 * DIM * CLASSES


def eval_flops(config: dict, traffic: dict) -> int:
    return traffic["data"]["num_test"] * 2 * DIM * CLASSES


def validate_traffic(traffic: dict) -> None:
    if traffic["fl"]["local_steps"] * traffic["batch_size"] > traffic[
            "data"]["samples"]:
        raise ValueError("a client's tau batches exceed its samples")
