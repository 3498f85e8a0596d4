"""The client-batched local update equals the vmapped one.

`fl.local_update_clients` over the CNN's client-batched loss
(`small.cnn_apply_clients`) must give every client the deltas and mean
loss that `jax.vmap(fl.local_update)` over `small.cnn_apply` gives it,
and a `FedServer("cnn", ...)` run, which takes the batched path, must
follow the trajectory of the same run forced down the vmap path. All
products run at HIGHEST precision, so the two formulations differ only
in summation order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import fl
from repro.data import synthetic
from repro.models import small

TOL = 1e-5
B, TAU, LR = 8, 2, 0.05


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _loss(p, b):
    return small.classification_loss(small.cnn_apply, p, *b)


def _clients_loss(p, b):
    return small.classification_loss(small.cnn_apply_clients, p, *b)


def _batches(k, seed=1):
    kx, ky = jax.random.split(jax.random.key(seed))
    x = jax.random.uniform(kx, (k, TAU, B, 28, 28, 1))
    y = jax.random.randint(ky, (k, TAU, B), 0, 10)
    return x, y


def _stacked(k, differ):
    """K copies of one CNN, or (differ) K CNNs from their own keys."""
    if differ:
        return jax.vmap(small.cnn_init)(jax.random.split(jax.random.key(0), k))
    p = small.cnn_init(jax.random.key(0))
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (k, *a.shape)), p)


def _close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=TOL, atol=TOL)


@pytest.mark.parametrize("k,prox_mu,differ", [
    (1, 0.0, False), (3, 0.0, False), (10, 0.0, False),
    (3, 0.1, False), (2, 0.0, True),
], ids=["k1", "k3", "k10", "k3-prox", "k2-params-differ"])
def test_batched_local_update_equals_vmap(k, prox_mu, differ):
    params = _stacked(k, differ)
    batches = _batches(k)
    want = jax.jit(jax.vmap(
        lambda p, b: fl.local_update(_loss, p, b, LR, prox_mu)))(
            params, batches)
    got = jax.jit(lambda p, b: fl.local_update_clients(
        _clients_loss, p, b, LR, prox_mu))(params, batches)
    assert got[1].shape == (k,)
    _close(got, want)


def test_clients_apply_is_per_client_apply():
    """Logit [:, k] is `cnn_apply` of client k's params on its images."""
    k = 3
    params = _stacked(k, differ=True)
    x = _batches(k)[0][:, 0]  # (K, B, 28, 28, 1)
    got = small.cnn_apply_clients(params, jnp.swapaxes(x, 0, 1))
    want = jax.vmap(small.cnn_apply)(params, x)
    assert got.shape == (B, k, 10)
    _close(jnp.swapaxes(got, 0, 1), want)


def _cnn_server(engine):
    train, test = synthetic.make_image_task(seed=0, num_train=1500,
                                            num_test=200)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * 2 + [("xclass", 1)] * 2,
        samples_per_node=40, seed=1)
    cfg = repro.FLConfig(num_clients=4, clients_per_round=3, local_steps=2,
                         method="fedadp", base_lr=0.05, engine=engine)
    return repro.FedServer("cnn", cfg, nodes, test, batch_size=20, seed=0)


@pytest.mark.parametrize("engine", ["tree", "flat"])
def test_cnn_server_batched_run_equals_vmap_run(engine, monkeypatch):
    """Two scanned rounds through each engine: the batched path's
    losses, weights, accuracies and params match the vmap path's."""
    runs = {}
    for path in ("batched", "vmap"):
        if path == "vmap":
            monkeypatch.delitem(small.CLIENTS_APPLY, "cnn")
        s = _cnn_server(engine)
        assert s.local_update == path
        h = s.run(2, eval_every=1, mode="scanned", block=2)
        runs[path] = (h.loss, h.weights, h.accuracy, s.params)
    assert len(runs["batched"][0]) == 2
    _close(runs["batched"], runs["vmap"])
