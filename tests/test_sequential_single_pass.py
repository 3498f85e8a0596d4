"""The sequential round trains each client once where its K trained
params fit beside the round's other trees, and twice otherwise: the rule
(`fl.single_pass_fits`) on explicit (K, tree bytes, limit) triples, and
the two schedules against each other from the same state and batches,
on the language-model stand-in (counters on) and on the image models,
with and without an angle keep list. `stale_angles` keeps its one pass
of the second kind, checked against a plain loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import fl, weighting
from repro.data import synthetic

from test_dsv2 import _silos, small_config

# dsv2lite.silo: 535,060,992 f32 params on one v5e chip
CELL_TREE = 2_140_243_968
V5E_LIMIT = 16_909_336_064

SINGLE = 1 << 62  # a device limit under which every test round fits
TWO = None  # no limit reported: the two-pass round


@pytest.mark.parametrize("k,tree_bytes,limit,want", [
    (4, CELL_TREE, V5E_LIMIT, True),
    (8, CELL_TREE, V5E_LIMIT, False),
    (5, CELL_TREE, V5E_LIMIT, False),
    (4, 236_000_000_000 * 4, V5E_LIMIT, False),
    (4, CELL_TREE // 2, V5E_LIMIT, True),
    (1, CELL_TREE, V5E_LIMIT, True),
    (64, 31_400, V5E_LIMIT, True),
    (4, 31_400, None, False),
    (4, 31_400, 0, False),
], ids=["cell", "k8", "k5", "236b", "bf16", "k1", "mlr-k64",
        "no-limit", "zero-limit"])
def test_single_pass_rule(k, tree_bytes, limit, want):
    assert fl.single_pass_fits(k, tree_bytes, limit) is want


def test_rule_is_monotone_in_k_and_bytes():
    fits = [fl.single_pass_fits(k, CELL_TREE, V5E_LIMIT) for k in range(1, 9)]
    assert fits == sorted(fits, reverse=True) and fits[0] and not fits[-1]
    assert not fl.single_pass_fits(4, 2 * CELL_TREE, V5E_LIMIT)


def test_cpu_reports_no_limit():
    """The CPU's devices report no memory limit, so rounds built there
    take the two-pass schedule unless a test forces the limit."""
    assert fl.device_bytes_limit() is None


def _steps(server, rounds):
    return [server.step(eval_every=1) for _ in range(rounds)]


def _assert_same_rounds(one, two, ms1, ms2, tol):
    def close(a, b, err=""):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol, err_msg=err)

    jax.tree.map(close, one.state.params, two.state.params)
    jax.tree.map(close, one.state.prev_delta, two.state.prev_delta)
    close(one.state.angle.smoothed, two.state.angle.smoothed)
    assert (np.asarray(one.state.angle.count).tolist()
            == np.asarray(two.state.angle.count).tolist())
    for m1, m2 in zip(ms1, ms2):
        for key in ("theta", "theta_smoothed", "weights", "loss",
                    "divergence", "accuracy"):
            close(m1[key], m2[key], key)
        assert float(m1["train_passes"]) == 1.0
        assert float(m2["train_passes"]) == 2.0


def test_lm_stand_in_single_pass_equals_two_passes(monkeypatch):
    """Three FedAdp rounds of the MLA + MoE stand-in (4 silos, expert-row
    counters on) from one state and one batch stream: the single pass
    gives the two-pass round's params, `prev_delta`, angle state, angles,
    weights and losses to 1e-6, and counts half its expert rows, in the
    round's metrics and in `History` beside `train_passes`."""
    cfg = small_config()
    nodes, test = _silos(cfg.vocab_size)
    conf = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                          method="fedadp", alpha=5.0, base_lr=0.05,
                          lr_decay=0.995, mode="sequential", engine="tree")
    runs = []
    for limit in (SINGLE, TWO):
        monkeypatch.setattr(fl, "device_bytes_limit", lambda limit=limit: limit)
        s = repro.FedServer(repro.lm_client(cfg, eval_chunk=2), conf, nodes,
                            test, batch_size=2, seed=3_000_000_019)
        runs.append((s, _steps(s, 3)))
    (one, ms1), (two, ms2) = runs
    _assert_same_rounds(one, two, ms1, ms2, 1e-6)
    for m1, m2 in zip(ms1, ms2):
        assert 0 < 2 * int(m1["expert_rows"]) == int(m2["expert_rows"])
    h1, h2 = one.run(1, eval_every=0), two.run(1, eval_every=0)
    assert (h1.train_passes, h2.train_passes) == ([1.0], [2.0])
    assert 0 < 2 * h1.expert_rows[0] == h2.expert_rows[0]


def _image_task(n_nodes=4):
    train, test = synthetic.make_image_task(seed=0, num_train=1500,
                                            num_test=200)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * (n_nodes // 2)
        + [("xclass", 1)] * (n_nodes - n_nodes // 2),
        samples_per_node=100, seed=1)
    return nodes, test


def _no_bias(keys, leaf):
    return not keys[-1].startswith("b")


@pytest.mark.parametrize("keep", [None, _no_bias], ids=["all", "keep-list"])
def test_image_model_single_pass_equals_two_passes(monkeypatch, keep):
    """The same on an image model (MLR), with every leaf in the angle or
    only the weights (`angle_pred`)."""
    nodes, test = _image_task()
    conf = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                          method="fedadp", base_lr=0.05, mode="sequential",
                          engine="tree")
    runs = []
    for limit in (SINGLE, TWO):
        monkeypatch.setattr(fl, "device_bytes_limit", lambda limit=limit: limit)
        s = repro.FedServer("mlr", conf, nodes, test, batch_size=50, seed=5,
                            angle_pred=keep)
        runs.append((s, _steps(s, 3)))
    (one, ms1), (two, ms2) = runs
    _assert_same_rounds(one, two, ms1, ms2, 1e-6)


@pytest.mark.parametrize("mode", ["stepwise", "scanned"])
def test_history_records_train_passes(monkeypatch, mode):
    """`History.train_passes` reads 1.0 a round where the K trained params
    fit and 2.0 where they do not, through either driver; a parallel
    round records none."""
    nodes, test = _image_task()
    conf = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                          method="fedadp", base_lr=0.05, mode="sequential",
                          engine="tree")
    for limit, want in ((SINGLE, 1.0), (TWO, 2.0)):
        monkeypatch.setattr(fl, "device_bytes_limit", lambda limit=limit: limit)
        s = repro.FedServer("mlr", conf, nodes, test, batch_size=50, seed=5)
        assert s.run(2, eval_every=1, mode=mode).train_passes == [want] * 2
    par = repro.FedServer("mlr", repro.FLConfig(
        num_clients=4, clients_per_round=4, local_steps=2), nodes, test,
        batch_size=50, seed=5)
    assert par.run(2, eval_every=1, mode=mode).train_passes == []


def test_angle_keep_list_bites():
    """The keep list changes the angles, so the test above compares two
    different statistics and not one twice."""
    nodes, test = _image_task()
    conf = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                          method="fedadp", base_lr=0.05, mode="sequential",
                          engine="tree")
    th = [repro.FedServer("mlr", conf, nodes, test, batch_size=50, seed=5,
                          angle_pred=keep).step()["theta"]
          for keep in (None, _no_bias)]
    assert not np.allclose(th[0], th[1])


def _toy(K=4, tau=3, B=8, d=12):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(K, tau, B, d)).astype(np.float32)
    Y = np.einsum("ktbd,kde->ktbe", X,
                  rng.normal(size=(K, d, 1)).astype(np.float32))
    params = {"w": jnp.zeros((d, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, loss_fn, (jnp.asarray(X), jnp.asarray(Y))


@pytest.mark.parametrize("k", [1, 3])
def test_single_pass_any_cohort(monkeypatch, k):
    """A cohort of one keeps no stacked client; a cohort of three stacks
    two: both give the two-pass round over three rounds."""
    params, loss_fn, batches = _toy(K=k)
    cfg = fl.FLConfig(num_clients=k, clients_per_round=k, local_steps=3,
                      method="fedadp", mode="sequential", base_lr=0.05)
    sel = jnp.arange(k, dtype=jnp.int32)
    sizes = jnp.arange(1, k + 1, dtype=jnp.float32) * 10.0
    out = []
    for limit in (SINGLE, TWO):
        monkeypatch.setattr(fl, "device_bytes_limit", lambda limit=limit: limit)
        rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
        st = fl.init_round_state(cfg, params)
        for _ in range(3):
            st, m = rf(st, batches, sel, sizes)
        out.append((st, m))
    (s1, m1), (s2, m2) = out
    assert (float(m1["train_passes"]), float(m2["train_passes"])) == (1.0, 2.0)
    for a, b in zip(jax.tree.leaves((s1.params, s1.prev_delta, s1.angle)),
                    jax.tree.leaves((s2.params, s2.prev_delta, s2.angle))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    for key in ("theta", "theta_smoothed", "weights", "loss"):
        np.testing.assert_allclose(np.asarray(m1[key]), np.asarray(m2[key]),
                                   rtol=1e-6, atol=1e-6, err_msg=key)


@pytest.mark.parametrize("limit", [SINGLE, TWO], ids=["fits", "no-limit"])
def test_stale_angles_keep_their_one_pass(monkeypatch, limit):
    """`stale_angles` measures each client's angle against the previous
    round's FedAvg delta and trains once, whatever the device's limit:
    four rounds against a plain loop over the clients."""
    monkeypatch.setattr(fl, "device_bytes_limit", lambda: limit)
    params, loss_fn, batches = _toy()
    K, alpha, lr = 4, 5.0, 0.05
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      method="fedadp", mode="sequential", stale_angles=True,
                      base_lr=lr, alpha=alpha)
    rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
    st = fl.init_round_state(cfg, params)
    sel = jnp.arange(K, dtype=jnp.int32)
    sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])

    p = jax.tree.map(np.asarray, params)
    prev = jax.tree.map(np.zeros_like, p)
    smoothed, count = np.zeros(K), np.zeros(K)
    for r in range(4):
        st, m = rf(st, batches, sel, sizes)
        assert float(m["train_passes"]) == 1.0
        lr_r = np.float32(lr * cfg.lr_decay ** r)
        deltas = [fl.local_update(loss_fn, p, jax.tree.map(lambda a: a[i],
                                                           batches), lr_r)[0]
                  for i in range(K)]
        flat = [np.concatenate([np.ravel(d["b"]), np.ravel(d["w"])])
                for d in deltas]
        g = np.concatenate([np.ravel(prev["b"]), np.ravel(prev["w"])])
        eps = weighting._EPS
        cos = [f @ g / (np.sqrt(max(f @ f, eps)) * np.sqrt(max(g @ g, eps)))
               for f in flat]
        theta = np.arccos(np.clip(cos, -1.0 + eps, 1.0 - eps))
        sm = (count * smoothed + theta) / (count + 1)
        smoothed, count = sm, count + 1
        w = np.asarray(weighting.fedadp_weights(jnp.asarray(sm, jnp.float32),
                                                sizes, alpha))
        psi = np.asarray(sizes) / np.sum(np.asarray(sizes))
        p = jax.tree.map(
            lambda q, *ds: q + sum(wi * np.asarray(d) for wi, d in zip(w, ds)),
            p, *deltas)
        prev = jax.tree.map(
            lambda *ds: sum(si * np.asarray(d) for si, d in zip(psi, ds)),
            *deltas)
        np.testing.assert_allclose(np.asarray(m["weights"]), w, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(m["theta_smoothed"]), sm,
                                   rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), b, rtol=1e-5, atol=1e-6), st.params, p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), b, rtol=1e-5, atol=1e-6), st.prev_delta, prev)
