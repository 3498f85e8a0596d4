"""Device-resident driver tests: the scanned == stepwise contract, the
device data pipeline, partial participation across all three engines, and
the delta-encoded downlink.

The central pin: `FedServer.run_scanned` (chunked `lax.scan` over rounds)
and `FedServer.run` (one jit dispatch per round) share ONE compiled step
— selection, per-client epoch batching, the round, and the eval all run
from the device RNG inside it — so R scanned rounds must reproduce R
stepwise rounds to 1e-5, under full participation AND subset selection,
including the early-exit/rounds-to-target bookkeeping.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import transport
from repro.core import driver, fl
from repro.core.server import FedServer, _epoch_batcher
from repro.data import synthetic
from repro.data.synthetic import Dataset
from repro.launch.mesh import make_mesh

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "convergence.json")


def _small_task(seed=0):
    train, test = synthetic.make_image_task(seed=seed, num_train=3000,
                                            num_test=400)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * 2 + [("xclass", 1)] * 2,
        samples_per_node=200, seed=1)
    return nodes, test


def _servers(cfg, seed=0):
    nodes, test = _small_task()
    return (FedServer("mlr", cfg, nodes, test, batch_size=50, seed=seed),
            FedServer("mlr", cfg, nodes, test, batch_size=50, seed=seed))


# ------------------------------------------------ scanned == stepwise


@pytest.mark.parametrize("method", ["fedadp", "fedavg"])
def test_scanned_matches_stepwise(method):
    """R scanned rounds == R stepwise steps to 1e-5 (shared device RNG:
    selection and batching happen inside the one step both paths run)."""
    cfg = fl.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                      method=method, base_lr=0.05)
    s_loop, s_scan = _servers(cfg)
    h_loop = s_loop.run(6, eval_every=2)
    h_scan = s_scan.run_scanned(6, eval_every=2, block=4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        s_loop.state.params, s_scan.state.params)
    np.testing.assert_allclose(s_loop.state.angle.smoothed,
                               s_scan.state.angle.smoothed, atol=1e-5)
    np.testing.assert_allclose(h_loop.loss, h_scan.loss, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(h_loop.accuracy, h_scan.accuracy, atol=1e-6)
    assert len(h_scan.accuracy) == 3  # eval_every=2 over 6 rounds


def test_scanned_matches_stepwise_subset_selection():
    """Client sampling comes from the shared device RNG, so the scanned
    and stepwise paths must pick the SAME cohorts — per-client Eq. 9
    participation counts agree exactly, trajectories to 1e-5."""
    cfg = fl.FLConfig(num_clients=4, clients_per_round=2, local_steps=4,
                      method="fedadp", base_lr=0.05)
    s_loop, s_scan = _servers(cfg)
    h_loop = s_loop.run(7, eval_every=2)
    h_scan = s_scan.run_scanned(7, eval_every=2, block=3)
    assert (s_loop.state.angle.count.tolist()
            == s_scan.state.angle.count.tolist())
    assert int(np.sum(s_loop.state.angle.count)) == 7 * 2
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        s_loop.state.params, s_scan.state.params)
    np.testing.assert_allclose(h_loop.loss, h_scan.loss, rtol=1e-5,
                               atol=1e-6)


def test_scanned_early_exit_matches_stepwise_target_semantics():
    """rounds_to_target must be the exact first eval round at/above the
    target in BOTH paths, even though the scan runs to its block edge."""
    cfg = fl.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                      method="fedadp", base_lr=0.05)
    s_loop, s_scan = _servers(cfg)
    # a target low enough to be hit quickly on the tiny task
    h_loop = s_loop.run(20, target_acc=0.15, eval_every=2)
    h_scan = s_scan.run_scanned(20, target_acc=0.15, eval_every=2, block=8)
    assert h_loop.rounds_to_target is not None
    assert h_scan.rounds_to_target == h_loop.rounds_to_target
    assert len(h_scan.loss) == len(h_loop.loss) == h_loop.rounds_to_target
    np.testing.assert_allclose(h_loop.accuracy, h_scan.accuracy, atol=1e-6)


def test_in_scan_eval_matches_host_eval():
    """The device-side eval (inside the compiled step) and the host-side
    `evaluate()` measure the same accuracy of the same params."""
    cfg = fl.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                      method="fedadp", base_lr=0.05)
    s, _ = _servers(cfg)
    m = s.step(eval_every=1)
    assert m["accuracy"] >= 0.0
    assert abs(float(m["accuracy"]) - s.evaluate()) < 1e-6


# ------------------------------------------------ device data pipeline


def test_stack_nodes_rejects_batch_larger_than_node():
    """tau = 0 used to crash the numpy batcher with an opaque reshape
    error; the device pipeline must refuse with the node named."""
    nodes = [Dataset(np.zeros((60, 4, 4, 1), np.float32),
                     np.zeros((60,), np.int32)),
             Dataset(np.zeros((30, 4, 4, 1), np.float32),
                     np.zeros((30,), np.int32))]
    with pytest.raises(ValueError, match="node 1"):
        driver.stack_nodes(nodes, batch_size=50)


def test_epoch_batcher_rejects_batch_larger_than_dataset():
    """The host-side reference batcher raises the same clear error."""
    ds = Dataset(np.zeros((30, 4, 4, 1), np.float32),
                 np.zeros((30,), np.int32))
    with pytest.raises(ValueError, match="batch_size=50"):
        next(_epoch_batcher(ds, batch_size=50, seed=0))


def test_stack_nodes_rejects_unequal_tau():
    nodes = [Dataset(np.zeros((100, 2), np.float32),
                     np.zeros((100,), np.int32)),
             Dataset(np.zeros((200, 2), np.float32),
                     np.zeros((200,), np.int32))]
    with pytest.raises(ValueError, match="tau"):
        driver.stack_nodes(nodes, batch_size=50)


def test_epoch_batches_never_sample_padding():
    """Ragged node sizes: the masked permutation must only draw real rows
    (padding is NaN-poisoned here and must never appear), and one epoch
    must not repeat a sample within a client."""
    rng = np.random.default_rng(0)
    nodes = [
        Dataset(rng.normal(size=(110, 3)).astype(np.float32),
                np.arange(110, dtype=np.int32)),
        Dataset(rng.normal(size=(100, 3)).astype(np.float32),
                np.arange(100, dtype=np.int32)),
    ]
    data = driver.stack_nodes(nodes, batch_size=50)
    assert data.tau == 2
    # poison the padding rows: sampling one would go NaN loudly
    x = np.array(data.x)
    x[1, 100:] = np.nan
    data = data._replace(x=jnp.asarray(x))
    xb, yb = driver.epoch_batches(jax.random.key(0), data,
                                  jnp.asarray([0, 1], jnp.int32))
    assert xb.shape == (2, 2, 50, 3)
    assert np.all(np.isfinite(np.asarray(xb)))
    for c in range(2):
        drawn = np.asarray(yb[c]).ravel()
        assert len(set(drawn.tolist())) == 100  # no within-epoch repeats
        assert drawn.max() < len(nodes[c].y)


# ------------------------------- partial participation, all engines


def test_partial_participation_pinned_across_engines():
    """clients_per_round < num_clients under the quantized uplink + EF:
    every engine must (a) advance Eq. 9 participation counts ONLY for the
    selected clients, (b) leave unselected clients' EF rows untouched,
    and (c) agree with the tree reference to 1e-5."""
    rng = np.random.default_rng(0)
    K, C, tau, B, d = 3, 8, 3, 8, 12
    params = {"w": jnp.zeros((d, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}
    X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
    wt = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, wt))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    mesh = make_mesh((1,), ("data",))
    sel = jnp.asarray([1, 4, 6], jnp.int32)
    sizes = jnp.asarray([10.0, 20.0, 30.0])
    outs = {}
    for engine in ("tree", "flat", "flat_sharded"):
        cfg = fl.FLConfig(num_clients=C, clients_per_round=K,
                          local_steps=tau, method="fedadp", engine=engine,
                          transport="int8", error_feedback=True,
                          base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(
            loss_fn, cfg, mesh=mesh if engine == "flat_sharded" else None))
        st = fl.init_round_state(cfg, params)
        for _ in range(2):
            st, m = rf(st, (X, Y), sel, sizes)
        outs[engine] = st
        assert st.angle.count.tolist() == [0, 2, 0, 0, 2, 0, 2, 0], engine
        ef = np.asarray(st.ef)
        unselected = [0, 2, 3, 5, 7]
        assert np.all(ef[unselected] == 0.0), engine
        assert np.abs(ef[np.asarray(sel)]).sum() > 0.0, engine
    for engine in ("flat", "flat_sharded"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            outs["tree"].params, outs[engine].params)
        np.testing.assert_allclose(np.asarray(outs["tree"].ef),
                                   np.asarray(outs[engine].ef), atol=1e-6)
        np.testing.assert_allclose(outs["tree"].angle.smoothed,
                                   outs[engine].angle.smoothed, atol=1e-5)


# ------------------------------------------------ delta-encoded downlink


def test_downlink_delta_roundtrip_tracks_small_diffs():
    """The delta-encoded hop reconstructs within the int8 bound of the
    DIFF — far tighter than compressing the full model when the per-round
    step is small (the whole point of shipping diffs)."""
    rng = np.random.default_rng(0)
    n = transport.CHUNK + 600
    prev = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    vec = prev + 1e-3 * jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    rt = transport.downlink.delta_roundtrip(vec, prev, "int8")
    err_delta = np.abs(np.asarray(rt - vec))
    err_direct = np.abs(np.asarray(
        transport.downlink.broadcast_roundtrip(vec, "int8") - vec))
    # elementwise int8 bound on the diff: half a quant step of the diff
    q = transport.downlink.delta_compress(vec, prev, "int8")
    bound = np.repeat(np.asarray(q.scales)[0], transport.CHUNK)[:n]
    assert np.all(err_delta <= 0.5 * bound * (1 + 1e-6) + 1e-8)
    assert err_delta.max() < 0.1 * err_direct.max()


def test_downlink_delta_stream_never_drifts():
    """Server and clients advance the same reconstruction: replaying the
    compressed diffs client-side lands exactly on the broadcast the round
    function trained its clients from."""
    rng = np.random.default_rng(1)
    n = 3000
    prev = jnp.zeros((n,), jnp.float32)
    model = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    for step in range(4):
        q = transport.downlink.delta_compress(model, prev, "int8")
        prev = transport.downlink.delta_decompress(q, prev)
        model = model + 0.01 * jnp.asarray(
            rng.normal(size=(n,)).astype(np.float32))
    # after several hops the stream still tracks the model to the bound
    # of the LAST diff, not the accumulated model magnitude
    assert float(jnp.max(jnp.abs(prev - model))) < 0.1


def test_downlink_delta_engines_agree():
    """downlink_delta is applied upstream of the engine branch: tree ==
    flat == flat_sharded to 1e-5, the broadcast chain head advancing
    identically."""
    rng = np.random.default_rng(0)
    K, tau, B, d = 4, 3, 8, 12
    params = {"w": jnp.full((d, 1), 0.05, jnp.float32),
              "b": jnp.full((1,), 0.01, jnp.float32)}
    X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
    wt = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, wt))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    mesh = make_mesh((1,), ("data",))
    sel = jnp.arange(K, dtype=jnp.int32)
    sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
    outs = {}
    for engine in ("tree", "flat", "flat_sharded"):
        cfg = fl.FLConfig(num_clients=K, clients_per_round=K,
                          local_steps=tau, method="fedadp", engine=engine,
                          downlink="int8", downlink_delta=True,
                          base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(
            loss_fn, cfg, mesh=mesh if engine == "flat_sharded" else None))
        st = fl.init_round_state(cfg, params)
        for _ in range(3):
            st, _ = rf(st, (X, Y), sel, sizes)
        outs[engine] = st
        assert st.bcast is not None
        assert np.abs(np.asarray(st.bcast.head)).sum() > 0
        assert int(st.bcast.head_ver) == 2  # three rounds: versions 0..2
    for engine in ("flat", "flat_sharded"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            outs["tree"].params, outs[engine].params)
        np.testing.assert_allclose(
            np.asarray(outs["tree"].bcast.head),
            np.asarray(outs[engine].bcast.head), atol=1e-6)


def test_downlink_delta_requires_quantized_downlink():
    def loss_fn(p, b):
        return 0.0

    cfg = fl.FLConfig(num_clients=4, clients_per_round=4, local_steps=2,
                      downlink="f32", downlink_delta=True)
    with pytest.raises(ValueError, match="downlink_delta"):
        fl.make_round_fn(loss_fn, cfg)


def test_downlink_delta_convergence_parity():
    """Delta-encoding the int8 broadcast must not cost rounds: within the
    1.1x acceptance band of the golden f32/f32 reference."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import node_spec, run_fl

    with open(GOLDEN) as f:
        g = json.load(f)
    task = g["task"]
    hist, _ = run_fl(
        "fedadp", node_spec(5, 5, 1), rounds=task["max_rounds"],
        target=task["target"], engine=task["engine"], transport="f32",
        downlink="int8", downlink_delta=True, seed=task["seed"],
        eval_every=task["eval_every"])
    ref = g["entries"]["fedadp/f32/f32"]
    assert hist.rounds_to_target is not None
    assert hist.rounds_to_target <= 1.1 * ref + 1, (hist.rounds_to_target,
                                                    ref)


# ------------------------------------------- scanned golden convergence


SCANNED_GOLDEN_CASES = [
    ("fedadp", "f32", "f32"),
    ("fedavg", "f32", "f32"),
    ("fedadp", "int4", "int8"),
]


def test_scanned_driver_reproduces_golden_convergence():
    """Acceptance: the scanned driver reproduces the golden convergence
    table through its OWN path — fedadp <= fedavg, and every re-run wire
    within the 1.1x band of its golden entry in both directions."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import node_spec, run_fl

    with open(GOLDEN) as f:
        g = json.load(f)
    task = g["task"]
    got = {}
    for method, uplink, downlink in SCANNED_GOLDEN_CASES:
        hist, _ = run_fl(
            method, node_spec(5, 5, 1), rounds=task["max_rounds"],
            target=task["target"], engine=task["engine"],
            transport=uplink, downlink=downlink,
            group_size=task["group_size"], seed=task["seed"],
            eval_every=task["eval_every"], scan=True, scan_block=10)
        key = f"{method}/{uplink}/{downlink}"
        got[key] = hist.rounds_to_target
        golden = g["entries"][key]
        assert got[key] is not None, key
        assert got[key] <= 1.1 * golden and golden <= 1.1 * got[key], (
            key, got[key], golden)
    assert got["fedadp/f32/f32"] <= got["fedavg/f32/f32"]


def test_scanned_flat_sharded_8device_subprocess():
    """The scanned driver composes with the client-sharded engine: on an
    8-way host-device mesh, run_scanned == stepwise run for
    engine="flat_sharded" (shard_map inside lax.scan)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import fl
        from repro.core.server import FedServer
        from repro.data import synthetic
        train, test = synthetic.make_image_task(seed=0, num_train=3000,
                                                num_test=400)
        nodes = synthetic.make_federated(
            train, [("iid", None)] * 4 + [("xclass", 1)] * 4,
            samples_per_node=200, seed=1)
        mesh = make_mesh((8,), ("data",))
        cfg = fl.FLConfig(num_clients=8, clients_per_round=8, local_steps=4,
                          method="fedadp", engine="flat_sharded",
                          transport="int8", base_lr=0.05)
        servers = [FedServer("mlr", cfg, nodes, test, batch_size=50,
                             seed=0, mesh=mesh) for _ in range(2)]
        h1 = servers[0].run(6, eval_every=2)
        h2 = servers[1].run_scanned(6, eval_every=2, block=4)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            servers[0].state.params, servers[1].state.params)
        np.testing.assert_allclose(h1.loss, h2.loss, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(h1.accuracy, h2.accuracy, atol=1e-6)
        print("SCANNED_SHARDED_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SCANNED_SHARDED_OK" in out.stdout, out.stderr[-2000:]
