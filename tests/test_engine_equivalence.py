"""Flat-buffer vs tree round-engine equivalence (the engine="flat" contract).

The tree engine is the reference implementation; the flat engine re-routes
the identical round math through `tree_ravel_stacked` + the fused Pallas
kernels (`round_stats`, `weighted_agg`), now chunked over the client axis
so ANY K is served (no MAX_K ceiling). Multi-round trajectories must agree
to 1e-5 for both methods, with and without the MoE angle filter, for K
across chunk boundaries (1, 33, 64), and the parallel engines must agree
with the sequential scan under full participation. The client-sharded
variant (engine="flat_sharded") is pinned against both on an 8-way
host-device mesh in a subprocess.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fl
from repro.launch.mesh import make_mesh

K = 4


def _toy_problem(K=K, tau=3, B=8, d=12, seed=0):
    """Non-IID linear-regression clients, plus a rank-4 'ffn/w_gate' leaf so
    angle_filter="dense_only" (moe_dense_only_pred) actually drops a segment
    of the flat buffer."""
    rng = np.random.default_rng(seed)
    params = {
        "w": jnp.zeros((d, 1), jnp.float32),
        "b": jnp.zeros((1,), jnp.float32),
        "ffn": {"w_gate": jnp.full((1, 1, 4, 4), 0.1, jnp.float32)},
    }
    X = rng.normal(size=(K, tau, B, d)).astype(np.float32)
    w_true = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = np.einsum("ktbd,kde->ktbe", X, w_true)

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ p["w"] + p["b"] + jnp.sum(p["ffn"]["w_gate"] ** 2)
        return jnp.mean((pred - y) ** 2)

    return params, loss_fn, (jnp.asarray(X), jnp.asarray(Y))


def _run(engine, method, angle_filter="all", mode="parallel", rounds=4,
         seed=0, k=K):
    params, loss_fn, batches = _toy_problem(K=k, seed=seed)
    cfg = fl.FLConfig(num_clients=k, clients_per_round=k, local_steps=3,
                      method=method, mode=mode, engine=engine,
                      angle_filter=angle_filter, base_lr=0.05)
    rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
    st = fl.init_round_state(cfg, params)
    sel = jnp.arange(k, dtype=jnp.int32)
    sizes = jnp.asarray(10.0 * (1.0 + np.arange(k, dtype=np.float32)))
    ms = []
    for r in range(rounds):
        st, m = rf(st, batches, sel, sizes)
        ms.append(m)
    return st.params, st.angle, ms


def _assert_trees_close(a, b, atol=1e-5):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=atol),
        a, b,
    )


@pytest.mark.parametrize("angle_filter", ["all", "dense_only"])
@pytest.mark.parametrize("method", ["fedadp", "fedavg"])
def test_flat_matches_tree_multi_round(method, angle_filter):
    p_t, s_t, m_t = _run("tree", method, angle_filter)
    p_f, s_f, m_f = _run("flat", method, angle_filter)
    _assert_trees_close(p_t, p_f)
    np.testing.assert_allclose(s_t.smoothed, s_f.smoothed, atol=1e-5)
    assert s_t.count.tolist() == s_f.count.tolist()
    for mt, mf in zip(m_t, m_f):
        for key in ("theta", "theta_smoothed", "weights", "divergence",
                    "loss", "cos", "expected_contribution"):
            np.testing.assert_allclose(
                np.asarray(mt[key]), np.asarray(mf[key]), rtol=1e-5,
                atol=1e-5, err_msg=f"metric {key}")


@pytest.mark.parametrize("method", ["fedadp", "fedavg"])
def test_flat_matches_tree_bf16(method):
    """bf16 params: both engines compute angle stats from the UNROUNDED f32
    global delta, so trajectories agree to bf16 resolution (params are
    rounded to bf16 each round, so exact 1e-5 equality is a f32-only
    contract), and param dtype survives the round trip."""
    rng = np.random.default_rng(0)
    d = 12
    X = jnp.asarray(rng.normal(size=(K, 3, 8, d)).astype(np.float32))
    w_true = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, w_true))

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
        return jnp.mean((pred - y) ** 2)

    outs = {}
    for engine in ("tree", "flat"):
        params = {"w": jnp.zeros((d, 1), jnp.bfloat16),
                  "b": jnp.zeros((1,), jnp.bfloat16)}
        cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                          method=method, engine=engine, base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
        st = fl.init_round_state(cfg, params)
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
        for r in range(3):
            st, m = rf(st, (X, Y), sel, sizes)
        outs[engine] = (st.params, m)
    for a, b in zip(jax.tree.leaves(outs["tree"][0]),
                    jax.tree.leaves(outs["flat"][0])):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)
    np.testing.assert_allclose(np.asarray(outs["tree"][1]["theta"]),
                               np.asarray(outs["flat"][1]["theta"]),
                               atol=1e-2)


def test_dense_only_filter_changes_angles_in_both_engines():
    """The segment mask must actually bite (w_gate deltas are nonzero), and
    it must bite identically in both engines."""
    for engine in ("tree", "flat"):
        _, _, m_all = _run(engine, "fedadp", "all")
        _, _, m_dense = _run(engine, "fedadp", "dense_only")
        assert not np.allclose(np.asarray(m_all[-1]["theta"]),
                               np.asarray(m_dense[-1]["theta"])), engine


@pytest.mark.parametrize("engine", ["tree", "flat"])
def test_parallel_engine_matches_sequential(engine):
    """Under full participation both parallel engines implement the same
    math as the sequential two-pass scan."""
    p_par, s_par, m_par = _run(engine, "fedadp", mode="parallel")
    p_seq, s_seq, m_seq = _run("tree", "fedadp", mode="sequential")
    _assert_trees_close(p_par, p_seq, atol=2e-5)
    np.testing.assert_allclose(s_par.smoothed, s_seq.smoothed, rtol=2e-4)
    np.testing.assert_allclose(m_par[-1]["weights"], m_seq[-1]["weights"],
                               rtol=2e-4)


def test_flat_engine_requires_parallel_mode():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      mode="sequential", engine="flat")
    with pytest.raises(ValueError, match="flat"):
        fl.make_round_fn(loss_fn, cfg)


@pytest.mark.parametrize("k", [1, 33, 64])
def test_flat_engine_unbounded_k(k):
    """Regression for the former MAX_K=32 trace-time error: the chunked
    kernels serve any K — K=1 (degenerate chunk), K=33 (ragged chunk), and
    K=64 (multiple full chunks) must all match the tree reference."""
    p_t, s_t, m_t = _run("tree", "fedadp", rounds=2, k=k)
    p_f, s_f, m_f = _run("flat", "fedadp", rounds=2, k=k)
    _assert_trees_close(p_t, p_f)
    np.testing.assert_allclose(s_t.smoothed, s_f.smoothed, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(m_t[-1]["weights"]), np.asarray(m_f[-1]["weights"]),
        rtol=1e-5, atol=1e-5)


def test_flat_engine_k128():
    """Acceptance: FLConfig(engine="flat") works for K=128 (one round)."""
    p_t, _, m_t = _run("tree", "fedadp", rounds=1, k=128)
    p_f, _, m_f = _run("flat", "fedadp", rounds=1, k=128)
    _assert_trees_close(p_t, p_f)
    np.testing.assert_allclose(
        np.asarray(m_t[0]["theta"]), np.asarray(m_f[0]["theta"]), atol=1e-5)


def test_flat_sharded_requires_mesh():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      engine="flat_sharded")
    with pytest.raises(ValueError, match="mesh"):
        fl.make_round_fn(loss_fn, cfg)


def test_flat_sharded_nondivisible_k_matches_tree_subprocess():
    """K % shards != 0 no longer raises: the client axis is zero-padded
    before sharding (padded rows carry zero deltas and zero data size, so
    they get exactly zero weight and zero stats). K=13 on an 8-way mesh is
    pinned against the tree engine, for the f32, int8 and packed-int4
    wires — the int4 leg under a quantized (int8) downlink, so sharded
    parity is exercised with BOTH directions of the wire compressed."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import fl
        K, d, tau, B = 13, 12, 2, 4
        rng = np.random.default_rng(0)
        params = {"w": jnp.full((d, 1), 0.05, jnp.float32),
                  "b": jnp.zeros((1,), jnp.float32)}
        X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
        wt = rng.normal(size=(K, d, 1)).astype(np.float32)
        Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, wt))
        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
        mesh = make_mesh((8,), ("data",))
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray(np.linspace(10.0, 40.0, K, dtype=np.float32))
        for tr, dl in (("f32", "f32"), ("int8", "f32"), ("int4", "int8")):
            outs = {}
            for engine in ("tree", "flat_sharded"):
                cfg = fl.FLConfig(num_clients=K, clients_per_round=K,
                                  local_steps=tau, method="fedadp",
                                  engine=engine, transport=tr, downlink=dl,
                                  group_size=32, base_lr=0.05)
                rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh))
                st = fl.init_round_state(cfg, params)
                with mesh:
                    for r in range(2):
                        st, m = rf(st, (X, Y), sel, sizes)
                outs[engine] = (st.params, m)
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
                outs["tree"][0], outs["flat_sharded"][0])
            np.testing.assert_allclose(
                np.asarray(outs["tree"][1]["weights"]),
                np.asarray(outs["flat_sharded"][1]["weights"]),
                rtol=1e-5, atol=1e-6)
        print("RAGGED_SHARD_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "RAGGED_SHARD_OK" in out.stdout, out.stderr[-2000:]


def test_flat_sharded_single_device_matches_flat():
    """On a 1-way client mesh the sharded flat engine is the flat engine
    plus no-op psums; trajectories must agree to 1e-5."""
    params, loss_fn, batches = _toy_problem()
    mesh = make_mesh((1,), ("data",))
    outs = {}
    for engine in ("flat", "flat_sharded"):
        cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                          method="fedadp", engine=engine, base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh))
        st = fl.init_round_state(cfg, params)
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
        for r in range(3):
            st, m = rf(st, batches, sel, sizes)
        outs[engine] = (st.params, st.angle, m)
    _assert_trees_close(outs["flat"][0], outs["flat_sharded"][0])
    np.testing.assert_allclose(outs["flat"][1].smoothed,
                               outs["flat_sharded"][1].smoothed, atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs["flat"][2]["weights"]),
                               np.asarray(outs["flat_sharded"][2]["weights"]),
                               rtol=1e-5, atol=1e-6)


def test_flat_sharded_matches_tree_8way_subprocess():
    """Acceptance pin: sharded-flat == flat == tree to 1e-5 over multi-round
    runs on an 8-way host-device client mesh (subprocess — this session is
    pinned to one device)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import fl
        K, d, tau, B = 16, 12, 3, 8
        rng = np.random.default_rng(0)
        params = {"w": jnp.zeros((d, 1), jnp.float32),
                  "b": jnp.zeros((1,), jnp.float32)}
        X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
        wt = rng.normal(size=(K, d, 1)).astype(np.float32)
        Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, wt))
        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
        mesh = make_mesh((8,), ("data",))
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray(np.linspace(10.0, 40.0, K, dtype=np.float32))
        outs = {}
        for engine in ("tree", "flat", "flat_sharded"):
            cfg = fl.FLConfig(num_clients=K, clients_per_round=K,
                              local_steps=tau, method="fedadp",
                              engine=engine, base_lr=0.05)
            rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh))
            st = fl.init_round_state(cfg, params)
            with mesh:
                for r in range(3):
                    st, m = rf(st, (X, Y), sel, sizes)
            outs[engine] = (st.params, st.angle, m)
        for engine in ("flat", "flat_sharded"):
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
                outs["tree"][0], outs[engine][0])
            np.testing.assert_allclose(outs["tree"][1].smoothed,
                                       outs[engine][1].smoothed, atol=1e-5)
            np.testing.assert_allclose(np.asarray(outs["tree"][2]["weights"]),
                                       np.asarray(outs[engine][2]["weights"]),
                                       rtol=1e-5, atol=1e-6)
        print("SHARDED_FLAT_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_FLAT_OK" in out.stdout, out.stderr[-2000:]


def test_unknown_engine_rejected():
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      engine="nope")
    with pytest.raises(ValueError, match="engine"):
        fl.make_round_fn(loss_fn, cfg)


def test_unknown_angle_filter_rejected():
    """A typo'd filter must not silently run with unfiltered stats."""
    params, loss_fn, _ = _toy_problem()
    cfg = fl.FLConfig(num_clients=K, clients_per_round=K, local_steps=3,
                      angle_filter="dense-only")
    with pytest.raises(ValueError, match="angle_filter"):
        fl.make_round_fn(loss_fn, cfg)


def test_flat_engine_subset_selection():
    """Subset participation: angle-state slots update identically."""
    params, loss_fn, batches = _toy_problem()
    outs = {}
    for engine in ("tree", "flat"):
        cfg = fl.FLConfig(num_clients=8, clients_per_round=K, local_steps=3,
                          method="fedadp", engine=engine, base_lr=0.05)
        rf = jax.jit(fl.make_round_fn(loss_fn, cfg))
        sel = jnp.asarray([1, 3, 5, 7], jnp.int32)
        st, _ = rf(fl.init_round_state(cfg, params), batches, sel,
                   jnp.ones((K,)))
        outs[engine] = (st.params, st.angle)
    _assert_trees_close(outs["tree"][0], outs["flat"][0])
    np.testing.assert_allclose(outs["tree"][1].smoothed,
                               outs["flat"][1].smoothed, atol=1e-5)
    assert outs["flat"][1].count.tolist() == [0, 1, 0, 1, 0, 1, 0, 1]


def test_flat_sharded_2d_mesh_matches_tree_subprocess():
    """Tentpole acceptance: the flat engine on 2D (client x model) meshes —
    (2,4) and (4,2) over 8 host devices — matches the tree engine on the
    SAME mesh to 1e-5 for all four uplink transports, with K=6 pinning the
    non-divisible client-axis padding on the (4,2) leg. For the elementwise
    wires (f32/bf16) the trajectory additionally matches the unsharded 1D
    flat engine; the int8/int4 wires are mesh-derived (shard-local scale
    chunks), so their cross-mesh identity is intentionally NOT pinned —
    tree-on-the-same-mesh is the reference (it consumes the identical
    blocked wire through fl_shard_map.make_blocked_roundtrip)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import fl
        K, d, h, tau, B = 6, 12, 8, 2, 4
        rng = np.random.default_rng(0)
        params = {"wq": jnp.asarray(rng.normal(size=(d, h)).astype(np.float32) * 0.1),
                  "w_down": jnp.asarray(rng.normal(size=(h, 1)).astype(np.float32) * 0.1),
                  "b": jnp.zeros((1,), jnp.float32),
                  "scale": jnp.full((5,), 0.3, jnp.float32)}
        X = jnp.asarray(rng.normal(size=(K, tau, B, d)).astype(np.float32))
        wt = rng.normal(size=(K, d, 1)).astype(np.float32)
        Y = jnp.asarray(np.einsum("ktbd,kde->ktbe", X, wt))
        def loss_fn(p, batch):
            x, y = batch
            pred = (x @ p["wq"]) @ p["w_down"] + p["b"] + jnp.sum(p["scale"] ** 2)
            return jnp.mean((pred - y) ** 2)
        sel = jnp.arange(K, dtype=jnp.int32)
        sizes = jnp.asarray(np.linspace(10.0, 40.0, K, dtype=np.float32))
        def leafcmp(a, b, atol, msg):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_allclose(
                    np.asarray(la, np.float32), np.asarray(lb, np.float32),
                    rtol=1e-5, atol=atol, err_msg=msg)
        def run(engine, mesh, tr):
            cfg = fl.FLConfig(num_clients=K, clients_per_round=K,
                              local_steps=tau, method="fedadp", engine=engine,
                              transport=tr, group_size=8, base_lr=0.05)
            rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh))
            st = fl.init_round_state(cfg, params)
            import contextlib
            ctx = mesh if mesh is not None else contextlib.nullcontext()
            with ctx:
                for r in range(2):
                    st, m = rf(st, (X, Y), sel, sizes)
            return st, m
        for shape in ((2, 4), (4, 2)):
            mesh = make_mesh(shape, ("data", "model"))
            for tr in ("f32", "bf16", "int8", "int4"):
                st_t, m_t = run("tree", mesh, tr)
                st_f, m_f = run("flat_sharded", mesh, tr)
                leafcmp(st_t.params, st_f.params, 1e-5,
                        f"params {shape} {tr}")
                np.testing.assert_allclose(
                    np.asarray(st_t.angle.smoothed),
                    np.asarray(st_f.angle.smoothed), atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(m_t["weights"]), np.asarray(m_f["weights"]),
                    rtol=1e-5, atol=1e-6, err_msg=f"weights {shape} {tr}")
                if tr in ("f32", "bf16"):
                    # elementwise wire: identical to the 1D flat engine too
                    st_1, m_1 = run("flat", None, tr)
                    leafcmp(st_1.params, st_f.params, 1e-5,
                            f"1d-vs-2d {shape} {tr}")
                    np.testing.assert_allclose(
                        np.asarray(m_1["weights"]),
                        np.asarray(m_f["weights"]), rtol=1e-5, atol=1e-6)
        print("MESH2D_EQUIV_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH2D_EQUIV_OK" in out.stdout, out.stderr[-2000:]


def test_flat_sharded_2d_keeps_sharded_leaves_sharded():
    """No-gather acceptance: lower the 2D round region alone with sharded
    inputs and assert (a) the aggregated outputs RETAIN the model-axis
    sharding of their param specs, and (b) the compiled module contains no
    all-gather as large as a full model-sharded leaf — the blocked ravel
    is what buys this, so a regression to full-width raveling shows up as
    a big gather here. (Replicated leaves legitimately re-join via O(leaf)
    gathers of their column slices; the threshold only bounds gathers at
    the SHARDED leaf's full stacked size.)"""
    prog = textwrap.dedent("""
        import os, re
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import fl_shard_map
        from repro.models import sharding as msharding
        K, d, h = 8, 8, 256
        rng = np.random.default_rng(0)
        params = {"wq": jnp.zeros((d, h), jnp.float32),
                  "b": jnp.zeros((7,), jnp.float32)}
        deltas = {"wq": jnp.asarray(rng.normal(size=(K, d, h)).astype(np.float32)),
                  "b": jnp.asarray(rng.normal(size=(K, 7)).astype(np.float32))}
        mesh = make_mesh((2, 4), ("data", "model"))
        pspecs = msharding.param_pspecs(params, mesh)
        assert "model" in str(pspecs["wq"]), pspecs
        stacked = jax.tree.map(lambda s: P("data", *tuple(s)), pspecs,
                               is_leaf=lambda x: isinstance(x, P))
        deltas = jax.tree.map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
            deltas, stacked)
        psi = jnp.full((K,), 1.0 / K, jnp.float32)
        z = jnp.zeros((K,), jnp.float32)
        sizes = jnp.ones((K,), jnp.float32)
        from repro.core import weighting
        op = fl_shard_map.make_round_ops(
            mesh, weigh=lambda t, d: weighting.fedadp_weights(t, d, 5.0),
            transport="int8", template=deltas, pspecs=pspecs)
        jop = jax.jit(op)
        g, dots, sqs, sqg, delta, theta, tsm, w = jop(deltas, psi, z, z, sizes)
        # (a) output sharding retains the model axis on the sharded leaf
        assert "model" in str(g["wq"].sharding.spec), g["wq"].sharding
        assert "model" in str(delta["wq"].sharding.spec), delta["wq"].sharding
        # (b) compiled HLO: no all-gather at the sharded leaf's full size
        hlo = jop.lower(deltas, psi, z, z, sizes).compile().as_text()
        full = K * d * h  # stacked wq elements (the thing we must not gather)
        biggest = 0
        for m in re.finditer(r"all-gather[^=]*=?[^f\\n]*f32\\[([0-9,]+)\\]", hlo):
            dims = [int(x) for x in m.group(1).split(",") if x]
            n = int(np.prod(dims)) if dims else 1
            biggest = max(biggest, n)
        assert biggest < d * h, (biggest, d * h)
        # sanity: the module is genuinely partitioned (psums present)
        assert "all-reduce" in hlo
        print("MESH2D_NOGATHER_OK", biggest)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH2D_NOGATHER_OK" in out.stdout, out.stderr[-2000:]
