"""shard_map FedAdp aggregation vs the pjit/treemath path.

Covers both engines: "tree" (per-leaf reductions, model-axis sharding
allowed) and "flat" (client-row-sharded (K, N) buffer through the fused
Pallas kernels). The multi-device equivalence check runs in a subprocess
(the test session itself is pinned to 1 device; the dry-run
placeholder-device trick is reserved for repro.launch.dryrun).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import fl_shard_map, treemath, weighting
from repro.launch.mesh import make_mesh


def _reference(deltas, sizes, sm_prev, cnt_prev, alpha=5.0):
    psi = weighting.fedavg_weights(sizes)
    g_avg = treemath.tree_weighted_sum(deltas, psi)
    theta = weighting.instantaneous_angle(
        treemath.tree_vdot_batched(deltas, g_avg),
        treemath.tree_sqnorm_batched(deltas),
        treemath.tree_sqnorm(g_avg),
    )
    cnt = cnt_prev.astype(jnp.float32) + 1
    sm = ((cnt - 1) * sm_prev + theta) / cnt
    w = weighting.fedadp_weights(sm, sizes, alpha)
    return treemath.tree_weighted_sum(deltas, w), theta, w


def test_single_device_mesh_matches_reference():
    mesh = make_mesh((1, 1), ("data", "model"))
    K = 4
    deltas = {
        "a": jax.random.normal(jax.random.key(0), (K, 8, 6)),
        "b": jax.random.normal(jax.random.key(1), (K, 16)),
    }
    pspecs = {"a": P("data", None, "model"), "b": P("data", None)}
    sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
    sm_prev = jnp.asarray([0.5, 0.2, 0.9, 0.4])
    cnt_prev = jnp.asarray([1, 2, 0, 3], jnp.int32)
    agg = fl_shard_map.fedadp_aggregate(mesh, pspecs, alpha=5.0)
    with mesh:
        delta, theta, _, w = jax.jit(agg)(deltas, sizes, sm_prev, cnt_prev)
    dref, tref, wref = _reference(deltas, sizes, sm_prev, cnt_prev)
    np.testing.assert_allclose(np.asarray(theta), np.asarray(tref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wref), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-6),
        delta, dref,
    )


def test_single_device_flat_engine_matches_reference():
    """engine="flat" on a 1x1 mesh: the kernel path with no-op psums must
    reproduce the treemath reference."""
    mesh = make_mesh((1, 1), ("data", "model"))
    K = 4
    deltas = {
        "a": jax.random.normal(jax.random.key(0), (K, 8, 6)),
        "b": jax.random.normal(jax.random.key(1), (K, 16)),
    }
    pspecs = {"a": P("data", None, None), "b": P("data", None)}
    sizes = jnp.asarray([10.0, 20.0, 30.0, 40.0])
    sm_prev = jnp.asarray([0.5, 0.2, 0.9, 0.4])
    cnt_prev = jnp.asarray([1, 2, 0, 3], jnp.int32)
    agg = fl_shard_map.fedadp_aggregate(mesh, pspecs, alpha=5.0,
                                        engine="flat")
    with mesh:
        delta, theta, _, w = jax.jit(agg)(deltas, sizes, sm_prev, cnt_prev)
    dref, tref, wref = _reference(deltas, sizes, sm_prev, cnt_prev)
    np.testing.assert_allclose(np.asarray(theta), np.asarray(tref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wref), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-4, atol=1e-6),
        delta, dref,
    )


def test_flat_engine_rejects_model_sharded_specs():
    """Model-axis-sharded leaves cannot ravel into contiguous client rows;
    the flat engine must refuse them at build time."""
    mesh = make_mesh((1, 1), ("data", "model"))
    pspecs = {"a": P("data", None, "model")}
    with pytest.raises(ValueError, match="client-only"):
        fl_shard_map.fedadp_aggregate(mesh, pspecs, alpha=5.0, engine="flat")


def test_unknown_shard_map_engine_rejected():
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="engine"):
        fl_shard_map.fedadp_aggregate(mesh, {"a": P("data", None)},
                                      alpha=5.0, engine="nope")


def test_multi_device_mesh_matches_reference_subprocess():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.core import fl_shard_map, treemath, weighting
        mesh = make_mesh((4, 2), ("data", "model"))
        K = 4
        deltas = {"a": jax.random.normal(jax.random.key(0), (K, 8, 6)),
                  "b": jax.random.normal(jax.random.key(1), (K, 16))}
        pspecs = {"a": P("data", None, "model"), "b": P("data", None)}
        sizes = jnp.asarray([10., 20., 30., 40.])
        sm = jnp.asarray([.5, .2, .9, .4]); cnt = jnp.asarray([1,2,0,3], jnp.int32)
        agg = fl_shard_map.fedadp_aggregate(mesh, pspecs, alpha=5.0)
        with mesh:
            delta, theta, _, w = jax.jit(agg)(deltas, sizes, sm, cnt)
        psi = weighting.fedavg_weights(sizes)
        g = treemath.tree_weighted_sum(deltas, psi)
        tref = weighting.instantaneous_angle(
            treemath.tree_vdot_batched(deltas, g),
            treemath.tree_sqnorm_batched(deltas), treemath.tree_sqnorm(g))
        c = cnt.astype(jnp.float32)+1
        wref = weighting.fedadp_weights(((c-1)*sm + tref)/c, sizes, 5.0)
        dref = treemath.tree_weighted_sum(deltas, wref)
        np.testing.assert_allclose(np.asarray(theta), np.asarray(tref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w), np.asarray(wref), rtol=1e-5)
        jax.tree.map(lambda a,b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), delta, dref)
        # flat engine: same math through client-row-sharded fused kernels
        # (client-only pspecs; the "model" axis sees the buffer replicated)
        pspecs2 = {"a": P("data", None, None), "b": P("data", None)}
        agg2 = fl_shard_map.fedadp_aggregate(mesh, pspecs2, alpha=5.0,
                                             engine="flat")
        with mesh:
            d2, t2, _, w2 = jax.jit(agg2)(deltas, sizes, sm, cnt)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(tref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w2), np.asarray(wref), rtol=1e-5)
        jax.tree.map(lambda a,b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), d2, dref)
        print("SHARD_MAP_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARD_MAP_OK" in out.stdout, out.stderr[-2000:]
