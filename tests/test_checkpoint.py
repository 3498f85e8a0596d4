"""Checkpoint layer tests: the io round-trip bugfix regressions, the
RoundState <-> nested-dict codec (structure per FLConfig, elastic-K), the
checkpoint-directory machinery (atomicity, latest pointer, retention),
and the tier-1 gate of the whole layer — kill/resume golden invariance:
a run interrupted at any scan-block boundary and resumed from the
checkpoint reproduces the uninterrupted run's rounds-to-85% and
bit-identical final RoundState, stepwise and scanned, for the reference
f32/f32 wire and the fully quantized int4/int8 pair.
"""
import itertools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import transport
from repro.checkpoint import io as ckpt_io
from repro.core import fl
from repro.core.server import FedServer
from repro.data import synthetic


def _assert_bitexact(a, b, what=""):
    """Bitwise pytree equality (typed PRNG keys compared via key_data)."""
    assert jax.tree.structure(a) == jax.tree.structure(b), what
    flat = jax.tree_util.tree_flatten_with_path(a)[0]
    for (path, x), y in zip(flat, jax.tree.leaves(b)):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        assert x.dtype == y.dtype and x.shape == y.shape, (
            f"{what}{jax.tree_util.keystr(path)}")
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), (
            f"{what}{jax.tree_util.keystr(path)} differs bitwise")


# ------------------------------------------------ io bugfix regressions


def test_save_load_agree_on_suffixless_path(tmp_path):
    """Regression: np.savez appends '.npz' when the path lacks it, so
    load(path) used to FileNotFoundError for the very path save(path)
    was handed."""
    p = str(tmp_path / "ckpt")  # no .npz suffix
    ckpt_io.save(p, {"a": jnp.arange(3)})
    assert ckpt_io.load(p)["a"].tolist() == [0, 1, 2]
    # the suffixed spelling finds the same file
    assert ckpt_io.load(p + ".npz")["a"].tolist() == [0, 1, 2]
    assert os.listdir(tmp_path) == ["ckpt.npz"]


def test_none_leaves_and_empty_subtrees_roundtrip(tmp_path):
    """Regression: _flatten silently dropped None leaves and empty-dict
    subtrees, so load(save(tree)) changed pytree structure for configs
    with optional RoundState fields off."""
    tree = {"params": {"w": jnp.ones((2,))}, "ef": None, "dl_ef": None,
            "nested": {"inner": None}, "empty": {}}
    p = str(tmp_path / "t.npz")
    ckpt_io.save(p, tree)
    back = ckpt_io.load(p)
    assert back["ef"] is None and back["dl_ef"] is None
    assert back["nested"]["inner"] is None
    assert back["empty"] == {}
    none_leaf = lambda x: x is None  # noqa: E731
    assert (jax.tree.structure(back, is_leaf=none_leaf)
            == jax.tree.structure(tree, is_leaf=none_leaf))


def test_slash_in_key_rejected(tmp_path):
    """Regression: a '/' inside a dict key used to corrupt the flattened
    path (splitting one field into a fake subtree on load)."""
    with pytest.raises(ValueError, match="a/b"):
        ckpt_io.save(str(tmp_path / "t"), {"a/b": jnp.zeros(1)})
    with pytest.raises(ValueError, match="separator"):
        ckpt_io.save(str(tmp_path / "t"), {"sub": {"x/y": jnp.zeros(1)}})


def test_typed_prng_key_roundtrip(tmp_path):
    """Regression: jax.random.key(...) arrays crashed np.asarray in
    _flatten; they now ship as key_data + an impl tag and come back as
    typed keys producing the identical stream."""
    key = jax.random.key(7)
    p = str(tmp_path / "k.npz")
    ckpt_io.save(p, {"rng": key, "nested": {"k2": jax.random.fold_in(key, 3)}})
    back = ckpt_io.load(p)
    for got, want in ((back["rng"], key),
                      (back["nested"]["k2"], jax.random.fold_in(key, 3))):
        assert jax.dtypes.issubdtype(got.dtype, jax.dtypes.prng_key)
        np.testing.assert_array_equal(jax.random.key_data(got),
                                      jax.random.key_data(want))
        np.testing.assert_array_equal(jax.random.uniform(got, (4,)),
                                      jax.random.uniform(want, (4,)))


def test_old_style_uint32_key_loads_as_raw_array(tmp_path):
    """Old-style raw uint32 keys are plain arrays on the wire — the codec
    (state_from_tree) wraps them back into typed keys."""
    raw = jax.random.PRNGKey(3)  # uint32 (2,)
    p = str(tmp_path / "k.npz")
    ckpt_io.save(p, {"rng": raw})
    back = ckpt_io.load(p)["rng"]
    assert back.dtype == jnp.uint32 and back.shape == (2,)


def test_module_docstring_points_at_the_codec():
    """Regression: the docstring referenced core.server.ServerState.to_tree,
    gone since the PR 5 RoundState refactor."""
    assert "ServerState.to_tree" not in (ckpt_io.__doc__ or "")
    assert "state_to_tree" in ckpt_io.__doc__
    assert hasattr(fl, "state_to_tree") and hasattr(fl, "state_from_tree")


def test_all_leaf_dtypes_roundtrip_exactly(tmp_path):
    rng = np.random.default_rng(0)
    tree = {
        "f32": jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32)),
        "f16": jnp.asarray(rng.normal(size=(5,)).astype(np.float16)),
        "bf16": jnp.asarray(rng.normal(size=(4, 2)), jnp.bfloat16),
        "i8": jnp.asarray(rng.integers(-128, 127, (7,)), jnp.int8),
        "u8": jnp.asarray(rng.integers(0, 255, (6,)), jnp.uint8),
        "i32": jnp.asarray(rng.integers(-2**31, 2**31 - 1, (3,)), jnp.int32),
        "u32": jnp.asarray(rng.integers(0, 2**32 - 1, (3,)), jnp.uint32),
        "bool": jnp.asarray([True, False, True]),
        "scalar": jnp.float32(3.5),
        "key": jax.random.key(11),
    }
    p = str(tmp_path / "dtypes.npz")
    ckpt_io.save(p, tree)
    _assert_bitexact(ckpt_io.load(p), tree)


# ------------------------------------------ checkpoint-directory layer


def test_save_checkpoint_latest_pointer_and_retention(tmp_path):
    d = str(tmp_path / "run")
    for step in (2, 4, 6, 8):
        ckpt_io.save_checkpoint(d, step, {"x": jnp.int32(step)}, keep=2)
    steps = [s for s, _ in ckpt_io.list_checkpoints(d)]
    assert steps == [6, 8]  # retention kept the newest 2
    step, tree = ckpt_io.load_latest(d)
    assert step == 8 and int(tree["x"]) == 8
    assert not [f for f in os.listdir(d) if ".tmp." in f]  # atomic writes


def test_latest_pointer_survives_torn_writer(tmp_path):
    """A writer killed mid-save leaves only temp garbage / a stale
    pointer; load_latest must still resolve a complete archive."""
    d = str(tmp_path / "run")
    ckpt_io.save_checkpoint(d, 3, {"x": jnp.int32(3)})
    # torn archive write: garbage tmp file must be ignored
    with open(os.path.join(d, "ckpt_00000009.npz.tmp.999"), "wb") as f:
        f.write(b"partial garbage")
    # stale pointer: names an archive that never finished its rename
    with open(os.path.join(d, "latest"), "w") as f:
        f.write("ckpt_00000009.npz\n")
    step, tree = ckpt_io.load_latest(d)
    assert step == 3 and int(tree["x"]) == 3
    assert ckpt_io.load_latest(str(tmp_path / "nowhere")) is None


# ------------------------------------------------------ RoundState codec


_PARAMS = {"w": jnp.linspace(-1.0, 1.0, 8).reshape(4, 2),
           "b": jnp.asarray([0.5, -0.25], jnp.bfloat16)}


def _combo_cfg(ef, dlef, dld, num_clients=5):
    return fl.FLConfig(
        num_clients=num_clients, clients_per_round=3, local_steps=2,
        transport="int8" if ef else "f32",
        downlink="int8" if (dlef or dld) else "f32",
        error_feedback=ef, downlink_error_feedback=dlef,
        downlink_delta=dld)


@pytest.mark.parametrize("ef,dlef,dld",
                         list(itertools.product([False, True], repeat=3)))
def test_state_tree_roundtrip_every_optional_combo(tmp_path, ef, dlef, dld):
    """save(state_to_tree) -> load -> state_from_tree is the identity for
    every optional-field combination: same pytree structure as
    init_round_state and bitwise-equal leaves."""
    cfg = _combo_cfg(ef, dlef, dld)
    state = fl.init_round_state(cfg, _PARAMS, seed=3)
    p = str(tmp_path / "state")
    ckpt_io.save(p, fl.state_to_tree(state))
    back = fl.state_from_tree(cfg, ckpt_io.load(p))
    _assert_bitexact(back, state)


def test_state_from_tree_rejects_optional_field_mismatch(tmp_path):
    cfg_ef = _combo_cfg(True, False, False)
    tree = fl.state_to_tree(fl.init_round_state(cfg_ef, _PARAMS))
    with pytest.raises(ValueError, match="error_feedback=False"):
        fl.state_from_tree(_combo_cfg(False, False, False), tree)
    tree_plain = fl.state_to_tree(
        fl.init_round_state(_combo_cfg(False, False, False), _PARAMS))
    with pytest.raises(ValueError, match="no 'ef'"):
        fl.state_from_tree(cfg_ef, tree_plain)


def test_state_from_tree_validates_shape_and_dtype():
    cfg = _combo_cfg(True, False, False)
    tree = fl.state_to_tree(fl.init_round_state(cfg, _PARAMS))
    bad = dict(tree, prev_delta={"w": tree["prev_delta"]["w"],
                                 "b": jnp.zeros((3,), jnp.float32)})
    with pytest.raises(ValueError, match="prev_delta"):
        fl.state_from_tree(cfg, bad)
    # EF width must match THIS model's parameter count
    bad = dict(tree, ef=jnp.zeros((cfg.num_clients, 3), jnp.float32))
    with pytest.raises(ValueError, match="ef"):
        fl.state_from_tree(cfg, bad)
    with pytest.raises(ValueError, match="lacks required"):
        fl.state_from_tree(cfg, {k: v for k, v in tree.items()
                                 if k != "rng"})


def test_state_from_tree_rejects_legacy_prev_broadcast():
    """A checkpoint written by the shared-vector revision carries
    'prev_broadcast' instead of 'bcast' — its per-client decode bases
    are unrecoverable, so the codec refuses with a pointed error rather
    than silently resyncing every client."""
    cfg = _combo_cfg(False, False, True)
    tree = fl.state_to_tree(fl.init_round_state(cfg, _PARAMS))
    bcast = tree.pop("bcast")
    tree["prev_broadcast"] = bcast["head"]
    with pytest.raises(ValueError, match="prev_broadcast"):
        fl.state_from_tree(cfg, tree)


def test_state_from_tree_wraps_old_style_raw_key():
    cfg = _combo_cfg(False, False, False)
    tree = fl.state_to_tree(fl.init_round_state(cfg, _PARAMS))
    tree["rng"] = np.asarray(jax.random.PRNGKey(5))  # raw uint32 (2,)
    back = fl.state_from_tree(cfg, tree)
    assert jax.dtypes.issubdtype(back.rng.dtype, jax.dtypes.prng_key)
    np.testing.assert_array_equal(jax.random.key_data(back.rng),
                                  np.asarray(jax.random.PRNGKey(5)))


# ---------------------------------------------------- elastic-K restore


def test_elastic_k_repad_semantics():
    """K=10 -> 13: surviving clients' angle/EF rows restore bit-exactly,
    new clients start from zero residual and unseen angle. K=10 -> 7:
    departed clients' slots are dropped."""
    n = fl.param_count(_PARAMS)
    cfg10 = _combo_cfg(True, False, False, num_clients=10)
    st = fl.init_round_state(cfg10, _PARAMS, seed=1)
    st = st._replace(
        angle=fl.AngleState(
            smoothed=jnp.arange(10, dtype=jnp.float32) * 0.1,
            count=jnp.arange(10, dtype=jnp.int32)),
        ef=jnp.tile(jnp.arange(10, dtype=jnp.float32)[:, None], (1, n)))
    tree = fl.state_to_tree(st)

    b13 = fl.state_from_tree(_combo_cfg(True, False, False, 13), tree)
    assert b13.angle.smoothed.shape == (13,) and b13.ef.shape == (13, n)
    np.testing.assert_array_equal(b13.angle.smoothed[:10], st.angle.smoothed)
    np.testing.assert_array_equal(b13.angle.count[:10], st.angle.count)
    np.testing.assert_array_equal(np.asarray(b13.ef)[:10], np.asarray(st.ef))
    assert np.all(np.asarray(b13.angle.smoothed[10:]) == 0.0)
    assert np.all(np.asarray(b13.angle.count[10:]) == 0)
    assert np.all(np.asarray(b13.ef)[10:] == 0.0)

    b7 = fl.state_from_tree(_combo_cfg(True, False, False, 7), tree)
    assert b7.angle.count.shape == (7,) and b7.ef.shape == (7, n)
    np.testing.assert_array_equal(b7.angle.count,
                                  np.asarray(st.angle.count)[:7])
    np.testing.assert_array_equal(np.asarray(b7.ef), np.asarray(st.ef)[:7])
    # the K-independent pieces are untouched
    _assert_bitexact(b7.params, st.params)
    np.testing.assert_array_equal(jax.random.key_data(b7.rng),
                                  jax.random.key_data(st.rng))


def test_elastic_k_bcast_repad_semantics():
    """The broadcast-delta state is part K-dependent (ver) and part
    model-dependent (ring/head/head_ver). K=10 -> 13: surviving clients
    keep their last-pulled version bit-exactly, new clients start
    NEVER_PULLED (they must take a full resync). K=10 -> 7: departed
    clients' version rows are dropped. The ring, head, and head_ver are
    K-independent and restore bit-exactly in both directions."""
    cfg10 = _combo_cfg(False, False, True, num_clients=10)
    st = fl.init_round_state(cfg10, _PARAMS, seed=1)
    n = fl.param_count(_PARAMS)
    st = st._replace(bcast=st.bcast._replace(
        ring=st.bcast.ring.at[0].set(0.125),
        head=jnp.full((n,), 0.5, jnp.float32),
        head_ver=jnp.int32(4),
        ver=jnp.arange(10, dtype=jnp.int32) - 1))  # client 0 never pulled
    tree = fl.state_to_tree(st)

    b13 = fl.state_from_tree(_combo_cfg(False, False, True, 13), tree)
    assert b13.bcast.ver.shape == (13,)
    np.testing.assert_array_equal(np.asarray(b13.bcast.ver)[:10],
                                  np.asarray(st.bcast.ver))
    assert np.all(np.asarray(b13.bcast.ver)[10:]
                  == transport.downlink.NEVER_PULLED)
    _assert_bitexact((b13.bcast.ring, b13.bcast.head, b13.bcast.head_ver),
                     (st.bcast.ring, st.bcast.head, st.bcast.head_ver))

    b7 = fl.state_from_tree(_combo_cfg(False, False, True, 7), tree)
    assert b7.bcast.ver.shape == (7,)
    np.testing.assert_array_equal(np.asarray(b7.bcast.ver),
                                  np.asarray(st.bcast.ver)[:7])
    _assert_bitexact((b7.bcast.ring, b7.bcast.head, b7.bcast.head_ver),
                     (st.bcast.ring, st.bcast.head, st.bcast.head_ver))


# --------------------------------------- kill/resume golden invariance


@pytest.fixture(scope="module")
def golden_task():
    """The golden-convergence task: 12k-train image problem, 5 IID +
    non-IID one-class nodes (600 samples each), MLR, rounds-to-85%."""
    return synthetic.make_image_task(seed=0, num_train=12000, num_test=2000)


def _golden_server(task, cfg, num_nodes=None, seed=0):
    train, test = task
    spec = [("iid", None)] * 5 + [("xclass", 1)] * 8
    nodes = synthetic.make_federated(
        train, spec[:num_nodes or cfg.num_clients],
        samples_per_node=600, seed=1)
    return FedServer("mlr", cfg, nodes, test, batch_size=50, seed=seed)


WIRES = [("f32", "f32"), ("int4", "int8")]


@pytest.mark.parametrize("uplink,downlink", WIRES)
def test_kill_resume_scanned_invariance(tmp_path, golden_task, uplink,
                                        downlink):
    """Tier-1 gate: a scanned run killed at ANY block boundary and
    resumed from the checkpoint reproduces the uninterrupted run —
    bit-identical final RoundState (params, angle, EF, rng, round) and
    the identical per-round accuracy trace, hence identical
    rounds-to-85%."""
    rounds, block, target = 6, 2, 0.85
    cfg = fl.FLConfig(num_clients=10, clients_per_round=10, local_steps=12,
                      method="fedadp", engine="flat", transport=uplink,
                      downlink=downlink, base_lr=0.05)
    d = str(tmp_path / "ckpts")
    ref = _golden_server(golden_task, cfg)
    h_ref = ref.run_scanned(rounds, eval_every=1, block=block,
                            ckpt_dir=d, ckpt_keep=0)
    acc_ref = np.asarray(h_ref.accuracy)
    hits = np.flatnonzero(acc_ref >= target)
    assert hits.size, f"golden task no longer reaches {target}: {acc_ref}"
    rtt_ref = int(hits[0]) + 1

    edges = {step: path for step, path in ckpt_io.list_checkpoints(d)}
    assert sorted(edges) == [2, 4, 6]  # every block boundary snapshotted
    for edge in (2, 4):  # kill points strictly inside the run
        res = _golden_server(golden_task, cfg)
        assert res.restore(edges[edge]) == edge
        h_res = res.run_scanned(rounds - edge, eval_every=1, block=block)
        # identical accuracy tail => identical rounds-to-target
        np.testing.assert_array_equal(np.asarray(h_res.accuracy),
                                      acc_ref[edge:])
        _assert_bitexact(res.state, ref.state, what=f"edge {edge}: ")

    # absolute rounds-to-target bookkeeping through a resumed early-exit
    res = _golden_server(golden_task, cfg)
    res.restore(edges[2])
    h = res.run_scanned(rounds - 2, target_acc=target, eval_every=1,
                        block=block)
    assert h.rounds_to_target == rtt_ref


def test_kill_resume_stepwise_invariance(tmp_path, golden_task):
    """The stepwise path (one jit dispatch per round) restores just as
    bit-exactly: 3 rounds + save + restore + 3 rounds == 6 rounds."""
    cfg = fl.FLConfig(num_clients=10, clients_per_round=10, local_steps=12,
                      method="fedadp", engine="flat", base_lr=0.05)
    ref = _golden_server(golden_task, cfg)
    for _ in range(6):
        ref.step()

    part = _golden_server(golden_task, cfg)
    for _ in range(3):
        part.step()
    d = str(tmp_path / "ckpts")
    part.save_checkpoint(d)
    res = _golden_server(golden_task, cfg)
    assert res.restore(d) == 3
    for _ in range(3):
        res.step()
    assert res.round == 6
    _assert_bitexact(res.state, ref.state)


def test_kill_resume_subset_selection_downlink_delta(tmp_path,
                                                     golden_task):
    """Kill/resume with the per-client broadcast state in play: 5-of-10
    subset selection + delta-encoded downlink, so the checkpoint carries
    a mid-flight ring, chain head, and staggered per-client versions.
    The resumed run must reproduce the uninterrupted one bit-exactly —
    state AND accuracy trace (the 85%-target assertion is owned by the
    full-participation legs; subset selection converges slower)."""
    rounds, block = 6, 2
    cfg = fl.FLConfig(num_clients=10, clients_per_round=5, local_steps=12,
                      method="fedadp", engine="flat", downlink="int8",
                      downlink_delta=True, base_lr=0.05)
    d = str(tmp_path / "ckpts")
    ref = _golden_server(golden_task, cfg)
    h_ref = ref.run_scanned(rounds, eval_every=1, block=block,
                            ckpt_dir=d, ckpt_keep=0)
    # the checkpointed state really is mid-stream per-client state:
    # chain advanced every round, versions staggered by selection
    assert int(ref.state.bcast.head_ver) == rounds - 1
    ver = np.asarray(ref.state.bcast.ver)
    assert len(set(ver.tolist())) > 1, f"degenerate schedule: {ver}"

    edges = dict(ckpt_io.list_checkpoints(d))
    for edge in (2, 4):
        res = _golden_server(golden_task, cfg)
        assert res.restore(edges[edge]) == edge
        h_res = res.run_scanned(rounds - edge, eval_every=1, block=block)
        np.testing.assert_array_equal(np.asarray(h_res.accuracy),
                                      np.asarray(h_ref.accuracy)[edge:])
        _assert_bitexact(res.state, ref.state, what=f"edge {edge}: ")


def test_elastic_k_restore_converges(tmp_path, golden_task):
    """Acceptance: a K=10 checkpoint restores into K=13 and K=7 fleets,
    new clients start unseen (EF zero / angle count zero), and both
    resumed fleets still reach the 85% target."""
    mk = lambda k: fl.FLConfig(  # noqa: E731
        num_clients=k, clients_per_round=k, local_steps=12,
        method="fedadp", engine="flat", transport="int8",
        error_feedback=True, base_lr=0.05)
    d = str(tmp_path / "ckpts")
    s10 = _golden_server(golden_task, mk(10))
    s10.run_scanned(2, eval_every=0, block=2, ckpt_dir=d)

    for k in (13, 7):
        sk = _golden_server(golden_task, mk(k))
        assert sk.restore(d) == 2
        counts = np.asarray(sk.state.angle.count)
        ef = np.asarray(sk.state.ef)
        if k > 10:  # new clients: unseen angle, zero residual
            assert np.all(counts[10:] == 0) and np.all(ef[10:] == 0.0)
        assert np.all(counts[:min(k, 10)] == 2)  # survivors keep history
        h = sk.run_scanned(40, target_acc=0.85, eval_every=1, block=4)
        assert h.rounds_to_target is not None, f"K={k} failed to converge"


def test_kill_resume_flat_sharded_8device_subprocess(tmp_path):
    """The checkpoint layer composes with the client-sharded engine: on
    an 8-way host-device mesh, kill/resume of a scanned flat_sharded run
    restores bit-exactly."""
    prog = textwrap.dedent("""
        import os, sys, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.checkpoint import io as ckpt_io
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
                          transport="int8", error_feedback=True,
                          base_lr=0.05)
        d = tempfile.mkdtemp()
        mk = lambda: FedServer("mlr", cfg, nodes, test, batch_size=50,
                               seed=0, mesh=mesh)
        ref = mk()
        ref.run_scanned(4, eval_every=1, block=2, ckpt_dir=d)
        res = mk()
        step, tree = ckpt_io.load_latest(d)
        assert step == 4
        res.restore(ckpt_io.checkpoint_path(d, 2))
        res.run_scanned(2, eval_every=1, block=2)
        for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(res.state)):
            if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        print("RESUME_SHARDED_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "RESUME_SHARDED_OK" in out.stdout, out.stderr[-2000:]
