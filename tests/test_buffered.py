"""Buffered-async aggregation server tests (FLConfig(aggregation="buffered")).

Four layers of pinning:

* sync-equivalence — with buffer_m == K and no stragglers/dropouts the
  buffered round reduces to the synchronous round through masked
  identities (where(True, x) == x, exp(-0) == 1, x - 0.0 == x), so the
  trajectories must agree per engine: tree/flat f32 bit-for-bit over
  multiple rounds, quantized flat wires one round at 1e-5 (the fused
  dequant kernels reassociate fp against the buffered dequant-then-plain
  path; over rounds the int4 quantizer amplifies that chaotically, which
  is expected and NOT a regression).
* flush semantics — an explicit fixed_arrival_schedule drives the
  admission/landing/flush state machine tick by tick: no flush below
  buffer_m, a straggler lands late and is aggregated at age > 0 with the
  staleness discount, a dropped report never lands, the buffer drains
  back to all-free.
* convergence — the golden buffered section (tests/golden/
  convergence.json, regenerated by scripts/gen_golden_convergence.py)
  re-runs within 10% both ways, and stays within 1.1x of the SYNC golden
  rounds — the acceptance bound — on the f32/f32 and int4/int8 wires; an
  8-host-device subprocess pins the same through engine="flat_sharded".
* state — ReportBuffer round-trips through the RoundState codec, codec
  config/checkpoint mismatches raise, and a buffered kill/resume
  reproduces the uninterrupted run bit-for-bit.
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

import repro
from repro.core import buffer as buffer_mod
from repro.core import driver, fl, weighting
from repro.data import synthetic
from repro.launch.mesh import make_mesh

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "convergence.json")


# ------------------------------------------------------------- unit layer


def test_staleness_discount_and_weight_decay():
    age = jnp.asarray([0, 1, 2, 4], jnp.int32)
    d = weighting.staleness_discount(age, beta=0.5)
    assert float(d[0]) == 1.0
    assert np.all(np.diff(np.asarray(d)) < 0)
    # two otherwise-identical landed reports: the older one's weight is
    # suppressed by exactly exp(-beta * age) relative to the fresh one
    theta = jnp.full((4,), 0.7)
    sizes = jnp.full((4,), 30.0)
    landed = jnp.asarray([True, True, False, False])
    w = weighting.buffered_fedadp_weights(theta, sizes, age, landed,
                                          alpha=5.0, beta=0.5)
    np.testing.assert_allclose(float(w[1] / w[0]), np.exp(-0.5), rtol=1e-6)
    assert float(w[2]) == 0.0 and float(w[3]) == 0.0
    np.testing.assert_allclose(float(jnp.sum(w)), 1.0, rtol=1e-6)
    wa = weighting.buffered_fedavg_weights(sizes, age, landed, beta=0.5)
    np.testing.assert_allclose(float(wa[1] / wa[0]), np.exp(-0.5), rtol=1e-6)
    assert float(wa[2]) == 0.0 and float(wa[3]) == 0.0


def test_buffered_weights_reduce_to_sync_at_age_zero():
    """All landed at age 0 must reproduce the synchronous Eq. 11 / Eq. 1
    weights exactly — the identity that pins buffered == sync."""
    theta = jnp.asarray([0.3, 0.7, 1.1])
    sizes = jnp.asarray([10.0, 20.0, 30.0])
    zero = jnp.zeros((3,), jnp.int32)
    all_landed = jnp.ones((3,), bool)
    w_b = weighting.buffered_fedadp_weights(theta, sizes, zero, all_landed,
                                            alpha=5.0, beta=0.9)
    w_s = weighting.fedadp_weights(theta, sizes, alpha=5.0)
    np.testing.assert_array_equal(np.asarray(w_b), np.asarray(w_s))


def test_report_buffer_state_machine():
    buf = buffer_mod.init_report_buffer(3, 4)
    assert bool(jnp.all(buf.free))
    rows = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    sel = jnp.asarray([5, 1, 3], jnp.int32)
    sizes = jnp.asarray([10.0, 20.0, 30.0])
    delay = jnp.asarray([0, 2, 0], jnp.int32)
    admit_mask = jnp.asarray([True, True, False])  # row 2's report dropped
    buf = buffer_mod.admit(buf, admit_mask, rows, sel, sizes, delay)
    busy = buffer_mod.population_busy(buf, 8)
    assert busy.tolist() == [False, True, False, False, False, True,
                             False, False]
    landed = buffer_mod.landed_mask(buf)
    assert landed.tolist() == [True, False, False]
    # flush the landed row: it frees, the straggler survives and ages
    buf = buffer_mod.advance(buf, landed, jnp.asarray(True))
    assert buf.free.tolist() == [True, False, True]
    assert buf.age.tolist() == [0, 1, 0]
    assert buf.wait.tolist() == [0, 1, 0]
    # next tick the straggler lands; a no-flush tick must not age it
    landed = buffer_mod.landed_mask(
        buf._replace(wait=jnp.maximum(buf.wait - 1, 0)))
    assert landed.tolist() == [False, True, False]


def test_select_clients_avoiding_prefers_free_clients():
    key = jax.random.key(0)
    busy = jnp.asarray([False, True, False, True, False, False])
    for i in range(5):
        sel = driver.select_clients_avoiding(jax.random.fold_in(key, i),
                                             6, 3, busy)
        assert len(set(sel.tolist())) == 3
        assert not (set(sel.tolist()) & {1, 3})
    # full participation stays the deterministic identity
    sel = driver.select_clients_avoiding(key, 4, 4, jnp.zeros((4,), bool))
    assert sel.tolist() == [0, 1, 2, 3]


# ------------------------------------------- sync-equivalence (round_fn)


def _toy_problem(K=4, tau=3, B=8, d=12, seed=0):
    rng = np.random.default_rng(seed)
    params = {"w": jnp.zeros((d, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}
    X = rng.normal(size=(K, tau, B, d)).astype(np.float32)
    w_true = rng.normal(size=(K, d, 1)).astype(np.float32)
    Y = np.einsum("ktbd,kde->ktbe", X, w_true)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, loss_fn, (jnp.asarray(X), jnp.asarray(Y))


def _run_rounds(cfg, rounds, sel, mesh=None, angle_pred=None):
    params, loss_fn, batches = _toy_problem(K=len(sel))
    rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=mesh,
                                  angle_pred=angle_pred))
    st = fl.init_round_state(cfg, params)
    sizes = jnp.asarray([10.0 * (i + 1) for i in range(len(sel))])
    ws = []
    for _ in range(rounds):
        st, m = rf(st, batches, jnp.asarray(sel, jnp.int32), sizes)
        ws.append(np.asarray(m["weights"]))
    return st, ws


def _drop_b(keys, leaf):
    """Angle filter: the toy's bias leaves the statistics."""
    return keys[-1] != "b"


# (engine, uplink, downlink, error_feedback, method, angle_pred, rounds,
# atol): f32 paths are bit-exact over multiple rounds; quantized flat
# wires pin one round at 1e-5 (fused-dequant vs dequant-then-aggregate fp
# reassociation). Under FedAvg the flat engines' params stay bit-exact
# but their angles, which weigh nothing there, differ by an ulp between
# the two programs (XLA fuses the shared g differently).
EQUIV_CASES = [
    ("tree", "f32", "f32", False, "fedadp", None, 3, 0.0),
    ("flat", "f32", "f32", False, "fedadp", None, 3, 0.0),
    ("tree", "int8", "f32", True, "fedadp", None, 3, 0.0),
    ("flat", "int4", "int8", False, "fedadp", None, 1, 1e-5),
    ("flat_sharded", "int8", "int8", True, "fedadp", None, 1, 1e-5),
    ("flat_sharded", "f32", "f32", False, "fedadp", None, 3, 0.0),
    ("flat_sharded", "int4", "int8", False, "fedadp", None, 1, 1e-5),
    ("tree", "f32", "f32", False, "fedavg", None, 3, 0.0),
    ("flat", "f32", "f32", False, "fedavg", None, 3, 1e-6),
    ("flat_sharded", "f32", "f32", False, "fedavg", None, 3, 1e-6),
    ("tree", "f32", "f32", False, "fedadp", _drop_b, 3, 0.0),
    ("flat", "f32", "f32", False, "fedadp", _drop_b, 3, 0.0),
    ("flat_sharded", "f32", "f32", False, "fedadp", _drop_b, 3, 0.0),
]


def _equiv_id(case):
    engine, uplink, downlink, ef, method, pred, rounds, atol = case
    tags = [engine, uplink, downlink, ef, rounds, atol]
    tags += [method] if method != "fedadp" else []
    tags += [pred.__name__.strip("_")] if pred else []
    return "-".join(map(str, tags))


@pytest.mark.parametrize(
    "engine,uplink,downlink,ef,method,angle_pred,rounds,atol", EQUIV_CASES,
    ids=[_equiv_id(c) for c in EQUIV_CASES])
def test_buffered_full_cohort_matches_sync(engine, uplink, downlink, ef,
                                           method, angle_pred, rounds, atol):
    """buffer_m == K with no stragglers admits, lands, and flushes the
    whole cohort every tick — the sync round, engine by engine, through
    the engine step both share. Subset selection (8-client population,
    explicit 3-cohort) exercises the masked Eq. 9 scatter and the EF rows
    under buffering."""
    mesh = make_mesh((1,), ("data",)) if engine == "flat_sharded" \
        else None
    base = dict(num_clients=8, clients_per_round=3, local_steps=3,
                method=method, base_lr=0.05, engine=engine,
                transport=uplink, downlink=downlink, error_feedback=ef,
                group_size=512)
    sel = [1, 4, 6]
    st_s, w_s = _run_rounds(fl.FLConfig(**base), rounds, sel, mesh=mesh,
                            angle_pred=angle_pred)
    st_b, w_b = _run_rounds(
        fl.FLConfig(**base, aggregation="buffered"), rounds, sel, mesh=mesh,
        angle_pred=angle_pred)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=atol),
        st_s.params, st_b.params)
    np.testing.assert_allclose(np.asarray(st_s.angle.smoothed),
                               np.asarray(st_b.angle.smoothed), atol=atol)
    assert st_s.angle.count.tolist() == st_b.angle.count.tolist()
    np.testing.assert_allclose(w_s, w_b, atol=max(atol, 1e-7))
    if ef:
        np.testing.assert_allclose(np.asarray(st_s.ef),
                                   np.asarray(st_b.ef), rtol=0, atol=atol)
    # the buffer fully drained: every tick flushed the whole cohort
    assert bool(jnp.all(st_b.buf.free))
    assert int(jnp.max(st_b.buf.age)) == 0


# ----------------------------------------- flush semantics (FedServer)


def _small_task(seed=0):
    train, test = synthetic.make_image_task(seed=seed, num_train=3000,
                                            num_test=400)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * 2 + [("xclass", 1)] * 2,
        samples_per_node=200, seed=1)
    return nodes, test


def _server(cfg, arrival_fn=None, seed=0):
    nodes, test = _small_task()
    return repro.FedServer("mlr", cfg, nodes, test, batch_size=50,
                           seed=seed, arrival_fn=arrival_fn)


def test_fixed_schedule_flush_semantics():
    """Tick-by-tick against an explicit schedule (K=4, buffer_m=3):
    tick 0 admits 3 (one dropped), only 2 land -> NO flush, params frozen;
    tick 1 re-admits the dropped client, 3 land -> flush while the
    straggler waits; tick 2 the straggler lands at age 1 (one model
    version behind) and the buffer drains to all-free."""
    delays = np.zeros((4, 4), np.int32)
    drops = np.zeros((4, 4), bool)
    delays[0, 1] = 2   # client 1 straggles 2 ticks
    drops[0, 2] = True  # client 2's tick-0 report is lost in transit
    cfg = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                         base_lr=0.05, aggregation="buffered", buffer_m=3,
                         staleness_beta=0.5)
    s = _server(cfg, arrival_fn=repro.fixed_arrival_schedule(delays, drops))
    p0 = jax.device_get(s.state.params)

    m = s.step()
    assert int(m["flushed"]) == 0 and int(m["buffer_landed"]) == 2
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p0, jax.device_get(s.state.params))
    assert s.state.buf.free.tolist() == [False, False, True, False]

    m = s.step()
    assert int(m["flushed"]) == 1 and int(m["buffer_landed"]) == 3
    assert float(m["staleness"]) == 0.0
    # the straggler's row survived the flush and aged one model version
    assert s.state.buf.free.tolist() == [True, False, True, True]
    assert s.state.buf.age.tolist() == [0, 1, 0, 0]

    m = s.step()
    assert int(m["flushed"]) == 1 and int(m["buffer_landed"]) == 4
    np.testing.assert_allclose(float(m["staleness"]), 1.0 / 4.0, rtol=1e-6)
    # the stale report was aggregated, not given full weight: its
    # softmax logit carried -beta*age
    assert float(m["weights"][1]) > 0.0
    assert bool(jnp.all(s.state.buf.free))


def test_buffered_scanned_matches_stepwise():
    """RoundState.buf is part of the scan carry: the chunked-scan driver
    must reproduce the stepwise buffered trajectory exactly, mid-flight
    reports included."""
    delays = np.zeros((6, 4), np.int32)
    drops = np.zeros((6, 4), bool)
    delays[0, 1] = 1
    delays[2, 3] = 2
    drops[1, 0] = True
    arrival = repro.fixed_arrival_schedule(delays, drops)
    cfg = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                         base_lr=0.05, aggregation="buffered", buffer_m=3,
                         staleness_beta=0.5)
    s_loop = _server(cfg, arrival_fn=arrival)
    s_scan = _server(cfg, arrival_fn=arrival)
    h_loop = s_loop.run(6, eval_every=2)
    h_scan = s_scan.run(6, eval_every=2, mode="scanned", block=4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        s_loop.state.params, s_scan.state.params)
    np.testing.assert_array_equal(np.asarray(s_loop.state.buf.free),
                                  np.asarray(s_scan.state.buf.free))
    np.testing.assert_array_equal(np.asarray(s_loop.state.buf.age),
                                  np.asarray(s_scan.state.buf.age))
    np.testing.assert_allclose(h_loop.loss, h_scan.loss, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(h_loop.accuracy, h_scan.accuracy, atol=1e-6)


def test_stochastic_arrivals_are_seed_deterministic():
    """The device-RNG straggler/dropout draw is part of the carried rng:
    two servers with the same seed walk the same schedule."""
    cfg = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                         base_lr=0.05, aggregation="buffered", buffer_m=3,
                         straggle_prob=0.3, straggle_max=2,
                         dropout_prob=0.2)
    a, b = _server(cfg, seed=7), _server(cfg, seed=7)
    for _ in range(5):
        ma, mb = a.step(), b.step()
        assert int(ma["flushed"]) == int(mb["flushed"])
        assert int(ma["buffer_landed"]) == int(mb["buffer_landed"])
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)),
        jax.device_get(a.state.params), jax.device_get(b.state.params))


def test_draw_arrivals_honors_zero_straggle_max():
    """straggle_max=0 means stragglers are IMPOSSIBLE: every delay is 0,
    even at straggle_prob=1. The old max(straggle_max, 1) clamp silently
    minted 1-tick delays instead. The drop stream must not depend on
    whether straggling is enabled (same key split both ways)."""
    key = jax.random.PRNGKey(3)
    delay, drop = buffer_mod.draw_arrivals(
        key, 8, straggle_prob=1.0, straggle_max=0, dropout_prob=0.5)
    assert delay.tolist() == [0] * 8
    _, drop_on = buffer_mod.draw_arrivals(
        key, 8, straggle_prob=1.0, straggle_max=3, dropout_prob=0.5)
    np.testing.assert_array_equal(np.asarray(drop), np.asarray(drop_on))
    # and with straggling enabled the same draw does mint delays in range
    delay_on, _ = buffer_mod.draw_arrivals(
        key, 64, straggle_prob=1.0, straggle_max=3, dropout_prob=0.0)
    assert int(jnp.min(delay_on)) >= 1 and int(jnp.max(delay_on)) <= 3


# --------------------------------------------------- state / checkpoint


def test_buffered_state_roundtrips_through_codec(tmp_path):
    """A mid-flight ReportBuffer survives state_to_tree/state_from_tree,
    and the restored server finishes the run bit-for-bit with the
    uninterrupted one (buffered kill/resume invariance)."""
    delays = np.zeros((8, 4), np.int32)
    drops = np.zeros((8, 4), bool)
    delays[0, 1] = 3   # still in flight at the checkpoint tick
    drops[2, 3] = True
    arrival = repro.fixed_arrival_schedule(delays, drops)
    cfg = repro.FLConfig(num_clients=4, clients_per_round=4, local_steps=4,
                         base_lr=0.05, aggregation="buffered", buffer_m=3,
                         staleness_beta=0.5)
    s_full = _server(cfg, arrival_fn=arrival)
    s_half = _server(cfg, arrival_fn=arrival)
    s_full.run(6, eval_every=0)
    s_half.run(3, eval_every=0)
    assert not bool(jnp.all(s_half.state.buf.free))  # buf is non-trivial

    tree = repro.state_to_tree(s_half.state)
    st2 = repro.state_from_tree(cfg, tree)
    for field in ("data", "slot", "sizes", "age", "wait", "free"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_half.state.buf, field)),
            np.asarray(getattr(st2.buf, field)))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(s_half.state.rng)),
        np.asarray(jax.random.key_data(st2.rng)))

    path = s_half.save_checkpoint(str(tmp_path))
    s_resume = _server(cfg, arrival_fn=arrival)
    assert s_resume.restore(path) == 3
    s_resume.run(3, eval_every=0)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        jax.device_get(s_full.state.params),
        jax.device_get(s_resume.state.params))
    np.testing.assert_array_equal(np.asarray(s_full.state.buf.age),
                                  np.asarray(s_resume.state.buf.age))


def test_codec_rejects_aggregation_mismatch():
    cfg_b = repro.FLConfig(num_clients=4, clients_per_round=4,
                           local_steps=4, aggregation="buffered")
    cfg_s = repro.FLConfig(num_clients=4, clients_per_round=4,
                           local_steps=4)
    params = {"w": jnp.zeros((3, 2)), "b": jnp.zeros((2,))}
    tree_b = repro.state_to_tree(repro.init_round_state(cfg_b, params))
    tree_s = repro.state_to_tree(repro.init_round_state(cfg_s, params))
    with pytest.raises(ValueError, match="has no 'buf'"):
        repro.state_from_tree(cfg_b, tree_s)
    with pytest.raises(ValueError, match="carries 'buf'"):
        repro.state_from_tree(cfg_s, tree_b)


# -------------------------------------------------- golden convergence


def _golden_buffered():
    with open(GOLDEN) as f:
        return json.load(f)


def _arrival_from_schedule(sched):
    delays = np.zeros((sched["ticks"], sched["num_clients"]), np.int32)
    drops = np.zeros_like(delays, bool)
    for t, k in sched["stragglers"]:
        delays[t, k] = sched["delay"]
    for t, k in sched["drops"]:
        drops[t, k] = True
    return repro.fixed_arrival_schedule(delays, drops)


def _ratio_ok(rounds, reference, bound=1.1):
    return (rounds is not None and reference is not None
            and rounds <= bound * reference)


def test_golden_buffered_within_acceptance_of_sync():
    """The committed buffered golden itself must satisfy the acceptance
    bound: under the fixed straggler/dropout schedule, buffered fedadp
    reaches the target within 1.1x of the SYNC golden's rounds on both
    the reference and the fully-compressed wire."""
    g = _golden_buffered()
    for key, ticks in g["buffered"]["entries"].items():
        assert isinstance(ticks, int), (key, ticks)
        assert _ratio_ok(ticks, g["entries"][key]), (
            key, ticks, g["entries"][key])


@pytest.mark.parametrize("uplink,downlink",
                         [("f32", "f32"), ("int4", "int8")])
def test_golden_buffered_reproduces(uplink, downlink):
    """Recomputed buffered ticks-to-target must match the golden within
    10% in BOTH directions — same task, same fixed arrival schedule."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import node_spec, run_fl

    g = _golden_buffered()
    t = g["buffered"]["task"]
    hist, _ = run_fl(
        "fedadp", node_spec(5, 5, 1), rounds=t["max_rounds"],
        target=t["target"], engine=t["engine"], transport=uplink,
        downlink=downlink, group_size=t["group_size"], seed=t["seed"],
        eval_every=t["eval_every"], aggregation="buffered",
        buffer_m=t["buffer_m"], staleness_beta=t["staleness_beta"],
        arrival_fn=_arrival_from_schedule(t["schedule"]),
    )
    golden = g["buffered"]["entries"][f"fedadp/{uplink}/{downlink}"]
    got = hist.rounds_to_target
    assert _ratio_ok(got, golden) and _ratio_ok(golden, got), (got, golden)


def test_golden_buffered_sharded_subprocess_quantized():
    """engine="flat_sharded" on an 8-way host-device mesh must converge
    in the golden's ticks under the golden arrival schedule with the
    fully-compressed wire — the buffered flush region (padded rows,
    zero-weight masking, staleness logits) pinned over a real trajectory."""
    g = _golden_buffered()
    golden = g["buffered"]["entries"]["fedadp/int4/int8"]
    t = g["buffered"]["task"]
    sched = json.dumps(t["schedule"])
    prog = f"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.mesh import make_mesh
import numpy as np
import repro
from benchmarks.common import node_spec, run_fl
s = json.loads('''{sched}''')
delays = np.zeros((s["ticks"], s["num_clients"]), np.int32)
drops = np.zeros_like(delays, bool)
for tk, k in s["stragglers"]:
    delays[tk, k] = s["delay"]
for tk, k in s["drops"]:
    drops[tk, k] = True
mesh = make_mesh((8,), ("data",))
hist, _ = run_fl(
    "fedadp", node_spec(5, 5, 1), rounds={t["max_rounds"]},
    target={t["target"]}, engine="flat_sharded", transport="int4",
    downlink="int8", group_size={t["group_size"]}, seed={t["seed"]},
    eval_every={t["eval_every"]}, mesh=mesh, aggregation="buffered",
    buffer_m={t["buffer_m"]}, staleness_beta={t["staleness_beta"]},
    arrival_fn=repro.fixed_arrival_schedule(delays, drops))
print("ROUNDS_TO_TARGET", hist.rounds_to_target)
"""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "ROUNDS_TO_TARGET" in out.stdout, out.stderr[-2000:]
    got = out.stdout.split("ROUNDS_TO_TARGET", 1)[1].split()[0]
    got = None if got == "None" else int(got)
    assert _ratio_ok(got, golden) and _ratio_ok(golden, got), (got, golden)


def test_buffered_flush_2d_mesh_matches_sync_and_tree_subprocess():
    """Buffered flush on a 2D (client x model) mesh: the flush region
    tiles the buffer's COLUMNS over the model axis (with N=110 not
    divisible by the model-axis size 4, pinning the zero-column padding
    and output slicing). Admission is engine-independent (the buffer
    holds dequantized f32 rows), so buffered flat_sharded on the 2D mesh
    must match BOTH sync flat_sharded on the same mesh (full-cohort
    flush == the sync round) and the buffered tree engine, per
    transport."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import fl
        K, d, h, tau, B = 3, 12, 8, 2, 4
        NC = 8
        rng = np.random.default_rng(3)
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
        sel = jnp.asarray([1, 4, 6], jnp.int32)
        sizes = jnp.asarray([10.0, 25.0, 40.0], np.float32)
        mesh = make_mesh((2, 4), ("data", "model"))
        def run(engine, m, tr, buffered):
            cfg = fl.FLConfig(num_clients=NC, clients_per_round=K,
                              local_steps=tau, method="fedadp", engine=engine,
                              transport=tr, group_size=8, base_lr=0.05,
                              **({"aggregation": "buffered"} if buffered else {}))
            rf = jax.jit(fl.make_round_fn(loss_fn, cfg, mesh=m))
            st = fl.init_round_state(cfg, params)
            import contextlib
            ctx = m if m is not None else contextlib.nullcontext()
            with ctx:
                for r in range(2):
                    st, met = rf(st, (X, Y), sel, sizes)
            return st, met
        for tr in ("f32", "int8"):
            st_b2, m_b2 = run("flat_sharded", mesh, tr, True)
            st_bt, m_bt = run("tree", None, tr, True)
            refs = [("buffered-tree", (st_bt, m_bt), 1e-5)]
            if tr == "f32":
                # quantized transports compress at ADMISSION in global
                # tree-ravel order (the buffer holds dequantized f32
                # rows), while the sync 2D engine quantizes shard-local
                # blocked chunks in-region — only the uncompressed wire
                # is sync-identical on the 2D mesh.
                st_s2, m_s2 = run("flat_sharded", mesh, tr, False)
                refs.insert(0, ("sync-2d", (st_s2, m_s2), 1e-6))
            for ref_name, (st_r, m_r), atol in refs:
                for ka in params:
                    np.testing.assert_allclose(
                        np.asarray(st_b2.params[ka], np.float32),
                        np.asarray(st_r.params[ka], np.float32),
                        rtol=1e-5, atol=atol,
                        err_msg=f"{tr} vs {ref_name} leaf {ka}")
                np.testing.assert_allclose(
                    np.asarray(m_b2["weights"]), np.asarray(m_r["weights"]),
                    rtol=1e-5, atol=atol, err_msg=f"{tr} vs {ref_name}")
            assert bool(jnp.all(st_b2.buf.free))
        print("BUFFERED_2D_OK")
    """)
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "BUFFERED_2D_OK" in out.stdout, out.stderr[-2000:]
