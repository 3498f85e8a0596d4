"""Golden convergence regression — the paper's Table-I claim as a test.

tests/golden/convergence.json pins fixed-seed rounds-to-85% for fedadp vs
fedavg on the 5 IID + 5 one-class synthetic task across EVERY (uplink,
downlink) wire pair (scripts/gen_golden_convergence.py regenerates it).
Three layers of pinning:

* the committed file itself must satisfy the paper's claims (fedadp <=
  fedavg per pair; every wire within 10% of the f32/f32 reference) — a
  regenerated golden that violates them cannot be committed green;
* a re-run subset must reproduce the golden counts within the same 10%
  bound (catching silent convergence regressions, not just file edits);
* an 8-host-device subprocess re-runs the fully-compressed pair
  (int4 uplink + int8 downlink) through engine="flat_sharded", so the
  sharded engine's convergence — not merely its one-round numerics — is
  pinned under the bidirectional quantized wire.
"""
import json
import os
import subprocess
import sys

import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "convergence.json")


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


def _ratio_ok(rounds, reference, bound=1.1):
    return (rounds is not None and reference is not None
            and rounds <= bound * reference)


def test_golden_file_exists_and_is_complete():
    g = _golden()
    from repro import transport

    want = {f"{m}/{u}/{d}"
            for m in ("fedadp", "fedavg")
            for u in transport.TRANSPORTS
            for d in transport.DOWNLINKS}
    assert set(g["entries"]) == want
    # every wire pair REACHED the target inside the budget — a null here
    # means compression broke convergence outright
    assert all(isinstance(v, int) for v in g["entries"].values()), g["entries"]


def test_golden_fedadp_beats_fedavg_per_wire_pair():
    """Table I, per transport: adaptive weighting must reduce rounds under
    every wire pair, compressed or not."""
    e = _golden()["entries"]
    for key, rounds in e.items():
        if not key.startswith("fedadp/"):
            continue
        avg = e["fedavg/" + key.split("/", 1)[1]]
        assert rounds <= avg, (key, rounds, avg)


def test_golden_transport_ratio_within_10pct():
    """Compression must not cost rounds: every (uplink, downlink) pair
    stays within 1.1x of that method's f32/f32 reference — int4 and the
    quantized downlinks included (the acceptance bound)."""
    e = _golden()["entries"]
    for method in ("fedadp", "fedavg"):
        ref = e[f"{method}/f32/f32"]
        for key, rounds in e.items():
            if key.startswith(method + "/"):
                assert _ratio_ok(rounds, ref), (key, rounds, ref)


# the re-run subset: the reference, the fully-compressed fedadp pair, an
# intermediate pair, and the slowest fedavg wire (the 1.1-bound extreme)
REPRO_CASES = [
    ("fedadp", "f32", "f32"),
    ("fedadp", "int4", "int8"),
    ("fedadp", "int8", "bf16"),
    ("fedavg", "int4", "int8"),
]


@pytest.mark.parametrize("method,uplink,downlink", REPRO_CASES)
def test_golden_reproduces(method, uplink, downlink):
    """Recomputed rounds-to-target must match the golden within the 10%
    acceptance band in BOTH directions (neither regressed nor silently
    shifted) — same task inputs, fixed seed."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import node_spec, run_fl

    g = _golden()
    task = g["task"]
    hist, _ = run_fl(
        method, node_spec(5, 5, 1), rounds=task["max_rounds"],
        target=task["target"], engine=task["engine"], transport=uplink,
        downlink=downlink, group_size=task["group_size"],
        seed=task["seed"], eval_every=task["eval_every"],
    )
    golden = g["entries"][f"{method}/{uplink}/{downlink}"]
    got = hist.rounds_to_target
    assert _ratio_ok(got, golden) and _ratio_ok(golden, got), (got, golden)


def test_golden_delta_section_complete_and_claims_hold():
    """The subset-selection delta-downlink section: every delta wire pair
    plus the per-method f32/f32 reference is present and reached the
    target; fedadp <= fedavg per wire; every delta wire within 1.1x of
    that method's plain-broadcast reference under the SAME 5-of-10
    selection (delta encoding must not cost rounds)."""
    d = _golden()["delta"]
    wires = [tuple(w) for w in d["wires"]]
    want = {f"{m}/{u}/{dn}"
            for m in ("fedadp", "fedavg")
            for u, dn in [("f32", "f32")] + wires}
    assert set(d["entries"]) == want
    assert all(isinstance(v, int) for v in d["entries"].values()), d["entries"]
    assert d["task"]["clients_per_round"] < 10  # genuinely partial
    for u, dn in wires:
        assert d["entries"][f"fedadp/{u}/{dn}"] <= d["entries"][f"fedavg/{u}/{dn}"]
    for method in ("fedadp", "fedavg"):
        ref = d["entries"][f"{method}/f32/f32"]
        for u, dn in wires:
            rounds = d["entries"][f"{method}/{u}/{dn}"]
            assert _ratio_ok(rounds, ref), (method, u, dn, rounds, ref)


@pytest.mark.parametrize("method,uplink,downlink,delta", [
    ("fedadp", "f32", "f32", False),   # the subset-selection reference
    ("fedadp", "int4", "int8", True),  # fully-compressed delta wire
    ("fedavg", "f32", "int8", True),   # slow-method delta wire
])
def test_golden_delta_reproduces(method, uplink, downlink, delta):
    """Recomputed subset-selection rounds-to-target must match the delta
    golden within the 10% band in both directions — this re-runs the
    per-client broadcast-state path (ring + versions + byte split) end
    to end on every CI leg."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import node_spec, run_fl

    d = _golden()["delta"]
    task = d["task"]
    hist, _ = run_fl(
        method, node_spec(5, 5, 1), rounds=task["max_rounds"],
        target=task["target"], engine=task["engine"], transport=uplink,
        downlink=downlink, downlink_delta=delta,
        downlink_ring=task["downlink_ring"],
        group_size=task["group_size"], seed=task["seed"],
        eval_every=task["eval_every"],
        clients_per_round=task["clients_per_round"],
    )
    golden = d["entries"][f"{method}/{uplink}/{downlink}"]
    got = hist.rounds_to_target
    assert _ratio_ok(got, golden) and _ratio_ok(golden, got), (got, golden)


def test_golden_sharded_subprocess_quantized_both_directions():
    """engine="flat_sharded" on an 8-way host-device mesh must converge in
    the same rounds as the golden for the fully-compressed wire (int4
    uplink + int8 downlink) — K=10 clients pad to 16 rows over 8 shards,
    so the padded-row/zero-weight path runs every round of a REAL
    convergence trajectory, not just a one-round parity check."""
    g = _golden()
    golden = g["entries"]["fedadp/int4/int8"]
    task = g["task"]
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.mesh import make_mesh
from benchmarks.common import node_spec, run_fl
mesh = make_mesh((8,), ("data",))
hist, _ = run_fl(
    "fedadp", node_spec(5, 5, 1), rounds={task["max_rounds"]},
    target={task["target"]}, engine="flat_sharded", transport="int4",
    downlink="int8", group_size={task["group_size"]}, seed={task["seed"]},
    eval_every={task["eval_every"]}, mesh=mesh)
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
