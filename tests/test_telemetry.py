"""Round-level telemetry layer tests.

The central pins: (1) the OFF path is really off — `telemetry=None`
keeps the engines' metrics keyset exactly the pre-telemetry set and the
trajectory bit-identical to a telemetry="node" run, and the compiled
step's jaxpr is a pure function of the config (no ambient telemetry
state); (2) scanned and stepwise runs stream IDENTICAL telemetry through
the one shared adapter (`sinks.emit_round_block`) — per-round per-node
angle/weight rows match to 1e-5; (3) a JSONL stream alone reproduces the
run's rounds-to-target (the Table-I claim is auditable from telemetry);
(4) the in-scan eval sentinel is a pinned constant masked by every
reader.
"""
import collections
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import driver, fl
from repro.data import synthetic
from repro.telemetry import report, schema, sinks

FLSTAT = os.path.join(os.path.dirname(__file__), "..", "scripts",
                      "flstat.py")

# metrics every round carries with telemetry OFF — the exact pre-telemetry
# keyset. Growing it is an intentional act (and a jaxpr change); the
# telemetry layer must never leak tel/* keys into the off path.
OFF_KEYS_SYNC = {"loss", "theta", "theta_smoothed", "weights", "divergence",
                 "lr", "cos", "expected_contribution", "accuracy"}
TEL_KEYS_SYNC = {"tel/nodes", "tel/cohort", "tel/weight_entropy",
                 "tel/bytes_up", "tel/bytes_down"}


def _task(n_nodes=4, samples=100):
    train, test = synthetic.make_image_task(seed=0, num_train=1500,
                                            num_test=200)
    nodes = synthetic.make_federated(
        train, [("iid", None)] * (n_nodes // 2)
        + [("xclass", 1)] * (n_nodes - n_nodes // 2),
        samples_per_node=samples, seed=1)
    return nodes, test


def _server(cfg, seed=0, **kw):
    nodes, test = _task(cfg.num_clients)
    return repro.FedServer("mlr", cfg, nodes, test, batch_size=50,
                           seed=seed, **kw)


def _cfg(**kw):
    base = dict(num_clients=4, clients_per_round=4, local_steps=2,
                method="fedadp", base_lr=0.05, telemetry="node")
    base.update(kw)
    return fl.FLConfig(**base)


# --------------------------------------------------- off path is off


def test_validate_rejects_unknown_telemetry():
    with pytest.raises(ValueError, match="unknown telemetry"):
        _cfg(telemetry="verbose").validate()


def test_off_keyset_is_exactly_the_pre_telemetry_set():
    m_off = _server(_cfg(telemetry=None)).step(eval_every=1)
    assert set(m_off) == OFF_KEYS_SYNC
    m_on = _server(_cfg()).step(eval_every=1)
    assert set(m_on) == OFF_KEYS_SYNC | TEL_KEYS_SYNC


def test_telemetry_on_off_trajectories_bit_identical():
    """telemetry="node" only ADDS metrics — params, angles, RNG advance
    bit-for-bit the same with it on or off."""
    s_on, s_off = _server(_cfg()), _server(_cfg(telemetry=None))
    for _ in range(3):
        m_on, m_off = s_on.step(eval_every=2), s_off.step(eval_every=2)
    for k in OFF_KEYS_SYNC:
        np.testing.assert_array_equal(np.asarray(m_on[k]),
                                      np.asarray(m_off[k]), err_msg=k)
    def host(x):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(x)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(host(a), host(b)),
        s_on.state, s_off.state)


def test_off_jaxpr_is_a_pure_function_of_the_config():
    """Two independently built telemetry=None steps lower to the same
    jaxpr — no ambient sink/span state can leak into the compiled path —
    and a config derived by switching telemetry OFF is indistinguishable
    from one born off."""
    import dataclasses

    s1 = _server(_cfg(telemetry=None))
    s2 = _server(dataclasses.replace(_cfg(), telemetry=None))
    args = (s1.state, jnp.int32(1))
    j1 = str(jax.make_jaxpr(s1._step_fn)(*args))
    j2 = str(jax.make_jaxpr(s2._step_fn)(*args))
    assert j1 == j2
    assert "tel/" not in j1


# ------------------------------------- scanned == stepwise telemetry


@pytest.mark.parametrize("engine", ["tree", "flat"])
def test_scanned_stream_matches_stepwise_stream(engine):
    """Acceptance: the scanned run emits per-round per-node angle+weight
    rows matching the stepwise run to 1e-5, through the SAME adapter."""
    cfg = _cfg(engine=engine)
    s_step, s_scan = _server(cfg), _server(cfg)
    k_step, k_scan = sinks.MemorySink(), sinks.MemorySink()
    s_step.run(6, eval_every=2, mode="stepwise", sink=k_step)
    s_scan.run(6, eval_every=2, mode="scanned", block=4, sink=k_scan)
    schema.validate_events(k_step.events)
    schema.validate_events(k_scan.events)
    for kind in ("round", "node", "summary"):
        a, b = k_step.of_type(kind), k_scan.of_type(kind)
        assert len(a) == len(b), kind
        for ea, eb in zip(a, b):
            assert set(ea) == set(eb), kind
            for f, va in ea.items():
                vb = eb[f]
                if isinstance(va, float) and va is not None:
                    assert abs(va - vb) < 1e-5, (kind, f, ea, eb)
                else:
                    assert va == vb, (kind, f, ea, eb)
    # six rounds, four nodes each
    assert len(k_scan.of_type("round")) == 6
    assert len(k_scan.of_type("node")) == 24


def test_flat_sharded_8device_stream_matches_stepwise():
    """The telemetry metrics survive the client-sharded shard_map engine:
    on an 8-way host mesh the scanned stream matches stepwise to 1e-5."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        import repro
        from repro.core import fl
        from repro.data import synthetic
        from repro.telemetry import schema, sinks
        train, test = synthetic.make_image_task(seed=0, num_train=1500,
                                                num_test=200)
        nodes = synthetic.make_federated(
            train, [("iid", None)] * 4 + [("xclass", 1)] * 4,
            samples_per_node=100, seed=1)
        mesh = make_mesh((8,), ("data",))
        cfg = fl.FLConfig(num_clients=8, clients_per_round=8, local_steps=2,
                          method="fedadp", engine="flat_sharded",
                          base_lr=0.05, telemetry="node")
        servers = [repro.FedServer("mlr", cfg, nodes, test, batch_size=50,
                                   seed=0, mesh=mesh) for _ in range(2)]
        ks = [sinks.MemorySink(), sinks.MemorySink()]
        servers[0].run(4, eval_every=2, mode="stepwise", sink=ks[0])
        servers[1].run(4, eval_every=2, mode="scanned", block=4, sink=ks[1])
        for k in ks:
            schema.validate_events(k.events)
        a, b = ks[0].of_type("node"), ks[1].of_type("node")
        assert len(a) == len(b) == 4 * 8, (len(a), len(b))
        for ea, eb in zip(a, b):
            assert (ea["round"], ea["node"]) == (eb["round"], eb["node"])
            for f in ("theta", "theta_smoothed", "weight"):
                assert abs(ea[f] - eb[f]) < 1e-5, (ea, eb, f)
        print("SHARDED_TELEMETRY_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_TELEMETRY_OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------ buffered mode


def test_buffered_stream_carries_staleness_and_occupancy():
    """Buffered ticks attribute node rows by buffer slot and carry the
    report ages, landed mask, and buffer occupancy; flush ticks satisfy
    the weight-sum invariant, non-flush ticks are exempt."""
    K, M = 4, 3
    # node 0's tick-0 report straggles 2 ticks: it misses the round-1 and
    # round-2 flushes (which proceed, 3 on-time reports >= M) and lands
    # at tick 2 aged by those two model versions.
    delays = np.zeros((3, K), np.int32)
    delays[0, 0] = 2
    drops = np.zeros((3, K), bool)
    cfg = _cfg(num_clients=K, aggregation="buffered", buffer_m=M)
    s = _server(cfg, arrival_fn=repro.fixed_arrival_schedule(delays, drops))
    sink = sinks.MemorySink()
    s.run(3, eval_every=0, mode="scanned", block=3, sink=sink)
    schema.validate_events(sink.events)
    rounds = sink.of_type("round")
    assert [e["flushed"] for e in rounds] == [1, 1, 1]
    assert all("occupancy" in e and "staleness" in e for e in rounds)
    node_rows = sink.of_type("node")
    assert all("age" in e and "landed" in e for e in node_rows)
    straggler = {e["round"]: e for e in node_rows if e["node"] == 0}
    assert [straggler[r]["landed"] for r in (1, 2, 3)] == [False, False,
                                                           True]
    assert straggler[3]["age"] == 2
    assert straggler[1]["weight"] == straggler[2]["weight"] == 0.0
    # mean landed age surfaces as the round's staleness metric
    assert rounds[2]["staleness"] == pytest.approx(2 / K)
    assert report.check_weight_sums(sink.events) == 3  # every flush tick


# ------------------------------------------- JSONL stream + sentinel


def test_jsonl_roundtrip_and_flstat_cli(tmp_path):
    """A JSONL stream written by the sink reads back validated, its
    rounds-to-target matches the in-process History, and the flstat CLI
    parses it with weight sums intact."""
    path = str(tmp_path / "telemetry.jsonl")
    sink = sinks.JSONLSink(path)
    s = _server(_cfg())
    hist = s.run(12, target_acc=0.15, eval_every=2, mode="scanned",
                 block=4, sink=sink)
    sink.close()
    events = sinks.load_events(path)
    schema.validate_events(events)
    assert events[0]["event"] == "manifest"
    assert events[0]["schema"] == schema.SCHEMA_VERSION
    assert events[0]["config"]["telemetry"] == "node"
    # the stream alone reproduces the run's headline claim
    assert hist.rounds_to_target is not None
    assert report.rounds_to_target(events, 0.15) == hist.rounds_to_target
    s_sum = report.summarize(events, target=0.15)
    assert s_sum["rounds_to_target"] == hist.rounds_to_target
    assert s_sum["spans"]["scan_block"]["count"] >= 1
    out = subprocess.run(
        [sys.executable, FLSTAT, path, "--target", "0.15", "--validate",
         "--assert-weight-sums", "--nodes"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"rounds_to_15%={hist.rounds_to_target}" in out.stdout
    assert "weight sums ok" in out.stdout


def test_percentiles_interpolate_linearly():
    """report._percentile pins: linear interpolation between bracketing
    samples (numpy's default method), not nearest-rank. The old round()
    on the fractional rank used banker's rounding — p50 of [1,2,3,4]
    came out 2 (round(1.5) -> 2... but round(0.5) -> 0), picking lower
    or upper inconsistently by parity."""
    assert report._percentile([1.0, 2.0, 3.0, 4.0], 0.50) == 2.5
    assert report._percentile([10.0, 20.0, 30.0, 40.0, 50.0], 0.90) == 46.0
    assert report._percentile([1.0, 2.0, 3.0, 4.0, 5.0], 0.99) == \
        pytest.approx(4.96)
    # exact ranks hit the sample itself
    assert report._percentile([1.0, 2.0, 3.0], 0.50) == 2.0
    assert report._percentile([7.0], 0.90) == 7.0
    assert report._percentile([3.0, 9.0], 0.0) == 3.0
    assert report._percentile([3.0, 9.0], 1.0) == 9.0
    # numpy cross-check on an awkward span list
    vals = sorted([0.03, 0.011, 0.8, 0.07, 0.22, 0.013, 0.4])
    for q in (0.5, 0.9, 0.99):
        assert report._percentile(vals, q) == \
            pytest.approx(float(np.percentile(vals, q * 100)))
    assert report._percentile([], 0.5) != report._percentile([], 0.5)  # nan


def test_partial_final_block_emits_exact_round_count():
    """rounds=10 with block=8 ends on a partial block: the stream must
    hold EXACTLY 10 round events, absolute rounds 1..10, no padding."""
    s = _server(_cfg())
    sink = sinks.MemorySink()
    s.run(10, eval_every=3, mode="scanned", block=8, sink=sink)
    rounds = sink.of_type("round")
    assert [e["round"] for e in rounds] == list(range(1, 11))
    # eval cadence survives the block split: rounds 3, 6, 9 carry a real
    # accuracy, every other round is masked to None (never the sentinel)
    acc = {e["round"]: e["accuracy"] for e in rounds}
    assert all(acc[r] is not None for r in (3, 6, 9))
    assert all(acc[r] is None for r in acc if r % 3)


def test_telemetry_every_subsamples_rounds():
    s = _server(_cfg())
    sink = sinks.MemorySink()
    s.run(8, eval_every=0, mode="scanned", block=4, sink=sink,
          telemetry_every=3)
    assert [e["round"] for e in sink.of_type("round")] == [3, 6]
    assert len(sink.of_type("node")) == 2 * 4
    assert len(sink.of_type("summary")) == 1


def test_eval_sentinel_is_pinned_and_masked():
    """The in-scan eval fill value is the named constant — an exact
    float the readers mask; changing it is a schema change."""
    assert driver.EVAL_SENTINEL == schema.EVAL_SENTINEL == -1.0
    m = _server(_cfg(telemetry=None)).step(eval_every=0)
    assert float(m["accuracy"]) == schema.EVAL_SENTINEL  # exact, ==
    assert schema.mask_accuracy(m["accuracy"]) is None
    assert not schema.is_real_accuracy(m["accuracy"])
    with pytest.raises(ValueError, match="sentinel"):
        schema.validate_event({"event": "round", "round": 1, "loss": 1.0,
                               "lr": 0.1, "divergence": 0.0,
                               "accuracy": schema.EVAL_SENTINEL})


def test_csv_sink_writes_per_node_rows(tmp_path):
    import csv

    path = str(tmp_path / "telemetry.csv")
    sink = sinks.CSVSink(path)
    _server(_cfg()).run(3, eval_every=1, mode="stepwise", sink=sink)
    sink.close()
    with open(path) as fh:
        rows = list(csv.DictReader(fh))
    assert len(rows) == 3 * 4
    assert set(rows[0]) == set(sinks.CSVSink.COLUMNS)
    w = sum(float(r["weight"]) for r in rows if r["round"] == "1")
    assert abs(w - 1.0) < 1e-5


# ------------------------------------- host spans and device scopes

# every phase scope of the round step (fl.make_round_fn, driver.make_step_fn)
SCOPES = {"fl.select", "fl.downlink", "fl.local_train", "fl.uplink",
          "fl.stats", "fl.weights", "fl.aggregate", "fl.eval"}


def test_span_timer_writes_fl_event_to_profiler_trace(tmp_path):
    """A span is a "fl/<name>" annotation on the host plane of a profiler
    trace, and one call in `counts`."""
    import glob

    from jax.profiler import ProfileData

    spans = repro.SpanTimer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("probe"):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events]
    assert "fl/probe" in names
    assert spans.counts == {"probe": 1}


@pytest.mark.parametrize("mode,rounds,block,want", [
    ("scanned", 8, 4, {"run": 1, "read_round": 2, "scan_block": 2,
                       "dispatch": 2, "fetch": 2, "history": 1}),
    ("scanned", 6, 4, {"run": 1, "read_round": 2, "scan_block": 2,
                       "dispatch": 2, "fetch": 2, "history": 1}),
    ("scanned", 4, 8, {"run": 1, "read_round": 2, "scan_block": 1,
                       "dispatch": 1, "fetch": 1, "history": 1}),
    ("stepwise", 3, 8, {"run": 1, "read_round": 1, "dispatch": 3,
                        "fetch": 3}),
], ids=["scanned-2-blocks", "scanned-ragged", "scanned-1-block",
        "stepwise"])
def test_run_counts_its_host_spans(mode, rounds, block, want):
    """`FedServer.spans.counts` is the program's host-sync counter: one
    `read_round` per blocking read of the round counter and one `fetch`
    per block (or stepwise round); a sink gets the same spans as events
    (and one `sink_emit` per scanned block), after the manifest and
    before the summary."""
    s = _server(_cfg(telemetry=None))
    s.reset(1)
    assert s.spans.counts == {"reset": 1}
    s.run(rounds, eval_every=2, mode=mode, block=block)
    assert {k: v for k, v in s.spans.counts.items() if k != "reset"} == want
    before = collections.Counter(s.spans.counts)
    sink = sinks.MemorySink()
    s.run(rounds, eval_every=2, mode=mode, block=block, sink=sink)
    schema.validate_events(sink.events)
    assert sink.events[-1]["event"] == "summary"
    assert sink.events[-1]["rounds"] == rounds
    streamed = collections.Counter(e["name"] for e in sink.of_type("span"))
    assert streamed == collections.Counter(s.spans.counts) - before
    assert streamed - collections.Counter(want) == collections.Counter(
        {"sink_emit": want["fetch"]} if mode == "scanned" else {})


@pytest.mark.parametrize("engine,extra,want", [
    ("flat", {}, SCOPES),
    ("tree", {}, SCOPES),
    ("flat", {"aggregation": "buffered", "buffer_m": 2}, SCOPES),
    # the sequential round ships no wire: it streams each delta's
    # statistics leaf by leaf, with no raveled uplink buffer
    ("tree", {"mode": "sequential", "transport": "f32", "downlink": "f32"},
     SCOPES - {"fl.downlink", "fl.uplink"}),
], ids=["flat", "tree", "buffered", "sequential"])
def test_round_phases_are_named_scopes_in_the_hlo(engine, extra, want):
    """Each phase scope reaches the `op_name` metadata of the lowered step
    (what a profiler trace names each device op by), with int8 wires so
    that downlink and uplink run ops."""
    import re

    cfg = _cfg(telemetry=None, engine=engine,
               **dict({"transport": "int8", "downlink": "int8"}, **extra))
    s = _server(cfg)
    text = jax.jit(s._step_fn).lower(s.state, jnp.int32(1)).as_text(
        dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert {m for n in names for m in re.findall(r"fl\.[a-z_]+", n)} == want


@pytest.mark.parametrize("model,mesh,want", [
    ("cnn", False, "batched"), ("mlr", False, "vmap"), ("cnn", True, "vmap"),
], ids=["cnn", "mlr", "cnn-mesh"])
def test_manifest_names_the_local_update_path(model, mesh, want):
    """`server.local_update` and the run manifest's `extra` say which
    local update the step runs: the CNN's client-batched one, except on
    a mesh, and the vmapped one for MLR."""
    from repro.launch.mesh import make_mesh

    nodes, test = _task()
    s = repro.FedServer(model, _cfg(telemetry=None), nodes, test,
                        batch_size=50,
                        mesh=make_mesh((1,), ("data",)) if mesh else None)
    assert s.local_update == want
    sink = sinks.MemorySink()
    s.run(0, sink=sink)
    (manifest,) = sink.of_type("manifest")
    assert manifest["extra"] == {"local_update": want}
