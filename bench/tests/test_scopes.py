"""The scope and program-span reader, on hand-made events, on a trace
recorded on the chip (`data/scoped.xplane.pb.gz`, see
`test_recorded_scoped_trace`) and driving one small cell on the CPU."""
import collections
import json
import os
from types import SimpleNamespace

import pytest

from bench import scopes, trace
from bench.tests.test_harness import MLR_TTT, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "scoped.xplane.pb.gz")
SCOPES = ("fl.select", "fl.downlink", "fl.local_train", "fl.uplink",
          "fl.stats", "fl.weights", "fl.aggregate", "fl.eval")


def ev(name, start, end):
    return trace.Event(name, start, end, name)


def op(name, start, end, path):
    return scopes.Op(name, start, end, path, "jit_run_block")


@pytest.mark.parametrize("path, scope", [
    ("jit(run_block)/while/body/closed_call/fl.local_train/vmap()/while/"
     "body/transpose(jvp())/conv_general_dilated:", "fl.local_train"),
    # inside transform wrappers, and the innermost scope of a nest
    ("jit(step)/transpose(jvp(fl.local_train))/mul", "fl.local_train"),
    ("jit(step)/fl.eval/cond/branch_1_fun/fl.stats/x", "fl.stats"),
    ("jit(step)/fl.stats/jit(weighted_agg)/pallas_call", "fl.stats"),
    # a fused op's own name is the first of a ";" list
    ("jit(a)/fl.uplink/x;jit(a)/fl.stats/y", "fl.uplink"),
    ("", None),
    ("jit(run_block)/while/body/mul", None),
    ("jit(a)/myfl.stats/x", None),
    ("jit(a)/fl/x", None),
])
def test_scope_of_reads_path_segments(path, scope):
    assert scopes.scope_of(path) == scope


def scoped():
    """One device: a while op holding two scoped ops, an unscoped op and
    a copy with no path, in a 10 s window; the host in nested fl/ spans."""
    body = "jit(run_block)/while/body/closed_call/"
    ops = [op("while.1", 0.0, 3.0, "jit(run_block)/while"),
           op("fusion.1", 0.0, 2.0, body + "fl.local_train/vmap()/add:"),
           op("fusion.2", 2.0, 3.0, body + "fl.eval/cond/reduce:"),
           op("copy.3", 4.0, 5.0, ""),
           op("fusion.4", 5.0, 5.5, body + "mul:")]
    devices = {"/device:TPU:0": [ev(o.name, o.start, o.end) for o in ops]}
    spans = [ev("fl/reset", 3.2, 3.8), ev("fl/run", 3.8, 9.0),
             ev("fl/read_round", 3.8, 4.5), ev("fl/scan_block", 4.5, 8.0),
             ev("fl/dispatch", 4.5, 4.6), ev("fl/fetch", 4.6, 8.0),
             ev("fl/history", 8.0, 8.5)]
    base = trace.Summary(devices, [], (0.0, 10.0))
    return scopes.Scopes(base, {"/device:TPU:0": ops}, spans)


def test_scope_seconds_and_scoped_share():
    s = scoped()
    assert s.scope_seconds() == {"fl.local_train": pytest.approx(2.0),
                                 "fl.eval": pytest.approx(1.0),
                                 "jit_run_block": pytest.approx(1.5)}
    assert s.scoped_share() == pytest.approx(100.0 * 3.0 / 4.5)
    assert s.scoped_share("jit_other") is None


def test_idle_by_innermost_and_top_level_span():
    # idle gaps 3-4 and 5.5-10
    s = scoped()
    assert s.idle() == {"fl/reset": pytest.approx(0.6),
                        "fl/read_round": pytest.approx(0.2),
                        "fl/fetch": pytest.approx(2.5),
                        "fl/history": pytest.approx(0.5),
                        "fl/run": pytest.approx(0.5),
                        scopes.NO_SPAN: pytest.approx(1.2)}
    shares = s.idle_shares()
    assert shares == {"fl/reset": pytest.approx(6.0),
                      "fl/run": pytest.approx(37.0),
                      scopes.NO_SPAN: pytest.approx(12.0)}
    # the top-level spans and the time outside them add up to the idle
    assert sum(shares.values()) == pytest.approx(100 * s.base.idle_share())


def test_spans_that_do_not_nest_are_cut_at_their_parent():
    segs = scopes.owner_segments([ev("fl/a", 0.0, 2.0), ev("fl/b", 1.0, 3.0),
                                  ev("fl/c", 4.0, 5.0)], outermost=False)
    assert segs == [(0.0, 1.0, "fl/a"), (1.0, 2.0, "fl/b"),
                    (4.0, 5.0, "fl/c")]


def fake_profile():
    """A `ProfileData` stand-in: one device with a module line and two
    ops, the host with an fl/ span and a bench/ span."""
    def e(name, start_ns, end_ns, offset=None):
        stats = [] if offset is None else [("device_offset_ps", offset)]
        return SimpleNamespace(name=name, start_ns=start_ns, end_ns=end_ns,
                               stats=stats)

    def plane(name, **lines):
        return SimpleNamespace(name=name, lines=[
            SimpleNamespace(name=k.replace("_", " "), events=v)
            for k, v in lines.items()])

    return SimpleNamespace(planes=[
        plane("/device:TPU:0",
              XLA_Modules=[e("jit_run_block(123)", 0, 100)],
              XLA_Ops=[e("%fusion.1 = f32[2] fusion(...)", 10, 20, 10_000),
                       e("%copy.2 = f32[2] copy(...)", 30, 40, 30_000)]),
        plane("/device:CUSTOM:Megascale Trace"),
        plane("/host:CPU", main=[e("bench/window", 0, 100),
                                 e("fl/run", 5, 95), e("other", 1, 2)])])


def test_from_profile_joins_name_paths():
    path = "jit(run_block)/while/body/fl.stats/add"
    s = scopes.from_profile(fake_profile(), {("/device:TPU:0", 10_000): path})
    assert list(s.ops) == ["/device:TPU:0"]
    ops = {o.name: o for o in s.ops["/device:TPU:0"]}
    assert (ops["fusion.1"].path, ops["copy.2"].path) == (path, "")
    assert {o.module for o in ops.values()} == {"jit_run_block"}
    assert [x.name for x in s.spans] == ["fl/run"]
    assert s.base.window == pytest.approx((0.0, 100e-9))
    assert s.scope_seconds() == {"fl.stats": pytest.approx(10e-9),
                                 "jit_run_block": pytest.approx(10e-9)}


def test_pinned_xprof_has_the_trace_viewer_entry_points():
    """`xprof_paths` calls a private converter of `xprof` and parses its
    Trace proto; a new `xprof` that moves either fails here by name."""
    from xprof.convert import raw_to_tool_data
    from xprof.protobuf import trace_events_old_pb2

    plugin = getattr(raw_to_tool_data, "_pywrap_profiler_plugin", None)
    assert hasattr(plugin, "xspace_to_tools_data"), (
        "xprof.convert.raw_to_tool_data._pywrap_profiler_plugin."
        "xspace_to_tools_data is gone: bench.scopes.xprof_paths must move")
    assert hasattr(trace_events_old_pb2, "Trace"), (
        "xprof.protobuf.trace_events_old_pb2.Trace is gone: "
        "bench.scopes.xprof_paths must move")


def test_recorded_scoped_trace():
    """`data/scoped.xplane.pb.gz`, recorded on the chip by
    `bench/tests/record_trace.py`: under a bench/window span, one
    training (a reset and one 2-round block) of a CNN cell shrunk to 2
    clients of 32 samples with int8 wires, under the program's spans and
    scopes."""
    s = scopes.load(SCOPED)
    assert list(s.ops) == ["/device:TPU:0"]
    by = s.scope_seconds()
    for scope in SCOPES:
        assert by[scope] > 0, scope
    # at this size the scan's carry copies, under no scope, are a sixth
    assert s.scoped_share() > 80.0
    # the kernels keep their wrappers' names inside the scopes
    paths = {(o.start, o.name): o.path for o in s.ops["/device:TPU:0"]}
    stats = s.base.kernel_events("round_stats_q")
    agg = s.base.kernel_events("weighted_agg_q")
    assert (len(stats), len(agg)) == (2, 4)
    assert {scopes.scope_of(paths[e.start, e.name]) for e in stats} == {
        "fl.stats"}
    assert {scopes.scope_of(paths[e.start, e.name]) for e in agg} == {
        "fl.stats", "fl.aggregate"}
    assert collections.Counter(x.name for x in s.spans) == {
        "fl/reset": 1, "fl/run": 1, "fl/read_round": 2, "fl/scan_block": 1,
        "fl/dispatch": 1, "fl/fetch": 1, "fl/history": 1}
    shares = s.idle_shares()
    assert shares["fl/reset"] > 0 and shares["fl/run"] > 0
    assert sum(shares.values()) == pytest.approx(100 * s.base.idle_share(),
                                                 rel=1e-9)
    assert sum(s.idle().values()) == pytest.approx(
        sum(s.idle(outermost=True).values()), rel=1e-9)


def test_cell_window_on_the_cpu(capsys):
    """The window loop of a training cell, on the CPU at a small size:
    the span counts per training; the CPU trace has no device plane, so
    no scope or idle time."""
    cell = tiny_cell("mlr", MLR_TTT, "mlr.paper")
    assert scopes.main(["--workload", cell.name, "--seed", "3",
                        "--seconds", "0.5"], require_tpu=False,
                       cell=cell) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["per"] == "training" and out["units"] >= 1
    counts = out["span_counts"]
    assert counts["reset"] == counts["run"] == counts["history"] == 1.0
    # a read of the round counter at the start of FedServer.run and of
    # run_rounds, and a dispatch and a fetch per block
    assert counts["read_round"] == 2.0
    assert counts["dispatch"] == counts["fetch"] == counts["scan_block"]
    assert out["host_syncs"] == 2.0 + counts["fetch"]
    assert out["scope_ms_per_round"] == {} and out["idle_share_by_span"] == {}
