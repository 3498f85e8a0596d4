"""The trace reducer: busy union, kernel sums, exposed collectives and
gap attribution, on hand-made events and on a trace recorded on the
chip (`data/small.xplane.pb.gz`: two 2-round blocks of a CNN cell shrunk to
2 clients of 32 samples, under the harness's spans)."""
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small.xplane.pb.gz")


def ev(name, start, end, label=None):
    return trace.Event(name, start, end, label or name)


def test_union_and_subtract():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                                  (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]


def test_busy_idle_and_attribution():
    agg = ('%weighted_agg.4 = f32[8,128] custom-call(f32[2,1] %a, '
           'f32[2,8,128] %b), custom_call_target="tpu_custom_call"')
    devices = {"/device:TPU:0": [ev("while.1", 0.0, 3.0),
                                 ev("fusion.1", 0.0, 2.0),
                                 ev("fusion.2", 2.0, 3.0),
                                 ev("weighted_agg.4", 6.0, 7.0, agg),
                                 ev("custom-call.5", 7.0, 7.0,
                                    'custom_call_target="AllocateBuffer"'),
                                 ev("fusion.1", 9.0, 12.0)]}
    spans = [ev("bench/window", 0.0, 10.0), ev("bench/run", 0.0, 5.5),
             ev("bench/reset", 5.5, 6.0), ev("bench/run", 6.0, 10.0)]
    s = trace.Summary(devices, spans, (0.0, 10.0))
    assert s.busy_seconds() == [pytest.approx(3.0 + 1.0 + 1.0)]
    assert s.idle_share() == pytest.approx(0.5)
    assert [e.name for e in s.kernel_events("weighted_agg")] == [
        "weighted_agg.4"]
    assert s.kernel_events("weighted_agg_q4") == []
    assert s.kernel_events("round_stats") == []
    # gaps: 3-6 (run covers 2.5 of it), 7-9 (second run)
    idle = s.idle_by_span()
    assert idle["bench/run"] == pytest.approx(5.0)
    ops = s.op_seconds()
    assert ops["fusion.1"] == pytest.approx(3.0)  # 2 + 1, clipped at 10
    assert "while.1" not in ops  # a container of other ops
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["idle_gaps"][0] == ["bench/run", pytest.approx(5.0)]


def test_exposed_collectives():
    devices = {
        "/device:TPU:0": [ev("all-reduce.1", 0.0, 2.0),
                          ev("fusion.1", 1.0, 3.0)],
        "/device:TPU:1": [ev("all-reduce.1", 0.0, 2.0),
                          ev("fusion.1", 2.0, 3.0)]}
    s = trace.Summary(devices, [], (0.0, 4.0))
    assert s.exposed_collective_share() == pytest.approx((1.0 + 2.0) / 2 / 4)
    solo = trace.Summary({"/device:TPU:0": [ev("fusion.1", 0.0, 1.0)]}, [],
                         (0.0, 4.0))
    assert solo.exposed_collective_share() is None
    assert trace.Summary({}, [], (0.0, 1.0)).idle_share() is None


def test_recorded_chip_trace():
    s = trace.load(RECORDED)
    assert list(s.devices) == ["/device:TPU:0"]
    # no bench/window span in this recording: the window is the extent
    # of the device's events
    busy = s.busy_seconds()[0]
    assert 0 < busy <= s.window_seconds()
    assert s.idle_share() == pytest.approx(1 - busy / s.window_seconds())
    stats = s.kernel_events("round_stats")
    agg = s.kernel_events("weighted_agg")
    # two blocks of two rounds: one stats call and two aggregations a round
    assert (len(stats), len(agg)) == (4, 8)
    assert sum(e.end - e.start for e in stats) == pytest.approx(40.145e-6,
                                                                rel=1e-3)
    assert sum(e.end - e.start for e in agg) == pytest.approx(109.592e-6,
                                                              rel=1e-3)
    assert s.kernel_events("weighted_agg_q4") == []
    # the host sat in FedServer.run while the device idled
    idle = s.idle_by_span()
    assert max(idle, key=idle.get) == "bench/run"
    assert sum(idle.values()) == pytest.approx(
        s.window_seconds() - busy, rel=1e-6)
    ops = s.breakdown()["device_ops"]
    assert len(ops) == 10 and not any(n.startswith("while") for n, _ in ops)
    assert s.exposed_collective_share() is None
