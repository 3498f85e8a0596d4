"""The per-layer readers of the program's scopes, spans and counters, on
the trace recorded on the chip (`data/scoped.xplane.pb.gz`: one training
of a 2-round block, see `test_scopes.test_recorded_scoped_trace`), and
`step_mfu` through the cell's family."""
import collections
import json
import os
from types import SimpleNamespace

import pytest

from bench import counts, run, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "scoped.xplane.pb.gz")


def _with(ns, **changes):
    return SimpleNamespace(**{**vars(ns), **changes})


@pytest.fixture(scope="module")
def recorded():
    s = scopes.load(SCOPED)
    # the program's counter over the recording: one count a span
    spans = collections.Counter(x.name[len(scopes.SPAN_PREFIX):]
                                for x in s.spans)
    return SimpleNamespace(scopes=s, trace=s.base, rounds=2,
                           span_counts=dict(spans), histories=[object()])


@pytest.mark.parametrize("metric,scope", [("local_train_ms", "fl.local_train"),
                                          ("eval_ms", "fl.eval")])
def test_scope_ms_per_round(recorded, metric, scope):
    value = run.load_metric(metric).read(recorded)
    by = recorded.scopes.scope_seconds()
    assert value == pytest.approx(1e3 * by[scope] / 2)
    assert 0 < value < 1e3 * recorded.trace.window_seconds()
    assert run.load_metric(metric).read(
        _with(recorded, rounds=0)) is None


def test_scope_absent_reads_nothing(recorded):
    empty = scopes.Scopes(recorded.trace, {}, [])
    ctx = _with(recorded, scopes=empty)
    for metric in ("local_train_ms", "eval_ms", "reset_idle_share"):
        assert run.load_metric(metric).read(ctx) is None


def test_reset_idle_share(recorded):
    value = run.load_metric("reset_idle_share").read(recorded)
    shares = recorded.scopes.idle_shares()
    assert value == shares["fl/reset"] > 0
    assert value < 100 * recorded.trace.idle_share()


def test_host_syncs_per_training(recorded):
    """Two reads of the round counter and one fetch of the block."""
    read = run.load_metric("host_syncs_per_training").read
    assert read(recorded) == 3.0
    assert read(_with(recorded, histories=[])) is None


def test_step_mfu_through_the_family_is_the_counts_arithmetic():
    with open(os.path.join(run.ROOT, "bench", "configs", "cnn.json")) as f:
        cfg = json.load(f)
    cell = run.load_cell("cnn.paper")
    tr = cell.traffic
    peaks = {"bf16_flops": 197e12}
    ctx = SimpleNamespace(family=run.load_family(cfg["family"]), config=cfg,
                          traffic=tr, rounds=112, evals=112, window_s=4.17,
                          peaks=peaks, chips=1)
    before = (112 * counts.round_flops(cfg, tr, eval_round=False)
              + 112 * tr["data"]["num_test"] * counts.forward_flops(cfg))
    assert run.load_metric("step_mfu").read(ctx) == (
        100.0 * before / (4.17 * 197e12))
    assert run.load_metric("step_mfu").read(
        _with(ctx, rounds=0)) is None
