"""The harness off the chip: it refuses to run without a TPU, and a run
driven on the CPU at a small size comes out correct, and not correct
with the timed path broken underneath."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _json(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "mlr.paper",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def tiny_cell(model: str, loop: dict, limits_of: str, **fl) -> run.SimpleNamespace:
    """A cell of the given model at a CPU-sized scale, held to the limits
    of the named full-size cell."""
    traffic = {
        "data": {"seed": 0, "num_train": 3000, "num_test": 500,
                 "split": {"kind": "xclass", "samples": 100, "seed": 0,
                           "nodes": [["iid", None]] * 2 + [["xclass", 1]] * 2}},
        "fl": dict({"num_clients": 4, "clients_per_round": 4,
                    "local_steps": 5, "method": "fedadp", "alpha": 5.0,
                    "base_lr": 0.05, "lr_decay": 0.995, "engine": "flat",
                    "transport": "f32", "downlink": "f32"}, **fl),
        "batch_size": 20, "loop": loop, "trace_seconds": 1,
        "check": dict(_json(f"bench/traffic/{limits_of}.json")["check"],
                      sample_trainings=2, reference_device=None),
        "model": model}
    if loop["kind"] == "steady":  # rounds-to-target is for trainings
        traffic["check"]["limits"] = {
            k: v for k, v in traffic["check"]["limits"].items() if k != "rtt"}
    bench = _json("BENCHMARK.json")
    e2e = "round_s" if loop["kind"] == "steady" else "time_to_target_s"
    return run.SimpleNamespace(
        name="tiny." + model, chips=1, config=_json(f"bench/configs/{model}.json"),
        traffic=traffic, per_layer=[],
        end_to_end=[m for m in bench["end_to_end"]
                    if m["name"] in ("setup_s", e2e)],
        bench=bench)


MLR_TTT = {"kind": "to_target", "block": 4, "eval_every": 1,
           "target_acc": 0.8, "max_rounds": 12}
CNN_STEADY = {"kind": "steady", "block": 2, "eval_every": 1}


def drive(cell, capsys, seed=3000000011, trace=0):
    rc = run.main(["--workload", cell.name, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)],
                  require_tpu=False, cell=cell,
                  peaks=_json("bench/peaks.json")["devices"]["TPU v5 lite"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


def test_sound_run_is_correct(capsys):
    out = drive(tiny_cell("mlr", MLR_TTT, "mlr.paper"), capsys)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"time_to_target_s", "setup_s"}
    assert list(out)[-1] == "check"


def test_sound_cnn_run_is_correct(capsys):
    out = drive(tiny_cell("cnn", CNN_STEADY, "cnn.paper", num_clients=4,
                          clients_per_round=2), capsys)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"round_s", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.core import driver

    real = driver.make_step_fn

    def make_step_fn(*a, **k):
        step = real(*a, **k)
        return lambda state, every: (state, step(state, every)[1])

    monkeypatch.setattr(driver, "make_step_fn", make_step_fn)


def _half_batch(monkeypatch):
    from repro.models import small

    real = small.classification_loss

    def loss(apply_fn, params, x, y):
        h = x.shape[0] // 2
        return real(apply_fn, params, x[:h], y[:h])

    monkeypatch.setattr(small, "classification_loss", loss)


def _answer_altered(monkeypatch):
    from repro.core import driver

    real = driver.make_eval_fn

    def make_eval_fn(*a, **k):
        ev = real(*a, **k)
        return lambda params: ev(params) + 0.02

    monkeypatch.setattr(driver, "make_eval_fn", make_eval_fn)


@pytest.mark.parametrize("cell,fault", [
    ("mlr.paper", _state_unchanged), ("mlr.paper", _half_batch),
    ("mlr.paper", _answer_altered), ("cnn.paper", _state_unchanged),
    ("cnn.paper", _half_batch),
], ids=["mlr-state_unchanged", "mlr-half_batch", "mlr-answer_altered",
        "cnn-state_unchanged", "cnn-half_batch"])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch, capsys):
    """Each fault the cell can have makes `correct` false under the
    cell's own limits (`cnn.paper` compares no eval answer)."""
    fault(monkeypatch)
    jax.clear_caches()
    if cell == "mlr.paper":
        small = tiny_cell("mlr", MLR_TTT, cell)
    else:
        small = tiny_cell("cnn", CNN_STEADY, cell, num_clients=4,
                          clients_per_round=2)
    out = drive(small, capsys)
    assert not out["correct"], out["check"]


def test_traced_steady_run_reports_per_layer_metrics(capsys):
    cell = tiny_cell("mlr", {"kind": "steady", "block": 4, "eval_every": 2},
                     "mlr.paper")
    cell.per_layer = [m for m in cell.bench["per_layer"]
                      if m["name"] == "step_mfu"]
    out = drive(cell, capsys, trace=1)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"step_mfu"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
