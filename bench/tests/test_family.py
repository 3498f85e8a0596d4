"""A client-model family added through new files alone: the files under
`toy/` (a family module with its own data and plain reference, its
configuration, its traffic and a per-layer metric) and entries appended
to `BENCHMARK.json` go into a copy of the benchmark, whose harness then
runs the new cell on the CPU, steady and traced, and judges it correct,
and not correct with the program's state left unchanged."""
import filecmp
import json
import os
import shutil

import jax
import pytest

from bench import run
from bench.tests.test_harness import _state_unchanged

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "bench", "tests", "toy")
UNTOUCHED = ("run.py", "check.py", "trace.py", "scopes.py")
with open(os.path.join(ROOT, "bench", "peaks.json")) as _f:
    PEAKS = json.load(_f)["devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d, _, files in os.walk(TOY):
        for f in files:
            if f == "entries.json":
                continue
            rel = os.path.relpath(os.path.join(d, f), TOY)
            dest = os.path.join(root, "bench", rel)
            assert not os.path.exists(dest), f"{rel} is not a new file"
            shutil.copy(os.path.join(d, f), dest)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(TOY, "entries.json")) as f:
        entries = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in entries["add_cell_to"]:
            m["workloads"].append(entries["workloads"][0]["name"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for name in UNTOUCHED:
        assert filecmp.cmp(os.path.join(root, "bench", name),
                           os.path.join(ROOT, "bench", name), shallow=False)
    return root


def drive(root, capsys, trace=0):
    cell = run.load_cell("toy.blobs", root)
    rc = run.main(["--workload", cell.name, "--seed", "3000000013",
                   "--seconds", "1", "--trace", str(trace)],
                  require_tpu=False, root=root, cell=cell, peaks=PEAKS)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_new_family_cell_is_correct(toy_root, capsys):
    out = drive(toy_root, capsys)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    assert set(out["check"]) == {"loss", "update", "weights"}


def test_new_family_cell_traced(toy_root, capsys):
    out = drive(toy_root, capsys, trace=1)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"step_mfu", "fetches_per_block"}
    # one scanned block a call, so one metric fetch
    assert out["metrics"]["fetches_per_block"]["value"] == 1.0
    assert out["metrics"]["step_mfu"]["value"] > 0


def test_new_family_state_unchanged_is_not_correct(toy_root, monkeypatch,
                                                   capsys):
    _state_unchanged(monkeypatch)
    jax.clear_caches()
    out = drive(toy_root, capsys)
    assert not out["correct"], out["check"]
    assert out["check"]["update"]["value"] >= 1.0
