"""BENCHMARK.json holds to its contract, and every cell and metric it
names has the files the harness finds by that name; each cell's traffic
keeps its family's rules."""
import json
import os
import re

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    assert NAME.match(entry["name"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("bench/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "families",
                                       cfg["family"] + ".py"))
    assert 1 <= len(entry["why"]) <= 200


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_has_traffic_and_config(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    path = os.path.join(ROOT, "bench", "traffic", cell["name"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["loop"]["kind"] in ("steady", "to_target")
    assert traffic["check"]["limits"], "a cell's check compares something"
    cfg = run.load_cell(cell["name"], ROOT).config
    run.load_family(cfg["family"], ROOT).validate_traffic(traffic)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_shape(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "metrics", metric["name"] + ".py"))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for cell in BENCH["workloads"]:
        e2e = [m for m in BENCH["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        per = [m for m in BENCH["per_layer"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        assert len(e2e) >= 2 and per
        for m in per:
            assert m["moves"] in {e["name"] for e in e2e}
