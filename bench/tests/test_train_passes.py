"""`train_passes` reads the program's `History.train_passes` and reads
nothing from a program that records none."""
import os
from types import SimpleNamespace

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _read(*hists):
    return run.load_metric("train_passes", ROOT).read(
        SimpleNamespace(histories=list(hists)))


@pytest.mark.parametrize("rounds,want", [
    ([[1.0, 1.0], [1.0, 1.0]], 1.0),
    ([[2.0, 2.0], [2.0, 2.0]], 2.0),
    ([[1.0, 2.0, 2.0, 2.0]], 1.75),
], ids=["single", "two", "mixed"])
def test_mean_over_the_window(rounds, want):
    assert _read(*(SimpleNamespace(train_passes=r) for r in rounds)) == want


def test_absent_without_the_counter():
    assert _read(SimpleNamespace(expert_rows=[3]), SimpleNamespace()) is None
    assert _read(SimpleNamespace(train_passes=[])) is None
    assert _read() is None
