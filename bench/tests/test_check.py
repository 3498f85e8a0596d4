"""The comparison's leaf walk over nested and flat params trees."""
import numpy as np
import pytest

from bench import check, run


def _flat_gaps(params0, prog, ref):
    """`check.leaf_gaps` as it was for flat dicts: leaves by sorted key."""
    names = sorted(ref)
    dp = [np.linalg.norm(np.asarray(prog[k], np.float64)
                         - np.asarray(params0[k], np.float64)) for k in names]
    dr = [np.linalg.norm(np.asarray(ref[k], np.float64)
                         - np.asarray(params0[k], np.float64)) for k in names]
    med = float(np.median(dr))
    return {k: abs(p - r) / max(r, med) for k, p, r in zip(names, dp, dr)
            if r >= check.KEEP_SHARE * med}


def test_nested_tree_leaves_by_key_path():
    """One leaf moves and one does not: the still one is left out, the
    moving one read against its own norm."""
    p0 = {"layer": {"w": np.zeros((3, 2), np.float32),
                    "b": np.ones(4, np.float32)}}
    ref = {"layer": {"w": np.full((3, 2), 0.5, np.float32),
                     "b": np.ones(4, np.float32)}}
    prog = {"layer": {"w": np.full((3, 2), 0.51, np.float32),
                      "b": np.ones(4, np.float32)}}
    gaps = check.leaf_gaps(p0, prog, ref)
    assert list(gaps) == ["layer/w"]
    assert gaps["layer/w"] == pytest.approx(0.01 / 0.5, rel=1e-5)
    assert check.leaf_gaps(p0, p0, ref) == {"layer/w": pytest.approx(1.0)}
    assert run.fl_param_count(p0) == 10


def test_flat_dict_names_order_and_readings_unchanged():
    rng = np.random.default_rng(0)
    shapes = {"w": (784, 10), "b": (10,), "c1": (5, 5, 1, 32), "fb2": (10,)}
    p0 = {k: rng.normal(size=s).astype(np.float32) for k, s in shapes.items()}
    ref = {k: v + rng.normal(scale=0.1, size=v.shape) for k, v in p0.items()}
    prog = {k: v + rng.normal(scale=1e-3, size=v.shape)
            for k, v in ref.items()}
    ref["b"] = p0["b"] + 1e-9  # moves by round-off alone: left out
    gaps = check.leaf_gaps(p0, prog, ref)
    old = _flat_gaps(p0, prog, ref)
    assert list(gaps) == list(old) == ["c1", "fb2", "w"]
    assert gaps == old
    assert run.fl_param_count(p0) == 7850 + 800 + 10
