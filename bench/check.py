"""The comparison that decides `correct`.

The program's readings of its first rounds (and, for trainings to a
target, of a sample of the window's trainings) are set against the plain
reference's readings of the same rounds from the same seed and data.
Each number below is compared with its own limit, stated per cell in the
traffic file's "check.limits"; a number a cell has no limit for is not
compared there.

* `loss`    — per round, |program - reference| / |reference| of the mean
  local training loss; the largest over the rounds.
* `update`  — the change of the params over the rounds compared, by the
  worst leaf: | ||program change|| - ||reference change|| | over the
  larger of that leaf's reference norm and the median leaf's. A leaf
  whose reference change is under a thousandth of the median leaf's is
  left out (it moves by round-off alone). Leaves of a nested tree are
  matched by their key paths.
* `update_median` — the same gap of the median leaf, steady where a
  quantized wire makes one small leaf's gap swing from seed to seed.
* `loss_first` — the `loss` gap of the first round alone.
* `weights` — the largest |program - reference| FedAdp weight of any
  client in any round (round_stats, Eq. 9 and the Gompertz weights).
* `acc`     — the largest |program - reference| test accuracy of an eval
  round.
* `acc_first` — the same at the first eval round alone, before the
  trajectories have had rounds to part.
* `rtt`     — trainings whose rounds-to-target differ from the
  reference's, leaving out those where the reference's accuracy at the
  first round in dispute lies within the `acc` limit of the target.
"""
from __future__ import annotations

import jax
import numpy as np

KEEP_SHARE = 1e-3


def leaves_by_path(tree) -> dict:
    """{"outer/inner": leaf} of a params tree, in the tree's own leaf
    order (sorted keys for dicts); a flat dict keeps its keys as names."""
    return {jax.tree_util.keystr(path, simple=True, separator="/"): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_gaps(params0, prog, ref) -> dict:
    """Per leaf, the gap of the change norms over the larger of the
    leaf's reference norm and the median leaf's; leaves that move by
    round-off alone are left out. The trees may nest; leaves are matched
    by their key paths."""
    start, mine, theirs = (leaves_by_path(t) for t in (params0, prog, ref))
    names = list(theirs)
    dp = [np.linalg.norm(np.asarray(mine[k], np.float64)
                         - np.asarray(start[k], np.float64)) for k in names]
    dr = [np.linalg.norm(np.asarray(theirs[k], np.float64)
                         - np.asarray(start[k], np.float64)) for k in names]
    med = float(np.median(dr))
    if med == 0.0:  # the reference did not move: any change is a gap
        return {k: (0.0 if p == 0.0 else float("inf"))
                for k, p in zip(names, dp)}
    return {k: abs(p - r) / max(r, med) for k, p, r in zip(names, dp, dr)
            if r >= KEEP_SHARE * med}


def loss_gap(prog: list, ref: list) -> float:
    n = min(len(prog), len(ref))
    p, r = np.asarray(prog[:n], np.float64), np.asarray(ref[:n], np.float64)
    if not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def weights_gap(prog: list, ref: list) -> float:
    n = min(len(prog), len(ref))
    return float(max(np.max(np.abs(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64)))
                     for a, b in zip(prog[:n], ref[:n])))


def acc_gap(prog: dict, ref: dict, first: bool = False) -> float | None:
    common = sorted(set(prog) & set(ref))
    if not common:
        return None
    if first:
        common = common[:1]
    return float(max(abs(prog[r] - ref[r]) for r in common))


def rtt_disputed(prog_rtt, ref_rtt, ref_acc: dict, target: float,
                 acc_limit: float) -> bool:
    """True where the two rounds-to-target differ beyond what precision
    can explain."""
    if prog_rtt == ref_rtt:
        return False
    first = min(r for r in (prog_rtt, ref_rtt) if r is not None)
    a = ref_acc.get(first)
    return a is None or abs(a - target) > acc_limit


def readings(prog: dict, ref: dict) -> dict:
    """The numbers of one comparison (one seed, or one training)."""
    leaves = leaf_gaps(ref["params0"], prog["params"], ref["params"])
    out = {"loss": loss_gap(prog["loss"], ref["loss"]),
           "loss_first": loss_gap(prog["loss"][:1], ref["loss"][:1]),
           "update": float(max(leaves.values())) if leaves else 1.0,
           "update_median": float(np.median(list(leaves.values())))
           if leaves else 1.0,
           "weights": weights_gap(prog["weights"], ref["weights"])}
    acc = acc_gap(prog["acc"], ref["acc"])
    if acc is not None:
        out["acc"] = acc
        out["acc_first"] = acc_gap(prog["acc"], ref["acc"], first=True)
    return out


def merge(rows: list[dict]) -> dict:
    """Worst reading of each number over several comparisons."""
    out = {}
    for row in rows:
        for k, v in row.items():
            out[k] = max(out.get(k, v), v)
    return out


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; a number that has a limit but no reading fails."""
    table = {}
    ok = True
    for name, limit in limits.items():
        value = found.get(name)
        table[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            ok = False
    return ok, table
