"""Readings that the check's limits are set from, for one cell.

    python3 -m bench.calibrate --workload <name> [--seeds 12] [--faulty-seeds 3]

On the chip, at the cell's own size, in one process:

* the program: for each of `--seeds` seeds, the first block (or the first
  training to the target) through the window's own call, compared with
  the plain reference exactly as a run's check compares it;
* the variants of the cell's family (`reference_variants`; for the
  image family the control, the reference in bfloat16 put in the
  program's place, and a planted fault, the reference with each local
  step's loss taken over half of its minibatch), each on `--faulty-seeds`
  seeds.

A state left unchanged reads 1 for `update` by that number's definition,
so it needs no run. The readings go to standard output, one JSON line
per comparison, and a summary of the largest sound reading and the
smallest faulty one of each number last.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time


def _side_run(family, s, data, seed, rounds, every, target):
    """A reference variant's readings shaped like the program's: its
    per-round lists stop at its own rounds-to-target, as a History does."""
    out = family.follow(s, data, seed, rounds, every)
    rtt = None
    if target is not None:
        hit = [r for r, a in sorted(out["acc"].items()) if a >= target]
        rtt = hit[0] if hit else None
        if rtt is not None:
            out["loss"], out["weights"] = out["loss"][:rtt], out["weights"][:rtt]
            out["acc"] = {r: a for r, a in out["acc"].items() if r <= rtt}
    out["rtt"] = rtt
    return out


def _compare_all(args, tr, data, firsts, seeds, rows, family, check):
    """Readings of the program and of each variant against the reference."""
    loop = tr["loop"]
    every, target = loop["eval_every"], loop.get("target_acc")
    acc_limit = tr["check"]["limits"].get("acc", 0.0)
    s_ref = family.reference_setup(tr)
    refs = {}

    def compare(kind, prog, ref):
        row = check.readings(prog, ref)
        row["leaves"] = check.leaf_gaps(ref["params0"], prog["params"],
                                        ref["params"])
        if target is not None:
            hit = [r for r, a in sorted(ref["acc"].items()) if a >= target]
            row["rtt"] = float(check.rtt_disputed(
                prog["rtt"], hit[0] if hit else None, ref["acc"], target,
                acc_limit))
            row["rtt_pair"] = [prog["rtt"], hit[0] if hit else None]
        row.update(kind=kind, seed=prog["seed"])
        rows[kind].append(row)
        print(json.dumps(row), flush=True)

    t = time.perf_counter()
    for first in firsts:
        ref = family.follow(s_ref, data, first["seed"], first["rounds"],
                            every)
        refs[first["seed"]] = (first["rounds"], ref)
        compare("program", first, ref)
    print(f"# program + reference: {time.perf_counter() - t:.1f} s", flush=True)
    for kind, s in family.reference_variants(tr).items():
        for seed in seeds[: args.faulty_seeds]:
            rounds, ref = refs[seed]
            out = _side_run(family, s, data, seed, rounds, every, target)
            out["seed"] = seed
            compare(kind, out, ref)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--faulty-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=3_000_000_019)
    args = p.parse_args(argv)

    from bench import run as bench_run

    root = bench_run.ROOT
    jax = bench_run.use_compile_cache(root)
    import numpy as np

    sys.path.insert(0, os.path.join(root, "src"))
    import repro
    from bench import check

    cell = bench_run.load_cell(args.workload, root)
    family = bench_run.load_family(cell.config["family"], root)
    tr = cell.traffic
    loop = tr["loop"]
    nodes, test = family.make_data(tr)
    fl = repro.FLConfig(**tr["fl"]).validate()
    seeds = [int(x) for x in np.random.default_rng(args.first_seed).integers(
        0, 2**31 - 1, size=args.seeds)]
    server = family.make_server(repro, fl, tr, nodes, test, seeds[0])
    if loop["kind"] == "to_target":
        server.run(loop["max_rounds"], target_acc=math.inf,
                   eval_every=loop["eval_every"], mode="scanned",
                   block=loop["block"])
    firsts = [bench_run.first_steps(jax, server, tr, s) for s in seeds]
    del server
    rows = collections.defaultdict(list)
    with bench_run.reference_device(jax, tr):
        data = family.reference_data(nodes, test)
        _compare_all(args, tr, data, firsts, seeds, rows, family, check)
    names = ["loss", "loss_first", "update", "update_median", "weights",
             "acc", "acc_first", "rtt"]
    summary = {}
    for kind, rs in rows.items():
        summary[kind] = {
            n: (max if kind == "program" else min)(r[n] for r in rs)
            for n in names if rs and all(n in r for r in rs)}
    summary["state_unchanged"] = {"update": 1.0}
    print(json.dumps({"summary": summary, "seeds": seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
