"""Run one benchmark cell once, on the accelerator this process finds.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is the entry of `BENCHMARK.json`'s
"workloads" named <name>: its model configuration
(`bench/configs/<config>.json`) and its traffic (`bench/traffic/<name>.json`),
which sets the data, the federated configuration and the loop. The
configuration names its family, the module `bench/families/<family>.py`
that makes the cell's data, builds its server and holds its plain
reference and model FLOP counts (`bench.families.image` says what a
family gives). The loops:

* "steady": back-to-back `FedServer.run(block, mode="scanned")` calls,
  each one block of rounds, for `--seconds`; `round_s` is the window over
  the rounds completed.
* "to_target": back-to-back trainings, each a `FedServer.reset` from a
  seed drawn from `--seed` and a `FedServer.run(max_rounds, target_acc,
  mode="scanned")`; no training starts after `--seconds`, and
  `time_to_target_s` is the window over the trainings completed.

Set-up (`setup_s`, process start to the window) makes the data, builds
the server and drives it through its first block (or its first training)
with the window's own call, which compiles every program the window runs.
Those first rounds are what the check compares with the family's plain
reference (`bench.check`) once the window has closed.

With `--trace 1` the window runs under the JAX profiler for at most the
traffic's "trace_seconds"; the per-layer metrics named in `BENCHMARK.json`
for the cell are read by `bench/metrics/<name>.py` from the trace, with
the program's scopes and spans in it (`bench.scopes`), and from the
program's span counts over the window.

The last line of standard output is the result object; earlier lines,
each prefixed "bench:", carry the compile and cache counts, the peak
device bytes, the wire bytes per round and the rounds-to-target. The
numbers compared by the check are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache: a fixed path inside the checkout
CACHE = ".jax_cache"


def log(key: str, **fields) -> None:
    print("bench: " + json.dumps({"what": key, **fields}), flush=True)


def load_cell(name: str, root: str = ROOT) -> SimpleNamespace:
    """The workload entry, its configuration and its traffic, by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        model = json.load(f)
    with open(os.path.join(root, "bench", "traffic", name + ".json")) as f:
        traffic = json.load(f)
    traffic["model"] = model["model"]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return SimpleNamespace(name=name, chips=cell["chips"], config=model,
                           traffic=traffic, per_layer=per_layer,
                           end_to_end=end_to_end, bench=bench)


def _load_file(kind: str, name: str, root: str):
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: str = ROOT):
    """The reader `bench/metrics/<name>.py` of a per-layer metric."""
    return _load_file("metrics", name, root)


def load_family(name: str, root: str = ROOT):
    """The family module `bench/families/<name>.py`."""
    return _load_file("families", name, root)


class Compiles:
    """Counts of JAX's compile and persistent-cache events."""

    def __init__(self, jax):
        self.events = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.events[event] += 1

    def _duration(self, event, secs, **_):
        self.seconds[event] += secs

    def snapshot(self) -> dict:
        e, s = self.events, self.seconds
        return {
            "backend_compiles": e.get("/jax/compilation_cache/cache_misses", 0),
            "cache_hits": e.get("/jax/compilation_cache/cache_hits", 0),
            "cache_requests": e.get(
                "/jax/compilation_cache/compile_requests_use_cache", 0),
            "compile_s": s.get("/jax/core/compile/backend_compile_duration", 0.0),
            "lower_s": s.get("/jax/core/compile/jaxpr_to_mlir_module_duration",
                             0.0),
            "trace_s": s.get("/jax/core/compile/jaxpr_trace_duration", 0.0),
        }


def accuracy_by_round(hist, eval_every: int, stop: int) -> dict:
    """Map a fresh run's History eval accuracies to their rounds."""
    rounds = [r for r in range(1, stop + 1)
              if eval_every > 0 and r % eval_every == 0]
    return dict(zip(rounds, hist.accuracy))


def use_compile_cache(root: str):
    """Import JAX with its persistent compilation cache at the fixed
    `CACHE` directory of the checkout, every program written to it."""
    cache = os.path.join(root, CACHE)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    # a program whose named scopes moved is another program: the scope
    # readers must not be served an executable of other code
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a step program that carries its cell's data as
    # constants is larger than a size cap set in the environment
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def rounds_asked(loop: dict, rtt) -> int:
    """Rounds the window's call runs: one block in a steady cell; in a
    training to a target, whole blocks up to the one where the target is
    met, at most the cap (the driver's early exit between blocks)."""
    if loop["kind"] == "steady":
        return loop["block"]
    cap, block = loop["max_rounds"], loop["block"]
    return cap if rtt is None else min(cap, -(-rtt // block) * block)


def first_steps(jax, server, tr: dict, seed: int) -> dict:
    """Drive the server from `seed` through its first block (steady
    rounds) or its first training (to a target) with the window's own
    call; returns the program's readings of those rounds."""
    loop = tr["loop"]
    block, every = loop["block"], loop["eval_every"]
    server.reset(seed)
    if loop["kind"] == "steady":
        hist = server.run(block, eval_every=every, mode="scanned", block=block)
        stop = block
    else:
        hist = server.run(loop["max_rounds"], target_acc=loop["target_acc"],
                          eval_every=every, mode="scanned", block=block)
        stop = hist.rounds_to_target or loop["max_rounds"]
    return {"seed": seed, "loss": list(hist.loss), "weights": hist.weights,
            "acc": accuracy_by_round(hist, every, stop),
            "params": jax.device_get(server.state.params),
            "rounds": rounds_asked(loop, hist.rounds_to_target),
            "rtt": hist.rounds_to_target}


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT,
         cell: SimpleNamespace | None = None,
         peaks: dict | None = None) -> int:
    """One run of one cell. `require_tpu=False` and a `cell` given in
    place of the one `BENCHMARK.json` names let the tests drive the rest
    of a run on the CPU at a small size, with `peaks` standing in for the
    chip's row of the peaks table."""
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    jax = use_compile_cache(root)
    import numpy as np

    compiles = Compiles(jax)

    cell = cell or load_cell(args.workload, root)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2
    if platform == "tpu":
        with open(os.path.join(root, "bench", "peaks.json")) as f:
            table = json.load(f)["devices"]
        kind = devices[0].device_kind
        if kind not in table:
            print(f"bench: no peaks for device kind {kind!r} in "
                  "bench/peaks.json", file=sys.stderr)
            return 2
        peaks = table[kind]

    sys.path.insert(0, os.path.join(root, "src"))
    import repro
    from bench import check, counts, scopes

    family = load_family(cell.config["family"], root)
    tr = cell.traffic
    loop = tr["loop"]
    rng = np.random.default_rng(args.seed)
    t = time.perf_counter()
    nodes, test = family.make_data(tr)
    data_s = time.perf_counter() - t
    fl = repro.FLConfig(**tr["fl"]).validate()
    block, every = loop["block"], loop["eval_every"]

    def new_seed() -> int:
        return int(rng.integers(0, 2**31 - 1))

    seed0 = new_seed()
    server = family.make_server(repro, fl, tr, nodes, test, seed0)
    steady = loop["kind"] == "steady"
    if not steady:
        cap, target = loop["max_rounds"], loop["target_acc"]
        # every block length a training can use: `cap` rounds in blocks,
        # the last one shorter where the block does not divide the cap
        server.run(cap, target_acc=math.inf, eval_every=every,
                   mode="scanned", block=block)
    first = first_steps(jax, server, tr, seed0)
    setup_compiles = compiles.snapshot()
    setup_spans = dict(server.spans.counts)

    tracing = bool(args.trace)
    seconds = min(args.seconds, tr["trace_seconds"]) if tracing else args.seconds
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if tracing else None
    annotate = (jax.profiler.TraceAnnotation if tracing
                else lambda name: contextlib.nullcontext())
    rounds = failed = 0
    trainings = []  # (seed, History, final params, round counter)
    histories = []
    evals = 0
    t_start = time.perf_counter()
    setup_s = t_start - _T0
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with annotate("bench/window"):
        if steady:
            while True:
                with annotate("bench/run"):
                    h = server.run(block, eval_every=every, mode="scanned",
                                   block=block)
                histories.append(h)
                evals += len(h.accuracy)
                rounds += block
                failed += int(np.sum(~np.isfinite(np.asarray(h.loss))))
                if time.perf_counter() - t_start >= seconds:
                    break
            with annotate("bench/read"):
                jax.block_until_ready(server.state)
        else:
            while time.perf_counter() - t_start < seconds:
                s = new_seed()
                with annotate("bench/reset"):
                    server.reset(s)
                with annotate("bench/run"):
                    h = server.run(cap, target_acc=target, eval_every=every,
                                   mode="scanned", block=block)
                histories.append(h)
                # the final params, kept on the device for the check, and
                # in the traced run the server's round counter
                trainings.append((s, h, server.state.params, None))
                if tracing:
                    with annotate("bench/read"):
                        trainings[-1] = (s, h, server.state.params,
                                         server.round)
    t_end = time.perf_counter()
    span_counts = {k: v - setup_spans.get(k, 0)
                   for k, v in server.spans.counts.items()}
    if tracing:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    window_compiles = {k: v - setup_compiles[k]
                       for k, v in compiles.snapshot().items()}

    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    # ---- results of the window
    metrics = {}
    if steady:
        attempted = rounds
        metrics["round_s"] = window_s / max(rounds, 1)
    else:
        attempted = len(trainings)
        failed = sum(1 for tr_ in trainings if tr_[1].rounds_to_target is None)
        metrics["time_to_target_s"] = window_s / max(len(trainings), 1)
        rtts = [tr_[1].rounds_to_target for tr_ in trainings]
        hit = [r for r in rtts if r is not None]
        log("rounds_to_target", trainings=len(rtts), missed=len(rtts) - len(hit),
            histogram=dict(sorted(collections.Counter(hit).items())),
            setup_training=first["rtt"])
    metrics["setup_s"] = setup_s

    n = fl_param_count(first["params"])
    k = tr["fl"]["clients_per_round"]
    gs = tr["fl"].get("group_size", 512)
    log("wire_bytes_per_round",
        up=counts.wire_bytes(k, n, tr["fl"].get("transport", "f32"), gs),
        down=k * counts.wire_bytes(1, n, tr["fl"].get("downlink", "f32"), gs),
        clients=k, params=n)
    log("compile", setup=setup_compiles, window=window_compiles,
        cache_dir=os.path.join(root, CACHE), data_s=data_s)
    log("memory", peak_bytes_in_use=peak,
        bytes_limit=max(s.get("bytes_limit", 0) for s in stats))

    # ---- the check: free the program's state, then run the reference
    samples = []
    if not steady and trainings:
        pick = np.random.default_rng([args.seed, 1]).choice(
            len(trainings), size=min(tr["check"]["sample_trainings"],
                                     len(trainings)), replace=False)
        for i in sorted(pick):
            s, h, params, _ = trainings[int(i)]
            stop_i = h.rounds_to_target or cap
            samples.append({"seed": s, "loss": list(h.loss),
                            "weights": h.weights,
                            "acc": accuracy_by_round(h, every, stop_i),
                            "params": jax.device_get(params),
                            "rounds": rounds_asked(loop, h.rounds_to_target),
                            "rtt": h.rounds_to_target})
    # (rounds-to-target, rounds run) of each training, for the trace run
    ttt_rows = [(h.rounds_to_target, rnd) for _, h, _, rnd in trainings
                ] if tracing else []
    del server, trainings
    gc.collect()

    t_check = time.perf_counter()
    found = run_check(jax, cell, nodes, test, [first] + samples, family,
                      check)
    check_s = time.perf_counter() - t_check
    correct, table = check.judge(found, tr["check"]["limits"])
    log("check", seconds=check_s, compared=1 + len(samples),
        readings=found)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if tracing:
        scoped = scopes.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = scoped.base
        ctx = SimpleNamespace(
            cell=cell, traffic=tr, config=cell.config, trace=summary,
            scopes=scoped, span_counts=span_counts, histories=histories,
            family=family, window_s=window_s, rounds=rounds, evals=evals,
            trainings=ttt_rows, chips=cell.chips, peaks=peaks, clients=k,
            params=n, counts=counts)
        per = {}
        for m in cell.per_layer:
            v = load_metric(m["name"], root).read(ctx)
            if v is not None:
                per[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = per
        busy = summary.busy_seconds()
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = summary.window_seconds()
        out["device"] = device
        out["breakdown"] = summary.breakdown()
    else:
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["check"] = table
    print(json.dumps(out), flush=True)
    for name, row in table.items():
        print(f"check {name} = {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


def fl_param_count(params) -> int:
    """Values in a params tree, nested or flat."""
    import jax

    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


def reference_device(jax, traffic: dict):
    """Where the reference runs: the traffic's "check.reference_device"
    platform (the default backend where it names none)."""
    platform = traffic["check"].get("reference_device")
    return jax.default_device(jax.devices(platform)[0] if platform else None)


def run_check(jax, cell, nodes, test, runs: list, family, check) -> dict:
    """Follow each compared run with the family's reference; worst
    readings."""
    tr = cell.traffic
    s = family.reference_setup(tr)
    every = tr["loop"]["eval_every"]
    rows, disputed = [], 0
    with reference_device(jax, tr):
        data = family.reference_data(nodes, test)
        refs = [family.follow(s, data, run["seed"], run["rounds"], every)
                for run in runs]
    for run, ref in zip(runs, refs):
        rows.append(check.readings(run, ref))
        if tr["loop"]["kind"] == "to_target":
            target = tr["loop"]["target_acc"]
            ref_hit = [r for r, a in sorted(ref["acc"].items()) if a >= target]
            ref_rtt = ref_hit[0] if ref_hit else None
            limit = tr["check"]["limits"].get("acc", 0.0)
            disputed += check.rtt_disputed(run["rtt"], ref_rtt, ref["acc"],
                                           target, limit)
    found = check.merge(rows)
    if tr["loop"]["kind"] == "to_target":
        found["rtt"] = float(disputed)
    return found


if __name__ == "__main__":
    sys.exit(main())
