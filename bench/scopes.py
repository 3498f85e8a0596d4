"""The program's named scopes and host spans, read from a profiler trace.

The round step runs its phases under `jax.named_scope` scopes named
``fl.<phase>`` (`repro.core.driver`, `repro.core.fl`), and `FedServer`
times its host phases with `SpanTimer`, whose spans are profiler
annotations named ``fl/<name>`` (`repro.telemetry.spans`). Beside the
harness's reducer (`bench.trace`, whose `Summary` the benchmark's metrics
read), this reads both from the same `.xplane.pb`:

* device time by scope: each leaf op's JAX name path (its HLO `op_name`,
  which holds every `jax.named_scope` it ran under) gives it the
  innermost ``fl.<phase>`` scope (`scope_of`); an op under none is
  counted under its program (e.g. "jit_run_block");
* idle time by span: the device's idle gaps in the window, split by the
  ``fl/`` span the host was in, innermost or outermost, exactly where
  they overlap.

The name paths are not in `jax.profiler.ProfileData`'s events. `xprof`'s
trace-viewer conversion of the same file gives each device op's `tf_op`
(its name path) and `device_offset_ps`, which are joined to the events
here on (device, offset).

    python3 -m bench.scopes --workload <name> --seed <n> --seconds <s>

runs on the chip, from the root of a checkout, the cell's window loop as
`python3 -m bench.run --trace 1` does (set-up first, then back-to-back
blocks or trainings under the harness's "bench/..." spans and the
profiler) and prints one JSON line: device milliseconds by scope per
round, the share of the step program's leaf-op time under a scope, idle
shares by top-level span, idle seconds by innermost span, and the
window's span counts per training or per block.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from typing import NamedTuple

from bench import trace

SPAN_PREFIX = "fl/"
NO_SPAN = "none"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "jit_run_block"
# an "fl.<phase>" scope as a segment of a name path, also inside transform
# wrappers such as "transpose(jvp(fl.local_train))"
_SCOPE = re.compile(r"(?:^|[/(])(fl\.[A-Za-z_]+)(?=[)/:;]|$)")


class Op(NamedTuple):
    name: str  # the HLO instruction's name, e.g. "fusion.294"
    start: float  # seconds on the trace's clock
    end: float
    path: str  # its JAX name path (HLO op_name), "" where unknown
    module: str  # its program, e.g. "jit_run_block"


def scope_of(path: str) -> str | None:
    """The innermost "fl.<phase>" scope of an op's name path, or None.
    A fused op's path may list several names joined by ";": the first
    is the fusion's own."""
    found = _SCOPE.findall(path.split(";", 1)[0])
    return found[-1] if found else None


class Scopes:
    """One traced window: the harness's `Summary`, the device ops with
    their name paths and the program's "fl/..." spans."""

    def __init__(self, base: trace.Summary, ops: dict, spans: list):
        self.base = base
        w0, w1 = base.window
        self.ops = {
            d: [o._replace(start=max(o.start, w0), end=min(o.end, w1))
                for o in evs if o.end > w0 and o.start < w1]
            for d, evs in sorted(ops.items())}
        # nested as the host's calls are: outer spans first
        self.spans = sorted(spans, key=lambda s: (s.start, -s.end))

    def leaf_ops(self):
        """The ops that hold no other op (a `while` or `conditional` event
        spans the ops of its body, which are events of their own)."""
        for evs in self.ops.values():
            evs = sorted(evs, key=lambda o: (o.start, -o.end))
            for i, o in enumerate(evs):
                if not (i + 1 < len(evs) and evs[i + 1].start < o.end):
                    yield o

    def scope_seconds(self) -> collections.Counter:
        """Summed leaf-op time, over the devices, by the innermost
        "fl.<phase>" scope of each op; an op under none (or of unknown
        path, such as a copy the compiler put in) is counted under its
        program, "?" where that is unknown too."""
        out = collections.Counter()
        for o in self.leaf_ops():
            out[scope_of(o.path) or o.module or "?"] += o.end - o.start
        return out

    def scoped_share(self, program: str = STEP_PROGRAM) -> float | None:
        """Percent of `program`'s leaf-op time under some scope; None
        where the program ran no op."""
        scoped = every = 0.0
        for o in self.leaf_ops():
            if o.module == program:
                every += o.end - o.start
                scoped += (o.end - o.start) * bool(scope_of(o.path))
        return 100.0 * scoped / every if every else None

    def idle(self, outermost: bool = False) -> collections.Counter:
        """Device idle seconds in the window, averaged over the devices,
        by the "fl/..." span the host was in: the innermost one, or with
        `outermost` the top-level one; `NO_SPAN` where it was in none."""
        segs = owner_segments(self.spans, outermost)
        out = collections.Counter()
        n = max(len(self.base.devices), 1)
        for d in self.base.devices:
            gaps = trace.subtract([self.base.window], self.base.busy(d))
            for name, sec in overlap(gaps, segs).items():
                out[name] += sec / n
        return out

    def idle_shares(self) -> dict:
        """Percent of the window in which the device idles, by top-level
        span: these and `NO_SPAN` add up to the harness's idle share."""
        w = self.base.window_seconds()
        return {k: 100.0 * v / w
                for k, v in self.idle(outermost=True).most_common()}


def owner_segments(spans, outermost: bool) -> list:
    """Disjoint (start, end, name) pieces of the spans' union, each owned
    by the innermost (or outermost) span covering it. `spans` are sorted
    by (start, -end) and nest, as one thread's context managers do; a
    span that outlasts its parent is cut at the parent's end."""
    segs = []
    if outermost:
        for s in spans:
            if not segs or s.start >= segs[-1][1]:
                segs.append((s.start, s.end, s.name))
        return segs
    stack, cur = [], float("-inf")  # stack: (end, name), innermost last

    def advance(t):
        """Hand the time from `cur` to `t` to the spans open in it."""
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
            cur = max(cur, end)
        if stack and t > cur:
            segs.append((cur, t, stack[-1][1]))
        cur = max(cur, t)

    for s in spans:
        advance(s.start)
        stack.append((min(s.end, stack[-1][0]) if stack else s.end, s.name))
    advance(float("inf"))
    return segs


def overlap(gaps, segs) -> collections.Counter:
    """Seconds of the sorted disjoint `gaps` in each owner's segments,
    `NO_SPAN` for the rest."""
    out, j = collections.Counter(), 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        covered, k = 0.0, j
        while k < len(segs) and segs[k][0] < g1:
            o = min(g1, segs[k][1]) - max(g0, segs[k][0])
            if o > 0:
                out[segs[k][2]] += o
                covered += o
            k += 1
        if g1 - g0 > covered:
            out[NO_SPAN] += g1 - g0 - covered
    return out


# ------------------------------------------------------------------ loading

def _offset_ps(event) -> int | None:
    for k, v in event.stats:
        if k == "device_offset_ps":
            return int(v)
    return None


def from_profile(pd, paths: dict) -> Scopes:
    """`Scopes` of a `ProfileData`; `paths` ({(device plane, offset in
    ps): name path}, from `xprof_paths`) gives each device op its path."""
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if trace.OPS_LINE not in lines:
                continue
            modules = [(e.start_ns * 1e-9, e.name.split("(", 1)[0])
                       for e in lines.get(MODULES_LINE, [])]
            starts = [m[0] for m in modules]
            evs = []
            for e in lines[trace.OPS_LINE]:
                start = e.start_ns * 1e-9
                i = bisect.bisect_right(starts, start) - 1
                evs.append(Op(trace._name(e.name), start, e.end_ns * 1e-9,
                              paths.get((plane.name, _offset_ps(e)), ""),
                              modules[i][1] if i >= 0 else ""))
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            spans += [trace.Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  e.name)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return Scopes(trace.from_profile(pd), ops, spans)


def xprof_paths(xplane: str) -> dict:
    """{(device plane, device_offset_ps): name path} of every device op,
    from `xprof`'s trace-viewer conversion of the `.xplane.pb` file. The
    conversion's Trace proto is read directly: its JSON form, which
    `xprof.convert.raw_to_tool_data.xspace_to_tool_data` returns, costs
    several times more (`bench/tests/test_scopes.py` checks that the
    pinned `xprof` still has both names used here)."""
    from xprof.convert import raw_to_tool_data
    from xprof.protobuf import trace_events_old_pb2

    raw, ok = raw_to_tool_data._pywrap_profiler_plugin.xspace_to_tools_data(
        [xplane], "trace_viewer", {"use_saved_result": True})
    if not ok:
        raise ValueError(f"xprof could not convert {xplane}: {raw!r}")
    proto = trace_events_old_pb2.Trace()
    proto.ParseFromString(raw)
    planes = {i: d.name for i, d in proto.devices.items()}
    out = {}
    for e in proto.trace_events:
        a = e.args
        if "tf_op" in a and "device_offset_ps" in a:
            out[(planes.get(e.device_id), int(a["device_offset_ps"]))] = (
                a["tf_op"])
    return out


def load(path: str) -> Scopes:
    """`Scopes` of the trace file (gzipped where it ends in .gz), or of
    the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace.find_xplane(path)
    with tempfile.TemporaryDirectory() as tmp:
        if path.endswith(".gz"):
            raw = os.path.join(tmp, os.path.basename(path)[:-len(".gz")])
            with gzip.open(path, "rb") as f, open(raw, "wb") as g:
                shutil.copyfileobj(f, g)
            path = raw
        paths = xprof_paths(path)
        pd = ProfileData.from_file(path)
    return from_profile(pd, paths)


# ---------------------------------------------------------------- one cell

def main(argv=None, *, require_tpu: bool = True, cell=None) -> int:
    """One traced window of one cell. `require_tpu=False` and a `cell`
    given in place of the one `BENCHMARK.json` names let the tests drive
    it on the CPU at a small size, as `bench.run.main`'s do."""
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)

    from bench import run

    root = run.ROOT
    jax = run.use_compile_cache(root)
    if require_tpu and jax.default_backend() != "tpu":
        print("bench.scopes: needs a TPU chip", file=sys.stderr)
        return 2
    import numpy as np

    sys.path.insert(0, os.path.join(root, "src"))
    import repro

    cell = cell or run.load_cell(args.workload, root)
    family = run.load_family(cell.config["family"], root)
    tr, loop = cell.traffic, cell.traffic["loop"]
    block, every = loop["block"], loop["eval_every"]
    rng = np.random.default_rng(args.seed)

    def new_seed() -> int:
        return int(rng.integers(0, 2**31 - 1))

    nodes, test = family.make_data(tr)
    server = family.make_server(repro, repro.FLConfig(**tr["fl"]).validate(),
                                tr, nodes, test, new_seed())
    annotate = jax.profiler.TraceAnnotation
    steady = loop["kind"] == "steady"

    def one():
        """One block (steady) or one training (to a target)."""
        if steady:
            with annotate("bench/run"):
                server.run(block, eval_every=every, mode="scanned",
                           block=block)
            return block
        with annotate("bench/reset"):
            server.reset(new_seed())
        with annotate("bench/run"):
            h = server.run(loop["max_rounds"], target_acc=loop["target_acc"],
                           eval_every=every, mode="scanned", block=block)
        return run.rounds_asked(loop, h.rounds_to_target)

    if not steady:  # every block length a training can use
        server.run(loop["max_rounds"], target_acc=math.inf, eval_every=every,
                   mode="scanned", block=block)
    one()
    jax.block_until_ready(server.state)
    counts0 = dict(server.spans.counts)
    trace_dir = tempfile.mkdtemp(prefix="bench-scopes-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    units = rounds = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_start = time.perf_counter()
    with annotate("bench/window"):
        while units == 0 or time.perf_counter() - t_start < args.seconds:
            rounds += one()
            units += 1
        jax.block_until_ready(server.state)
    window_s = time.perf_counter() - t_start
    jax.profiler.stop_trace()
    counts = {k: (v - counts0.get(k, 0)) / units
              for k, v in server.spans.counts.items()}

    t_load = time.perf_counter()
    s = load(trace_dir)
    load_s = time.perf_counter() - t_load
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "window_s": window_s,
        "per": "block" if steady else "training", "units": units,
        "rounds": rounds, "load_s": load_s,
        "idle_share": 100.0 * (s.base.idle_share() or 0.0),
        "scope_ms_per_round": {k: 1e3 * v / rounds
                               for k, v in s.scope_seconds().most_common()},
        "scoped_share": s.scoped_share(),
        "idle_share_by_span": s.idle_shares(),
        "idle_s_by_innermost_span": dict(s.idle().most_common()),
        "span_counts": counts,
        "host_syncs": counts.get("read_round", 0) + counts.get("fetch", 0),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
