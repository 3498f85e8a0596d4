"""Reduce a JAX profiler trace to the benchmark's per-layer numbers.

The trace is the `.xplane.pb` that `jax.profiler.start_trace` writes. Its
device planes are named "/device:TPU:<n>"; the operations that ran on a
device are the events of the plane's "XLA Ops" line, with start and
duration in nanoseconds on the same clock as the host plane
("/host:CPU"), where the harness's own `TraceAnnotation` spans (named
"bench/...") are events too. The traced window is the "bench/window"
span; device events are clipped to it.

* busy time: the union of a device's op intervals in the window;
* idle gaps: the complement of that union in the window, each attributed
  to the "bench/..." span that covers most of it ("outside" where none
  does);
* kernel time: the summed durations of a Pallas kernel's events: the
  `tpu_custom_call` instructions named after the kernel's jitted wrapper
  ("%weighted_agg.18 = ... custom_call_target="tpu_custom_call"");
* top operations: the leaf instructions (a `while` or `conditional`
  event spans the ops of its body, which are events of their own) by
  summed time;
* exposed collective time: the part of the window in which a collective
  runs on a device and no other operation does.
"""
from __future__ import annotations

import bisect
import collections
import glob
import gzip
import os
import re
from typing import NamedTuple

OPS_LINE = "XLA Ops"
WINDOW = "bench/window"
SPAN_PREFIX = "bench/"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


class Event(NamedTuple):
    name: str  # the HLO instruction's name, e.g. "fusion.294"
    start: float  # seconds on the trace's clock
    end: float
    label: str  # the instruction's text as the trace gives it


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the (merged) intervals `a` not covered by (merged) `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Summary:
    """One traced window: device ops per device and the harness's spans."""

    def __init__(self, devices: dict, spans: list, window: tuple):
        self.window = window
        w0, w1 = window
        self.devices = {
            d: [Event(e.name, max(e.start, w0), min(e.end, w1), e.label)
                for e in evs if e.end > w0 and e.start < w1]
            for d, evs in sorted(devices.items())}
        self.spans = sorted((s for s in spans if s.name != WINDOW),
                            key=lambda s: s.start)
        self._starts = [s.start for s in self.spans]
        self._longest = max((s.end - s.start for s in self.spans), default=0.0)

    def window_seconds(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, device) -> list[tuple[float, float]]:
        return union((e.start, e.end) for e in self.devices[device])

    def busy_seconds(self) -> list[float]:
        return [total(self.busy(d)) for d in self.devices]

    def idle_share(self) -> float | None:
        """1 - busy / window, averaged over the devices; None without any."""
        busy = self.busy_seconds()
        if not busy or not any(busy):
            return None
        w = self.window_seconds()
        return sum(1.0 - b / w for b in busy) / len(busy)

    def op_seconds(self) -> collections.Counter:
        """Summed time of each leaf instruction, over the devices."""
        out = collections.Counter()
        for evs in self.devices.values():
            evs = sorted(evs, key=lambda e: (e.start, -e.end))
            for i, e in enumerate(evs):
                inner = i + 1 < len(evs) and evs[i + 1].start < e.end
                if not inner:
                    out[e.name] += e.end - e.start
        return out

    def kernel_events(self, kernel: str) -> list[Event]:
        """Events of the Pallas kernel made by the jitted wrapper `kernel`."""
        return [e for evs in self.devices.values() for e in evs
                if KERNEL_TARGET in e.label
                and re.sub(r"\.\d+$", "", e.name) == kernel]

    def idle_gaps(self) -> list[tuple[float, float]]:
        gaps = []
        for d in self.devices:
            gaps += subtract([self.window], self.busy(d))
        return gaps

    def gap_owner(self, gap) -> str:
        """The harness span that covers most of the gap."""
        best, best_cover = "outside", 0.0
        i = bisect.bisect_left(self._starts, gap[1]) - 1
        # the harness's spans follow one another, so walk back from the
        # last one that starts before the gap ends
        while i >= 0:
            s = self.spans[i]
            cover = min(gap[1], s.end) - max(gap[0], s.start)
            if cover > best_cover:
                best, best_cover = s.name, cover
            if s.end <= gap[0] and s.start < gap[0] - self._longest:
                break
            i -= 1
        return best

    def idle_by_span(self) -> collections.Counter:
        out = collections.Counter()
        n = max(len(self.devices), 1)
        for g in self.idle_gaps():
            out[self.gap_owner(g)] += (g[1] - g[0]) / n
        return out

    def exposed_collective_share(self) -> float | None:
        """Share of the window in which a collective runs on a device and
        no other operation does, averaged over devices; None where no
        collective ran."""
        shares, seen = [], False
        w = self.window_seconds()
        for evs in self.devices.values():
            coll = union((e.start, e.end) for e in evs
                         if any(e.name.startswith(c) for c in COLLECTIVES))
            other = union((e.start, e.end) for e in evs
                          if not any(e.name.startswith(c)
                                     for c in COLLECTIVES))
            seen = seen or bool(coll)
            shares.append(total(subtract(coll, other)) / w)
        if not seen:
            return None
        return sum(shares) / len(shares)

    def breakdown(self, top: int = 10) -> dict:
        ops = self.op_seconds().most_common(top)
        gaps = self.idle_by_span().most_common(top)
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


# ------------------------------------------------------------------ loading

def _name(text: str) -> str:
    """"%fusion.294 = f32[...] fusion(...)" -> "fusion.294"."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_profile(pd) -> Summary:
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    Event(_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9,
                          e.name)
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns * 1e-9,
                                           e.end_ns * 1e-9, e.name))
    win = [s for s in spans if s.name == WINDOW]
    if win:
        window = (win[0].start, win[0].end)
    else:
        evs = [e for d in devices.values() for e in d]
        window = ((min(e.start for e in evs), max(e.end for e in evs))
                  if evs else (0.0, 0.0))
    return Summary(devices, spans, window)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Summary:
    """Summary of the trace file (gzipped where it ends in .gz), or of the
    newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()))
    return from_profile(ProfileData.from_file(path))
