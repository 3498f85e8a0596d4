"""Host-level phase timing spans.

The compiled round is one dispatch: the host cannot see downlink, local
training, uplink or aggregation as separate wall-clock phases inside it.
Those are `jax.named_scope` scopes (``fl.<phase>``) in the compiled step
and show up in a profiler trace as each device op's name path. What the
host CAN bound is each dispatch-granular phase of a run — a reset, a
dispatch, a blocking fetch of metrics, a read of the round counter, the
History build, checkpoint writes, sink flushes.

    spans = SpanTimer(sink)
    with spans.span("scan_block", round=done):
        with spans.span("dispatch"):
            state, ms = run_block(state, ...)
        with spans.span("fetch"):
            ms = jax.device_get(ms)

Every span:

* enters a `jax.profiler.TraceAnnotation` named ``"fl/" + name``, so a
  profiler trace holds it on the host plane, on the device ops' clock
  (about a microsecond when no trace is running);
* counts one call in `counts` (calls per span name — the program's own
  counter; a host sync is one ``read_round`` or one ``fetch``);
* emits a ``span`` event (`telemetry.schema`) into `sink` when one is
  set; `scripts/flstat.py` reports p50/p90/p99 per span name from that
  stream.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

from repro.telemetry.sinks import TelemetrySink

# the profiler name of a span is PREFIX + its name
PREFIX = "fl/"


class SpanTimer:
    """Named wall-clock spans -> profiler annotations, call counts and
    sink events."""

    def __init__(self, sink: Optional[TelemetrySink] = None):
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.sink = sink
        self.counts: dict = {}

    @contextlib.contextmanager
    def span(self, name: str, round: Optional[int] = None):
        t0 = time.perf_counter()
        with self._annotate(PREFIX + name):
            yield
        dur = time.perf_counter() - t0
        self.counts[name] = self.counts.get(name, 0) + 1
        if self.sink is not None:
            ev = {"event": "span", "name": name, "dur_s": dur, "t0": t0}
            if round is not None:
                ev["round"] = int(round)
            self.sink.emit(ev)
