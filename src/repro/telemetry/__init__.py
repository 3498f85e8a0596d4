"""Round-level telemetry: per-node contribution traces, phase timing
spans, and pluggable sinks.

FedAdp's mechanism is an observable quantity — the angle between each
node's delta and the global delta, mapped through the Gompertz function
into an aggregation weight. This package makes a run's internals
inspectable WITHOUT touching the compiled path when it is off:

* **In-scan metrics** — `FLConfig(telemetry="node")` makes every
  engine's `round_fn` metrics dict carry the per-node internals
  (``tel/*`` keys: node attribution, cohort mask, weight entropy, wire
  bytes; buffered mode adds staleness ages, landed mask, occupancy).
  With the default ``telemetry=None`` the metrics dict — and the jaxpr
  — are byte-identical to a build without this package.
* **Sinks** (`telemetry.sinks`) — the `TelemetrySink` protocol with
  JSONL (manifest-headed, durable), CSV, and in-memory implementations;
  `emit_round_block` adapts stacked scan metrics to schema events at
  block boundaries.
* **Spans** (`telemetry.spans`) — `SpanTimer`, host phase timing: each
  span is a ``fl/<name>`` `jax.profiler` trace annotation, a call count
  and, with a sink, a ``span`` event.
* **Schema** (`telemetry.schema`) — the versioned JSONL event contract,
  including the in-scan eval sentinel `EVAL_SENTINEL`.
* **Manifest** (`telemetry.manifest`) — run provenance (commit, jax
  version, device topology, config hash), shared with ``BENCH_*.json``.
* **Report** (`telemetry.report`) — the `scripts/flstat.py` logic:
  summaries, rounds-to-target from the stream alone, weight-sum checks.
"""
from repro.telemetry import manifest, report, schema, sinks, spans  # noqa: F401
from repro.telemetry.manifest import run_manifest  # noqa: F401
from repro.telemetry.schema import EVAL_SENTINEL, SCHEMA_VERSION  # noqa: F401
from repro.telemetry.sinks import (  # noqa: F401
    CSVSink,
    JSONLSink,
    MemorySink,
    TelemetrySink,
    emit_manifest,
    emit_round_block,
    emit_summary,
    load_events,
)
from repro.telemetry.spans import SpanTimer  # noqa: F401
