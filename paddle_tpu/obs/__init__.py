"""paddle_tpu.obs — always-on production telemetry.

One low-overhead surface over every subsystem's counters (ROADMAP open
item 5): a process-wide metrics registry (`Counter` / `Gauge` /
`Histogram` with fixed log-spaced buckets → p50/p95/p99 without
per-sample storage), exporters (`snapshot()` nested JSON,
`prometheus_text()` exposition, the opt-in `MetricsServer` HTTP
endpoint with ``/metrics`` + ``/healthz``), and declared objectives with
their gate (`obs.slo`: `Objective`, `evaluate`, `write_baseline`).

Instrumented out of the box (each registers its existing `stats()` dict
as a collector — single source of truth, no duplicated bookkeeping):

* `inference.ServingPool` — request/queue-wait/execute latency
  histograms, batch occupancy + flush reasons, member health
  (``metrics=False`` disables; ``pool.serve_metrics(port=0)`` exports);
* `inference.ServingRouter` — per-replica health, failovers, swap
  generations (``router.serve_metrics(...)``);
* `inference.DecodeEngine` — occupancy, fragmentation, TTFT and
  queue-wait histograms, scheduler-phase spans (`decode.round*`);
* `distributed` Engine — dispatch/device_put/step counts, an
  `engine.dispatch` root span a train call;
* `profiler` — `Profiler.summary()` publishes steps/sec;
  `profiled_span(name, histogram=...)` feeds any span into a latency
  histogram even when no native tracer is recording.

Distributed request tracing rides on top (`obs.trace` + `obs.flight`):
Dapper-style spans with cross-thread/process context propagation, an
always-on bounded per-thread flight recorder, postmortem retention of
typed-failure traces, per-bucket histogram exemplars (last trace id —
scrape → p99 bucket → trace id → ``/traces/<id>``), and the
``/traces`` endpoints on `MetricsServer`. The engines' threads keep a
whole window of spans (`flight.reserve` / `spans_between`) and put
them on the profiler's clock as ``pt::<name>`` annotations.
``PADDLE_TPU_TRACE=0`` reduces every probe to a flag check.

See docs/observability.md for the full API, knobs, and the SLO ratchet
workflow; tools/metrics_dump.py and tools/trace_dump.py scrape/dump
from the command line.
"""
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, default_latency_buckets,
    registry,
)
from .export import render_json, render_prometheus  # noqa: F401
from .http import MetricsServer  # noqa: F401
from . import flight, slo, trace  # noqa: F401
from .flight import FlightRecorder, recorder  # noqa: F401
from .trace import TraceContext  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_latency_buckets", "registry", "render_json",
    "render_prometheus", "MetricsServer", "slo", "trace", "flight",
    "TraceContext", "FlightRecorder", "recorder",
]
