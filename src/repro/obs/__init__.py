"""Unified telemetry subsystem: tracing, metrics, overlap analysis.

- ``trace``   — `Tracer`: structured spans/instants in a bounded ring,
  exported as Chrome trace-event / Perfetto JSON, each span also a
  ``jax.profiler`` annotation; `NullTracer` makes disabled telemetry a
  no-op (``NULL_TRACER`` is the shared instance);
- ``metrics`` — `MetricsRegistry`: counters, gauges, fixed-bucket
  histograms, plus named collectors that re-home the existing subsystem
  stats snapshots; Prometheus-style text exposition;
- ``overlap`` — `OverlapAnalyzer`: post-processes the trace into
  hidden-vs-exposed transfer time per tier pair and per scheduler step —
  the direct measurement of the paper's latency-hiding claim — and
  cross-validates it against `TransferStats`;
- ``check``   — trace-file schema checker (`python -m repro.obs.check`),
  the CI gate on exported traces.

The session front door (`repro.api`) owns ONE tracer and ONE registry per
session (``OffloadConfig.telemetry``) and hands them to every subsystem it
constructs; subsystems accept a ``tracer=None`` kwarg and stay silent
without one.
"""

from repro.obs.metrics import (
    SECONDS_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
)
from repro.obs.overlap import OverlapAnalyzer
from repro.obs.trace import NULL_TRACER, NullTracer, TraceEvent, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SECONDS_BUCKETS",
    "OverlapAnalyzer",
    "NULL_TRACER", "NullTracer", "TraceEvent", "Tracer",
]
