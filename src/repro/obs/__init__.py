"""repro.obs — dependency-free telemetry: metrics, tracing, workload capture.

Three pieces, wired through every layer of the stack:

* :mod:`repro.obs.metrics` — process-wide :class:`MetricsRegistry`
  (counters / gauges / fixed-bucket histograms) with a contextvar-scoped
  override (``metrics_scope``) so tests and sessions get isolated
  registries.
* :mod:`repro.obs.trace` — ``span`` on two sinks: the JAX profiler's
  timeline (a ``TraceAnnotation``) and, under ``with tracing(t):``, an
  in-memory :class:`Tracer` with a Chrome-trace/Perfetto export; the
  ``instant``/``counter`` helpers are no-ops when no tracer is active.  One
  ``jax.monitoring`` listener counts compiles into the active registry.
* :mod:`repro.obs.recorder` — :class:`WorkloadRecorder`, the live-traffic →
  offline-tuning seam (ROADMAP: always-on autotuning).

Everything is stdlib + numpy; jax is imported on the first span, not by
importing the package.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               active_registry, counter, default_registry,
                               exponential_edges, gauge, histogram,
                               metrics_scope)
from repro.obs.recorder import WorkloadKey, WorkloadRecorder, tail_jsonl
from repro.obs.trace import (Span, Tracer, active_tracer, compile_seconds,
                             instant, load_trace, span, tracing,
                             validate_events, validate_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "active_registry",
    "counter", "default_registry", "exponential_edges", "gauge", "histogram",
    "metrics_scope", "WorkloadKey", "WorkloadRecorder", "tail_jsonl",
    "Span", "Tracer", "compile_seconds",
    "active_tracer", "instant", "load_trace", "span", "tracing",
    "validate_events", "validate_trace",
]
