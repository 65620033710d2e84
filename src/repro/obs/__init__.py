"""repro.obs — runtime observability for the batch/streaming pipeline.

Three layers, increasingly optional:

* :mod:`repro.obs.trace` — hierarchical span tracer (stdlib-only).
  Every hot module times its stages through ``Span`` handles; enabling
  the process tracer (``EngineConfig.trace`` / ``PathSession(trace=True)``
  / ``serve --trace``) records them into a ring buffer exportable as
  Chrome-trace/Perfetto JSON.
* :mod:`repro.obs.metrics` — process-wide registry of counters, gauges
  and log-bucketed histograms (cache hit/miss/evict/bytes, per-query
  latency p50/p95/p99/p99.9), with ``snapshot()/since()`` windowing and
  a plain-text exposition dump.
* :mod:`repro.obs.jaxprof` — opt-in ``jax.profiler`` bridge: span
  annotations on the device timeline, ``start_trace``/``stop_trace``
  capture.

``python -m repro.obs summarize <trace.json>`` aggregates a saved trace;
see ``docs/observability.md`` for the span taxonomy and metric names.
"""
from . import metrics, trace  # noqa: F401  (jaxprof imported lazily)
from .metrics import registry  # noqa: F401
from .trace import Span, Tracer, disable, enable, span, tracer  # noqa: F401

__all__ = ["trace", "metrics", "registry", "Span", "Tracer",
           "enable", "disable", "span", "tracer"]
