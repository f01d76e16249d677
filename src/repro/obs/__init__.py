"""repro.obs — unified observability: metrics, exposition, span tracing.

The third leg after benchmarks (``benchmarks/``, the BENCH_*.json
trajectory) and static analysis (``repro.analysis.lint``): *runtime*
visibility.  Three stdlib-only pieces:

* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` of typed
  instruments (Counter / Gauge / fixed-bucket Histogram with labels),
  all behind one lock so snapshots are consistent cuts.
* :mod:`repro.obs.export` — Prometheus text exposition
  (:func:`render_prometheus`), mounted as ``GET /metrics`` by
  ``repro.launch.serve_http`` and dumped offline by
  ``repro.launch.metrics``.
* :mod:`repro.obs.tracing` — ring-buffered :class:`Tracer` spans
  threaded through the serving hot path, the fit pipeline, and
  ``DatasetStore.ingest``; queue-wait vs device-time comes from span
  durations, with optional JSONL export.  Scoped spans always appear
  in a ``jax.profiler`` capture (``POST /debug/profile`` shows them),
  on the device trace's clock.  Spans carry
  trace context (``trace_id`` / ``links``) so ``Tracer.trace(rid)``
  reconstructs a per-request timeline; :class:`SlowLog` is the
  append-only sink for over-threshold request timelines.
* :mod:`repro.obs.resources` — :class:`ResourceMonitor`, a background
  sampler publishing ``resource_*`` gauges (RSS, device memory, live
  array bytes, jit-cache entries, queue depths, hot-model bytes).
* :mod:`repro.obs.profiling` — :class:`Profiler`, serialized bounded
  ``jax.profiler`` captures behind ``POST /debug/profile``.

Scoping convention: serving components (scheduler / admission / model
registry) each default to a *private* registry+tracer for test and
benchmark isolation, and ``serve_http`` wires one shared pair through
all of them.  Offline single-pipeline processes (``train_forest``,
``ingest``) use the process-wide defaults below, which
``repro.launch.metrics`` dumps.  See ``docs/observability.md`` for the
operator guide and the full instrument reference.
"""
from __future__ import annotations

from repro.obs.export import CONTENT_TYPE, render_prometheus
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.profiling import ProfileInProgress, Profiler
from repro.obs.resources import ResourceMonitor
from repro.obs.tracing import SlowLog, Span, Tracer

__all__ = [
    "CONTENT_TYPE",
    "Counter",
    "DEFAULT_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ProfileInProgress",
    "Profiler",
    "ResourceMonitor",
    "SlowLog",
    "Span",
    "Tracer",
    "default_registry",
    "default_tracer",
    "render_prometheus",
]

_default_registry = None
_default_tracer = None


def default_registry() -> MetricsRegistry:
    """The process-wide registry used by offline paths (fit, ingest)."""
    global _default_registry
    if _default_registry is None:
        _default_registry = MetricsRegistry()
    return _default_registry


def default_tracer() -> Tracer:
    """The process-wide tracer used by offline paths (fit, ingest)."""
    global _default_tracer
    if _default_tracer is None:
        _default_tracer = Tracer(capacity=4096)
    return _default_tracer
