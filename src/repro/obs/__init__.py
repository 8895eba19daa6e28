"""Observability layer: tracing spans + metrics registry (DESIGN.md §14).

Pure Python — importing ``repro.obs`` (or any submodule) must NOT import
jax, mirroring the serving-scheduler guarantee (tests/test_obs.py keeps
this honest with a subprocess guard). The jax-facing integration lives in
the layers that already import jax (``core.stages.TimedBackend``,
``engine.handle``, ``serving.server``); this package only records what
they report.

Two halves:

  * ``repro.obs.trace`` — a thread-safe :class:`Tracer` ring buffer of
    complete spans with Chrome trace-event JSON export
    (Perfetto-loadable) and a shared :func:`validate_chrome_trace` used
    by both the test suite and ``scripts/validate_trace.py``.
  * ``repro.obs.metrics`` — a :class:`MetricsRegistry` of named
    counters/gauges/reservoir histograms behind every stats surface
    (``ServingStats``, ``Renderer.stats()``, the render-cache registry,
    the autotune cache), exported as a schema-versioned snapshot dict or
    Prometheus text.
"""
from repro.obs.trace import (
    REQUEST_PHASES,
    SpanEvent,
    emit_request_spans,
    Tracer,
    get_tracer,
    set_annotation_factory,
    set_tracer,
    span,
    trace_env_enabled,
    trace_span,
    validate_chrome_trace,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    percentile,
)

__all__ = [
    "REQUEST_PHASES",
    "SpanEvent",
    "emit_request_spans",
    "Tracer",
    "get_tracer",
    "set_annotation_factory",
    "set_tracer",
    "span",
    "trace_env_enabled",
    "trace_span",
    "validate_chrome_trace",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "percentile",
]
