"""Structured observability for the serving stack.

Three layers, one import surface:

* ``obs.trace`` — nested span tracing (:class:`Tracer`), exportable as
  JSON-lines and Chrome ``trace_event`` format (chrome://tracing /
  Perfetto). Disabled tracing is a zero-work no-op singleton span, so the
  instrumentation can live on the ingest/flush hot paths permanently.
* ``obs.metrics`` — :class:`MetricsRegistry` of counters, gauges, and
  fixed-bucket histograms with a bounded exact-percentile window; exported
  as a JSON snapshot and Prometheus text format.
* ``obs.device`` — jax device hooks: ``jax.profiler`` trace capture around
  serving phases, per-dispatch ``cost_analysis`` of jitted programs, and
  live device-memory gauges.
* ``obs.slo`` — live SLO engine: declarative :class:`Objective` targets
  evaluated over rolling windows with multi-window burn-rate alerts and a
  ``health()`` snapshot, published through the metrics registry.
* ``obs.history`` — benchmark history store: schema-validated JSON-lines
  records per run (git SHA, timestamp, flattened metrics) and the robust
  Theil–Sen slope gate over the resulting series.

The serve stack records against the process-default tracer/registry
(:func:`tracer` / :func:`metrics`); launchers flip them on with ``--trace``
/ ``--metrics-out``; tests isolate state via :func:`set_tracer` /
:func:`set_metrics`.
"""
from .device import compiled_cost, device_profile, record_memory
from .history import (
    SCHEMA_VERSION,
    append_record,
    load_history,
    slope_failures,
    theil_sen,
    trend_series,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_latency_buckets,
    metrics,
    set_metrics,
)
from .schema import SchemaError, load_schema, validate, validate_or_raise
from .slo import Objective, SLOEngine, default_slos
from .trace import (
    DEFAULT_EXEMPLAR_WATCH,
    NULL_SPAN,
    Span,
    Tracer,
    disable,
    enable,
    record,
    set_tracer,
    span,
    timed,
    tracer,
)

__all__ = [
    # trace
    "NULL_SPAN",
    "Span",
    "Tracer",
    "tracer",
    "set_tracer",
    "enable",
    "disable",
    "span",
    "timed",
    "record",
    "DEFAULT_EXEMPLAR_WATCH",
    # metrics
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "metrics",
    "set_metrics",
    "default_latency_buckets",
    # device
    "device_profile",
    "compiled_cost",
    "record_memory",
    # schema
    "SchemaError",
    "validate",
    "validate_or_raise",
    "load_schema",
    # slo
    "Objective",
    "SLOEngine",
    "default_slos",
    # history
    "SCHEMA_VERSION",
    "append_record",
    "load_history",
    "trend_series",
    "theil_sen",
    "slope_failures",
]
