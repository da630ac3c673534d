"""Observability: structured tracing and serving metrics.

This package is a LEAF dependency — it imports nothing from
:mod:`repro.core` or :mod:`repro.planner`, so both can thread tracer and
metrics hooks through their hot paths without an import cycle.  The three
surfaces:

* :mod:`repro.obs.trace` — a lightweight span/event :class:`Tracer` with
  a JSON-lines exporter, whose spans are also ``jax.profiler`` annotations
  (one clock with the device in a profiler trace), plus the module-global
  ``current_tracer()`` seam the engine and serving layers consult (one
  attribute read + ``None`` check when tracing is off);
* :mod:`repro.obs.metrics` — counters, gauges and bounded-memory latency
  histograms (p50/p95/p99) behind a :class:`MetricsRegistry` with a
  Prometheus-style text rendering;
* :mod:`repro.obs.faultinject` — the chaos suite's named fault-injection
  points (same disabled-path budget as the tracer: one attribute read);
* ``EXPLAIN ANALYZE`` lives in :mod:`repro.planner.explain`
  (``explain_analyze``): it needs the planner's cost model, which sits
  ABOVE this package in the import graph.

See docs/observability.md for the trace schema and the metrics catalog,
and docs/robustness.md for the fault seam and chaos suite.
"""
from . import faultinject
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import (TRACE_SCHEMA_VERSION, Tracer, current_tracer,
                    read_jsonl, set_tracer, trace_event, trace_span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "TRACE_SCHEMA_VERSION", "Tracer", "current_tracer", "faultinject",
    "read_jsonl", "set_tracer", "trace_event", "trace_span",
]
