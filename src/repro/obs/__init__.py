"""Observability substrate: metrics registry, query tracer, bucket stats.

Two small modules give every layer of the streaming/serving stack a shared
measurement vocabulary without adding dependencies:

* :mod:`repro.obs.metrics` — named counters, gauges, and log-bucketed
  latency histograms behind a thread-safe :class:`MetricsRegistry`; the
  rolling per-capacity-bucket :class:`BucketStats` accumulator whose
  snapshot schema is the input contract for the cost-based planner
  (ROADMAP item 1); Prometheus text rendering and a strict-JSON
  sanitizer shared with ``SegmentManager.stats()``.
* :mod:`repro.obs.trace` — :class:`QueryTrace` span trees of a query or
  a served flush, each span a host timer wrapped in a
  ``cubegraph.<name>`` ``jax.profiler.TraceAnnotation`` so that it lies
  on the device trace's clock; :class:`TraceLog`, the bounded sink of
  flush traces; and the process-wide compile counter
  (:func:`compile_counts`), whose events also land on the compiling
  thread's innermost open span.

Disabled instances (``MetricsRegistry(enabled=False)``, ``NULL_TRACE``)
hand out shared no-op singletons, so the instrumented hot paths cost a
few attribute lookups and no per-query allocations when observability is
off.  See ``docs/observability.md`` for the metric catalog and the span
tree.
"""
from .metrics import (NULL_METRIC, NULL_REGISTRY, BucketStats, Counter,
                      Gauge, Histogram, MetricsRegistry, StreamObs,
                      json_sanitize, prometheus_text)
from .trace import (NULL_TRACE, QueryTrace, Span, TraceLog, block_ready,
                    compile_counts)

__all__ = ["NULL_METRIC", "NULL_REGISTRY", "NULL_TRACE", "BucketStats",
           "Counter", "Gauge", "Histogram", "MetricsRegistry", "QueryTrace",
           "Span", "StreamObs", "TraceLog", "block_ready", "compile_counts",
           "json_sanitize", "prometheus_text"]
