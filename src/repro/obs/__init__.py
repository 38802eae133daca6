"""kvtrace: zero-sync telemetry for the serving stack.

Stdlib-only (no jax, no numpy): the tracer and metrics registry only
ever receive host-side Python values — scheduler counters, allocator
free-list sizes, `CacheMirror` row counts — so instrumentation can sit
inside the double-buffered decode loops without adding a single device
sync. Trace-off is the default (`NULL_TRACER` / `NULL_METRICS` are
falsy singletons) and costs one attribute check per event site.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, Metrics,
                               NULL_METRICS, NullMetrics,
                               write_metrics_json)
from repro.obs.trace import (NULL_TRACER, NullTracer, Phases, Span,
                             Tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "Metrics", "NullMetrics",
    "NULL_METRICS", "write_metrics_json",
    "NullTracer", "NULL_TRACER", "Phases", "Span", "Tracer",
]
