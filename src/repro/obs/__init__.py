"""Observability: tracing (Perfetto export or the JAX profiler's trace),
the spanned device-to-host read, and the unified metrics registry.  See
docs/observability.md."""
from repro.obs.metrics import (Counter, CounterDict, Gauge, Histogram,
                               LazyCounterGroup, MetricsRegistry)
from repro.obs.trace import (NULL_TRACER, PID_ENGINE, PID_REQUESTS,
                             NullTracer, ProfilerTracer, Tracer, to_host)
from repro.obs.views import (EMPTY_DIGEST_STATS, digest_block, ladder_block,
                             org_stats)

__all__ = [
    "Counter", "CounterDict", "Gauge", "Histogram", "LazyCounterGroup",
    "MetricsRegistry",
    "NULL_TRACER", "PID_ENGINE", "PID_REQUESTS", "NullTracer",
    "ProfilerTracer", "Tracer", "to_host",
    "EMPTY_DIGEST_STATS", "digest_block", "ladder_block", "org_stats",
]
