"""Engine observability: span tracing + metrics registry.

See docs/observability.md for the span taxonomy and metric catalog."""
from repro.obs.metrics import (DEFAULT_BUCKETS, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.trace import (ENGINE_TID, NullTracer, Tracer,  # noqa: F401
                             as_tracer, jit_cache_size, last_tracer,
                             request_tid)
