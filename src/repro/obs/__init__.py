# Deterministic observability plane: a typed metric registry + span traces
# shared by the DES executor, the batched search, and the Nimbus control
# plane.  Everything is clocked on sim-time or explicit step counters so a
# fixed seed yields byte-identical JSONL telemetry; ``obs.clock`` is the one
# justified wall-clock shim (span durations, profiling only).  While a jax
# profiler session captures, every span is also a ``TraceAnnotation`` and
# lands in the profile log ``profiled_spans()`` reads.
from .hub import (
    NULL_HUB,
    NULL_METRIC,
    NULL_SPAN,
    MetricsHub,
    ProfiledSpan,
    Span,
    get_hub,
    profiled_spans,
)
from .metrics import (
    DEFAULT_BUCKETS,
    QUEUE_DEPTH_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Series,
)

__all__ = [
    "MetricsHub",
    "Span",
    "ProfiledSpan",
    "get_hub",
    "profiled_spans",
    "NULL_HUB",
    "NULL_METRIC",
    "NULL_SPAN",
    "Counter",
    "Gauge",
    "Series",
    "Histogram",
    "DEFAULT_BUCKETS",
    "QUEUE_DEPTH_BUCKETS",
]
