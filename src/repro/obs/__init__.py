"""repro.obs — unified observability: registry, spans, latency (§12).

One :class:`MetricsRegistry` per serving stack; every layer publishes
into it under namespaced keys and the legacy ``stats()`` dicts become
compatibility views over the same snapshot.
"""

from __future__ import annotations

from .bridge import publish_counters, publish_flat
from .registry import (
    Counter,
    Gauge,
    Histogram,
    Info,
    LATENCY_BOUNDS_S,
    MetricsRegistry,
    histogram_percentile,
    log_buckets,
    merge_disjoint,
)
from .spans import PIPELINE_STAGES, Span, SpanTracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Info",
    "LATENCY_BOUNDS_S",
    "MetricsRegistry",
    "PIPELINE_STAGES",
    "Span",
    "SpanTracer",
    "histogram_percentile",
    "log_buckets",
    "merge_disjoint",
    "publish_counters",
    "publish_flat",
]
