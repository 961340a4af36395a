"""Serving observability: metrics registry, step-span tracing, and
profiler capture windows (DESIGN.md §13)."""
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    StatsView,
    log_buckets,
)
from .profiling import ProfileWindow, make_profile_window
from .tracing import NULL_SPAN, NULL_TRACER, RingLog, StepTracer

__all__ = [
    "RingLog",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StatsView",
    "log_buckets",
    "NULL_SPAN",
    "NULL_TRACER",
    "StepTracer",
    "ProfileWindow",
    "make_profile_window",
]
