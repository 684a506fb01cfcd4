"""Speculation-aware observability layer (DESIGN.md §7.9).

trace.py    — structured event recorder (no-op NullRecorder when disabled)
              and the engine's profiler spans (``annotate``)
registry.py — counter/gauge/histogram metrics registry
export.py   — Perfetto trace.json + metrics dumps + jax.profiler hooks
"""
from repro.obs.export import (perfetto_trace, profiler_session, write_metrics,
                              write_trace)
from repro.obs.registry import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import NULL_RECORDER, NullRecorder, TraceRecorder

__all__ = [
    "TraceRecorder", "NullRecorder", "NULL_RECORDER",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "perfetto_trace", "write_trace", "write_metrics", "profiler_session",
]
