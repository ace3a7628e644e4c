"""Observability with no dependency beyond JAX's profiler: metrics
registry (Prometheus text exposition + flat dict), Chrome trace-event
tracer, and host-clock sections that also land in a JAX profiler trace,
tied together by the `Telemetry` handle threaded through the serving
stack. Default is the no-op `NOOP` singleton — zero overhead unless
explicitly enabled.
"""
from repro.obs.registry import (
    TIME_BUCKETS,
    CounterFamily,
    GaugeFamily,
    HistogramFamily,
    MetricsRegistry,
)
from repro.obs.telemetry import BYTE_BUCKETS, NOOP, NullTelemetry, Telemetry
from repro.obs.tracing import Tracer

__all__ = [
    "TIME_BUCKETS",
    "BYTE_BUCKETS",
    "CounterFamily",
    "GaugeFamily",
    "HistogramFamily",
    "MetricsRegistry",
    "NOOP",
    "NullTelemetry",
    "Telemetry",
    "Tracer",
]
