"""Observability — logger, metrics, tracing (SURVEY §2.9/§5).

Re-designed analogs of the reference's cross-cutting subsystems:
``logger/`` (leveled logger with nop default), ``metrics.go``
(central prometheus registry), ``tracing/tracing.go`` (global Tracer
interface, nop default, profiled per-query spans).
"""

from pilosa_tpu.obs.logger import Logger, NopLogger, StderrLogger, new_logger
from pilosa_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
)
from pilosa_tpu.obs.tracing import (
    NopTracer,
    RecordingTracer,
    Span,
    TraceContext,
    Tracer,
    capture_context,
    get_tracer,
    set_tracer,
    span_into,
    start_span,
)

__all__ = [
    "Logger",
    "NopLogger",
    "StderrLogger",
    "new_logger",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "registry",
    "Tracer",
    "NopTracer",
    "RecordingTracer",
    "Span",
    "TraceContext",
    "capture_context",
    "span_into",
    "get_tracer",
    "set_tracer",
    "start_span",
]
