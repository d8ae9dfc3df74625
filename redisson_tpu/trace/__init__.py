"""Sampling trace subsystem: per-op spans, quantile latency, SLOWLOG /
MONITOR / LATENCY parity surfaces, Chrome-trace export, and named host
stages on the profiler's clock.

The executor, serving layer, journal and backend stamp events onto
sampled spans; the :class:`~redisson_tpu.trace.manager.TraceManager`
folds finished spans into histograms, the slowlog and live monitor taps.
Everything is bounded (rings, subscriber queues) and lock-light so the
dispatcher never blocks on introspection. :func:`stage`
(trace/stages.py) names each host stage of the served path in a
``jax.profiler`` capture, beside the device's ops.
"""

from redisson_tpu.trace.export import chrome_trace, prometheus_exposition
from redisson_tpu.trace.hist import HistogramSet, LatencyHistogram
from redisson_tpu.trace.manager import LatencyEvents, TraceManager
from redisson_tpu.trace.monitor import Monitor, MonitorTap, format_event
from redisson_tpu.trace.slowlog import SlowLog, SlowLogEntry
from redisson_tpu.trace.spans import Span, Tracer, stage_breakdown
from redisson_tpu.trace.stages import STAGES, stage

__all__ = [
    "HistogramSet",
    "LatencyEvents",
    "LatencyHistogram",
    "Monitor",
    "MonitorTap",
    "STAGES",
    "SlowLog",
    "SlowLogEntry",
    "Span",
    "TraceManager",
    "Tracer",
    "chrome_trace",
    "format_event",
    "prometheus_exposition",
    "stage",
    "stage_breakdown",
]
