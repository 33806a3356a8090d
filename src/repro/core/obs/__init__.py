"""Runtime observability: per-rank tracing, metrics, structured logging.

Enable tracing with ``MPIGNITE_TRACE=1`` (or ``pool.run(...,
trace=True)`` in cluster mode); set log verbosity with
``MPIGNITE_LOG=info``. See the README "Observability" section.
"""
from .log import LOG_ENV, RankLogger, get_logger
from .metrics import ChannelStats, cross_check_collectives, format_cross_check
from .trace import (
    DEFAULT_CAPACITY,
    TRACE_ENV,
    TRACE_EVENTS_ENV,
    CollSpan,
    JobTrace,
    Tracer,
    current_span,
    maybe_span,
    process_tracer,
    reset_process_tracer,
    set_current_span,
    trace_enabled,
)

__all__ = [
    "LOG_ENV", "RankLogger", "get_logger",
    "ChannelStats", "cross_check_collectives", "format_cross_check",
    "DEFAULT_CAPACITY", "TRACE_ENV", "TRACE_EVENTS_ENV",
    "CollSpan", "JobTrace", "Tracer",
    "current_span", "maybe_span", "set_current_span",
    "process_tracer", "reset_process_tracer", "trace_enabled",
]
