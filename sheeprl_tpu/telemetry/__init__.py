"""Runtime telemetry subsystem (ISSUE 2): always-on phase timers and loop spans, XLA
recompile/memory tracking, a NaN/inf watchdog, and a rank-0 structured JSONL
event log with console heartbeat — shared by every algorithm main. See
howto/observability.md for the schema and `tools/telemetry_report.py` for
offline analysis of a finished or crashed run."""

from .compile_tracker import CompileTracker, monitoring_supported
from .core import Telemetry, active_telemetry, emit
from .events import JsonlEventLog
from .phase import PhaseTimers
from .trace import (
    ClockSync,
    Span,
    Tracer,
    ensure_run_id,
    handle_profile_frame,
    install_profile_signal,
    new_span_id,
    profile_window,
    trace_enabled,
)

__all__ = [
    "ClockSync",
    "CompileTracker",
    "JsonlEventLog",
    "PhaseTimers",
    "Span",
    "Telemetry",
    "Tracer",
    "active_telemetry",
    "emit",
    "ensure_run_id",
    "handle_profile_frame",
    "install_profile_signal",
    "monitoring_supported",
    "new_span_id",
    "profile_window",
    "trace_enabled",
]
