"""XLA recompilation tracking: jit retraces become a metric, not a mystery.

A mid-run recompile (a shape drifting, a weak_type flip, a python-scalar
static arg changing) silently costs seconds to minutes on TPU and the only
prior symptom was a dip in `Time/step_per_second`. `jax.monitoring` fires a
duration event per backend compile (`/jax/core/compile/
backend_compile_duration`) plus tracing/lowering durations, so
counting those gives recompile count and total compile seconds with zero
instrumentation of the jitted functions themselves.

jax's listener registry is append-only (`clear_event_listeners` nukes
everyone's listeners, including jax's own internal ones), so ONE module-level
listener is installed lazily and forwards to the currently attached
`CompileTracker` instances — trackers attach/detach, the listener stays.

Fallback: on a jax without `jax.monitoring` (or with a renamed event key) the
tracker reports `supported=False` and zero counts rather than crashing; the
explicit shim alternative — wrapping `jit(...).lower().compile()` — only sees
AOT callers, so the monitoring path is primary and the absence is surfaced
honestly in the metrics (`XLA/recompiles` simply never appears).
"""

from __future__ import annotations

import threading

__all__ = ["CompileTracker", "monitoring_supported"]

# event-name fragments that mark one backend compile / its phases (jax
# emits /jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}
# _duration; the backend_compile one fires exactly once per XLA compile)
_COMPILE_EVENT = "backend_compile_duration"
_COMPILE_PHASE_FRAGMENT = "/jax/core/compile/"

_lock = threading.Lock()
_trackers: set["CompileTracker"] = set()
_installed: bool | None = None  # None = not attempted, True/False = outcome


def monitoring_supported() -> bool:
    return _install_listener()


def _on_duration(name: str, secs: float, **kw) -> None:
    if _COMPILE_PHASE_FRAGMENT not in name:
        return
    is_compile = name.endswith(_COMPILE_EVENT)
    with _lock:
        for t in _trackers:
            t._record(secs, is_compile)


def _install_listener() -> bool:
    global _installed
    if _installed is not None:
        return _installed
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
    except Exception:
        _installed = False
    return _installed


class CompileTracker:
    """Counts backend compiles and total compile-pipeline seconds (trace +
    lower + backend compile) seen while attached. `flush()` returns the
    interval delta plus running totals."""

    def __init__(self) -> None:
        self.supported = _install_listener()
        self._count = 0
        self._seconds = 0.0
        self._flushed_count = 0
        self._flushed_seconds = 0.0
        self._attached = False

    def attach(self) -> "CompileTracker":
        if self.supported and not self._attached:
            with _lock:
                _trackers.add(self)
            self._attached = True
        return self

    def detach(self) -> None:
        if self._attached:
            with _lock:
                _trackers.discard(self)
            self._attached = False

    # called from the module listener under _lock
    def _record(self, secs: float, is_compile: bool) -> None:
        if is_compile:
            self._count += 1
        self._seconds += secs

    def flush(self) -> dict[str, float]:
        """Interval delta + running totals since attach."""
        with _lock:
            count, seconds = self._count, self._seconds
        out = {
            "compiles": count - self._flushed_count,
            "compile_seconds": seconds - self._flushed_seconds,
            "total_compiles": count,
            "total_compile_seconds": seconds,
        }
        self._flushed_count, self._flushed_seconds = count, seconds
        return out
