"""Crash-safe training (ISSUE 12 tentpole part b): preemption grace and the
shared crash scope every algo main runs under.

Preemption contract (the Podracer/TPU-scheduler model, arXiv:2104.06272):
SIGTERM or SIGINT means "you are being evicted, wrap up" — the handler only
sets a flag; the training loop finishes its in-flight step, saves a BLOCKING
checkpoint through its own per-algo state dict, and raises `Preempted` at
the step boundary. The `@crashsafe` decorator turns that into: drain the
async checkpointer, emit a `preempt` lifecycle event, close telemetry, and
exit with `RC_PREEMPTED` (75, EX_TEMPFAIL) — the DISTINCT resumable return
code a supervisor keys restarts on (`--resume auto` picks the run back up).

Crash contract: any unhandled exception escaping a main emits a final
`crash` event to every live telemetry instance and drains the async
checkpointer BEFORE the process dies, so a crashed run always leaves a
parseable `telemetry.jsonl` tail and its last committed checkpoint — the
satellite that previously only clean exits guaranteed. On every way out
(return, preemption, crash, SystemExit) the live TensorBoard loggers are
closed, so the event file holds every scalar the run logged.

Wiring per main (the whole surface):

    @register_algorithm()
    @resilience.crashsafe
    def main(argv=None):
        ...
        guard = resilience.RunGuard.install(telem)
        for step in ...:
            guard.tick(step)          # fires injected sig* faults
            ... train ...
            if ... or guard.preempted:
                save_checkpoint(..., block=True)   # existing per-algo dict
            if guard.preempted:
                raise resilience.Preempted(step)
"""

from __future__ import annotations

import functools
import os
import signal
import sys
import threading
from typing import Any, Callable, Optional

from . import inject

__all__ = ["RC_PREEMPTED", "Preempted", "RunGuard", "crashsafe", "note_event"]

# EX_TEMPFAIL: "temporary failure, retry later" — distinct from both success
# and crash codes, so supervisors/CI can key auto-resume on it
RC_PREEMPTED = 75


class Preempted(Exception):
    """Raised by a main at the first step boundary after a preemption signal
    (its checkpoint already committed); `@crashsafe` maps it to
    SystemExit(RC_PREEMPTED)."""

    def __init__(self, step: int, signal_name: str = ""):
        super().__init__(f"preempted at step {step}")
        self.step = int(step)
        self.signal_name = signal_name


# events recorded before telemetry exists (resume resolution runs pre-logger);
# drained into the JSONL by RunGuard.install
_PENDING_NOTES: list[tuple[str, dict]] = []


def note_event(name: str, **data: Any) -> None:
    from ..telemetry import active_telemetry

    if active_telemetry():
        from ..telemetry import emit

        emit(name, **data)
    else:
        _PENDING_NOTES.append((name, dict(data)))


class RunGuard:
    """Preemption-grace signal handler + per-step fault tick.

    `install()` replaces the SIGTERM/SIGINT handlers (main thread only — a
    no-op flag-carrier elsewhere) and registers the Fault/* gauge source with
    the run's Telemetry. Handlers are restored by `@crashsafe`'s finally (or
    an explicit `uninstall()`), so in-process test invocations never leak
    handler state into the harness."""

    _current: Optional["RunGuard"] = None

    def __init__(self) -> None:
        self._preempt_signal: str | None = None
        self._prev_handlers: dict[int, Any] = {}
        self._lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def install(cls, telem: Any = None) -> "RunGuard":
        guard = cls()
        if telem is not None:
            telem.add_gauges(inject.gauges)
        if threading.current_thread() is threading.main_thread():
            for signum in (signal.SIGTERM, signal.SIGINT):
                try:
                    guard._prev_handlers[signum] = signal.signal(
                        signum, guard._on_signal
                    )
                except (ValueError, OSError):  # non-main thread / exotic host
                    pass
        cls._current = guard
        # flush pre-telemetry notes (resume resolution) into the JSONL
        from ..telemetry import emit

        while _PENDING_NOTES:
            name, data = _PENDING_NOTES.pop(0)
            emit(name, **data)
        return guard

    @classmethod
    def uninstall(cls) -> None:
        guard = cls._current
        if guard is None:
            return
        for signum, prev in guard._prev_handlers.items():
            try:
                signal.signal(signum, prev)
            except (ValueError, OSError):
                pass
        guard._prev_handlers.clear()
        cls._current = None

    # -- signal path ---------------------------------------------------------
    def _on_signal(self, signum, frame) -> None:
        name = signal.Signals(signum).name
        with self._lock:
            first = self._preempt_signal is None
            self._preempt_signal = name
        if first:
            inject.count("Fault/preemptions")
            # handlers run between bytecodes in the main thread: a JSONL
            # append here is safe and records WHEN the grace window opened.
            # Direct emit (not note_event): a signal without live telemetry
            # must not leak into some LATER run's event log.
            from ..telemetry import emit

            emit("preempt.signal", signal=name)

    @property
    def preempted(self) -> bool:
        return self._preempt_signal is not None

    @property
    def preempt_signal(self) -> str | None:
        return self._preempt_signal

    # -- per-step hook -------------------------------------------------------
    def tick(self, step: int) -> bool:
        """Call once per loop iteration BEFORE the step's work: fires any
        injected process-level fault declared for `step`, and returns the
        preemption flag (also consulted at the step's end via
        `.preempted`)."""
        plan = inject.get_plan()
        for site, signum in (
            ("sigterm", signal.SIGTERM),
            ("sigint", signal.SIGINT),
            ("sigkill", signal.SIGKILL),
            # peer.crash: same SIGKILL delivery, but launcher.retarget_sigkill
            # never moves it onto an actor — it always kills THIS host (the
            # replay-service-owning learner, or the serve server)
            ("peer.crash", signal.SIGKILL),
        ):
            if plan.fire_at(site, step) is not None:
                os.kill(os.getpid(), signum)
        return self.preempted


def crashsafe(fn: Callable[..., Any]) -> Callable[..., Any]:
    """The shared crash scope wrapping every algo main (see module doc)."""

    @functools.wraps(fn)
    def wrapper(*args: Any, **kwargs: Any):
        from ..telemetry import active_telemetry, emit

        try:
            return fn(*args, **kwargs)
        except Preempted as exc:
            from ..utils.checkpoint import wait_checkpoint

            wait_checkpoint()  # the grace checkpoint must be committed
            emit(
                "preempt",
                step=exc.step,
                signal=exc.signal_name or (
                    RunGuard._current.preempt_signal
                    if RunGuard._current
                    else None
                ),
                rc=RC_PREEMPTED,
            )
            for telem in active_telemetry():
                telem.close()
            raise SystemExit(RC_PREEMPTED) from None
        except SystemExit:
            raise
        except BaseException as exc:
            # shape-capture sweeps abort mains by design — not a crash
            if type(exc).__name__ == "CaptureComplete":
                raise
            err = f"{type(exc).__name__}: {exc}".replace("\n", " | ")[:500]
            for telem in active_telemetry():
                telem.event("crash", error=err, handled=True)
            try:
                from ..utils.checkpoint import wait_checkpoint

                wait_checkpoint()
            except Exception as wait_exc:  # the original crash must surface
                print(
                    f"[resilience] checkpoint drain failed during crash "
                    f"handling: {wait_exc}",
                    file=sys.stderr,
                )
            for telem in active_telemetry():
                telem.abort()
            raise
        finally:
            RunGuard.uninstall()
            _close_loggers()

    return wrapper


def _close_loggers() -> None:
    """Whatever road led out of the main, the event file holds every scalar
    it logged: the writer thread's queue is drained here (a no-op after the
    main's own `logger.close()`)."""
    from ..utils.logger import live_loggers

    for logger in live_loggers():
        try:
            logger.close()
        except RuntimeError as exc:  # reported: it must not replace the exception on its way out
            print(f"[resilience] {exc}: {exc.__cause__!r}", file=sys.stderr)
