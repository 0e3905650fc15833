"""sheepscope: the cross-process distributed tracing plane (ISSUE 17).

The repo runs three cooperating tiers — the learner, flock actor
processes, and the sheepserve server — and until this module only the
learner's rank-0 `telemetry.jsonl` existed. sheepscope adds:

  1. **Per-role telemetry shards.** Every process gets a real
     `Telemetry` instance writing `telemetry.<role>.jsonl` (role =
     ``actor{N}`` / ``serve``; the learner keeps the bare
     ``telemetry.jsonl`` name for backwards compatibility). Shards are
     keyed by a shared run id (`ensure_run_id`, exported through
     ``SHEEPRL_TPU_TRACE_RUN`` so subprocesses inherit it).

  2. **Spans.** A span is one JSONL event (``"event": "span"``) with a
     compact random id, an optional parent id, and wall-clock ``t0``/
     ``t1``. Parent ids cross process boundaries by riding FLK1 frame
     meta (PUSH / WEIGHTS / REQUEST / RESPONSE), giving end-to-end
     provenance actor-collect -> push -> ingest -> drain -> train ->
     publish -> served-response. `Tracer` is the per-shard emitter;
     `tools/sheeptrace.py` merges shards and reconstructs the chains.

  3. **Clock offsets.** Shards are written with each host's own wall
     clock. `ClockSync` piggybacks an NTP-style estimate on the existing
     HEARTBEAT exchange (actor sends its wall time, the service replies
     with its own): ``offset = server_wall - (t0 + t1) / 2`` with the
     minimum-RTT sample winning. The estimate is recorded as a
     ``trace.clock`` event in the actor's shard so the merge tool can
     map every shard onto the learner's timeline.

  4. **On-demand profiling.** `ProfileWindow` opens a bounded
     `jax.profiler.trace` window on a live process — triggered either by
     a PROFILE frame (`flock/wire.py` kind 17, handled by the flock
     service and the serve server) or by SIGUSR2
     (`install_profile_signal`). The artifact path is recorded as a
     ``profile.window.start``/``profile.window.stop`` telemetry event.

Kill switch: ``SHEEPRL_TPU_TRACE=0`` disables span/clock emission (the
wire fields simply stay absent; old peers never see a difference).
Span emission is per-chunk / per-update / per-request — never per env
step — so the trace plane stays within the telemetry overhead budget.
"""

from __future__ import annotations

import os
import random
import secrets
import signal
import threading
import time
from typing import Any

__all__ = [
    "ClockSync",
    "ProfileWindow",
    "RUN_ENV",
    "Span",
    "TRACE_ENV",
    "Tracer",
    "ensure_run_id",
    "handle_profile_frame",
    "install_profile_signal",
    "new_run_id",
    "new_span_id",
    "profile_window",
    "trace_enabled",
]

TRACE_ENV = "SHEEPRL_TPU_TRACE"
RUN_ENV = "SHEEPRL_TPU_TRACE_RUN"

PROFILE_DEFAULT_S = 3.0
PROFILE_MAX_S = 60.0


def trace_enabled() -> bool:
    return os.environ.get(TRACE_ENV, "1") != "0"


def new_run_id() -> str:
    return secrets.token_hex(4)


def ensure_run_id() -> str:
    """The run id every shard of one run shares. First caller (the
    learner's `Telemetry.from_args`) mints it and exports it through the
    environment; actor/serve subprocesses inherit the same value."""
    rid = os.environ.get(RUN_ENV)
    if not rid:
        rid = new_run_id()
        os.environ[RUN_ENV] = rid
    return rid


# per-emit span ids are hot-path (~3 per learner update); a private
# Random seeded from the OS is ~5x cheaper than secrets.token_hex and —
# unlike the global `random` state — immune to user code calling
# random.seed(k) in every process, which would collide ids across shards
_span_rng = random.Random(secrets.randbits(64))


def new_span_id() -> str:
    """Compact 8-hex-char span id — small enough to ride JSON frame meta
    on every PUSH without moving the payload-size needle."""
    return f"{_span_rng.getrandbits(32):08x}"


class Span:
    """One open span: `Tracer.begin` hands it out, `Tracer.end` emits it."""

    __slots__ = ("id", "name", "parent", "t0", "attrs")

    def __init__(self, sid: str, name: str, parent: str | None, t0: float):
        self.id = sid
        self.name = name
        self.parent = parent
        self.t0 = t0
        self.attrs: dict[str, Any] = {}

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self


class Tracer:
    """Span emitter bound to one Telemetry shard.

    Every method is a cheap no-op when tracing is off (kill switch) or
    the bound Telemetry is disabled, and every method tolerates a None
    span, so call sites never branch on enablement:

        span = tracer.begin("push", parent=collect_id)
        ...
        tracer.end(span, rows=rows)         # safe even if span is None
    """

    def __init__(self, telem: Any):
        self._telem = telem
        # the kill switch is an at-startup decision: read the environment
        # once here, not on every begin/end/point (an environ lookup per
        # span would be ~15% of the whole emit cost)
        self._env_on = trace_enabled()

    @property
    def enabled(self) -> bool:
        return self._env_on and bool(getattr(self._telem, "enabled", False))

    def begin(self, name: str, parent: str | None = None, **attrs: Any) -> Span | None:
        if not self.enabled:
            return None
        span = Span(new_span_id(), name, parent, time.time())
        span.attrs.update(attrs)
        return span

    def end(self, span: Span | None, **attrs: Any) -> str | None:
        if span is None or not self.enabled:
            return None
        span.attrs.update(attrs)
        t1 = time.time()
        self._telem.event(
            "span",
            name=span.name,
            span=span.id,
            parent=span.parent,
            t0=round(span.t0, 6),
            t1=round(t1, 6),
            dur_ms=round((t1 - span.t0) * 1000.0, 3),
            **span.attrs,
        )
        return span.id

    def point(
        self,
        name: str,
        parent: str | None = None,
        t0: float | None = None,
        **attrs: Any,
    ) -> str | None:
        """Emit a complete span in one call. With `t0` given the span
        covers [t0, now] (e.g. a wait measured by the caller); without,
        it is an instant."""
        if not self.enabled:
            return None
        t1 = time.time()
        sid = new_span_id()
        self._telem.event(
            "span",
            name=name,
            span=sid,
            parent=parent,
            t0=round(t1 if t0 is None else t0, 6),
            t1=round(t1, 6),
            dur_ms=round(0.0 if t0 is None else (t1 - t0) * 1000.0, 3),
            **attrs,
        )
        return sid


class ClockSync:
    """NTP-style clock-offset estimation over a request/reply exchange.

    The actor timestamps the request (`t0`) and the reply (`t1`) with its
    own wall clock; the peer stamps its reply with its wall clock
    (`server_wall`). Assuming symmetric latency,

        offset = server_wall - (t0 + t1) / 2       # peer = local + offset
        rtt    = t1 - t0

    and the minimum-RTT sample is the most trustworthy one (queuing only
    inflates RTT, never deflates it). Every improved sample is recorded
    as a ``trace.clock`` event so `sheeptrace` uses the best estimate a
    shard ever saw."""

    def __init__(self, telem: Any = None):
        self._telem = telem
        self._env_on = trace_enabled()
        self.offset_s: float | None = None
        self.rtt_s: float | None = None
        self.samples = 0

    def add(self, t0: float, server_wall: float, t1: float) -> bool:
        rtt = max(t1 - t0, 0.0)
        offset = server_wall - (t0 + t1) / 2.0
        self.samples += 1
        improved = self.rtt_s is None or rtt < self.rtt_s
        if improved:
            self.rtt_s = rtt
            self.offset_s = offset
            if self._telem is not None and self._env_on:
                self._telem.event(
                    "trace.clock",
                    offset_s=round(offset, 6),
                    rtt_s=round(rtt, 6),
                    samples=self.samples,
                )
        return improved


# ---------------------------------------------------------------------------
# on-demand profiling
# ---------------------------------------------------------------------------


class ProfileWindow:
    """A bounded `jax.profiler.trace` window that any live process can
    open on demand (PROFILE frame or SIGUSR2). One window at a time: an
    overlapping request is refused with the open window's path instead
    of corrupting the running trace. The stop side reuses the
    `StepProfiler` device barrier so async dispatch cannot cut the
    device timeline mid-step."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dir: str | None = None
        self._timer: threading.Timer | None = None

    @property
    def active(self) -> bool:
        with self._lock:
            return self._dir is not None

    def request(self, out_dir: str, seconds: float = PROFILE_DEFAULT_S) -> dict:
        """Open a window into a fresh subdirectory of `out_dir`; a
        background timer closes it after `seconds`. Returns
        ``{ok, dir, seconds, pid}`` or ``{ok: False, error, ...}``."""
        seconds = min(max(float(seconds), 0.01), PROFILE_MAX_S)
        with self._lock:
            if self._dir is not None:
                return {
                    "ok": False,
                    "error": "profile window already open",
                    "dir": self._dir,
                    "pid": os.getpid(),
                }
            path = os.path.join(out_dir, f"window_{int(time.time() * 1000)}")
            try:
                os.makedirs(path, exist_ok=True)
                import jax

                jax.profiler.start_trace(path)
            except Exception as err:
                return {
                    "ok": False,
                    "error": f"{type(err).__name__}: {err}",
                    "pid": os.getpid(),
                }
            self._dir = path
            self._timer = threading.Timer(seconds, self.close)
            self._timer.daemon = True
            self._timer.start()
        from .core import emit

        emit(
            "profile.window.start",
            dir=path, seconds=seconds, pid=os.getpid(),
        )
        return {"ok": True, "dir": path, "seconds": seconds, "pid": os.getpid()}

    def close(self) -> None:
        """Stop the open window (timer path and explicit teardown share
        this; a second close on a closed window is a no-op)."""
        with self._lock:
            path, self._dir = self._dir, None
            timer, self._timer = self._timer, None
        if path is None:
            return
        if timer is not None:
            timer.cancel()
        try:
            import jax
            import jax.numpy as jnp

            # the StepProfiler barrier: per-device execution is FIFO, so
            # blocking on a fresh op drains everything dispatched before it
            for d in jax.local_devices():
                jax.block_until_ready(jnp.add(jax.device_put(0.0, d), 1.0))
        # sheeplint: disable=SL012 — a poisoned backend must not stop the
        # trace flush below
        except Exception:
            pass
        try:
            import jax

            jax.profiler.stop_trace()
        finally:
            from .core import emit

            emit("profile.window.stop", dir=path, pid=os.getpid())


_window = ProfileWindow()


def profile_window() -> ProfileWindow:
    """This process's shared on-demand window (frame + signal triggers
    must agree on the one-window-at-a-time rule)."""
    return _window


def handle_profile_frame(req: dict, default_dir: str | None = None) -> dict:
    """Serve one PROFILE frame request: ``{seconds?, dir?}`` -> the
    `ProfileWindow.request` reply. Shared by the flock service and the
    serve server so both answer identically."""
    import tempfile

    out_dir = req.get("dir") or os.path.join(
        default_dir or tempfile.mkdtemp(prefix="sheepscope-"),
        "profile_ondemand",
    )
    return _window.request(out_dir, req.get("seconds") or PROFILE_DEFAULT_S)


def install_profile_signal(
    log_dir: str, seconds: float = PROFILE_DEFAULT_S
) -> bool:
    """SIGUSR2 -> open a bounded profile window into
    `<log_dir>/profile_ondemand`. Main-thread only (CPython restricts
    signal.signal); returns False when it cannot install."""
    if threading.current_thread() is not threading.main_thread():
        return False

    def _on_sigusr2(_signum, _frame):
        reply = _window.request(os.path.join(log_dir, "profile_ondemand"), seconds)
        if not reply.get("ok"):
            # unlike the PROFILE frame, the signal has no channel to
            # return the refusal — surface it as a telemetry event
            from .core import emit

            emit("profile.window.error", trigger="sigusr2", **reply)

    try:
        signal.signal(signal.SIGUSR2, _on_sigusr2)
    except (ValueError, OSError, AttributeError):
        # non-main thread race or a platform without SIGUSR2
        return False
    return True
