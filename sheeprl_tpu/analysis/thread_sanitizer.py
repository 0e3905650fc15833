"""Runtime thread sanitizer — the dynamic half of sheepsync (ISSUE 18).

`install()` replaces `threading.Lock` / `threading.RLock` /
`threading.Condition` with instrumented factories. Every lock allocated
afterwards records, per thread, the order it is acquired in, and every
acquisition is asserted against the **committed lock-order DAG** from
`analysis/budget/concurrency.json` plus the order observed so far in this
process:

  - acquiring B while holding A, when `B -> A` is a committed or
    already-observed edge, is a `sync.order_violation` telemetry event
    (the inversion that becomes a deadlock under the wrong interleaving);
  - an edge known to neither is counted as *undeclared* (gauge only —
    locks born outside the analyzed packages have no static identity);
  - hold times and contention (an acquire that had to block) are
    aggregated into `Sync/*` gauges.

Violations never raise and the wrappers preserve full Lock/RLock/
Condition semantics (`_is_owned`/`_release_save`/`_acquire_restore`
included, so `Condition.wait` works and correctly un-tracks the backing
lock while waiting). Overhead is a few dict operations per acquisition —
acceptable for tests, not for production serving.

Lock naming: the allocation site (`path:line`) is matched against the
ledger's `lock_sites` table, so a lock allocated at
`sheeprl_tpu/flock/service.py:221` reports as
`flock.service.ReplayService._lock`; unmatched sites keep the raw
`path:line` name.

Enablement: `install()` directly (tests), `maybe_install_from_env()` off
`SHEEPRL_TPU_SANITIZE_THREADS=1` (the flock/serve suites, subprocess
actors and the serve main export it), or the `--sanitize_threads` run flag.
"""

from __future__ import annotations

import _thread
import os
import sys
import threading
import time
from pathlib import Path
from typing import Any, Optional

__all__ = [
    "ENV_VAR",
    "ThreadSanitizer",
    "gauges",
    "install",
    "installed",
    "maybe_install_from_env",
    "uninstall",
]

ENV_VAR = "SHEEPRL_TPU_SANITIZE_THREADS"

_REPO = Path(__file__).resolve().parents[2]

_real_lock = threading.Lock
_real_rlock = threading.RLock
_real_condition = threading.Condition

_STATE: Optional["ThreadSanitizer"] = None


class _Held(threading.local):
    def __init__(self):
        self.stack: list = []  # innermost-last instrumented locks
        self.counts: dict = {}  # id(lock) -> recursion depth


class ThreadSanitizer:
    """Book-keeping shared by every instrumented lock in the process."""

    def __init__(self, ledger: Optional[dict] = None):
        conc = (ledger or {}).get("concurrency", {})
        self.sites: dict[str, str] = dict(conc.get("lock_sites", {}))
        edges = [tuple(e) for e in conc.get("lock_order", {}).get("edges", [])]
        self.committed: set[tuple[str, str]] = self._closure(edges)
        self.observed: set[tuple[str, str]] = set()
        self.violations: list[dict] = []
        self.acquisitions = 0
        self.contended = 0
        self.undeclared: set[tuple[str, str]] = set()
        self.hold_count = 0
        self.hold_total_ms = 0.0
        self.hold_max_ms = 0.0
        self.wait_max_ms = 0.0
        self._held = _Held()
        # internal guard: a RAW lock — instrumenting it would recurse
        self._meta = _real_lock()

    @staticmethod
    def _closure(edges) -> set:
        adj: dict[str, set[str]] = {}
        for a, b in edges:
            adj.setdefault(a, set()).add(b)
        out: set[tuple[str, str]] = set()
        for src in adj:
            seen, stack = set(), [src]
            while stack:
                n = stack.pop()
                if n in seen:
                    continue
                seen.add(n)
                stack.extend(adj.get(n, ()))
            out.update((src, d) for d in seen if d != src)
        return out

    # -- naming ----------------------------------------------------------------

    def name_for_site(self) -> str:
        """Walk out of this module to the allocation frame and map it
        through the ledger's lock_sites table."""
        frame = sys._getframe(2)
        here = __file__
        while frame is not None and frame.f_code.co_filename == here:
            frame = frame.f_back
        if frame is None:
            return "<unknown>"
        path = frame.f_code.co_filename
        try:
            rel = str(Path(path).resolve().relative_to(_REPO))
        except ValueError:
            rel = path
        site = f"{rel}:{frame.f_lineno}"
        return self.sites.get(site, site)

    # -- acquisition book-keeping ----------------------------------------------

    def note_acquire(self, lock: "_InstrumentedLock") -> None:
        held = self._held
        count = held.counts.get(id(lock), 0)
        held.counts[id(lock)] = count + 1
        if count:
            return  # reentrant RLock acquire: no new ordering information
        self.acquisitions += 1
        name = lock.sync_name
        for outer in held.stack:
            a = outer.sync_name
            if a == name:
                continue
            edge = (a, name)
            inverse = (name, a)
            if inverse in self.committed or inverse in self.observed:
                self._violation(a, name)
            elif edge not in self.committed:
                # any ordering the static ledger does not know about —
                # either a lock allocated outside the analyzed packages or
                # a genuinely new edge between known locks
                with self._meta:
                    self.undeclared.add(edge)
            with self._meta:
                self.observed.add(edge)
        held.stack.append(lock)
        lock.sync_acquired_at = time.monotonic()

    def note_release(self, lock: "_InstrumentedLock") -> None:
        held = self._held
        count = held.counts.get(id(lock), 0)
        if count > 1:
            held.counts[id(lock)] = count - 1
            return
        held.counts.pop(id(lock), None)
        try:
            held.stack.remove(lock)
        except ValueError:
            pass
        t0 = lock.sync_acquired_at
        if t0 is not None:
            ms = (time.monotonic() - t0) * 1000.0
            lock.sync_acquired_at = None
            with self._meta:
                self.hold_count += 1
                self.hold_total_ms += ms
                self.hold_max_ms = max(self.hold_max_ms, ms)

    def note_contention(self, lock: "_InstrumentedLock", waited_ms: float) -> None:
        with self._meta:
            self.contended += 1
            self.wait_max_ms = max(self.wait_max_ms, waited_ms)

    def drop_while_waiting(self, lock: "_InstrumentedLock") -> int:
        """Condition.wait path: fully un-track the backing lock; returns
        the saved recursion depth for restore."""
        held = self._held
        saved = held.counts.pop(id(lock), 0)
        try:
            held.stack.remove(lock)
        except ValueError:
            pass
        lock.sync_acquired_at = None
        return saved

    def restore_after_wait(self, lock: "_InstrumentedLock", saved: int) -> None:
        held = self._held
        self.note_acquire(lock)
        if saved > 1:
            held.counts[id(lock)] = saved

    def owned(self, lock: "_InstrumentedLock") -> bool:
        return self._held.counts.get(id(lock), 0) > 0

    def _violation(self, held_name: str, acquiring: str) -> None:
        record = {
            "acquiring": acquiring,
            "held": held_name,
            "thread": threading.current_thread().name,
            "ts": time.time(),
        }
        with self._meta:
            self.violations.append(record)
            if len(self.violations) > 200:
                del self.violations[: len(self.violations) - 200]
        self._emit("sync.order_violation", **record)

    @staticmethod
    def _emit(event: str, **data: Any) -> None:
        try:
            from ..telemetry import core as telemetry

            telemetry.emit(event, **data)
        # sheeplint: disable=SL012 — the sanitizer reports THROUGH telemetry;
        # a broken telemetry sink has nowhere better to report to
        except Exception:
            pass

    # -- views -----------------------------------------------------------------

    def gauges(self) -> dict[str, float]:
        avg = self.hold_total_ms / self.hold_count if self.hold_count else 0.0
        return {
            "Sync/acquisitions": float(self.acquisitions),
            "Sync/contended": float(self.contended),
            "Sync/order_violations": float(len(self.violations)),
            "Sync/undeclared_edges": float(len(self.undeclared)),
            "Sync/observed_edges": float(len(self.observed)),
            "Sync/hold_ms_avg": round(avg, 3),
            "Sync/hold_ms_max": round(self.hold_max_ms, 3),
            "Sync/wait_ms_max": round(self.wait_max_ms, 3),
        }

    def summary(self) -> dict:
        return {
            "violations": list(self.violations),
            "undeclared_edges": sorted(self.undeclared),
            "observed_edges": sorted(self.observed),
            **self.gauges(),
        }


class _InstrumentedLock:
    """Wraps a raw Lock or RLock; safe as a Condition backing lock."""

    def __init__(self, inner, san: ThreadSanitizer, name: str, reentrant: bool):
        self._inner = inner
        self._san = san
        self.sync_name = name
        self.sync_reentrant = reentrant
        self.sync_acquired_at: Optional[float] = None

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if not blocking:
            got = self._inner.acquire(False)
            if got:
                self._san.note_acquire(self)
            return got
        if self._inner.acquire(False):
            self._san.note_acquire(self)
            return True
        t0 = time.monotonic()
        got = self._inner.acquire(True, timeout)
        self._san.note_contention(self, (time.monotonic() - t0) * 1000.0)
        if got:
            self._san.note_acquire(self)
        return got

    def release(self) -> None:
        self._san.note_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def _at_fork_reinit(self) -> None:
        # threading._after_fork reinitializes every lock it knows about in
        # the child; without this delegation a fork with instrumented
        # Events/Conditions alive would AttributeError inside threading
        self._inner._at_fork_reinit()
        self.sync_acquired_at = None

    # Condition protocol ------------------------------------------------------

    def _is_owned(self) -> bool:
        return self._san.owned(self)

    def _release_save(self):
        saved = self._san.drop_while_waiting(self)
        if hasattr(self._inner, "_release_save"):
            return (self._inner._release_save(), saved)
        self._inner.release()
        return (None, saved)

    def _acquire_restore(self, state) -> None:
        inner_state, saved = state
        if hasattr(self._inner, "_acquire_restore"):
            self._inner._acquire_restore(inner_state)
        else:
            self._inner.acquire()
        self._san.restore_after_wait(self, saved)

    def __repr__(self) -> str:
        return f"<sheepsync {self.sync_name} wrapping {self._inner!r}>"


# -- factories (what threading.Lock/RLock/Condition become) --------------------


def _make_lock():
    san = _STATE
    if san is None:
        return _real_lock()
    return _InstrumentedLock(_real_lock(), san, san.name_for_site(), False)


def _make_rlock():
    san = _STATE
    if san is None:
        return _real_rlock()
    return _InstrumentedLock(_real_rlock(), san, san.name_for_site(), True)


def _make_condition(lock=None):
    san = _STATE
    if san is None:
        return _real_condition(lock)
    if lock is None:
        lock = _InstrumentedLock(_real_rlock(), san, san.name_for_site(), True)
    return _real_condition(lock)


# -- lifecycle -----------------------------------------------------------------


def install(ledger: Optional[dict] = None) -> ThreadSanitizer:
    """Patch the threading factories; idempotent. Loads the committed
    concurrency ledger unless an explicit one (or {}) is passed."""
    global _STATE
    if _STATE is not None:
        return _STATE
    if ledger is None:
        from . import concurrency_check

        ledger = concurrency_check.load_ledger() or {}
    _STATE = ThreadSanitizer(ledger)
    threading.Lock = _make_lock
    threading.RLock = _make_rlock
    threading.Condition = _make_condition
    _STATE._emit(
        "sync.sanitizer_start",
        committed_edges=len(_STATE.committed),
        known_sites=len(_STATE.sites),
        pid=os.getpid(),
    )
    return _STATE


def uninstall() -> Optional[dict]:
    """Restore the real factories; returns the final summary. Locks
    already handed out stay instrumented (and keep working) — only new
    allocations revert."""
    global _STATE
    if _STATE is None:
        return None
    summary = _STATE.summary()
    _STATE._emit(
        "sync.sanitizer_stop",
        order_violations=len(summary["violations"]),
        undeclared_edges=len(summary["undeclared_edges"]),
    )
    threading.Lock = _real_lock
    threading.RLock = _real_rlock
    threading.Condition = _real_condition
    _STATE = None
    return summary


def installed() -> Optional[ThreadSanitizer]:
    return _STATE


def maybe_install_from_env() -> Optional[ThreadSanitizer]:
    if os.environ.get(ENV_VAR, "0") not in ("0", "", "false", "off"):
        return install()
    return None


def gauges() -> dict[str, float]:
    """Telemetry gauge hook: {} when the sanitizer is not installed."""
    return _STATE.gauges() if _STATE is not None else {}
