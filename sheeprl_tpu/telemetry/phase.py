"""Hierarchical phase timers and the main loop's spans — the always-on
answer to "where did the step go?" that the reference's single
`Time/step_per_second` scalar cannot give (it has ONE wall-clock ratio,
reference ppo.py:372; a slow run is opaque).

Three usage styles over one accumulator:

  - `with timers.phase("train"):` — nestable context manager; nested phases
    get hierarchical names (`train/dispatch`), time is attributed to BOTH
    the child and its parent (the parent's span covers the child). Exception
    safe: the time up to the raise is still recorded.
  - `timers.mark("rollout")` — linear sectioning for the mains' top-level
    loops, where wrapping a 60-line hot loop in a `with` block would force a
    re-indent of the whole body: each mark ends the previous marked section
    and opens the named one; `mark(None)` just ends.
    `mark("rollout/pack", phase="rollout")` opens a span finer than the sum
    it is logged under: the span is `rollout/pack`, its seconds go to
    `rollout` (every logged scalar costs the loop time; a span costs none).
  - `timers.iteration(step)` — a mark of the same linear kind for the loop
    body itself: it ends the open mark and the previous iteration and opens
    the next; `iteration(None)` just ends.

Every transition does three things in this one place:

  1. accumulates seconds per phase (`Time/<phase>_seconds`); `flush()`
     returns them since the last flush and restarts any phase that is still
     open (an open phase contributes its elapsed time to the flushed
     interval and keeps running), so per-interval sums never lose or
     double-count time across logging intervals. The iteration is not a
     phase: it has no sum.
  2. opens / closes a `jax.profiler.TraceAnnotation` named
     `sheeprl/<phase>` (the iteration a `StepTraceAnnotation`), so that in
     any profiler session the program's phases lie in the host plane on the
     device trace's own clock. With no session open this is a flag test.
  3. keeps the closed span — name, start, end, parent (the enclosing phase,
     the mark or the iteration), the iteration's step as the identifier its
     spans share, and the counters `count()` attached while it was open —
     in a ring of fixed size. Nothing is written inside the loop:
     `drain()` hands the ring out as `span` records when the run ends.

Main-thread state; other threads use `Tracer` (trace.py).

Overhead: two `perf_counter()` calls, a flag test, a dict add and a deque
append per transition: about a microsecond, invisible next to an env step
or a jit dispatch.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Iterator

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .trace import new_span_id

__all__ = ["ITERATION", "PhaseTimers"]

ANNOTATION_PREFIX = "sheeprl/"
ITERATION = "iteration"
# closed spans kept: the newest hours of a slow loop, minutes of a fast one
# (12 to 18 spans an iteration in the DreamerV3 main), ~200 B each
RING_SPANS = 32768


class _Open:
    """One open span. `t_acc` is where its unflushed time starts (`flush()`
    moves it), `t0` where the span itself started."""

    __slots__ = ("name", "phase", "t_acc", "t0", "seq", "parent", "step", "annotation", "counters")

    def __init__(self, name: str, phase: str, now: float, seq: int, parent: "_Open | None", step: int | None):
        self.name = name
        self.phase = phase  # the sum its seconds go to
        self.t_acc = self.t0 = now
        self.seq = seq
        self.parent = None if parent is None else parent.seq
        self.step = step if parent is None else parent.step
        self.annotation = None
        self.counters: dict[str, Any] | None = None


class PhaseTimers:
    def __init__(self, spans: bool = True, ring: int = RING_SPANS) -> None:
        """`spans=False` (the tracing kill switch) keeps the accumulators
        alone: no annotation, nothing in the ring."""
        self._acc: dict[str, float] = {}
        self._stack: list[_Open] = []  # context-manager nesting
        self._mark: _Open | None = None  # linear mark() section
        self._iter: _Open | None = None  # linear iteration() section
        self._spans = spans
        self._seq = 0
        self._id_base = int(new_span_id(), 16)
        # perf_counter orders the spans and is the clock a profiler-side
        # reader shares with this process; the written t0/t1 are wall time,
        # as Tracer's are, converted once by this offset
        self._wall_minus_perf = time.time() - time.perf_counter()
        self.ring: deque[tuple] = deque(maxlen=ring)  # the newest closed spans; the oldest fall out

    # ---- the one place a span opens and closes ---------------------------
    def _open(self, name: str, now: float, parent: _Open | None, step: int | None = None, phase: str | None = None) -> _Open:
        self._seq += 1
        span = _Open(name, phase or name, now, self._seq, parent, step)
        if self._spans and TraceAnnotation.is_enabled():  # a profiler session is open
            if name == ITERATION:
                span.annotation = StepTraceAnnotation(ANNOTATION_PREFIX + name, step_num=step)
            else:
                span.annotation = TraceAnnotation(ANNOTATION_PREFIX + name)
            span.annotation.__enter__()
        return span

    def _close(self, span: _Open, now: float) -> None:
        if span.name != ITERATION:
            self._acc[span.phase] = self._acc.get(span.phase, 0.0) + (now - span.t_acc)
        if span.annotation is not None:
            span.annotation.__exit__(None, None, None)
        if self._spans:
            self.ring.append(
                (span.name, span.t0, now, span.seq, span.parent, span.step, span.counters)
            )

    def _innermost(self) -> _Open | None:
        return self._stack[-1] if self._stack else (self._mark or self._iter)

    # ---- context-manager style -------------------------------------------
    @contextmanager
    def phase(self, name: str) -> Iterator[None]:
        full = f"{self._stack[-1].name}/{name}" if self._stack else name
        self._stack.append(self._open(full, time.perf_counter(), self._innermost()))
        try:
            yield
        finally:
            self._close(self._stack.pop(), time.perf_counter())

    # ---- linear sectioning ------------------------------------------------
    def mark(self, name: str | None, phase: str | None = None) -> None:
        """End the current marked section (if any) and open `name`, a child
        of the open iteration, whose seconds go to the sum `phase` (its own
        name unless given)."""
        now = time.perf_counter()
        if self._mark is not None:
            self._close(self._mark, now)
        self._mark = None if name is None else self._open(name, now, self._iter, phase=phase)

    def iteration(self, step: int | None) -> None:
        """End the current marked section and the current iteration (if
        any) and open the iteration `step`."""
        now = time.perf_counter()
        if self._mark is not None:
            self._close(self._mark, now)
            self._mark = None
        if self._iter is not None:
            self._close(self._iter, now)
        self._iter = None if step is None else self._open(ITERATION, now, None, step)

    def count(self, **counters: Any) -> None:
        """Attach counters to the innermost open span: what the work between
        its two ends did, recorded where the work happens."""
        span = self._innermost()
        if span is not None and self._spans:
            if span.counters is None:
                span.counters = counters
            else:
                span.counters.update(counters)

    # ---- interval flush ---------------------------------------------------
    def flush(self) -> dict[str, float]:
        """Accumulated seconds per phase since the last flush. Open phases
        (mark sections or live context managers) contribute their elapsed
        time and restart at now."""
        now = time.perf_counter()
        out = dict(self._acc)
        self._acc.clear()
        for span in ([self._mark] if self._mark is not None else []) + self._stack:
            out[span.phase] = out.get(span.phase, 0.0) + (now - span.t_acc)
            span.t_acc = now
        return out

    # ---- the ring, written when the run ends ------------------------------
    def drain(self) -> Iterator[dict[str, Any]]:
        """End the open mark and iteration, then empty the ring as `span`
        records in `Tracer.end`'s schema (`name`, `span`, `parent`, wall-clock
        `t0` / `t1`, `dur_ms`) plus `step`, the start on the `perf_counter`
        clock (`p0`), and the span's counters."""
        self.iteration(None)
        while self.ring:
            name, t0, t1, seq, parent, step, counters = self.ring.popleft()
            yield {
                "name": name,
                "span": self._span_id(seq),
                "parent": None if parent is None else self._span_id(parent),
                "t0": round(t0 + self._wall_minus_perf, 6),
                "t1": round(t1 + self._wall_minus_perf, 6),
                "dur_ms": round((t1 - t0) * 1000.0, 4),
                "step": step,
                "p0": round(t0, 7),
                **(counters or {}),
            }

    def _span_id(self, seq: int) -> str:
        return f"{(self._id_base + seq) & 0xFFFFFFFF:08x}"
