"""Scan-unroll control: trace-time knob + a measured per-jit autotuner.

The Dreamer-family train step is dominated by sequential scans with TINY
step bodies (RSSM dynamic: T=64 steps of [B=16]-row matmuls through
512-wide layers; imagination: horizon 15 of the same shapes). XLA lowers
`lax.scan` to a while-loop with per-iteration control overhead that rivals
the step's compute at these shapes, so modest unrolls (4-8) can win real
throughput — at the cost of compile time and code size. That trade is
hardware- and shape-dependent, which is why it is measured, not hardcoded.

  - `scan_unroll()` is the trace-time read (Pallas-switch style): the
    process-global override (autotuner / `unroll()` context) wins, then the
    `SHEEPRL_TPU_SCAN_UNROLL` env var, then 1.
  - `SHEEPRL_TPU_SCAN_UNROLL=auto` arms the autotuner: the dreamer mains
    call `autotune_unroll` on their RSSM scan with the run's EXACT shapes
    before tracing the train step.

Since ISSUE 11 the ladder itself — per-rung AOT `lower().compile()`,
exec timing, BIT-EXACTNESS receipts vs rung 1, winner persistence — is
the unified measured-decision framework (`compile/decisions.py`, knob
family `scan_unroll`): winners live in the ONE decision cache next to the
compile cache (`decisions.json`).
`UnrollDecision` remains this module's typed view of the decision.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Callable, Sequence

__all__ = [
    "RUNGS",
    "UnrollDecision",
    "autotune_unroll",
    "checkpoint_body",
    "scan_unroll",
    "set_unroll",
    "unroll",
    "unroll_mode",
]


def checkpoint_body(step: Callable, remat: Any) -> Callable:
    """The ONE place a scan body is wrapped for rematerialization, shared
    by every dreamer-family RSSM/imagination scan. `remat` is the settled
    mode (`compile.decisions.remat_mode`): "on" (or legacy True) = full
    `jax.checkpoint` — store only the carry, recompute the whole step on
    backward; "policy" = checkpoint with
    `dots_with_no_batch_dims_saveable` — matmul outputs stay saved, only
    the cheap elementwise ops recompute (most of full remat's byte
    savings at near-zero exec cost, the rung the sheepopt ladder usually
    accepts on exec-bound hosts); anything else = `step` unchanged.
    `prevent_cse=False` throughout: under `lax.scan` the loop-carried
    dependence already blocks the CSE that flag guards against."""
    import jax

    mode = remat if isinstance(remat, str) else ("on" if remat else "off")
    mode = mode.strip().lower()
    if mode == "policy":
        return jax.checkpoint(
            step,
            prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    if mode in ("on", "true", "1", "yes"):
        return jax.checkpoint(step, prevent_cse=False)
    return step

RUNGS = (1, 4, 8, 16, 32)

_OVERRIDE: int | None = None


def unroll_mode() -> str:
    """The env knob's raw mode: 'auto' (measured ladder), 'env' (a fixed
    integer is set), or 'off' (unset/default)."""
    raw = os.environ.get("SHEEPRL_TPU_SCAN_UNROLL", "").strip().lower()
    if raw == "auto":
        return "auto"
    if raw:
        return "env"
    return "off"


def scan_unroll() -> int:
    """Unroll factor for the framework's time/horizon scans (default 1 =
    plain while-loop). Read at trace time like the Pallas kernel switches:
    the autotuner's installed winner (or an `unroll()` context) takes
    precedence, then `SHEEPRL_TPU_SCAN_UNROLL=k`; `lax.scan` handles
    non-divisible lengths."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    try:
        return max(1, int(os.environ.get("SHEEPRL_TPU_SCAN_UNROLL", "1")))
    except ValueError:
        return 1


def set_unroll(k: int | None) -> None:
    """Install (or clear, with None) the process-global unroll override —
    what the autotuner does with the measured winner."""
    global _OVERRIDE
    _OVERRIDE = None if k is None else max(1, int(k))


@contextlib.contextmanager
def unroll(k: int | None):
    """Scoped override: trace/compile under a specific rung, then restore."""
    global _OVERRIDE
    prev = _OVERRIDE
    _OVERRIDE = None if k is None else max(1, int(k))
    try:
        yield
    finally:
        _OVERRIDE = prev


@dataclasses.dataclass
class UnrollDecision:
    """One measured ladder: per-rung compile/exec seconds, per-rung
    bit-exactness receipts vs rung 1, and the winner. A typed view of the
    unified `compile/decisions.py` Decision for the scan_unroll family."""

    name: str
    winner: int
    timings: dict[int, float]  # rung -> median exec seconds
    compile_seconds: dict[int, float]  # rung -> AOT compile seconds
    bit_exact: dict[int, bool]  # rung -> outputs identical to rung 1
    source: str  # "measured" | "cache" | "env"
    key: str

    def as_event(self) -> dict[str, Any]:
        # "probe", not "name": the payload rides telemetry.event(name=...)
        return {
            "probe": self.name,
            "winner": int(self.winner),
            "timings_s": {str(k): v for k, v in self.timings.items()},
            "compile_s": {str(k): v for k, v in self.compile_seconds.items()},
            "bit_exact": {str(k): bool(v) for k, v in self.bit_exact.items()},
            "source": self.source,
        }

    def as_dict(self) -> dict[str, Any]:
        return {**self.as_event(), "key": self.key}

    @classmethod
    def from_decision(cls, decision: Any) -> "UnrollDecision":
        """Build the typed view from a `compile.decisions.Decision`."""
        timings: dict[int, float] = {}
        compile_s: dict[int, float] = {}
        bit_exact: dict[int, bool] = {}
        for label, rep in decision.candidates.items():
            rung = int(label)
            if rep.get("exec_seconds") is not None:
                timings[rung] = float(rep["exec_seconds"])
            if rep.get("compile_seconds") is not None:
                compile_s[rung] = float(rep["compile_seconds"])
            bit_exact[rung] = bool(rep.get("bit_exact"))
        return cls(
            name=decision.name,
            winner=int(decision.winner),
            timings=timings,
            compile_seconds=compile_s,
            bit_exact=bit_exact,
            source=decision.source,
            key=decision.key,
        )


def autotune_unroll(
    name: str,
    fn: Callable,
    example: Sequence[Any],
    *,
    rungs: Sequence[int] = RUNGS,
    repeats: int = 3,
    store_path: str | None = None,
    force: bool = False,
    apply: bool = True,
) -> UnrollDecision:
    """Measure the unroll ladder for one scan-bearing function and return
    (and by default install) the winner.

    `fn(*example)` must be jittable and contain scans whose `unroll=` reads
    `scan_unroll()` at trace time. The ladder rides the unified decision
    framework: per rung an AOT trial compile + timed execution + a
    bit-exactness receipt vs rung 1 (a non-bit-exact rung is disqualified);
    the winner is the fastest surviving rung, ties breaking toward the
    SMALLER rung (less code), and persists in the shared decision cache —
    a re-run with the same (name, avals, jax version, backend, device kind)
    key skips the whole ladder."""
    from ..compile import decisions as dec

    path = dec.cache_path(store_path)
    ladder = list(dict.fromkeys(int(r) for r in rungs))
    if 1 not in ladder:
        ladder.insert(0, 1)
    ladder.sort()  # rung 1 first (the baseline); ties break toward small
    decision = dec.decide(
        "scan_unroll",
        name,
        ladder,
        lambda _rung: (lambda *a: fn(*a)),
        example,
        objective="seconds",
        repeats=repeats,
        store_path=path,
        force=force,
        candidate_context=unroll,
    )
    result = UnrollDecision.from_decision(decision)
    if apply:
        set_unroll(result.winner)
    return result
