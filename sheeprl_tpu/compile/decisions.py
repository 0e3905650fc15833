"""sheepopt decisions — ONE measured-decision framework for every tuning
knob (ISSUE 11 tentpole).

The repo grew its perf knobs one bespoke ladder at a time: the scan-unroll
autotuner (ISSUE 9) measured rungs and persisted winners in its own
`scan_unroll.json`; `decide_batch_chunk` (ISSUE 5/10) trial-compiled and
never persisted anything; the `--remat` flag stayed a human decision fed by
sheepmem's advisor. This module generalizes the PR-9 rung-ladder machinery
into the one shape they all share:

    a Decision = (knob family, candidate ladder, example avals)
        -> per-candidate trial `lower().compile()` (compile time measured
           apart from exec, the PR-5 AOT machinery),
        -> per-candidate exec timing at the run's EXACT shapes,
        -> per-candidate XLA `memory_analysis()` peak/temp bytes,
        -> per-candidate BIT-EXACTNESS receipt vs the baseline candidate
           (a non-bit-exact candidate is disqualified, never silently kept),
        -> a winner under an explicit objective: `seconds` (fastest),
           `bytes` (smallest peak), or bytes-at-<=X%-time-cost (smallest
           peak among candidates within the time budget),
        -> persisted in ONE decision cache next to the compile cache
           (`decisions.json`, keyed family|name|avals|jax version|backend|
           device kind),
           so a re-run with the same key skips every trial compile exactly
           like a warm compile cache skips the compile.

Actuators built on top: `decide_remat` (the auto-remat acceptance gate:
peak-bytes reduction at <=5% exec-time cost), the migrated scan-unroll
ladder (`ops/scan.py:autotune_unroll`), and `decide_batch_chunk`'s
measured path (`measured_probe` memoizes its trial compile). Every future
knob (precision islands, chunk ladders, prefetch depths) gets trial
compiles + receipts + caching for free by naming a family and a ladder.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any, Callable, Iterator, Sequence

__all__ = [
    "CandidateReport",
    "Decision",
    "REMAT_LADDER",
    "cache_path",
    "decide",
    "decide_remat",
    "decision_key",
    "load_cache",
    "measured_probe",
    "remat_enabled",
    "remat_mode",
    "remat_time_cost_frac",
]

CACHE_BASENAME = "decisions.json"

# The auto-remat acceptance gate: remat wins only when it reduces peak
# bytes AND costs at most this fraction of the baseline's exec time.
DEFAULT_REMAT_TIME_COST_FRAC = 0.05


def remat_time_cost_frac() -> float:
    try:
        return float(
            os.environ.get(
                "SHEEPRL_TPU_REMAT_TIME_COST_FRAC", DEFAULT_REMAT_TIME_COST_FRAC
            )
        )
    except ValueError:
        return DEFAULT_REMAT_TIME_COST_FRAC


def remat_mode(value: Any) -> str:
    """The `--remat {off,on,policy,auto}` knob as the settled mode the
    trace sites consume: `on` = full `jax.checkpoint` of the scan body,
    `policy` = checkpoint with `dots_with_no_batch_dims_saveable` (matmul
    outputs stay saved, only cheap elementwise ops recompute — the
    bytes-at-near-zero-time-cost rung), `off` = store everything. `auto`
    reads "off" here: the mains resolve it via `decide_remat` BEFORE
    tracing, so an unresolved `auto` (e.g. a capture run that never
    reaches the decision) means baseline. Bools pass through for
    pre-ISSUE-11 checkpoints that stored one."""
    if isinstance(value, bool):
        return "on" if value else "off"
    v = str(value).strip().lower()
    if v in ("on", "true", "1", "yes"):
        return "on"
    if v == "policy":
        return "policy"
    return "off"


def remat_enabled(value: Any) -> bool:
    """True when the settled remat mode checkpoints anything at all."""
    return remat_mode(value) != "off"


# ---------------------------------------------------------------------------
# the decision cache: one store next to the compile cache
# ---------------------------------------------------------------------------


def cache_path(explicit: str | None = None) -> str:
    """The unified decision store lives in the compile-cache directory
    (compile/cache.py:cache_dir)."""
    from .cache import cache_dir

    return explicit or os.path.join(cache_dir(), CACHE_BASENAME)


def load_cache(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except Exception:
        return {}


def _save_cache(path: str, store: dict) -> None:
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(store, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # the store is an optimization; never fail the run on it


def _avals_tag(example: Sequence[Any]) -> str:
    import jax

    return ",".join(
        f"{getattr(getattr(a, 'dtype', None), 'name', type(a).__name__)}"
        f"{list(getattr(a, 'shape', []))}"
        for a in jax.tree_util.tree_leaves(example)
    )


def decision_key(family: str, name: str, example: Sequence[Any]) -> str:
    """The cache key: knob family + probe name + exact avals + jax version
    + backend + device kind. Any drift in any component is a miss — a
    decision measured on other shapes, another toolchain, or another chip
    generation (a "TPU v5 lite" winner on a v4) never leaks."""
    import jax

    return (
        f"{family}|{name}|{_avals_tag(example)}"
        f"|jax{jax.__version__}|{jax.default_backend()}"
        f"|{jax.devices()[0].device_kind}"
    )


# ---------------------------------------------------------------------------
# the Decision record
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CandidateReport:
    """One rung of one ladder: what it cost to build, what it costs to run,
    what it holds live, and whether its numerics survived the receipt."""

    label: str
    exec_seconds: float | None = None
    compile_seconds: float | None = None
    bit_exact: bool | None = None
    peak_bytes: int | None = None
    temp_bytes: int | None = None
    error: str | None = None
    # bounded-divergence acceptance (ISSUE 20): when the ladder runs with a
    # quality_metric, every candidate carries its measured divergence vs the
    # baseline and whether it stayed within quality_bound. Bit-exact ladders
    # leave both None — the receipt is bit-exactness, as before.
    divergence: float | None = None
    within_bound: bool | None = None

    def as_dict(self) -> dict[str, Any]:
        return {k: v for k, v in dataclasses.asdict(self).items() if k != "label"}


@dataclasses.dataclass
class Decision:
    """One measured ladder and its accepted winner. `accepted` means the
    winner differs from the baseline — the knob actually moved."""

    family: str
    name: str
    winner: str  # label of the winning candidate
    baseline: str  # label of the reference candidate (receipts compare to it)
    objective: str  # "seconds" | "bytes"
    candidates: dict[str, dict]  # label -> CandidateReport.as_dict()
    accepted: bool
    source: str  # "measured" | "cache"
    key: str
    max_time_cost_frac: float | None = None
    # the quality-receipt bound the ladder was accepted under (None for
    # bit-exact ladders) — committed next to the winner so the cache entry
    # IS the receipt
    quality_bound: float | None = None

    def candidate(self, label: str) -> dict:
        return self.candidates.get(str(label), {})

    def seconds_delta(self) -> float | None:
        """Winner exec seconds minus baseline (negative = faster)."""
        w = self.candidate(self.winner).get("exec_seconds")
        b = self.candidate(self.baseline).get("exec_seconds")
        if w is None or b is None:
            return None
        return float(w) - float(b)

    def bytes_delta(self) -> int | None:
        """Winner peak bytes minus baseline (negative = smaller)."""
        w = self.candidate(self.winner).get("peak_bytes")
        b = self.candidate(self.baseline).get("peak_bytes")
        if w is None or b is None:
            return None
        return int(w) - int(b)

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def as_event(self) -> dict[str, Any]:
        """The telemetry payload: compact — the full per-candidate ladder
        stays in the cache, the event carries the decision."""
        out = {
            "family": self.family,
            "probe": self.name,
            "winner": self.winner,
            "baseline": self.baseline,
            "objective": self.objective,
            "accepted": bool(self.accepted),
            "source": self.source,
            "candidates_tried": len(self.candidates),
        }
        sd, bd = self.seconds_delta(), self.bytes_delta()
        if sd is not None:
            out["seconds_delta"] = sd
        if bd is not None:
            out["bytes_delta"] = bd
        if self.quality_bound is not None:
            out["quality_bound"] = self.quality_bound
            div = self.candidate(self.winner).get("divergence")
            if div is not None:
                out["divergence"] = div
        # a candidate that RAISED (compile or run) loses the ladder by
        # design, but never silently: the event names it and its message
        errors = self.errors()
        if errors:
            out["errors"] = errors
        return out

    def errors(self) -> dict[str, str]:
        """label -> message of every candidate whose build/compile/run
        raised (as opposed to losing on time, bytes or the receipt)."""
        return {
            lbl: rep["error"] for lbl, rep in self.candidates.items() if rep.get("error")
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "Decision":
        return cls(
            family=str(d.get("family", "")),
            name=str(d.get("name", "")),
            winner=str(d.get("winner", "")),
            baseline=str(d.get("baseline", "")),
            objective=str(d.get("objective", "seconds")),
            candidates={str(k): dict(v) for k, v in d.get("candidates", {}).items()},
            accepted=bool(d.get("accepted", False)),
            source="cache",
            key=str(d.get("key", "")),
            max_time_cost_frac=d.get("max_time_cost_frac"),
            quality_bound=d.get("quality_bound"),
        )


def cached_decision(path: str, key: str) -> Decision | None:
    rec = load_cache(path).get(key)
    if not isinstance(rec, dict) or "candidates" not in rec:
        return None
    return Decision.from_dict({**rec, "key": key})


def _store(path: str, key: str, record: dict) -> None:
    store = load_cache(path)
    store[key] = record
    _save_cache(path, store)


# ---------------------------------------------------------------------------
# the measurement loop
# ---------------------------------------------------------------------------


def _bit_exact(a: Any, b: Any) -> bool:
    import jax
    import numpy as np

    la = [np.asarray(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x) for x in jax.tree_util.tree_leaves(b)]
    if len(la) != len(lb):
        return False
    return all(np.array_equal(x, y, equal_nan=True) for x, y in zip(la, lb))


@contextlib.contextmanager
def _null_context(_value: Any) -> Iterator[None]:
    yield


def _absorb_process_warmup(fn: Callable, example: Sequence[Any]) -> None:
    """A throwaway lower + trivial compile absorb the process's one-time
    tracing/MLIR/LLVM-backend warmup so it doesn't bias the first
    candidate's compile_seconds (the same first-call attribution trap as
    the r4/r5 compile-vs-exec mixup)."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda *a: fn(*a)).lower(*example)
    jax.block_until_ready(jax.jit(lambda v: v + 1.0)(jnp.float32(0.0)))


def decide(
    family: str,
    name: str,
    candidates: Sequence[Any],
    build: Callable[[Any], Callable],
    example: Sequence[Any],
    *,
    objective: str = "seconds",
    max_time_cost_frac: float | None = None,
    repeats: int = 3,
    store_path: str | None = None,
    force: bool = False,
    candidate_context: Callable[[Any], Any] | None = None,
    quality_metric: Callable[[Any, Any], float] | None = None,
    quality_bound: float | None = None,
) -> Decision:
    """Measure one candidate ladder and return (and persist) the decision.

    `build(candidate)` must return a JITtable callable for that candidate —
    a FRESH callable per call (jax's trace cache keys on function identity,
    so reusing one callable across candidates would silently measure the
    first candidate N times; `decide` wraps defensively anyway).
    `candidate_context(candidate)` (optional) is entered around the
    candidate's trace/compile/exec so trace-time knobs (the unroll
    override) see the candidate value.

    Per candidate: AOT `lower().compile()` (compile time measured apart
    from exec), `memory_analysis()` peak/temp bytes, one untimed warm-up
    call, then `repeats` timed calls (median). The FIRST candidate is the
    baseline: any candidate whose outputs are not bit-identical to it is
    disqualified. Winner selection by `objective`:

      - "seconds": fastest surviving candidate; ties break toward ladder
        order (callers list cheaper/simpler candidates first);
      - "bytes": smallest peak-bytes among surviving candidates whose exec
        time is within `max_time_cost_frac` of the baseline's (when set);
        a candidate must STRICTLY undercut the baseline's bytes to win.

    Bounded-divergence acceptance (the quantization path): passing
    `quality_metric` (a `(baseline_out, candidate_out) -> float` distance,
    e.g. max action divergence over a held-out calibration set) together
    with `quality_bound` relaxes the receipt — a non-bit-exact candidate
    survives when its measured divergence stays <= `quality_bound`, and is
    DISQUALIFIED past it exactly like a non-bit-exact remat rung. The
    divergence and the bound persist in the cache record: the decision
    entry IS the quality receipt.
    """
    import jax

    from .partition import compiled_memory_stats

    if objective not in ("seconds", "bytes"):
        raise ValueError(f"unknown objective {objective!r}")
    if (quality_metric is None) != (quality_bound is None):
        raise ValueError("quality_metric and quality_bound come together")
    labels = [str(c) for c in candidates]
    if len(set(labels)) != len(labels):
        raise ValueError(f"duplicate candidate labels in {labels}")
    path = cache_path(store_path)
    key = decision_key(family, name, example)
    if not force:
        hit = cached_decision(path, key)
        if hit is not None:
            return hit

    ctx = candidate_context or _null_context
    reports: dict[str, CandidateReport] = {}
    outputs: dict[str, Any] = {}

    with ctx(candidates[0]):
        _absorb_process_warmup(build(candidates[0]), example)
    for value, label in zip(candidates, labels):
        report = CandidateReport(label=label)
        reports[label] = report
        try:
            fn = build(value)
            fresh = lambda *a: fn(*a)  # noqa: E731 — fresh trace identity
            with ctx(value):
                t0 = time.perf_counter()
                # sheeplint: disable=SL004 — a fresh jit per candidate is
                # the POINT: each candidate must trace its own program, and
                # the ladder runs once per (family, shapes, backend) key
                compiled = jax.jit(fresh).lower(*example).compile()
                report.compile_seconds = time.perf_counter() - t0
                mem = compiled_memory_stats(compiled)
                if mem is not None:
                    report.peak_bytes = mem["peak_bytes"]
                    report.temp_bytes = mem["temp_bytes"]
                out = jax.block_until_ready(compiled(*example))  # warm-up
                samples = []
                for _ in range(max(1, repeats)):
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(compiled(*example))
                    samples.append(time.perf_counter() - t0)
        except Exception as err:
            # a broken candidate loses the ladder instead of aborting the
            # run; `Decision.errors()` / the event's `errors` keep it visible
            # and callers for whom it is fatal (serve --quant) raise on it
            report.error = f"{type(err).__name__}: {err}"[:500]
            continue
        samples.sort()
        report.exec_seconds = samples[len(samples) // 2]
        outputs[label] = out

    baseline = labels[0]
    if baseline not in outputs:
        raise RuntimeError(
            f"{family}/{name}: baseline candidate {baseline!r} failed to "
            f"compile or run: {reports[baseline].error}"
        )
    for label in labels:
        if label not in outputs:
            reports[label].bit_exact = False
            if quality_metric is not None:
                reports[label].within_bound = False
            continue
        reports[label].bit_exact = (
            True if label == baseline else _bit_exact(outputs[baseline], outputs[label])
        )
        if quality_metric is not None:
            if label == baseline:
                reports[label].divergence = 0.0
                reports[label].within_bound = True
            else:
                try:
                    div = float(quality_metric(outputs[baseline], outputs[label]))
                except Exception as err:  # an unmeasurable receipt disqualifies
                    reports[label].error = f"{type(err).__name__}: {err}"[:200]
                    reports[label].within_bound = False
                    continue
                reports[label].divergence = div
                reports[label].within_bound = div <= quality_bound

    winner = _pick_winner(
        labels, reports, objective, baseline, max_time_cost_frac
    )
    decision = Decision(
        family=family,
        name=name,
        winner=winner,
        baseline=baseline,
        objective=objective,
        candidates={lbl: rep.as_dict() for lbl, rep in reports.items()},
        accepted=winner != baseline,
        source="measured",
        key=key,
        max_time_cost_frac=max_time_cost_frac,
        quality_bound=quality_bound,
    )
    _store(path, key, decision.as_dict())
    return decision


def _pick_winner(
    labels: list[str],
    reports: dict[str, CandidateReport],
    objective: str,
    baseline: str,
    max_time_cost_frac: float | None,
) -> str:
    # a candidate survives on either receipt: bit-exactness (the default)
    # or a measured divergence within the quality bound (bounded
    # acceptance); everything else is disqualified
    eligible = [
        lbl
        for lbl in labels
        if (reports[lbl].bit_exact or reports[lbl].within_bound)
        and reports[lbl].exec_seconds is not None
    ]
    if objective == "seconds":
        return min(
            eligible, key=lambda lbl: (reports[lbl].exec_seconds, labels.index(lbl))
        )
    # objective == "bytes": strictly fewer peak bytes than baseline, within
    # the exec-time budget when one is set
    base = reports[baseline]
    best = baseline
    if base.peak_bytes is None:
        return baseline  # no memory analysis on this backend: keep baseline
    budget_s = (
        None
        if max_time_cost_frac is None or base.exec_seconds is None
        else base.exec_seconds * (1.0 + max_time_cost_frac)
    )
    for lbl in eligible:
        rep = reports[lbl]
        if lbl == baseline or rep.peak_bytes is None:
            continue
        if budget_s is not None and rep.exec_seconds > budget_s:
            continue
        if rep.peak_bytes < reports[best].peak_bytes:
            best = lbl
    return best


# ---------------------------------------------------------------------------
# actuator: auto-remat (ISSUE 11 tentpole a)
# ---------------------------------------------------------------------------


REMAT_LADDER = ("off", "policy", "on")


def decide_remat(
    name: str,
    build: Callable[[str], Callable],
    example: Sequence[Any],
    *,
    candidates: Sequence[str] = REMAT_LADDER,
    repeats: int = 3,
    store_path: str | None = None,
    force: bool = False,
    max_time_cost_frac: float | None = None,
) -> Decision:
    """The auto-remat acceptance gate: `build(mode)` returns the
    scan-bearing probe (typically a grad of the train step's dominant
    scan) with the scan body checkpointed per `mode` ("off" / "policy" =
    dots-saveable policy / "on" = full checkpoint; `remat_mode` +
    `ops.scan.checkpoint_body` are the shared plumbing). A remat rung is
    accepted only when it STRICTLY reduces `memory_analysis()` peak
    bytes, costs at most `max_time_cost_frac` (default 5%,
    SHEEPRL_TPU_REMAT_TIME_COST_FRAC) of the baseline's exec time, and is
    bit-exact vs the non-remat baseline — full remat typically buys the
    most bytes but pays a whole recomputed forward, so on exec-bound
    hosts the policy rung is the expected winner. The winner persists in
    the unified decision cache."""
    frac = remat_time_cost_frac() if max_time_cost_frac is None else max_time_cost_frac
    return decide(
        "remat",
        name,
        list(candidates),
        build,
        example,
        objective="bytes",
        max_time_cost_frac=frac,
        repeats=repeats,
        store_path=store_path,
        force=force,
    )


# ---------------------------------------------------------------------------
# measured probes: memoized one-off measurements (batch-chunk's trial)
# ---------------------------------------------------------------------------


def measured_probe(
    family: str,
    name: str,
    example: Sequence[Any],
    measure: Callable[[], dict],
    *,
    store_path: str | None = None,
    force: bool = False,
) -> tuple[dict, str]:
    """Memoize one expensive measurement (a trial compile, a lowering
    sweep) in the unified decision cache, keyed exactly like a ladder
    decision. Returns `(record, source)` with source `"measured"` or
    `"cache"`. The record must be JSON-serializable; the DECISION derived
    from it (e.g. the batch chunk) is recomputed by the caller from
    current budgets, so a budget change never serves a stale decision —
    only the measurement is cached."""
    path = cache_path(store_path)
    key = decision_key(family, name, example)
    if not force:
        rec = load_cache(path).get(key)
        if isinstance(rec, dict) and "probe" in rec:
            return dict(rec["probe"]), "cache"
    record = measure()
    if not record.get("error"):  # failed measurements re-probe next call
        _store(
            path, key, {"family": family, "name": name, "key": key, "probe": record}
        )
    return record, "measured"
