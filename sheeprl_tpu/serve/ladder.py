"""Batch-ladder sizing for the serving tier.

The server dispatches micro-batches through fixed-shape AOT executables,
one per ladder rung (1, 2, 4, ... up to --max_batch). Each rung costs one
XLA compile at startup and holds its peak working set for the lifetime of
the server, so the ladder is SIZED, not assumed: a rung is accepted when
its predicted peak bytes fit the serving memory budget.

The decision ladder mirrors `compile/partition.decide_batch_chunk`:

  0. ledger-first, CPU backend only — the committed sheepmem ledger carries
     argument/peak bytes captured on XLA:CPU for every
     `<spec>/policy_b<rung>` serving jit (the `@serve` capture variants,
     ISSUE 15 satellite); the live footprint is predicted by scaling with
     the argument-byte ratio, zero lowering, zero trial compile. A ledger
     captured on the CPU never steers a run on an accelerator;
  1. otherwise — trial-AOT-compile the rung once on the live backend and
     read XLA's own `memory_analysis()`; the measurement is memoized in
     the unified decision cache (compile/decisions.py, family
     `serve_ladder`), so a restarted server never re-probes. A rung whose
     trial compile fails raises: it could not serve either.

The budget is what the live device reports free (`memory_stats()`), or
the partition heuristic's host budget where the backend reports none
(XLA:CPU). Rung 1 is always kept (a server that can serve nothing is not a
server — if even batch 1 exceeds the budget the operator must shrink the
model, not the ladder).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

from ..compile.partition import (
    _example_arg_bytes,
    ledger_entry,
    partition_mem_budget_bytes,
)

__all__ = [
    "RungDecision",
    "derive_rung",
    "ledger_spec",
    "parse_rungs",
    "serve_mem_budget_bytes",
    "size_ladder",
]


def parse_rungs(ladder: str, max_batch: int) -> list[int]:
    """'auto' -> powers of two up to max_batch (always including
    max_batch); '1,2,8' -> that list, validated and sorted."""
    if ladder == "auto":
        rungs = []
        r = 1
        while r < max_batch:
            rungs.append(r)
            r *= 2
        rungs.append(max_batch)
        return rungs
    try:
        rungs = sorted({int(tok) for tok in ladder.split(",") if tok.strip()})
    except ValueError:
        raise ValueError(f"unparseable ladder {ladder!r} (want e.g. '1,2,8')")
    if not rungs or rungs[0] < 1:
        raise ValueError(f"ladder rungs must be >= 1, got {ladder!r}")
    if rungs[-1] > max_batch:
        raise ValueError(
            f"ladder rung {rungs[-1]} exceeds --max_batch {max_batch}"
        )
    return rungs


def derive_rung(avg_rows: float, rungs: list[int], max_batch: int) -> int | None:
    """Occupancy-driven rung derivation: the intermediate batch size live
    telemetry says dispatches actually carry. Returns None when the
    candidate is degenerate (<= 0), already a rung, over --max_batch, or
    within 1 of the rung it would relieve (padding one row is cheaper than
    holding another executable)."""
    cand = int(round(avg_rows))
    if cand <= 0 or cand in rungs or cand > max_batch:
        return None
    above = [r for r in rungs if r >= cand]
    if not above or above[0] - cand < 2:
        return None
    return cand


def ledger_spec(algo: str) -> str:
    """The capture-spec name whose committed budget file carries the
    serving jits: the base `serve` spec is the SAC ladder (the capture
    default), other algos are `<algo>@serve` variants."""
    return "serve" if algo == "sac" else f"{algo}@serve"


def serve_mem_budget_bytes() -> int:
    """Peak-bytes budget per serving executable: SHEEPRL_TPU_SERVE_MEM_MB
    when set, else the memory the live device reports free, else (a backend
    without `memory_stats()`, i.e. XLA:CPU) the partition heuristic's host
    budget."""
    mb = os.environ.get("SHEEPRL_TPU_SERVE_MEM_MB")
    if mb:
        return int(float(mb) * 2**20)
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
    return partition_mem_budget_bytes()


@dataclasses.dataclass
class RungDecision:
    rung: int
    accepted: bool
    source: str  # 'ledger' | 'probe' | 'floor'
    peak_bytes: int
    reason: str

    def as_event(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def size_ladder(
    fn: Callable,
    example_of: Callable[[int], tuple],
    rungs: list[int],
    spec: str,
    mem_budget_bytes: int | None = None,
    store_path: str | None = None,
) -> list[RungDecision]:
    """Decide, per requested rung, whether its executable fits the serving
    memory budget. `fn` is the jitted per-rung policy step, `example_of`
    maps a rung to its exact call arguments (live pytrees /
    ShapeDtypeStructs). Returns one RungDecision per rung, in order."""
    budget = serve_mem_budget_bytes() if mem_budget_bytes is None else mem_budget_bytes
    decisions: list[RungDecision] = []
    for rung in rungs:
        example = example_of(rung)
        peak, source, note = _predict_peak(fn, example, spec, rung, store_path)
        if peak <= budget:
            decisions.append(
                RungDecision(
                    rung, True, source, peak,
                    f"peak {peak / 2**20:.1f}MiB within budget "
                    f"{budget / 2**20:.0f}MiB ({note})",
                )
            )
        elif rung == min(rungs):
            decisions.append(
                RungDecision(
                    rung, True, "floor", peak,
                    f"peak {peak / 2**20:.1f}MiB EXCEEDS budget "
                    f"{budget / 2**20:.0f}MiB but the smallest rung is "
                    f"always kept ({note})",
                )
            )
        else:
            decisions.append(
                RungDecision(
                    rung, False, source, peak,
                    f"peak {peak / 2**20:.1f}MiB > budget "
                    f"{budget / 2**20:.0f}MiB ({note})",
                )
            )
    return decisions


def _predict_peak(
    fn: Callable, example: tuple, spec: str, rung: int, store_path: str | None
) -> tuple[int, str, str]:
    """-> (predicted peak bytes, source, note)."""
    import jax

    key = f"{spec}/policy_b{rung}"
    # the committed ledger is an XLA:CPU capture: it may predict for a CPU
    # run only, an accelerator run measures on the live device
    mem = ledger_entry(key, "memory") if jax.default_backend() == "cpu" else None
    if mem and mem.get("peak_bytes") and mem.get("argument_bytes"):
        try:
            live_args = _example_arg_bytes(example)
        except Exception:
            live_args = 0
        if live_args:
            # activations scale with the data; parameters cancel out of the
            # ratio (same scaling argument as decide_batch_chunk's step 0).
            # The >=1 floor guards against a ledger captured at a WIDER
            # model than the live one — but only when the executables share
            # their compute dtypes: a quantized (int8) live example against
            # an f32 ledger entry legitimately predicts BELOW the entry,
            # and flooring it would make every int8 rung inherit the f32
            # prediction unchanged (the ISSUE 20 satellite fix).
            ratio = live_args / max(int(mem["argument_bytes"]), 1)
            if _dtypes_match(example, key):
                ratio = max(ratio, 1.0)
            peak = int(int(mem["peak_bytes"]) * ratio)
            return peak, "ledger", f"ledger {key} x{ratio:.2f}"
    # no committed entry (an uncaptured algo/width): one trial compile,
    # memoized in the shared decision cache
    from ..compile import decisions as dec
    from ..compile.partition import compiled_memory_stats
    from ..compile.plan import avals_of

    def _measure() -> dict:
        exe = fn.lower(*avals_of(example)).compile()
        stats = compiled_memory_stats(exe) or {}
        return {"peak_bytes": int(stats.get("peak_bytes", 0))}

    record, src = dec.measured_probe(
        "serve_ladder", key, example, _measure, store_path=store_path
    )
    tag = "probe cache" if src == "cache" else "probe"
    return int(record.get("peak_bytes", 0)), "probe", tag


def _dtypes_match(example: tuple, key: str) -> bool:
    """True when the live example's leaf dtypes agree with the committed
    jit ledger entry's input dtypes (or when either side is unreadable —
    the conservative answer keeps the historical >=1 ratio floor)."""
    entry = ledger_entry(key, "jits")
    avals = entry.get("in_avals") if isinstance(entry, dict) else None
    if not avals:
        return True
    ledger_dtypes = {str(a).split("[", 1)[0] for a in avals}
    try:
        from ..compile.plan import avals_of

        live_dtypes = {
            getattr(getattr(a, "dtype", None), "name", "")
            for a in _leaves(avals_of(example))
        } - {""}
    except Exception:
        return True
    if not live_dtypes:
        return True
    return live_dtypes == ledger_dtypes


def _leaves(tree: Any):
    import jax

    return jax.tree_util.tree_leaves(tree)
