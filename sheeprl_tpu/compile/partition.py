"""Measured partitioning of compile-pathological jits (XLA:CPU conv grads).

The SAC-AE reconstruction update is the canonical pathology: one jit holding
a conv encoder/decoder forward+backward plus five optimizers compiles in
seconds on TPU but stalls XLA:CPU for minutes-to-hours at pixel sizes
(VERDICT r5: 951 s of a 1,037 s startup attributed to the recon jit at
batch 32 / 128 units; an unexplained >2.5 h outlier at the same nominal
scale). `--split_update` (per-model jits) removes the cross-model fusion
blowup but the recon jit alone still scales with BATCH: measured on the
round-6 dev host, first-call time of the isolated recon jit is 81 s at
batch 2 and 176 s at batch 4 at constant op count (23 stablehlo
convolutions, 1756 ops — the lowering is batch-invariant; the cost is in
XLA:CPU's conv-grad compilation, roughly linear in batch elements per
convolution).

That measurement is the heuristic: lower the candidate jit (sub-second),
count its convolutions, and predict

    compile_seconds ~= CPU_SECONDS_PER_CONV_ELEMENT * convolutions * batch

If the prediction exceeds the compile budget, partition the batch axis with
a PYTHON-level chunk loop over ONE chunk-sized executable (gradient
accumulation across chunks — see sac_ae's `chunked_recon`). In-jit loop
constructs do NOT work: `lax.map` with a batch-1 body still compiled in
173 s vs 176 s unchunked (measured), i.e. XLA:CPU pays the pathological
cost on the traced-through batch regardless of loop structure. A separate
chunk-sized executable really does compile at chunk cost (81 s at batch 2
on the same program). The chunk size is the largest batch divisor whose
predicted compile fits the budget. Nothing here is algorithm-specific: any
main can ask :func:`decide_batch_chunk` about any jit.

Attribution (round-6 isolation sweep, all at batch 4 / 64x64x9 pixels):
first call of the full recon-loss gradient 182 s; DECODER-only gradient
212 s; encoder-only gradient 3.1 s; forward-only 1.4 s; full grad at
cnn_channels_multiplier 4 instead of 16: 6.2 s. Separating the phases with
the AOT path (`lower().compile()` vs a timed call of the Compiled) then
showed that on the toolchain of that sweep (jaxlib 0.4.36 XLA:CPU; not
re-measured on the installed 0.9.0 — ROADMAP D5) the conv-grad
*compile* is flat in batch (1.5-2.7 s at batch 2 through 32) and the
scaling cost is EXECUTION of the transposed-conv gradient kernels
(~40 s/image at multiplier 16, superlinear in channels ~(C1/C0)^2.4) —
which resolves the VERDICT r5 951 s-vs->2.5 h "compile" discrepancy: the
number was execution (batch x per-image cost x host speed, and swappable
under memory pressure), conflated with compile by first-call timing. The
partition therefore decides on MEASURED quantities that still matter:

  - peak temp memory of the compiled executable (XLA's own
    `memory_analysis()`, read off a cheap trial AOT compile): batch-32
    conv-grad activations at pixel scale run to GiB — the memory-pressure
    path behind the 2.5 h outlier — and chunking divides them by
    batch/chunk;
  - trial compile seconds, for toolchains where conv-grad compile IS
    superlinear (the conv-count x batch predictor guards the trial so a
    pathological toolchain is not probed at full batch).

Budgets: SHEEPRL_TPU_COMPILE_BUDGET_S (default 120 s) and
SHEEPRL_TPU_PARTITION_MEM_MB (default 512 MiB).

Since ISSUE 10 the committed sheepmem ledger (`analysis/budget/`, section
`memory`) is the PREFERRED decision input: when the caller names its jit's
ledger key, the measured `memory_analysis()` temp bytes — scaled from the
capture avals to the live config by argument-byte ratio — decide the chunk
directly, with the conv-count predictor cross-validating from the
committed primitive histogram. The lower/trial-compile ladder below
remains the fallback for jits without a ledger entry.
"""

from __future__ import annotations

import os
import time as _time
from dataclasses import dataclass, field
from typing import Any, Callable

from .plan import avals_of

__all__ = [
    "CPU_SECONDS_PER_CONV_ELEMENT",
    "DEFAULT_COMPILE_BUDGET_S",
    "PartitionDecision",
    "chunk_for_budget",
    "compiled_memory_stats",
    "decide_batch_chunk",
    "ledger_entry",
    "lowered_op_counts",
    "partition_mem_budget_bytes",
    "predicted_cpu_compile_seconds",
]

# The compile-time predictor that GUARDS the trial compile. On the measured
# toolchain (jaxlib 0.4.36; not re-measured on the installed 0.9.0 — ROADMAP
# D5) conv-grad compile is flat in batch (~0.1 s per
# convolution, 2.3 s for the 23-conv recon at any batch), so this linear
# model is a deliberate over-estimate: it only blocks the trial compile on
# a toolchain whose conv-grad compile really is superlinear (the r4 dev-host
# report this subsystem was originally sized for).
CPU_SECONDS_PER_CONV_ELEMENT = 0.05

# Default per-jit compile budget the chunk chooser targets on XLA:CPU. The
# bounded receipt runners use ~900 s whole-run budgets, so a single jit
# predicted over 2 min is already pathological.
DEFAULT_COMPILE_BUDGET_S = 120.0


def compile_budget_s() -> float:
    try:
        return float(
            os.environ.get("SHEEPRL_TPU_COMPILE_BUDGET_S", DEFAULT_COMPILE_BUDGET_S)
        )
    except ValueError:
        return DEFAULT_COMPILE_BUDGET_S


def lowered_op_counts(fn: Callable, *example: Any) -> dict[str, int]:
    """Lower `fn` (jitted) at the example's avals — sub-second, no backend
    compile — and count the ops that drive XLA:CPU compile cost."""
    lowered = fn.lower(*avals_of(example))
    text = lowered.as_text()
    return {
        "convolutions": text.count("stablehlo.convolution"),
        "dots": text.count("stablehlo.dot"),
        "ops": text.count(" = "),
    }


def predicted_cpu_compile_seconds(convolutions: int, batch: int) -> float:
    return CPU_SECONDS_PER_CONV_ELEMENT * convolutions * max(batch, 1)


def chunk_for_budget(batch: int, convolutions: int, budget_s: float) -> int:
    """Largest divisor of `batch` whose predicted compile fits the budget
    (0 = no chunking needed). Divisors only: a ragged tail chunk would be a
    SECOND compiled body, paying the pathology twice."""
    if batch <= 1 or predicted_cpu_compile_seconds(convolutions, batch) <= budget_s:
        return 0
    best = 1
    for c in range(batch - 1, 0, -1):
        if batch % c == 0 and predicted_cpu_compile_seconds(convolutions, c) <= budget_s:
            best = c
            break
    return best if best < batch else 0


@dataclass
class PartitionDecision:
    """What the measured heuristic decided for one jit, and why — surfaced
    in telemetry (`compile.partition` event) so a receipt run records the
    decision inputs, not just the outcome."""

    chunk: int  # 0 = leave unpartitioned
    backend: str
    batch: int
    predicted_seconds: float
    budget_s: float
    counts: dict[str, int] = field(default_factory=dict)
    reason: str = ""

    def as_event(self) -> dict[str, Any]:
        return {
            "chunk": self.chunk,
            "backend": self.backend,
            "batch": self.batch,
            "predicted_seconds": round(self.predicted_seconds, 1),
            "budget_s": self.budget_s,
            **{f"count_{k}": v for k, v in self.counts.items()},
            "reason": self.reason,
        }


def partition_mem_budget_bytes() -> int:
    try:
        mb = float(os.environ.get("SHEEPRL_TPU_PARTITION_MEM_MB", "512"))
    except ValueError:
        mb = 512.0
    return int(mb * 2**20)


def compiled_memory_stats(compiled: Any) -> dict[str, int] | None:
    """XLA's `memory_analysis()` of a Compiled, as plain ints (None when
    the backend does not expose it). `peak_bytes` is the bytes one dispatch
    must have provisioned: arguments + outputs + temps + generated code.
    `alias_size_in_bytes` is deliberately not netted out — XLA reports it
    only on fresh compiles (persistent-cache deserializations return 0),
    so subtracting it makes the number drift with cache state."""
    try:
        ma = compiled.memory_analysis()
        arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out = int(getattr(ma, "output_size_in_bytes", 0) or 0)
        temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        gen = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
    except Exception:
        return None
    return {
        "peak_bytes": arg + out + temp + gen,
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": temp,
        "generated_code_bytes": gen,
    }


# ---------------------------------------------------------------------------
# the committed memory ledger as a decision input (ISSUE 10)
# ---------------------------------------------------------------------------


def _budget_dir() -> str:
    default = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "analysis",
        "budget",
    )
    return os.environ.get("SHEEPRL_TPU_BUDGET_DIR", default)


def ledger_entry(key: str, section: str = "memory") -> dict | None:
    """The committed `analysis/budget/` entry for `key` ('spec/jit'), from
    the given section — stdlib JSON only, None on any miss. This is how
    the partition heuristic reads sheepmem's measured bytes without
    importing the analysis package (which imports this module)."""
    import json

    spec = key.split("/", 1)[0]
    path = os.path.join(_budget_dir(), f"{spec}.json")
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh).get(section, {}).get(key)
    except (OSError, ValueError):
        return None


def _example_arg_bytes(example: tuple) -> int:
    """Total argument bytes of an example's avals — cheap (no lowering),
    used to scale the ledger's measured temp bytes from the tiny capture
    avals to the live config."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(avals_of(example)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * int(getattr(dtype, "itemsize", 4))
    return total


def _chunk_for_ratio(batch: int, ratio: float) -> int:
    """Largest divisor of `batch` at or below `batch * ratio` (>=1)."""
    target = max(int(batch * min(ratio, 1.0)), 1)
    for c in range(target, 0, -1):
        if batch % c == 0:
            return c
    return 1


def decide_batch_chunk(
    fn: Callable,
    example: tuple,
    batch: int,
    budget_s: float | None = None,
    backend: str | None = None,
    mem_budget_bytes: int | None = None,
    ledger_key: str | None = None,
    store_path: str | None = None,
) -> PartitionDecision:
    """Measure `fn` and decide whether (and how finely) to partition its
    batch axis on this backend. Non-CPU backends never partition — TPU
    compiles and runs the fused program fine and prefers the fusion.

    The decision ladder on CPU:
      0. `ledger_key` ('spec/jit') names a committed sheepmem fingerprint:
         its MEASURED temp bytes, scaled from the capture avals to the
         live config by argument-byte ratio, decide the chunk directly —
         byte-driven, zero lowering, zero trial compile. The conv-count x
         batch predictor still cross-validates from the committed
         primitive histogram (a superlinear-compile toolchain chunks by
         whichever constraint is tighter);
      1. no ledger entry: lower (sub-second) and count convolutions; if
         the conv-count x batch predictor says even ONE trial compile
         could be pathological on this toolchain, chunk by the predictor
         without probing further;
      2. otherwise trial-AOT-compile the lowered module (seconds on a
         healthy toolchain) and read XLA's own `memory_analysis()`: when
         peak temp bytes exceed the memory budget, chunk proportionally —
         bounding the conv-grad activation footprint that drives the
         memory-pressure/swap pathology at pixel batch sizes.
    """
    if backend is None:
        import jax

        backend = jax.default_backend()
    budget = compile_budget_s() if budget_s is None else budget_s
    mem_budget = (
        partition_mem_budget_bytes() if mem_budget_bytes is None else mem_budget_bytes
    )
    if backend != "cpu":
        return PartitionDecision(
            chunk=0, backend=backend, batch=batch, predicted_seconds=0.0,
            budget_s=budget, reason="non-cpu backend: keep fused",
        )
    if ledger_key is not None:
        decision = _decide_from_ledger(
            ledger_key, example, batch, budget, mem_budget, backend
        )
        if decision is not None:
            return decision

    # the MEASUREMENT (lowering + trial compile) is memoized in the unified
    # decision cache (compile/decisions.py, family `batch_chunk`, the same
    # store the scan-unroll ladder and the remat gate use): a repeat run at
    # the same (name, avals, jax version, backend) key skips every trial
    # compile. Only the measurement is cached — the CHUNK is re-derived
    # below from the budgets in force at call time, so a budget change
    # never serves a stale decision.
    from . import decisions as dec

    def _measure() -> dict:
        try:
            lowered = fn.lower(*avals_of(example))
            text = lowered.as_text()
        except Exception as err:
            return {"error": f"lowering failed: {type(err).__name__}"}
        rec: dict = {
            "counts": {
                "convolutions": text.count("stablehlo.convolution"),
                "dots": text.count("stablehlo.dot"),
                "ops": text.count(" = "),
            },
            "trial": False,
        }
        p = predicted_cpu_compile_seconds(rec["counts"]["convolutions"], batch)
        if p > budget * 10:
            # a toolchain with superlinear conv-grad compile would hang the
            # trial compile itself: decide on the predictor alone
            return rec
        try:
            t0 = _time.perf_counter()
            exe = lowered.compile()
            trial_s = _time.perf_counter() - t0
            ma = exe.memory_analysis()
            temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        except Exception as err:
            rec["error"] = f"trial compile failed: {type(err).__name__}"
            return rec
        rec.update(trial=True, trial_seconds=trial_s, temp_bytes=temp)
        return rec

    probe_name = _probe_name(fn, ledger_key, batch)
    record, source = dec.measured_probe(
        "batch_chunk", probe_name, example, _measure, store_path=store_path
    )
    counts = dict(record.get("counts", {}))
    if record.get("error") and not counts:
        return PartitionDecision(
            chunk=0, backend=backend, batch=batch, predicted_seconds=0.0,
            budget_s=budget, reason=record["error"],
        )
    pred = predicted_cpu_compile_seconds(counts.get("convolutions", 0), batch)
    if not record.get("trial") and not record.get("error") and pred <= budget * 10:
        # cached under a larger budget that skipped the trial; this budget
        # wants the measured quantities — re-measure once
        record, source = dec.measured_probe(
            "batch_chunk", probe_name, example, _measure,
            store_path=store_path, force=True,
        )
        counts = dict(record.get("counts", {}))
    tag = " [probe cache]" if source == "cache" else ""
    if record.get("error"):
        return PartitionDecision(
            chunk=0, backend=backend, batch=batch, predicted_seconds=pred,
            budget_s=budget, counts=counts, reason=record["error"] + tag,
        )
    if not record.get("trial"):
        chunk = chunk_for_budget(batch, counts.get("convolutions", 0), budget)
        return PartitionDecision(
            chunk=chunk, backend=backend, batch=batch, predicted_seconds=pred,
            budget_s=budget, counts=counts,
            reason=(
                f"predicted {pred:.0f}s compile: chunk {batch} -> {chunk} "
                f"without trial compile{tag}"
            ),
        )
    trial_s = float(record["trial_seconds"])
    temp_bytes = int(record["temp_bytes"])
    counts["temp_bytes"] = temp_bytes
    counts["trial_compile_ms"] = int(trial_s * 1000)
    if trial_s > budget:
        chunk = _chunk_for_ratio(batch, budget / trial_s)
        reason = (
            f"trial compile {trial_s:.0f}s > budget {budget:.0f}s: "
            f"chunk {batch} -> {chunk}"
        )
    elif temp_bytes > mem_budget:
        chunk = _chunk_for_ratio(batch, mem_budget / temp_bytes)
        reason = (
            f"peak temp {temp_bytes / 2**20:.0f}MiB > budget "
            f"{mem_budget / 2**20:.0f}MiB: chunk {batch} -> {chunk}"
        )
    else:
        chunk = 0
        reason = (
            f"compile {trial_s:.1f}s and peak temp "
            f"{temp_bytes / 2**20:.0f}MiB within budget"
        )
    if chunk >= batch:
        chunk = 0
    return PartitionDecision(
        chunk=chunk, backend=backend, batch=batch, predicted_seconds=pred,
        budget_s=budget, counts=counts, reason=reason + tag,
    )


def _probe_name(fn: Callable, ledger_key: str | None, batch: int) -> str:
    """A stable per-jit probe name for the decision cache: the ledger key
    when the caller has one, else the function's qualified name (locally
    defined probes stay distinct through `<locals>`)."""
    if ledger_key:
        base = ledger_key
    else:
        base = (
            f"{getattr(fn, '__module__', '')}."
            f"{getattr(fn, '__qualname__', getattr(fn, '__name__', 'fn'))}"
        )
    return f"{base}[batch={batch}]"


def _decide_from_ledger(
    ledger_key: str,
    example: tuple,
    batch: int,
    budget: float,
    mem_budget: int,
    backend: str,
) -> PartitionDecision | None:
    """Byte-driven partition decision from the committed sheepmem ledger
    (decision-ladder step 0). None when the ledger has no usable entry —
    the caller falls back to the measured lower/trial-compile ladder.

    The ledger's temp bytes were measured at the tiny capture avals; the
    live config's footprint is predicted by scaling with the argument-byte
    ratio (activations scale with the data, parameters cancel out of the
    ratio). The conv predictor cross-validates from the committed
    primitive histogram in the same spec file's `jits` section; the chunk
    honors whichever constraint is tighter."""
    mem = ledger_entry(ledger_key, "memory")
    if not mem or not mem.get("argument_bytes"):
        return None
    try:
        live_args = _example_arg_bytes(example)
    except Exception:
        return None
    ratio = max(live_args / max(int(mem["argument_bytes"]), 1), 1.0)
    predicted_temp = int(int(mem.get("temp_bytes", 0)) * ratio)
    jits = ledger_entry(ledger_key, "jits") or {}
    convs = int(jits.get("primitives", {}).get("conv_general_dilated", 0))
    pred_s = predicted_cpu_compile_seconds(convs, batch)
    counts = {
        "ledger_temp_bytes": int(mem.get("temp_bytes", 0)),
        "ledger_argument_bytes": int(mem["argument_bytes"]),
        "live_argument_bytes": live_args,
        "predicted_temp_bytes": predicted_temp,
        "convolutions": convs,
    }
    candidates = []
    if predicted_temp > mem_budget:
        candidates.append(_chunk_for_ratio(batch, mem_budget / predicted_temp))
    if pred_s > budget:
        candidates.append(chunk_for_budget(batch, convs, budget) or 1)
    chunk = min((c for c in candidates if c), default=0)
    if chunk >= batch:
        chunk = 0
    if chunk:
        reason = (
            f"ledger {ledger_key}: predicted temp "
            f"{predicted_temp / 2**20:.0f}MiB vs budget "
            f"{mem_budget / 2**20:.0f}MiB (predictor {pred_s:.0f}s): "
            f"chunk {batch} -> {chunk}"
        )
    else:
        reason = (
            f"ledger {ledger_key}: predicted temp "
            f"{predicted_temp / 2**20:.1f}MiB and predictor {pred_s:.0f}s "
            "within budget"
        )
    return PartitionDecision(
        chunk=chunk, backend=backend, batch=batch, predicted_seconds=pred_s,
        budget_s=budget, counts=counts, reason=reason,
    )
