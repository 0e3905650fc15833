"""sheepcheck: jaxpr-level whole-program analysis over the CompilePlan.

sheeplint (linter.py) proves hazards from SOURCE — it never sees through a
`jax.jit` boundary, a helper defined in another module, or anything that
only materializes in the traced program. Since PR 5 every hot jit of all 13
algo mains is registered in the CompilePlan with an example thunk producing
its exact input avals, and PR 6 made whole rollouts single jits — so the
program we actually dispatch is fully described by that registry, the way
MSRL's dataflow fragments describe a training job as an analyzable graph
(arXiv:2210.00882). This module closes the loop: instantiate a main's plan
in capture mode (`SHEEPRL_TPU_PLAN_MODE=capture` — CPU, tiny avals, zero
execution), abstract-eval each registered jit to a ClosedJaxpr via
`jit.trace(*avals)`, and run IR-level analyzers over it. Podracer-style
fully-jitted loops (arXiv:2104.06272) make exactly these hazards invisible
to AST linting: a dtype upcast, a host callback, or a dead donation inside
a `lax.scan` body is a property of the traced program, not of any one
source file.

Rule catalog (SC = sheepcheck; suppressions live in `SUPPRESSIONS` below,
keyed `(algo, jit, rule)`, each with a mandatory justification):

  SC001  silent dtype promotion — any float64 value, or a widening float
         `convert_element_type` (f32->f64 always; bf16->f32 only under
         `audit_bf16=True`, the ROADMAP-5c mixed-precision audit: a
         bf16 model whose jaxpr silently upcasts to f32 pays full-width
         FLOPs while claiming bf16).
  SC002  host callback / infeed / outfeed traced into the jit — pure/io/
         debug callbacks serialize the program on a host round-trip per
         dispatch (jax.debug.print left in a scan body is the classic).
  SC003  donation hazards — a donated argument aliased into >=2 outputs,
         donated but dead (unused in the jaxpr), or donated with no
         shape/dtype-compatible output to reuse its buffer (XLA drops the
         alias: the donation silently buys nothing).
  SC004  weak-type hazards — weak-typed scan-carry avals (the carry
         fixpoint retraces the body once per weak leaf, and any
         strong-typed caller of the same program retraces the whole jit),
         weak-typed top-level jit inputs (a python scalar at the call
         site: retrace on weak/strong mix + an implicit h2d put per call),
         or carry/output aval mismatches.
  SC005  conv work above the measured XLA:CPU pathology threshold — the
         conv-count x batch predictor from compile/partition.py says this
         jit lands in the transposed-conv-grad regime `--split_update
         auto` / `--recon_chunk` exist for.

Each analyzable jit also yields a *fingerprint* — primitive histogram, op
count, dtype set, donation map, FLOP/byte estimates from XLA's
`cost_analysis` — which `tools/sheepcheck.py` writes to the committed
`analysis/budget/` ledger. CI re-derives the fingerprints and fails on
unexplained drift (new dtypes, op-count growth past tolerance, lost
donations): "did this PR quietly bloat or de-optimize a jit?" becomes a
gated check instead of a regression found three rounds later.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Iterable, Iterator

from .rules import Rule

__all__ = [
    "SC_RULES",
    "SUPPRESSIONS",
    "CAPTURE_ARGV",
    "CAPTURE_VARIANTS",
    "resolve_capture",
    "Finding",
    "JitReport",
    "analyze_closed_jaxpr",
    "analyze_entry",
    "analyze_plan",
    "budget_dir_of",
    "budget_exists",
    "build_budget",
    "capture_plan",
    "check_budget",
    "declares_bf16",
    "fingerprint_jaxpr",
    "iter_eqns",
    "load_budget",
    "save_budget",
]

ERROR = "error"
WARNING = "warning"

_SC_RULES = [
    Rule(
        id="SC001",
        name="silent-dtype-promotion",
        severity=ERROR,
        summary=(
            "float64 value or widening float convert_element_type in the "
            "traced program (f32->f64 always; bf16->f32 under the "
            "mixed-precision audit) — double-width FLOPs and memory the "
            "source never asked for"
        ),
        autofix=(
            "pin dtypes at the boundary (jnp.float32(...)/astype), keep "
            "x64 disabled, and for bf16 paths cast moments/reductions "
            "explicitly so the audit sees intended upcasts only"
        ),
    ),
    Rule(
        id="SC002",
        name="host-callback-in-jit",
        severity=ERROR,
        summary=(
            "host callback (pure_callback/io_callback/debug_callback) or "
            "infeed/outfeed traced into a registered jit — every dispatch "
            "pays a host round-trip, and inside scan it serializes the "
            "whole rollout"
        ),
        autofix=(
            "remove the debug.print/io_callback from the hot jit (use "
            "telemetry gauges off-path), or suppress with justification "
            "for intentional instrumentation builds"
        ),
    ),
    Rule(
        id="SC003",
        name="donation-alias-conflict",
        severity=WARNING,
        summary=(
            "donated argument aliased into multiple outputs, dead in the "
            "jaxpr, or without any shape/dtype-matching output — XLA "
            "either rejects the alias or silently drops it, so the "
            "donation buys no buffer reuse"
        ),
        autofix=(
            "donate only arguments whose buffers a same-aval output can "
            "reuse (the train-state in, train-state out pattern); drop "
            "donate_argnums for pure readers"
        ),
    ),
    Rule(
        id="SC004",
        name="weak-type-instability",
        severity=WARNING,
        summary=(
            "weak-typed avals in positions that force extra traces: a "
            "lax.scan carry (the carry fixpoint retraces the body) or a "
            "top-level jit input (a python scalar at the call site — "
            "mixing weak/strong callers retraces the whole jit, and every "
            "call pays an implicit h2d put of the constant; the PR-2 "
            "gamma/lambda class), or a carry/output aval mismatch"
        ),
        autofix=(
            "initialize carries and call-site scalars with concrete-dtype "
            "arrays (jnp.float32(0.0), jnp.zeros(..., dtype)) instead of "
            "python scalars"
        ),
    ),
    Rule(
        id="SC005",
        name="cpu-conv-pathology",
        severity=WARNING,
        summary=(
            "convolution work above the measured XLA:CPU pathology "
            "threshold (conv-count x batch predictor, "
            "compile/partition.py) — transposed-conv-grad execution in "
            "this regime runs minutes-per-update on CPU"
        ),
        autofix=(
            "run the jit through decide_batch_chunk / --split_update auto "
            "/ --recon_chunk, or suppress where the jit only ever runs "
            "on TPU"
        ),
    ),
]

SC_RULES: dict[str, Rule] = {r.id: r for r in _SC_RULES}

# (algo, jit, rule) -> justification. A finding matching a key here is
# reported as suppressed, not failing; the justification is MANDATORY and
# printed in verbose output so every suppression stays auditable (same
# contract as sheeplint's `# sheeplint: disable=... — why`).
SUPPRESSIONS: dict[tuple[str, str, str], str] = {}

_HOST_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",  # what jax.debug.print traces to in the installed jax
    "callback",
    "infeed",
    "outfeed",
}

_FLOAT_WIDTH = {"bfloat16": 16, "float16": 16, "float32": 32, "float64": 64}


@dataclasses.dataclass
class Finding:
    rule: Rule
    algo: str
    jit: str
    message: str
    suppressed: str | None = None  # justification when suppressed

    def format(self) -> str:
        sup = f" [suppressed: {self.suppressed}]" if self.suppressed else ""
        return (
            f"{self.algo}/{self.jit}: {self.rule.id} [{self.rule.severity}] "
            f"{self.message}{sup}"
        )

    def as_dict(self) -> dict:
        return {
            "rule": self.rule.id,
            "severity": self.rule.severity,
            "algo": self.algo,
            "jit": self.jit,
            "message": self.message,
            "suppressed": self.suppressed,
        }


@dataclasses.dataclass
class JitReport:
    algo: str
    name: str
    fingerprint: dict | None = None
    findings: list[Finding] = dataclasses.field(default_factory=list)
    error: str | None = None  # not analyzable (no example / not lowerable)

    @property
    def failing(self) -> list[Finding]:
        return [f for f in self.findings if not f.suppressed]


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(params: dict) -> Iterator[Any]:
    """Yield every (Closed)Jaxpr reachable from an eqn's params — covers
    pjit/scan/remat ('jaxpr'), while ('cond_jaxpr'/'body_jaxpr'), cond
    ('branches'), custom_* ('call_jaxpr'), and any future param shape that
    stores jaxprs in lists/tuples."""
    from jax.extend import core as jex_core

    def walk(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for el in v:
                yield from walk(el)

    for v in params.values():
        yield from walk(v)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every eqn of `jaxpr` (a core.Jaxpr or ClosedJaxpr), recursively
    through call/control-flow sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _aval_str(aval: Any) -> str:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None:
        return str(aval)
    s = f"{dtype.name}[{','.join(str(d) for d in (shape or ()))}]"
    if getattr(aval, "weak_type", False):
        s += "~"  # weak-typed leaf
    return s


def _all_avals(closed: Any) -> Iterator[Any]:
    inner = closed.jaxpr
    for v in (*inner.invars, *inner.outvars):
        if hasattr(v, "aval"):
            yield v.aval
    for eqn in iter_eqns(inner):
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval


# ---------------------------------------------------------------------------
# analyzers (one per SC rule, all pure functions of the IR)
# ---------------------------------------------------------------------------


def _check_sc001(closed: Any, audit_bf16: bool) -> Iterator[str]:
    f64 = sorted(
        {
            _aval_str(a)
            for a in _all_avals(closed)
            if getattr(getattr(a, "dtype", None), "name", "") == "float64"
        }
    )
    if f64:
        yield (
            f"float64 values in the traced program ({len(f64)} distinct "
            f"avals, e.g. {f64[0]}) — x64 leaked into a TPU-targeted jit"
        )
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(eqn.invars[0].aval.dtype, "name", "")
        dst = getattr(eqn.outvars[0].aval.dtype, "name", "")
        if src not in _FLOAT_WIDTH or dst not in _FLOAT_WIDTH:
            continue
        if _FLOAT_WIDTH[dst] <= _FLOAT_WIDTH[src]:
            continue
        if dst == "float64":
            yield f"widening convert {src}->{dst} ({_aval_str(eqn.outvars[0].aval)})"
        elif audit_bf16 and src == "bfloat16":
            yield (
                f"bf16 upcast: convert {src}->{dst} "
                f"({_aval_str(eqn.outvars[0].aval)}) — audit whether this "
                "upcast is an intended fp32 island (moments/reductions)"
            )


def _check_sc002(closed: Any) -> Iterator[str]:
    hits: dict[str, int] = {}
    for eqn in iter_eqns(closed):
        if eqn.primitive.name in _HOST_PRIMS:
            hits[eqn.primitive.name] = hits.get(eqn.primitive.name, 0) + 1
    for name, count in sorted(hits.items()):
        yield f"{count}x `{name}` traced into the jit"


def _donated_flags(lowered: Any, closed: Any) -> list[bool]:
    """Donation flags aligned with the closed jaxpr's invars (flat arg
    order). Falls back to all-False when args_info is unavailable or the
    flattening disagrees with the jaxpr arity."""
    import jax

    try:
        leaves = jax.tree_util.tree_leaves(lowered.args_info)
        flags = [bool(getattr(info, "donated", False)) for info in leaves]
    except Exception:
        return [False] * len(closed.jaxpr.invars)
    if len(flags) != len(closed.jaxpr.invars):
        return [False] * len(closed.jaxpr.invars)
    return flags


def _check_sc003(closed: Any, donated: list[bool]) -> Iterator[str]:
    inner = closed.jaxpr
    if not any(donated):
        return
    used: set[int] = set()
    for eqn in iter_eqns(inner):
        for v in eqn.invars:
            if hasattr(v, "aval"):
                used.add(id(v))
    out_ids = [id(v) for v in inner.outvars if hasattr(v, "aval")]
    # greedy aval matching: every output reuses at most one donated buffer
    free_outputs: list[tuple[Any, Any]] = [
        (getattr(v.aval, "shape", None), getattr(v.aval, "dtype", None))
        for v in inner.outvars
        if hasattr(v, "aval")
    ]
    for i, (var, is_donated) in enumerate(zip(inner.invars, donated)):
        if not is_donated:
            continue
        alias_count = out_ids.count(id(var))
        if alias_count >= 2:
            yield (
                f"donated arg {i} ({_aval_str(var.aval)}) is returned as "
                f"{alias_count} outputs — one buffer cannot alias into both"
            )
            continue
        if id(var) not in used and alias_count == 0:
            yield (
                f"donated arg {i} ({_aval_str(var.aval)}) is dead: never "
                "read and never returned — the caller's buffer is "
                "invalidated for nothing"
            )
            continue
        key = (getattr(var.aval, "shape", None), getattr(var.aval, "dtype", None))
        if key in free_outputs:
            free_outputs.remove(key)  # claimed by this donation
        else:
            yield (
                f"donated arg {i} ({_aval_str(var.aval)}) has no "
                "shape/dtype-matching output left to reuse its buffer — "
                "XLA drops the alias silently"
            )


def _check_sc004(closed: Any) -> Iterator[str]:
    # top-level weak inputs: the registered example (and therefore the live
    # call site it mirrors) feeds a python scalar straight into the jit —
    # this is how sheepcheck caught ppo_decoupled's gae still taking raw
    # `args.gamma`/`args.gae_lambda` after PR 2 fixed coupled ppo
    for i, var in enumerate(closed.jaxpr.invars):
        aval = getattr(var, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            yield (
                f"jit input {i} is weak-typed ({_aval_str(aval)}) — the "
                "call site passes a python scalar; wrap it once as "
                "jnp.float32(...)"
            )
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params.get("jaxpr")
        if body is None:
            continue
        inner = getattr(body, "jaxpr", body)
        nc = int(eqn.params.get("num_consts", 0))
        nk = int(eqn.params.get("num_carry", 0))
        carry_in = inner.invars[nc : nc + nk]
        carry_out = inner.outvars[:nk]
        for i, vin in enumerate(carry_in):
            a_in = getattr(vin, "aval", None)
            a_out = getattr(carry_out[i], "aval", None) if i < len(carry_out) else None
            if a_in is not None and getattr(a_in, "weak_type", False):
                yield (
                    f"scan carry {i} is weak-typed ({_aval_str(a_in)}) — "
                    "initialize it with a concrete dtype"
                )
            elif (
                a_in is not None
                and a_out is not None
                and (
                    getattr(a_in, "dtype", None) != getattr(a_out, "dtype", None)
                    or getattr(a_in, "shape", None) != getattr(a_out, "shape", None)
                )
            ):
                yield (
                    f"scan carry {i} is unstable: in {_aval_str(a_in)} vs "
                    f"out {_aval_str(a_out)}"
                )


def _check_sc005(closed: Any) -> Iterator[str]:
    from ..compile.partition import compile_budget_s, predicted_cpu_compile_seconds

    convs = [e for e in iter_eqns(closed) if e.primitive.name == "conv_general_dilated"]
    if not convs:
        return
    batch = 1
    grad_convs = 0
    for eqn in convs:
        lhs_dil = eqn.params.get("lhs_dilation") or ()
        if any(d > 1 for d in lhs_dil):
            grad_convs += 1
        dn = eqn.params.get("dimension_numbers")
        lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
        bdim = dn.lhs_spec[0] if dn is not None else 0
        if lhs_shape:
            batch = max(batch, int(lhs_shape[bdim]))
    predicted = predicted_cpu_compile_seconds(len(convs), batch)
    budget = compile_budget_s()
    if (grad_convs and predicted > budget) or predicted > 10 * budget:
        yield (
            f"{len(convs)} convolutions ({grad_convs} gradient-class, "
            f"lhs-dilated) at batch {batch}: predictor says "
            f"{predicted:.0f}s on XLA:CPU (budget {budget:.0f}s) — the "
            "regime --split_update auto / --recon_chunk partition"
        )


def analyze_closed_jaxpr(
    closed: Any,
    *,
    algo: str = "<fixture>",
    name: str = "<jit>",
    donated: list[bool] | None = None,
    rules: set[str] | None = None,
    audit_bf16: bool = False,
) -> list[Finding]:
    """Run the SC analyzers over one ClosedJaxpr. `donated` is the per-flat-
    invar donation mask (from `Lowered.args_info`); fixture tests can pass
    it directly."""
    if donated is None:
        donated = [False] * len(closed.jaxpr.invars)
    checks: list[tuple[str, Iterable[str]]] = [
        ("SC001", _check_sc001(closed, audit_bf16)),
        ("SC002", _check_sc002(closed)),
        ("SC003", _check_sc003(closed, donated)),
        ("SC004", _check_sc004(closed)),
        ("SC005", _check_sc005(closed)),
    ]
    out: list[Finding] = []
    for rule_id, messages in checks:
        if rules is not None and rule_id not in rules:
            continue
        for message in messages:
            finding = Finding(SC_RULES[rule_id], algo, name, message)
            finding.suppressed = SUPPRESSIONS.get((algo, name, rule_id))
            out.append(finding)
    return out


# ---------------------------------------------------------------------------
# fingerprints + budget ledger
# ---------------------------------------------------------------------------


def _count_bf16_upcasts(closed: Any) -> int:
    """Number of bf16->f32 `convert_element_type` eqns in the program —
    the per-jit mixed-precision fingerprint. For an f32-only jit this is
    0; for a declared-bf16 jit it is exactly the committed fp32-island
    count the audit gate (`--gate-bf16` / check_budget) enforces."""
    count = 0
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(eqn.invars[0].aval.dtype, "name", "")
        dst = getattr(eqn.outvars[0].aval.dtype, "name", "")
        if src == "bfloat16" and dst == "float32":
            count += 1
    return count


def _count_int8_ops(closed: Any) -> int:
    """Number of eqns touching an int8 aval (invars or outvars) — the
    per-jit quantization fingerprint. For an unquantized jit this is 0;
    for a `--quant int8` serving rung it counts the quantize / int8
    dot_general / dequantize chain, and the budget gate treats a SHRINK
    as lost quantization coverage (a rung silently serving full-width
    again) the same way the bf16 gate treats lost bfloat16."""
    count = 0
    for eqn in iter_eqns(closed):
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            if getattr(getattr(aval, "dtype", None), "name", "") == "int8":
                count += 1
                break
    return count


def fingerprint_jaxpr(closed: Any, lowered: Any = None) -> dict:
    """The compile-cost fingerprint of one jit: what the budget ledger
    commits and the CI drift gate compares."""
    prims: dict[str, int] = {}
    op_count = 0
    for eqn in iter_eqns(closed):
        op_count += 1
        prims[eqn.primitive.name] = prims.get(eqn.primitive.name, 0) + 1
    dtypes = sorted(
        {
            getattr(getattr(a, "dtype", None), "name", "")
            for a in _all_avals(closed)
        }
        - {""}
    )
    fp: dict[str, Any] = {
        "in_avals": [_aval_str(v.aval) for v in closed.jaxpr.invars],
        "out_avals": [_aval_str(v.aval) for v in closed.jaxpr.outvars],
        "op_count": op_count,
        "primitives": dict(sorted(prims.items())),
        "dtypes": dtypes,
        # the DECLARED fp32 islands of a mixed-precision jit: every
        # committed bf16->f32 convert is an intended loss/logit/moment
        # boundary; the gate fails when a derived program exceeds this
        # count (a new SILENT upcast) — see check_budget
        "bf16_upcasts": _count_bf16_upcasts(closed),
        # the committed quantization coverage of an int8 serving rung:
        # check_budget fails a declared-int8 jit whose count shrinks (a
        # dequantized layer serving full-width under the int8 flag)
        "int8_ops": _count_int8_ops(closed),
        "donated": 0,
        "flops": None,
        "bytes_accessed": None,
    }
    if lowered is not None:
        donated = _donated_flags(lowered, closed)
        fp["donated"] = int(sum(donated))
        try:
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost:
                flops = cost.get("flops")
                touched = cost.get("bytes accessed")
                fp["flops"] = None if flops is None else round(float(flops), 1)
                fp["bytes_accessed"] = (
                    None if touched is None else round(float(touched), 1)
                )
        # sheeplint: disable=SL012 — cost model missing on this backend is an
        # expected configuration, not a failure; the fingerprint stays valid
        except Exception:
            pass  # cost model unavailable on this backend: fingerprint without it
    return fp


def analyze_entry(
    algo: str,
    entry: Any,
    rules: set[str] | None = None,
    audit_bf16: bool = False,
) -> JitReport:
    """Abstract-eval one CompilePlan entry (fn + example thunk) and analyze
    it. No execution: `trace` + `lower` only."""
    from ..compile.plan import avals_of

    report = JitReport(algo=algo, name=entry.name)
    fn, example = entry.fn, entry.example
    if example is None:
        report.error = "no example thunk (registered for timing only)"
        return report
    if not hasattr(fn, "trace") or not hasattr(fn, "lower"):
        report.error = "not traceable (wrapped callable without .trace/.lower)"
        return report
    try:
        specs = avals_of(example())
        traced = fn.trace(*specs)
        closed = traced.jaxpr
        lowered = traced.lower()
    except Exception as err:
        report.error = f"trace failed: {type(err).__name__}: {err}"[:300]
        return report
    report.fingerprint = fingerprint_jaxpr(closed, lowered)
    report.findings = analyze_closed_jaxpr(
        closed,
        algo=algo,
        name=entry.name,
        donated=_donated_flags(lowered, closed),
        rules=rules,
        audit_bf16=audit_bf16,
    )
    return report


def build_budget(reports: list[JitReport], op_count_frac: float = 0.25) -> dict:
    """The committed ledger: per-jit fingerprints + the drift tolerances
    they are gated with."""
    import jax

    return {
        "version": 1,
        "jax_version": jax.__version__,
        "tolerance": {"op_count_frac": op_count_frac},
        "jits": {
            f"{r.algo}/{r.name}": r.fingerprint
            for r in reports
            if r.fingerprint is not None
        },
    }


def check_budget(ledger: dict, derived: dict) -> tuple[list[str], list[str]]:
    """Compare a freshly derived budget against the committed ledger.

    Returns `(failures, notes)`. Failures are the ISSUE-gated drift classes
    — added/removed jits, new dtypes, op-count growth past tolerance, lost
    donations. Improvements (shrinking op counts, new donations) and
    primitive-mix changes are notes: visible, not blocking, and a prompt to
    refresh the ledger with `--update-budget`."""
    failures: list[str] = []
    notes: list[str] = []
    tol = float(ledger.get("tolerance", {}).get("op_count_frac", 0.25))
    old, new = ledger.get("jits", {}), derived.get("jits", {})
    for key in sorted(set(old) - set(new)):
        failures.append(f"{key}: jit disappeared from the plan (ledger has it)")
    for key in sorted(set(new) - set(old)):
        failures.append(f"{key}: new jit not in the ledger")
    for key in sorted(set(old) & set(new)):
        o, n = old[key], new[key]
        new_dtypes = sorted(set(n.get("dtypes", [])) - set(o.get("dtypes", [])))
        if new_dtypes:
            failures.append(f"{key}: new dtypes {new_dtypes}")
        # mixed-precision drift (ISSUE 9): a jit whose ledger entry declares
        # bf16 compute must keep it — losing bfloat16 from the dtype set is
        # a silent full-width regression, and growing the bf16->f32 convert
        # count beyond the committed fp32 islands is a silent upcast
        if "bfloat16" in o.get("dtypes", []):
            if "bfloat16" not in n.get("dtypes", []):
                failures.append(
                    f"{key}: declared-bf16 jit lost its bfloat16 compute "
                    "(silently upcast to full width)"
                )
            ou = o.get("bf16_upcasts")
            nu = n.get("bf16_upcasts")
            if ou is not None and nu is not None:
                if int(nu) > int(ou):
                    failures.append(
                        f"{key}: bf16->f32 upcasts grew {ou} -> {nu} — "
                        "undeclared fp32 island(s) inside a declared-bf16 "
                        "jit (audit with tools/sheepcheck.py --audit-bf16, "
                        "then --update-budget if intended)"
                    )
                elif int(nu) < int(ou):
                    notes.append(
                        f"{key}: bf16 upcasts shrank {ou} -> {nu} — refresh "
                        "the ledger"
                    )
        # quantization drift (ISSUE 20): a jit whose ledger entry declares
        # int8 compute (the `@int8` serving twins) must keep it — losing
        # int8 from the dtype set, or shrinking the int8-op count, means a
        # quantized rung silently serves full-width math again under the
        # int8 flag. Growth is a note: MORE quantized coverage is an
        # improvement that wants a ledger refresh, not a block.
        if "int8" in o.get("dtypes", []):
            if "int8" not in n.get("dtypes", []):
                failures.append(
                    f"{key}: declared-int8 jit lost its int8 compute "
                    "(quantized rung silently dequantized to full width)"
                )
            oi = o.get("int8_ops")
            ni = n.get("int8_ops")
            if oi is not None and ni is not None:
                if int(ni) < int(oi):
                    failures.append(
                        f"{key}: int8 ops shrank {oi} -> {ni} — lost "
                        "quantization coverage inside a declared-int8 jit "
                        "(re-run the capture, then --update-budget if "
                        "intended)"
                    )
                elif int(ni) > int(oi):
                    notes.append(
                        f"{key}: int8 ops grew {oi} -> {ni} — refresh the "
                        "ledger"
                    )
        oc, nc = int(o.get("op_count", 0)), int(n.get("op_count", 0))
        if nc > oc * (1.0 + tol):
            failures.append(
                f"{key}: op count grew {oc} -> {nc} "
                f"(+{(nc - oc) / max(oc, 1):.0%}, tolerance {tol:.0%})"
            )
        elif nc < oc * (1.0 - tol):
            notes.append(
                f"{key}: op count shrank {oc} -> {nc} — refresh the ledger"
            )
        od, nd = int(o.get("donated", 0)), int(n.get("donated", 0))
        if nd < od:
            failures.append(f"{key}: lost donations ({od} -> {nd})")
        elif nd > od:
            notes.append(f"{key}: gained donations ({od} -> {nd})")
        if o.get("primitives") != n.get("primitives") and not (
            new_dtypes or nc > oc * (1.0 + tol)
        ):
            changed = {
                p
                for p in set(o.get("primitives", {})) ^ set(n.get("primitives", {}))
            }
            if changed:
                notes.append(
                    f"{key}: primitive mix changed ({sorted(changed)[:6]})"
                )
    return failures, notes


# ---------------------------------------------------------------------------
# capture driver: instantiate a main's CompilePlan without running it
# ---------------------------------------------------------------------------

_DREAMER_TINY = [
    "--env_id", "discrete_dummy",
    "--num_envs", "1",
    "--sync_env",
    "--dry_run",
    "--per_rank_batch_size", "2",
    "--per_rank_sequence_length", "8",
    "--buffer_size", "64",
    "--learning_starts", "0",
    "--train_every", "1",
    "--horizon", "4",
    "--dense_units", "8",
    "--cnn_channels_multiplier", "2",
    "--recurrent_state_size", "8",
    "--hidden_size", "8",
    "--stochastic_size", "4",
    "--mlp_layers", "1",
    "--cnn_keys", "rgb",
]

_SAC_TINY = [
    "--env_id", "Pendulum-v1",
    "--num_envs", "1",
    "--sync_env",
    "--dry_run",
    "--per_rank_batch_size", "4",
    "--buffer_size", "16",
    "--learning_starts", "0",
    "--gradient_steps", "1",
    "--actor_hidden_size", "16",
    "--critic_hidden_size", "16",
]

# The shape-capture argv per algo main: tiny widths, dummy/classic-control
# envs, single data device (decoupled topologies need 2: player + trainer
# sub-meshes). These define the avals the committed budget.json fingerprints
# are derived at — change them and the ledger must be refreshed.
CAPTURE_ARGV: dict[str, list[str]] = {
    "ppo": [
        "--env_id", "discrete_dummy",
        "--num_envs", "1",
        "--sync_env",
        "--dry_run",
        "--num_devices", "1",
        "--rollout_steps", "8",
        "--per_rank_batch_size", "4",
        "--update_epochs", "1",
        "--dense_units", "8",
        "--mlp_layers", "1",
        "--actor_hidden_size", "8",
        "--critic_hidden_size", "8",
        "--cnn_channels_multiplier", "1",
        "--cnn_features_dim", "16",
        "--mlp_features_dim", "16",
    ],
    "ppo_decoupled": [
        "--env_id", "CartPole-v1",
        "--num_envs", "1",
        "--sync_env",
        "--dry_run",
        "--num_devices", "2",
        "--rollout_steps", "8",
        "--per_rank_batch_size", "4",
        "--update_epochs", "1",
        "--dense_units", "8",
        "--mlp_layers", "1",
        "--actor_hidden_size", "8",
        "--critic_hidden_size", "8",
    ],
    "ppo_recurrent": [
        "--env_id", "CartPole-v1",
        "--num_envs", "2",
        "--sync_env",
        "--dry_run",
        "--num_devices", "1",
        "--rollout_steps", "8",
        "--per_rank_batch_size", "4",
        "--per_rank_num_batches", "2",
        "--update_epochs", "2",
        "--dense_units", "8",
        "--mlp_layers", "1",
    ],
    "ppo_bd": [
        "--env_id", "TokenTask-v0",
        "--num_envs", "4",
        "--sync_env",
        "--dry_run",
        "--hidden_size", "32",
        "--head_dim", "8",
        "--num_hidden_layers", "1",
        "--moe_intermediate_size", "16",
    ],
    "sac": ["--num_devices", "1", *_SAC_TINY],
    "sac_decoupled": ["--num_devices", "2", *_SAC_TINY],
    "droq": ["--num_devices", "1", *_SAC_TINY],
    "sac_ae": [
        "--env_id", "continuous_dummy",
        "--num_envs", "1",
        "--sync_env",
        "--dry_run",
        "--num_devices", "1",
        "--per_rank_batch_size", "2",
        "--buffer_size", "8",
        "--learning_starts", "0",
        "--gradient_steps", "1",
        "--actor_hidden_size", "16",
        "--critic_hidden_size", "16",
        "--features_dim", "16",
        "--dense_units", "8",
        "--mlp_layers", "1",
        "--cnn_channels_multiplier", "1",
    ],
    "dreamer_v1": ["--num_devices", "1", *_DREAMER_TINY],
    "dreamer_v2": ["--num_devices", "1", *_DREAMER_TINY, "--discrete_size", "4"],
    "dreamer_v3": ["--num_devices", "1", *_DREAMER_TINY, "--discrete_size", "4"],
    "dreamer_v3_decoupled": [
        "--num_devices", "2", *_DREAMER_TINY, "--discrete_size", "4",
    ],
    "p2e_dv1": ["--num_devices", "1", *_DREAMER_TINY],
    "p2e_dv2": ["--num_devices", "1", *_DREAMER_TINY, "--discrete_size", "4"],
    # serving tier (ISSUE 15): one fixed-shape policy jit per batch-ladder
    # rung (`serve/policy_b{1,2,4}`); the checkpoint-free --model_argv init
    # builds the same tiny SAC the `sac` spec captures. The ledger's
    # argument/peak bytes per rung are what `serve/ladder.py` scales to
    # size production ladders without trial compiles.
    "serve": [
        "--algo", "sac",
        "--max_batch", "4",
        "--model_argv",
        "--env_id Pendulum-v1 --actor_hidden_size 16 --critic_hidden_size 16",
    ],
}

# Named capture VARIANTS: flag combinations of the same mains that register
# ADDITIONAL jits the default argv never builds — the PR-6 Anakin path
# (`--env_backend jax`), whose fully-jitted rollout collector is exactly
# the kind of program sheepcheck exists for, and since ISSUE 9 one
# `<algo>@bf16` variant PER MAIN (`--precision bfloat16`): the same jits
# traced under the mixed-precision policy, whose committed fingerprints
# (dtype set incl. bfloat16 + the `bf16_upcasts` fp32-island count) are
# what the bf16 half of check_budget and `--gate-bf16` enforce. Variant
# argv is APPENDED to the base algo's CAPTURE_ARGV (later flags win), and
# reports/ledger keys use the variant name (`ppo@anakin/anakin_rollout`).
_BF16 = ["--precision", "bfloat16"]

CAPTURE_VARIANTS: dict[str, tuple[str, list[str]]] = {
    "ppo@anakin": ("ppo", ["--env_backend", "jax", "--env_id", "CartPole-v1"]),
    "dreamer_v3@anakin": (
        "dreamer_v3",
        ["--env_backend", "jax", "--env_id", "pixeltoy"],
    ),
    # the DV3 player ladder: recurrent PlayerState in, mode actions out —
    # same serve main, dreamer_v3 policy family at _DREAMER_TINY widths
    "dreamer_v3@serve": (
        "serve",
        [
            "--algo", "dreamer_v3",
            "--model_argv",
            "--env_id discrete_dummy --cnn_keys rgb --dense_units 8 "
            "--cnn_channels_multiplier 2 --recurrent_state_size 8 "
            "--hidden_size 8 --stochastic_size 4 --discrete_size 4 "
            "--mlp_layers 1",
        ],
    ),
    # serve takes precision through the nested --model_argv (ServeArgs has
    # no --precision of its own): the whole string re-specifies last-wins,
    # and policies.py threads targs.precision into both policy builds
    "serve@bf16": (
        "serve",
        [
            "--model_argv",
            "--env_id Pendulum-v1 --actor_hidden_size 16 "
            "--critic_hidden_size 16 --precision bfloat16",
        ],
    ),
    **{f"{algo}@bf16": (algo, list(_BF16)) for algo in (
        "ppo",
        "ppo_decoupled",
        "ppo_recurrent",
        "ppo_bd",
        "sac",
        "sac_decoupled",
        "droq",
        "sac_ae",
        "dreamer_v1",
        "dreamer_v2",
        "dreamer_v3",
        "dreamer_v3_decoupled",
        "p2e_dv1",
        "p2e_dv2",
    )},
    # the ISSUE 20 quantized twins: same serve mains under `--quant int8`
    # (capture mode quantizes the checkpoint-free init and registers the
    # int8 step for every rung — no timed acceptance), so the committed
    # fingerprints carry the int8 dtype + `int8_ops` coverage count the
    # int8 half of check_budget enforces, and sheepmem can pair each
    # rung's argument bytes against its full-width twin
    "serve@int8": ("serve", ["--quant", "int8"]),
}
# dreamer_v3@serve@int8 composes the DV3 player-ladder variant's argv with
# the quant flag (the dict literal can't self-reference its own entries)
CAPTURE_VARIANTS["dreamer_v3@serve@int8"] = (
    CAPTURE_VARIANTS["dreamer_v3@serve"][0],
    [*CAPTURE_VARIANTS["dreamer_v3@serve"][1], "--quant", "int8"],
)


def declares_bf16(fingerprint: dict) -> bool:
    """True when a ledger entry declares bf16 compute (the `--gate-bf16`
    population: its upcast count is enforced, f32-only jits stay
    audit-only)."""
    return "bfloat16" in (fingerprint or {}).get("dtypes", [])


def declares_int8(fingerprint: dict) -> bool:
    """True when a ledger entry declares int8 compute (the `@int8` serving
    twins: check_budget enforces their dtype set and int8-op count, and
    sheepmem pairs their argument bytes against the full-width twin)."""
    return "int8" in (fingerprint or {}).get("dtypes", [])


def resolve_capture(spec: str) -> tuple[str, list[str]]:
    """Map a capture spec (an algo name or a CAPTURE_VARIANTS key) to the
    `(algo, extra_argv)` pair `capture_plan` consumes."""
    if spec in CAPTURE_VARIANTS:
        return CAPTURE_VARIANTS[spec]
    return spec, []


def _compose_base(base: list[str], extra: list[str]) -> list[str]:
    """`later flags win` for BOOL pairs too: argparse makes `--x`/`--no_x`
    mutually exclusive within one argv, so a variant that flips a base
    bool (e.g. the @remat twins' `--no_dry_run` over _DREAMER_TINY's
    `--dry_run`) must DROP the base token rather than append its negation
    after it. Only standalone flags (no following value token) are
    dropped — value-bearing flags already compose by last-wins."""
    negations = {f"--{t[5:]}" for t in extra if t.startswith("--no_")}
    negations |= {
        f"--no_{t[2:]}"
        for t in extra
        if t.startswith("--") and not t.startswith("--no_")
    }
    out: list[str] = []
    i = 0
    while i < len(base):
        tok = base[i]
        standalone = not (
            i + 1 < len(base) and not str(base[i + 1]).startswith("--")
        )
        if tok.startswith("--") and tok in negations and standalone:
            i += 1
            continue
        out.append(tok)
        i += 1
    return out


def capture_plan(algo: str, root_dir: str, extra_argv: list[str] | None = None):
    """Run `algo`'s main in capture mode and return its CompilePlan.

    Sets `SHEEPRL_TPU_PLAN_MODE=capture` (CompilePlan.start() raises
    CaptureComplete before the first collection step) and
    `SHEEPRL_TPU_DONATE=1` (donation metadata must survive into the
    lowering for SC003/the donation fingerprint even when the caller's
    environment carries the =0 kill switch)."""
    import sheeprl_tpu.algos  # noqa: F401 — fire @register_algorithm decorators
    from sheeprl_tpu.utils.registry import tasks

    from ..compile.plan import CaptureComplete

    if algo not in tasks:
        raise KeyError(f"unknown algo {algo!r}; registered: {sorted(tasks)}")
    argv = [
        *_compose_base(CAPTURE_ARGV.get(algo, []), extra_argv or []),
        "--platform", "cpu",
        "--root_dir", root_dir,
        "--run_name", f"sheepcheck_{algo}",
        *(extra_argv or []),
    ]
    saved = {
        k: os.environ.get(k) for k in ("SHEEPRL_TPU_PLAN_MODE", "SHEEPRL_TPU_DONATE")
    }
    os.environ["SHEEPRL_TPU_PLAN_MODE"] = "capture"
    os.environ["SHEEPRL_TPU_DONATE"] = "1"
    try:
        tasks[algo](argv)
    except CaptureComplete as done:
        return done.plan
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    raise RuntimeError(
        f"{algo}: main returned without calling plan.start() — no plan captured"
    )


def analyze_plan(
    algo: str,
    plan: Any,
    rules: set[str] | None = None,
    audit_bf16: bool = False,
) -> list[JitReport]:
    return [
        analyze_entry(algo, entry, rules=rules, audit_bf16=audit_bf16)
        for entry in plan._entries
    ]


# ---------------------------------------------------------------------------
# ledger persistence: per-algo dir layout (+ legacy single-blob reading)
# ---------------------------------------------------------------------------
#
# The ledger lives in `analysis/budget/` as one file per algo/variant spec
# (`ppo.json`, `ppo@anakin.json`, ...) plus `_meta.json` (version,
# jax_version, tolerances) — deterministic key order, one jit per block, so
# a PR's ledger diff reads as "which jits of which algo changed". Each spec
# file can hold several SECTIONS: `jits` (sheepcheck's compile-cost
# fingerprints), `comms` and `edges` (sheepshard's collective/contract
# fingerprints), and `memory` (sheepmem's buffer-lifetime fingerprints);
# savers only rewrite their own sections. The pre-split single-blob
# `analysis/budget.json` is NO LONGER readable (the PR-8 "one release"
# grace period is over): a blob path without the dir layout raises with a
# pointer at the migration, instead of silently gating against stale data.

_LEDGER_SECTIONS = ("jits", "comms", "edges", "memory")


def budget_dir_of(path: str) -> str:
    """Map a ledger path to its dir-layout root: `analysis/budget.json` ->
    `analysis/budget`; a dir path passes through."""
    if os.path.isdir(path):
        return path
    root, ext = os.path.splitext(path)
    return root if ext == ".json" else path


def budget_exists(path: str) -> bool:
    return os.path.isdir(budget_dir_of(path)) or os.path.exists(path)


def load_budget(path: str) -> dict:
    """Read the ledger in the per-algo dir layout. Empty sections are
    dropped so a jits-only ledger round-trips exactly. A legacy pre-split
    single-blob `budget.json` (without the dir next to it) is an ERROR —
    rebuild the dir layout rather than gating against stale data."""
    d = budget_dir_of(path)
    if not os.path.isdir(d):
        if os.path.exists(path):
            raise RuntimeError(
                f"{path} is a legacy single-blob budget ledger; the blob "
                "reader was removed (ISSUE 11). The ledger lives in the "
                f"per-algo dir layout now ({d}/_meta.json + one "
                "<spec>.json per algo/variant) — re-run "
                "`tools/sheepcheck.py --update-budget`, "
                "`tools/sheepshard.py --update-budget` and "
                "`tools/sheepmem.py --update-budget` to rebuild it, then "
                "delete the blob."
            )
        raise FileNotFoundError(f"no budget ledger dir at {d}")
    out: dict = {section: {} for section in _LEDGER_SECTIONS}
    meta_path = os.path.join(d, "_meta.json")
    if os.path.exists(meta_path):
        with open(meta_path, encoding="utf-8") as fh:
            out.update(json.load(fh))
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json") or name == "_meta.json":
            continue
        with open(os.path.join(d, name), encoding="utf-8") as fh:
            blob = json.load(fh)
        for section in _LEDGER_SECTIONS:
            out[section].update(blob.get(section, {}))
    for section in _LEDGER_SECTIONS:
        if not out.get(section):
            out.pop(section, None)
    return out


def save_budget(
    budget: dict, path: str, sections: tuple[str, ...] = ("jits",)
) -> None:
    """Write `budget` in the per-algo dir layout. Only `sections` are
    rewritten — and they are rewritten COMPLETELY: a spec file whose
    entries vanished from `budget` has that section stripped (callers
    doing partial sweeps merge into the loaded ledger first). Other
    sections in the files, and a legacy blob at `path`, are left alone."""
    d = budget_dir_of(path)
    os.makedirs(d, exist_ok=True)
    meta_path = os.path.join(d, "_meta.json")
    meta: dict = {}
    if os.path.exists(meta_path):
        with open(meta_path, encoding="utf-8") as fh:
            meta = json.load(fh)
    tol = dict(meta.get("tolerance", {}))
    tol.update(budget.get("tolerance", {}))
    meta.update({k: budget[k] for k in ("version", "jax_version") if k in budget})
    if tol:
        meta["tolerance"] = tol
    _write_json(meta, meta_path)
    by_spec: dict[str, dict[str, dict]] = {}
    for section in sections:
        for key, val in budget.get(section, {}).items():
            spec = key.split("/", 1)[0]
            by_spec.setdefault(spec, {}).setdefault(section, {})[key] = val
    existing = {
        name[: -len(".json")]
        for name in os.listdir(d)
        if name.endswith(".json") and name != "_meta.json"
    }
    for spec in sorted(existing | set(by_spec)):
        spec_path = os.path.join(d, f"{spec}.json")
        blob: dict = {}
        if os.path.exists(spec_path):
            with open(spec_path, encoding="utf-8") as fh:
                blob = json.load(fh)
        changed = not os.path.exists(spec_path)
        for section in sections:
            had = blob.pop(section, None)
            new_sec = by_spec.get(spec, {}).get(section)
            if new_sec:
                blob[section] = new_sec
            changed = changed or new_sec != had
        if not changed:
            # untouched managed sections: leave the file byte-identical —
            # a spec file carrying only a foreign section (e.g. sheepsync's
            # `concurrency`) must survive a jits/memory sweep unrewritten
            continue
        if any(blob.get(section) for section in blob):
            _write_json(blob, spec_path)
        elif os.path.exists(spec_path):
            os.remove(spec_path)


def _write_json(obj: dict, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1, sort_keys=True)
        fh.write("\n")
