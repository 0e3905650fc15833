"""sheepcheck receipts (ISSUE 7 tentpole): each SC rule fires on a
known-bad fixture jit and stays silent on a clean control; fingerprints are
stable and the budget ledger's drift gate fails on an injected regression.

Fixtures trace REAL jaxprs (jit.trace at ShapeDtypeStruct avals — no
execution), so these tests prove the analyzers read the IR jax actually
produces, not a mock of it."""

import json

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.analysis import jaxpr_check as jc
from sheeprl_tpu.compile import avals_of, sds


def _trace(fn, *specs):
    traced = fn.trace(*specs)
    return traced.jaxpr, traced.lower()


def _rules_hit(findings):
    return {f.rule.id for f in findings}


# ---------------------------------------------------------------------------
# clean control
# ---------------------------------------------------------------------------


def test_clean_control_no_findings():
    @jax.jit
    def step(w, x):
        def body(c, _):
            c = jnp.tanh(c @ w)
            return c, c.sum()

        return jax.lax.scan(body, x, None, length=4)

    closed, lowered = _trace(
        step, sds((8, 8), jnp.float32), sds((4, 8), jnp.float32)
    )
    findings = jc.analyze_closed_jaxpr(
        closed, donated=jc._donated_flags(lowered, closed), audit_bf16=True
    )
    assert findings == []


# ---------------------------------------------------------------------------
# SC001 dtype promotion
# ---------------------------------------------------------------------------


def test_sc001_float64_leak():
    @jax.jit
    def f(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        closed, _ = _trace(f, sds((4,), jnp.float32))
    findings = jc.analyze_closed_jaxpr(closed)
    assert "SC001" in _rules_hit(findings)
    msgs = " ".join(f.message for f in findings)
    assert "float64" in msgs


def test_sc001_bf16_upcast_only_under_audit():
    @jax.jit
    def f(x):
        h = x.astype(jnp.bfloat16)
        return (h @ h.T).astype(jnp.float32)  # the silent full-width island

    closed, _ = _trace(f, sds((4, 4), jnp.float32))
    assert "SC001" not in _rules_hit(jc.analyze_closed_jaxpr(closed))
    audited = jc.analyze_closed_jaxpr(closed, audit_bf16=True)
    assert "SC001" in _rules_hit(audited)
    assert any("bf16 upcast" in f.message for f in audited)


# ---------------------------------------------------------------------------
# SC002 host callbacks
# ---------------------------------------------------------------------------


def test_sc002_debug_print_in_scan():
    @jax.jit
    def rollout(x):
        def body(c, _):
            jax.debug.print("c = {c}", c=c.sum())
            return c + 1.0, c.sum()

        return jax.lax.scan(body, x, None, length=8)

    closed, _ = _trace(rollout, sds((4,), jnp.float32))
    findings = jc.analyze_closed_jaxpr(closed)
    assert "SC002" in _rules_hit(findings)


def test_sc002_pure_callback():
    import numpy as np

    @jax.jit
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((4,), jnp.float32), x
        )

    closed, _ = _trace(f, sds((4,), jnp.float32))
    assert "SC002" in _rules_hit(jc.analyze_closed_jaxpr(closed))


# ---------------------------------------------------------------------------
# SC003 donation hazards
# ---------------------------------------------------------------------------


def test_sc003_dead_donation():
    # arg 0 donated but never read and never returned
    def f(dead, x):
        return x * 2.0

    jf = jax.jit(f, donate_argnums=0)
    closed, lowered = _trace(jf, sds((8,), jnp.float32), sds((8,), jnp.float32))
    findings = jc.analyze_closed_jaxpr(
        closed, donated=jc._donated_flags(lowered, closed)
    )
    assert "SC003" in _rules_hit(findings)
    assert any("dead" in f.message for f in findings)


def test_sc003_double_alias():
    def f(state):
        return state, state  # one donated buffer cannot back two outputs

    jf = jax.jit(f, donate_argnums=0)
    closed, lowered = _trace(jf, sds((8,), jnp.float32))
    findings = jc.analyze_closed_jaxpr(
        closed, donated=jc._donated_flags(lowered, closed)
    )
    assert "SC003" in _rules_hit(findings)


def test_sc003_no_matching_output():
    def f(big, x):
        return (big.sum() + x).astype(jnp.float32)  # no f32[64] output to reuse

    jf = jax.jit(f, donate_argnums=0)
    closed, lowered = _trace(jf, sds((64,), jnp.float32), sds((), jnp.float32))
    findings = jc.analyze_closed_jaxpr(
        closed, donated=jc._donated_flags(lowered, closed)
    )
    assert "SC003" in _rules_hit(findings)
    assert any("no shape/dtype-matching output" in f.message for f in findings)


def test_sc003_good_donation_clean():
    def f(state, g):
        return state - 0.1 * g  # classic state-in state-out reuse

    jf = jax.jit(f, donate_argnums=0)
    closed, lowered = _trace(jf, sds((8, 8), jnp.float32), sds((8, 8), jnp.float32))
    findings = jc.analyze_closed_jaxpr(
        closed, donated=jc._donated_flags(lowered, closed)
    )
    assert "SC003" not in _rules_hit(findings)


# ---------------------------------------------------------------------------
# SC004 scan-carry hazards
# ---------------------------------------------------------------------------


def test_sc004_weak_carry():
    @jax.jit
    def f(xs):
        def body(c, x):
            return c + x, c

        # init 0.0 is a python scalar: the carry aval is weak-typed
        return jax.lax.scan(body, 0.0, xs)

    closed, _ = _trace(f, sds((8,), jnp.float32))
    findings = jc.analyze_closed_jaxpr(closed)
    assert "SC004" in _rules_hit(findings)
    assert any("weak-typed" in f.message for f in findings)


def test_sc004_weak_jit_input():
    """The in-tree catch: a call site passing a raw python float (the
    ppo_decoupled gamma/lambda class) shows up as a weak-typed top-level
    input aval of the traced jit."""

    @jax.jit
    def gae(values, gamma):
        return values * gamma

    # tracing with a live python scalar reproduces the weak-typed aval a
    # raw-float call site creates
    closed = gae.trace(jnp.zeros((4,), jnp.float32), 0.99).jaxpr
    findings = jc.analyze_closed_jaxpr(closed)
    assert "SC004" in _rules_hit(findings)
    assert any("jit input" in f.message and "weak-typed" in f.message
               for f in findings)
    # the fixed call site (committed f32 scalar) is clean
    closed = gae.trace(jnp.zeros((4,), jnp.float32), jnp.float32(0.99)).jaxpr
    assert "SC004" not in _rules_hit(jc.analyze_closed_jaxpr(closed))


def test_sc004_concrete_carry_clean():
    @jax.jit
    def f(xs):
        def body(c, x):
            return c + x, c

        return jax.lax.scan(body, jnp.float32(0.0), xs)

    closed, _ = _trace(f, sds((8,), jnp.float32))
    assert "SC004" not in _rules_hit(jc.analyze_closed_jaxpr(closed))


# ---------------------------------------------------------------------------
# SC005 conv pathology
# ---------------------------------------------------------------------------


def _conv_tower(batch):
    """Forward+backward through a small transposed-conv decoder — the
    gradient convs carry lhs_dilation, the SC005 signature."""

    def loss(w, x):
        y = jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        return (y * y).mean()

    @jax.jit
    def update(w, x):
        return jax.grad(loss)(w, x)

    return update, (
        sds((3, 3, 4, 4), jnp.float32),
        sds((batch, 16, 16, 4), jnp.float32),
    )


def test_sc005_fires_above_threshold(monkeypatch):
    monkeypatch.setenv("SHEEPRL_TPU_COMPILE_BUDGET_S", "0.01")
    update, specs = _conv_tower(batch=64)
    closed, _ = _trace(update, *specs)
    findings = jc.analyze_closed_jaxpr(closed)
    assert "SC005" in _rules_hit(findings)


def test_sc005_silent_below_threshold(monkeypatch):
    monkeypatch.setenv("SHEEPRL_TPU_COMPILE_BUDGET_S", "100000")
    update, specs = _conv_tower(batch=2)
    closed, _ = _trace(update, *specs)
    assert "SC005" not in _rules_hit(jc.analyze_closed_jaxpr(closed))


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_requires_justification(monkeypatch):
    @jax.jit
    def f(xs):
        return jax.lax.scan(lambda c, x: (c + x, c), 0.0, xs)

    closed, _ = _trace(f, sds((8,), jnp.float32))
    monkeypatch.setitem(
        jc.SUPPRESSIONS, ("algoX", "jitY", "SC004"), "intentional weak carry"
    )
    findings = jc.analyze_closed_jaxpr(closed, algo="algoX", name="jitY")
    hits = [f for f in findings if f.rule.id == "SC004"]
    assert hits and all(f.suppressed == "intentional weak carry" for f in hits)
    # suppressed findings don't fail a report
    report = jc.JitReport(algo="algoX", name="jitY", findings=findings)
    assert not [f for f in report.failing if f.rule.id == "SC004"]


# ---------------------------------------------------------------------------
# fingerprints + budget ledger
# ---------------------------------------------------------------------------


def _fixture_reports():
    def f(state, g):
        return state - 0.1 * g

    jf = jax.jit(f, donate_argnums=0)
    closed, lowered = _trace(jf, sds((8, 8), jnp.float32), sds((8, 8), jnp.float32))
    fp = jc.fingerprint_jaxpr(closed, lowered)
    return [jc.JitReport(algo="algoX", name="train_step", fingerprint=fp)]


def test_fingerprint_contents():
    (report,) = _fixture_reports()
    fp = report.fingerprint
    assert fp["op_count"] >= 1
    assert fp["dtypes"] == ["float32"]
    assert fp["donated"] == 1
    assert sum(fp["primitives"].values()) == fp["op_count"]
    assert fp["in_avals"] == ["float32[8,8]", "float32[8,8]"]
    json.dumps(fp)  # the ledger must be committable as-is


def test_fingerprint_deterministic():
    a = _fixture_reports()[0].fingerprint
    b = _fixture_reports()[0].fingerprint
    assert a == b


def test_budget_round_trip_clean():
    reports = _fixture_reports()
    ledger = jc.build_budget(reports)
    failures, notes = jc.check_budget(ledger, jc.build_budget(reports))
    assert failures == [] and notes == []


def test_budget_drift_gate_fails_on_injected_regression():
    """The ISSUE acceptance receipt: perturb a committed fingerprint and the
    gate must fail — for each gated drift class."""
    reports = _fixture_reports()
    ledger = jc.build_budget(reports)

    bloated = json.loads(json.dumps(ledger))
    fp = bloated["jits"]["algoX/train_step"]
    fp["op_count"] = int(fp["op_count"] * 2 + 10)  # past the 25% tolerance
    failures, _ = jc.check_budget(ledger, bloated)
    assert any("op count grew" in f for f in failures)

    retyped = json.loads(json.dumps(ledger))
    retyped["jits"]["algoX/train_step"]["dtypes"].append("float64")
    failures, _ = jc.check_budget(ledger, retyped)
    assert any("new dtypes" in f and "float64" in f for f in failures)

    undonated = json.loads(json.dumps(ledger))
    undonated["jits"]["algoX/train_step"]["donated"] = 0
    failures, _ = jc.check_budget(ledger, undonated)
    assert any("lost donations" in f for f in failures)

    renamed = json.loads(json.dumps(ledger))
    renamed["jits"]["algoX/other_step"] = renamed["jits"].pop("algoX/train_step")
    failures, _ = jc.check_budget(ledger, renamed)
    assert any("disappeared" in f for f in failures)
    assert any("new jit" in f for f in failures)


def test_budget_improvements_are_notes_not_failures():
    reports = _fixture_reports()
    ledger = jc.build_budget(reports)
    improved = json.loads(json.dumps(ledger))
    fp = improved["jits"]["algoX/train_step"]
    fp["op_count"] = max(1, fp["op_count"] // 4)
    fp["donated"] = fp["donated"] + 1
    failures, notes = jc.check_budget(ledger, improved)
    assert failures == []
    assert any("shrank" in n for n in notes)
    assert any("gained donations" in n for n in notes)


def test_budget_save_load_round_trip(tmp_path):
    ledger = jc.build_budget(_fixture_reports())
    path = str(tmp_path / "budget.json")
    jc.save_budget(ledger, path)
    assert jc.load_budget(path) == ledger


# ---------------------------------------------------------------------------
# plan capture (end-to-end on the cheapest real main)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(600)
def test_capture_plan_sac_end_to_end(tmp_path):
    """The tentpole wiring: run a REAL algo main in capture mode — setup
    proceeds to plan.start(), CaptureComplete unwinds before any training,
    and every registered jit abstract-evals to an analyzable jaxpr with a
    fingerprint. Uses sac (the cheapest main to build)."""
    plan = jc.capture_plan("sac", str(tmp_path))
    assert plan.capture_only and plan._entries
    reports = jc.analyze_plan("sac", plan)
    analyzed = [r for r in reports if r.fingerprint is not None]
    assert analyzed, [r.error for r in reports]
    names = {r.name for r in reports}
    assert "train_step" in names
    for r in analyzed:
        assert r.fingerprint["op_count"] > 0
        assert r.failing == [], [f.format() for f in r.failing]


def test_capture_plan_unknown_algo():
    with pytest.raises(KeyError):
        jc.capture_plan("not_an_algo", "/tmp")


def test_capture_mode_register_returns_raw_fn():
    """In capture mode register() must hand the main back its own callable
    (no WarmJit wrapper) and start() must raise CaptureComplete."""
    import os

    from sheeprl_tpu.compile import CaptureComplete, CompilePlan

    os.environ["SHEEPRL_TPU_PLAN_MODE"] = "capture"
    try:

        class _Args:
            warm_compile = "on"

        plan = CompilePlan.from_args(_Args())
        assert plan.capture_only and not plan.enabled
        fn = jax.jit(lambda x: x + 1)
        out = plan.register("j", fn, example=lambda: (sds((2,), jnp.float32),))
        assert out is fn
        with pytest.raises(CaptureComplete) as exc:
            plan.start()
        assert exc.value.plan is plan
    finally:
        os.environ.pop("SHEEPRL_TPU_PLAN_MODE", None)


# =============================================================================
# bf16 mixed-precision gate (ISSUE 9)
# =============================================================================


def _bf16_ledger():
    """A hand-built ledger with one declared-bf16 jit and one f32-only jit."""
    return {
        "version": 1,
        "tolerance": {"op_count_frac": 0.25},
        "jits": {
            "algo@bf16/train_step": {
                "op_count": 40,
                "dtypes": ["bfloat16", "float32"],
                "bf16_upcasts": 5,
                "donated": 0,
                "primitives": {},
            },
            "algo/train_step": {
                "op_count": 40,
                "dtypes": ["float32"],
                "bf16_upcasts": 0,
                "donated": 0,
                "primitives": {},
            },
        },
    }


def test_bf16_gate_clean_on_identical_budget():
    ledger = _bf16_ledger()
    failures, notes = jc.check_budget(ledger, json.loads(json.dumps(ledger)))
    assert failures == [] and notes == []


def test_bf16_gate_fails_on_new_silent_upcast():
    ledger = _bf16_ledger()
    drifted = json.loads(json.dumps(ledger))
    drifted["jits"]["algo@bf16/train_step"]["bf16_upcasts"] = 7
    failures, _ = jc.check_budget(ledger, drifted)
    assert any("upcasts grew 5 -> 7" in f for f in failures)


def test_bf16_gate_fails_on_lost_bfloat16_compute():
    ledger = _bf16_ledger()
    drifted = json.loads(json.dumps(ledger))
    drifted["jits"]["algo@bf16/train_step"]["dtypes"] = ["float32"]
    drifted["jits"]["algo@bf16/train_step"]["bf16_upcasts"] = 0
    failures, _ = jc.check_budget(ledger, drifted)
    assert any("lost its bfloat16 compute" in f for f in failures)


def test_bf16_gate_shrink_is_a_note_and_f32_jits_exempt():
    ledger = _bf16_ledger()
    drifted = json.loads(json.dumps(ledger))
    # fewer upcasts in the declared jit: improvement, not failure
    drifted["jits"]["algo@bf16/train_step"]["bf16_upcasts"] = 3
    # an f32-only jit growing an upcast count is NOT gated (audit-only)
    drifted["jits"]["algo/train_step"]["bf16_upcasts"] = 2
    failures, notes = jc.check_budget(ledger, drifted)
    assert failures == []
    assert any("bf16 upcasts shrank" in n for n in notes)


def _int8_ledger():
    """One declared-int8 serving rung and its full-width twin (ISSUE 20)."""
    return {
        "version": 1,
        "tolerance": {"op_count_frac": 0.25},
        "jits": {
            "serve@int8/policy_b2": {
                "op_count": 80,
                "dtypes": ["float32", "int32", "int8"],
                "bf16_upcasts": 0,
                "int8_ops": 8,
                "donated": 0,
                "primitives": {},
            },
            "serve/policy_b2": {
                "op_count": 60,
                "dtypes": ["float32"],
                "bf16_upcasts": 0,
                "int8_ops": 0,
                "donated": 0,
                "primitives": {},
            },
        },
    }


def test_int8_gate_clean_on_identical_budget():
    ledger = _int8_ledger()
    failures, notes = jc.check_budget(ledger, json.loads(json.dumps(ledger)))
    assert failures == [] and notes == []


def test_int8_gate_fails_on_lost_int8_compute():
    ledger = _int8_ledger()
    drifted = json.loads(json.dumps(ledger))
    drifted["jits"]["serve@int8/policy_b2"]["dtypes"] = ["float32", "int32"]
    drifted["jits"]["serve@int8/policy_b2"]["int8_ops"] = 0
    failures, _ = jc.check_budget(ledger, drifted)
    assert any("lost its int8 compute" in f for f in failures)


def test_int8_gate_fails_on_shrunk_coverage_notes_growth():
    ledger = _int8_ledger()
    drifted = json.loads(json.dumps(ledger))
    # a dequantized layer: int8 dtype survives but the op coverage shrank
    drifted["jits"]["serve@int8/policy_b2"]["int8_ops"] = 5
    failures, _ = jc.check_budget(ledger, drifted)
    assert any("int8 ops shrank 8 -> 5" in f for f in failures)
    grown = json.loads(json.dumps(ledger))
    grown["jits"]["serve@int8/policy_b2"]["int8_ops"] = 11
    failures, notes = jc.check_budget(ledger, grown)
    assert failures == []
    assert any("int8 ops grew" in n for n in notes)


def test_int8_fingerprint_counts_quantized_eqns():
    """fingerprint_jaxpr's int8_ops: zero on an f32 program, positive on
    the quantized twin of the same math."""
    import numpy as np

    from sheeprl_tpu.ops import quant as q

    w = np.random.default_rng(0).standard_normal((6, 4)).astype(np.float32)
    s = jnp.ones((6,), jnp.float32) * 0.1
    ws = q.absmax_scale(jnp.asarray(w) * s[:, None], axis=0)
    wq = q.quantize(jnp.asarray(w) * s[:, None], ws)

    f32 = jax.jit(lambda x: x @ w).trace(
        jax.ShapeDtypeStruct((2, 6), jnp.float32)
    ).jaxpr
    int8 = jax.jit(lambda x: q.int8_linear(x, s, wq, ws, None)).trace(
        jax.ShapeDtypeStruct((2, 6), jnp.float32)
    ).jaxpr
    fp32 = jc.fingerprint_jaxpr(f32)
    fpq = jc.fingerprint_jaxpr(int8)
    assert fp32["int8_ops"] == 0 and not jc.declares_int8(fp32)
    assert fpq["int8_ops"] > 0 and jc.declares_int8(fpq)
    assert "int8" in fpq["dtypes"]


def test_declares_bf16_predicate():
    ledger = _bf16_ledger()
    assert jc.declares_bf16(ledger["jits"]["algo@bf16/train_step"])
    assert not jc.declares_bf16(ledger["jits"]["algo/train_step"])
    assert not jc.declares_bf16({})
    assert not jc.declares_bf16(None)


def test_bf16_capture_variants_cover_all_mains():
    """The @bf16 sweep is the gate's population: one variant per main."""
    import sheeprl_tpu.algos  # noqa: F401
    from sheeprl_tpu.utils.registry import tasks

    bf16_specs = {s for s in jc.CAPTURE_VARIANTS if s.endswith("@bf16")}
    assert {s.split("@")[0] for s in bf16_specs} == set(tasks)
    for spec in bf16_specs:
        algo, extra = jc.resolve_capture(spec)
        # serve has no top-level --precision; its variant re-specifies the
        # nested --model_argv with the flag appended (last-wins)
        if extra[-2:] == ["--precision", "bfloat16"]:
            continue
        assert extra[-2] == "--model_argv"
        assert extra[-1].split()[-2:] == ["--precision", "bfloat16"]


def test_fingerprint_counts_bf16_upcasts():
    import jax
    import jax.numpy as jnp

    def f(x):
        y = (x.astype(jnp.bfloat16) * 2).astype(jnp.float32)  # one upcast
        z = (x.astype(jnp.bfloat16) + 1).astype(jnp.float32)  # another
        return y + z

    closed = jax.jit(f).trace(jax.ShapeDtypeStruct((4,), jnp.float32)).jaxpr
    fp = jc.fingerprint_jaxpr(closed)
    assert fp["bf16_upcasts"] == 2
    assert "bfloat16" in fp["dtypes"]
