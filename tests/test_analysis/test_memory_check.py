"""sheepmem receipts (ISSUE 10 tentpole): each SC010-SC013 rule fires on a
known-bad fixture and stays silent on a clean control; the memory
fingerprint is deterministic and carries the realized-alias / embedded-
constant / scan-buffer structure the ledger commits; and the CI drift gate
fails on the injected regressions the ISSUE names (peak bloat, a lost
realized alias, a new large constant, a per-shard budget breach, a bf16
variant whose full-width activation bytes stop undercutting its f32 twin).

Fixture jits are lowered AND compiled on the conftest 8-virtual-CPU-device
harness — the analyzers read the optimized HLO and CompiledMemoryStats XLA
actually emits, not a mock of it."""

import functools
import json
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.analysis import jaxpr_check as jc
from sheeprl_tpu.analysis import memory_check as mc
from sheeprl_tpu.compile import sds


def _entry(name, fn, example):
    # analyze_entry only reads .name/.fn/.example — a namespace stands in
    # for a CompilePlan._Entry without the capture-mode env dance
    return SimpleNamespace(name=name, fn=fn, example=example)


def _rules_hit(report):
    return {f.rule.id for f in report.findings}


# ---------------------------------------------------------------------------
# clean control + fingerprint shape
# ---------------------------------------------------------------------------


def test_clean_control_donated_train_state():
    """The canonical state-in/state-out update with donation: the alias is
    realized, no findings, and the fingerprint is committable as-is."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, grads):
        return jax.tree_util.tree_map(lambda s, g: s - 0.1 * g, state, grads)

    ex = lambda: (  # noqa: E731
        sds((256, 4), jnp.float32), sds((256, 4), jnp.float32)
    )
    report = mc.analyze_entry("fix@clean", _entry("step", step, ex))
    assert report.error is None
    assert report.findings == [], [f.format() for f in report.findings]
    m = report.memory
    assert m["donated"] == 1
    assert m["aliases"] == ["out{}<-arg0"] or m["aliases"] == ["out{0}<-arg0"]
    assert m["argument_bytes"] == 2 * 256 * 4 * 4
    assert m["peak_bytes"] > 0
    assert m["declares_bf16"] is False
    json.dumps(m)  # the ledger must be committable as-is


def test_fingerprint_deterministic():
    @jax.jit
    def f(x):
        return jnp.tanh(x) * 2.0

    ex = lambda: (sds((64, 64), jnp.float32),)  # noqa: E731
    a = mc.analyze_entry("fix@det", _entry("f", f, ex)).memory
    b = mc.analyze_entry("fix@det", _entry("f", f, ex)).memory
    assert a == b


def test_entry_without_example_is_skipped():
    report = mc.analyze_entry("fix@skip", _entry("f", lambda x: x, None))
    assert report.error is not None and report.memory is None


# ---------------------------------------------------------------------------
# SC010: missed donation
# ---------------------------------------------------------------------------


def _sc010_fixture(donate: bool):
    jit = (
        functools.partial(jax.jit, donate_argnums=(0,)) if donate else jax.jit
    )

    @jit
    def step(state, lr):
        return jax.tree_util.tree_map(lambda s: s * (1.0 - lr), state)

    ex = lambda: (  # noqa: E731
        sds((512, 8), jnp.float32), sds((), jnp.float32)
    )
    return _entry("step", step, ex)


def test_sc010_undonated_matching_input_fires():
    report = mc.analyze_entry("fix@missed", _sc010_fixture(donate=False))
    assert "SC010" in _rules_hit(report)
    msgs = [f.message for f in report.findings if f.rule.id == "SC010"]
    assert any("not donated but byte-matches an output" in m for m in msgs)


def test_sc010_donated_control_is_clean():
    report = mc.analyze_entry("fix@missed", _sc010_fixture(donate=True))
    assert "SC010" not in _rules_hit(report)


def test_sc010_below_floor_is_silent(monkeypatch):
    monkeypatch.setenv("SHEEPRL_TPU_MEM_DONATION_FLOOR", str(1 << 20))
    report = mc.analyze_entry("fix@missed", _sc010_fixture(donate=False))
    assert "SC010" not in _rules_hit(report)


def test_sc010_suppression_carries_justification(monkeypatch):
    monkeypatch.setitem(
        mc.MEM_SUPPRESSIONS, ("fix@missed", "step", "SC010"), "caller re-reads"
    )
    report = mc.analyze_entry("fix@missed", _sc010_fixture(donate=False))
    hits = [f for f in report.findings if f.rule.id == "SC010"]
    assert hits and all(f.suppressed == "caller re-reads" for f in hits)
    assert report.failing == []


# ---------------------------------------------------------------------------
# SC011: declared donation XLA dropped (realized-alias receipt)
# ---------------------------------------------------------------------------


def test_sc011_dropped_donation_fires():
    """Donate an argument no output can alias (byte-width change — a
    same-width dtype change IS aliased by the installed XLA): the jaxpr
    screen (SC003) flags intent, and SC011 proves from the EXECUTABLE that
    XLA realized no alias."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(x):
        return x.astype(jnp.bfloat16)

    ex = lambda: (sds((1024,), jnp.float32),)  # noqa: E731
    report = mc.analyze_entry("fix@dropped", _entry("step", step, ex))
    assert "SC011" in _rules_hit(report)
    msg = [f for f in report.findings if f.rule.id == "SC011"][0].message
    assert "NO realized input_output_alias" in msg
    assert report.memory["aliases"] == []
    assert report.memory["donated"] == 1


def test_sc011_realized_donation_control_is_clean():
    report = mc.analyze_entry("fix@dropped", _sc010_fixture(donate=True))
    assert "SC011" not in _rules_hit(report)
    assert len(report.memory["aliases"]) == 1


# ---------------------------------------------------------------------------
# SC012: executable-embedded constants
# ---------------------------------------------------------------------------

# random data: an arange would be strength-reduced to an iota by XLA and
# embed nothing — the closure must stay a real 128 KiB literal
_BIG_TABLE = jnp.asarray(
    np.random.RandomState(0).randn(32 * 1024).astype(np.float32)
)


def test_sc012_embedded_constant_fires():
    @jax.jit
    def step(x):
        return x + _BIG_TABLE

    ex = lambda: (sds((32 * 1024,), jnp.float32),)  # noqa: E731
    report = mc.analyze_entry("fix@const", _entry("step", step, ex))
    assert "SC012" in _rules_hit(report)
    assert report.memory["constant_bytes"] >= 128 * 1024
    assert any("f32[32768]" in c for c in report.memory["large_constants"])
    msg = [f for f in report.findings if f.rule.id == "SC012"][0].message
    assert "baked into" in msg


def test_sc012_argument_not_constant_is_clean():
    """The fix the rule prescribes: pass the table as an argument."""

    @jax.jit
    def step(x, table):
        return x + table

    ex = lambda: (  # noqa: E731
        sds((32 * 1024,), jnp.float32), sds((32 * 1024,), jnp.float32)
    )
    report = mc.analyze_entry("fix@const", _entry("step", step, ex))
    assert "SC012" not in _rules_hit(report)
    assert report.memory["large_constants"] == []


# ---------------------------------------------------------------------------
# SC013: per-shard peak over budget (mesh-bearing only)
# ---------------------------------------------------------------------------


def _mesh_fixture():
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    row = NamedSharding(mesh, P("data"))

    @jax.jit
    def step(x):
        return jnp.tanh(x * 2.0)

    ex = lambda: (sds((8, 4096), jnp.float32, row),)  # noqa: E731
    return _entry("step", step, ex)


def test_sc013_budget_breach_fires(monkeypatch):
    monkeypatch.setenv("SHEEPRL_TPU_MEM_PEAK_BUDGET_MB", "0")
    report = mc.analyze_entry("fix@mesh", _mesh_fixture())
    assert report.memory["num_partitions"] == 8
    assert "SC013" in _rules_hit(report)


def test_sc013_within_budget_and_single_device_silent(monkeypatch):
    report = mc.analyze_entry("fix@mesh", _mesh_fixture())
    assert "SC013" not in _rules_hit(report)
    # a single-device jit never trips SC013 even at budget 0
    monkeypatch.setenv("SHEEPRL_TPU_MEM_PEAK_BUDGET_MB", "0")
    report = mc.analyze_entry("fix@single", _sc010_fixture(donate=True))
    assert "SC013" not in _rules_hit(report)


# ---------------------------------------------------------------------------
# HLO parsing (deterministic unit receipts)
# ---------------------------------------------------------------------------

_HLO_FIXTURE = textwrap.dedent("""\
    HloModule jit_f, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, entry_computation_layout={...}

    ENTRY %main (p0: f32[64,64], p1: f32[], p2: f32[64,64]) -> (f32[64,64], f32[64,64]) {
      %c0 = f32[] constant(2)
      %c1 = f32[64,64]{1,0} constant({...})
      %c2 = s32[128]{0} constant({...})
      %w = (s32[], f32[4,16]{1,0}, bf16[8]{0}) while((s32[], f32[4,16]{1,0}, bf16[8]{0}) %t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
    }
""")


def test_parse_io_aliases():
    assert mc.parse_io_aliases(_HLO_FIXTURE) == [
        "out{0}<-arg0", "out{1}<-arg2",
    ]
    assert mc.aliased_params(mc.parse_io_aliases(_HLO_FIXTURE)) == {0, 2}
    assert mc.parse_io_aliases("HloModule bare\n") == []


def test_parse_embedded_constants():
    consts = mc.parse_embedded_constants(_HLO_FIXTURE)
    assert (64 * 64 * 4, "f32[64,64]") in consts
    assert (128 * 4, "s32[128]") in consts
    assert consts[0] == (64 * 64 * 4, "f32[64,64]")  # largest first


def test_parse_scan_buffers():
    bufs = mc.parse_scan_buffers(_HLO_FIXTURE)
    assert bufs[0] == {"shape": "f32[4,16]", "bytes": 4 * 16 * 4, "trip_count": 12}
    shapes = {b["shape"] for b in bufs}
    assert "bf16[8]" in shapes and all(b["trip_count"] == 12 for b in bufs)


def test_scan_buffers_from_real_jit():
    @jax.jit
    def rollout(h, w):
        def body(c, _):
            return jnp.tanh(c @ w), c.sum()

        return jax.lax.scan(body, h, None, length=16)

    ex = lambda: (  # noqa: E731
        sds((32, 32), jnp.float32), sds((32, 32), jnp.float32)
    )
    report = mc.analyze_entry("fix@scan", _entry("rollout", rollout, ex))
    bufs = report.memory["scan_buffers"]
    assert bufs, "no while loop found in the optimized HLO"
    assert any(b["trip_count"] == 16 for b in bufs)
    assert max(b["bytes"] for b in bufs) >= 32 * 32 * 4


def test_remat_advice_ranks_by_bytes():
    advice = mc.remat_advice(
        {
            "a/big": {"scan_buffers": [
                {"shape": "f32[1024,1024]", "bytes": 1 << 22, "trip_count": 15}
            ]},
            "a/small": {"scan_buffers": [
                {"shape": "f32[8]", "bytes": 32, "trip_count": None}
            ]},
        }
    )
    assert "a/big" in advice[0] and "x15 known iterations" in advice[0]
    assert "a/small" in advice[1] and "unknown trip count" in advice[1]


# ---------------------------------------------------------------------------
# the memory ledger: round-trip + drift gate on injected regressions
# ---------------------------------------------------------------------------


def _fixture_budget():
    reports = [
        mc.analyze_entry("fix@led", _sc010_fixture(donate=True)),
        mc.analyze_entry("fix@led", _mesh_fixture()),
    ]
    reports[1].name = "mesh_step"
    assert all(r.memory is not None for r in reports)
    return mc.build_memory_budget(reports)


def test_memory_budget_round_trip_clean():
    ledger = _fixture_budget()
    failures, notes = mc.check_memory_budget(
        ledger, json.loads(json.dumps(ledger))
    )
    assert failures == [] and notes == []


def test_memory_gate_fails_on_injected_peak_bloat():
    ledger = _fixture_budget()
    drifted = json.loads(json.dumps(ledger))
    fp = drifted["memory"]["fix@led/step"]
    fp["peak_bytes"] = int(fp["peak_bytes"] * 1.5) + 8192
    failures, _ = mc.check_memory_budget(ledger, drifted)
    assert any("peak bytes grew" in f for f in failures)

    shrunk = json.loads(json.dumps(ledger))
    shrunk["memory"]["fix@led/step"]["peak_bytes"] = 16
    failures, notes = mc.check_memory_budget(ledger, shrunk)
    assert failures == []
    assert any("shrank" in n for n in notes)


def test_memory_gate_fails_on_lost_alias():
    ledger = _fixture_budget()
    drifted = json.loads(json.dumps(ledger))
    drifted["memory"]["fix@led/step"]["aliases"] = []
    failures, _ = mc.check_memory_budget(ledger, drifted)
    assert any("realized alias" in f and "lost" in f for f in failures)
    # the reverse direction (a NEW alias) is an improvement: note only
    failures, notes = mc.check_memory_budget(drifted, ledger)
    assert not any("alias" in f for f in failures)
    assert any("new realized alias" in n for n in notes)


def test_memory_gate_fails_on_new_large_constant():
    ledger = _fixture_budget()
    drifted = json.loads(json.dumps(ledger))
    drifted["memory"]["fix@led/step"]["large_constants"] = [
        "f32[65536]:262144"
    ]
    failures, _ = mc.check_memory_budget(ledger, drifted)
    assert any("new large embedded constant" in f for f in failures)


def test_memory_gate_fails_on_added_and_removed_jits():
    ledger = _fixture_budget()
    drifted = json.loads(json.dumps(ledger))
    drifted["memory"]["fix@led/new_jit"] = drifted["memory"]["fix@led/step"]
    failures, _ = mc.check_memory_budget(ledger, drifted)
    assert any("new jit not in the memory ledger" in f for f in failures)
    gone = json.loads(json.dumps(ledger))
    del gone["memory"]["fix@led/step"]
    failures, _ = mc.check_memory_budget(ledger, gone)
    assert any("disappeared" in f for f in failures)


def test_memory_gate_fails_on_mesh_budget_breach(monkeypatch):
    ledger = _fixture_budget()
    drifted = json.loads(json.dumps(ledger))
    monkeypatch.setenv("SHEEPRL_TPU_MEM_PEAK_BUDGET_MB", "0")
    failures, _ = mc.check_memory_budget(ledger, drifted)
    # only the mesh-bearing jit breaches; the single-device one is exempt
    assert any(
        "fix@led/mesh_step" in f and "exceeds" in f for f in failures
    )
    assert not any("fix@led/step:" in f and "exceeds" in f for f in failures)


def test_memory_gate_bf16_twin_receipt():
    """The ISSUE-9 byte receipt: a declared-bf16 jit whose full-width
    activation bytes do NOT undercut its f32 twin fails the gate."""
    base = {
        "peak_bytes": 1000, "aliases": [], "large_constants": [],
        "num_partitions": 1,
    }
    good = {
        "memory": {
            "a/f": {**base, "wide_activation_bytes": 1000},
            "a@bf16/f": {
                **base, "wide_activation_bytes": 400, "declares_bf16": True,
            },
        }
    }
    failures, notes = mc.check_memory_budget(good, good)
    assert failures == []
    assert any("wide activation bytes 400 vs f32 twin 1000" in n for n in notes)

    bad = json.loads(json.dumps(good))
    bad["memory"]["a@bf16/f"]["wide_activation_bytes"] = 1000
    failures, _ = mc.check_memory_budget(bad, bad)
    assert any("not below the f32 twin" in f for f in failures)

    # a variant jit that never declared bf16 compute is exempt
    undeclared = json.loads(json.dumps(bad))
    undeclared["memory"]["a@bf16/f"]["declares_bf16"] = False
    failures, _ = mc.check_memory_budget(undeclared, undeclared)
    assert failures == []


def test_memory_gate_int8_twin_receipt():
    """The ISSUE-20 byte receipt: a declared-int8 serving rung must carry
    strictly fewer argument bytes than its full-width twin."""
    base = {
        "peak_bytes": 1000, "aliases": [], "large_constants": [],
        "num_partitions": 1,
    }
    good = {
        "memory": {
            "serve/policy_b2": {**base, "argument_bytes": 1432},
            "serve@int8/policy_b2": {
                **base, "argument_bytes": 744, "declares_int8": True,
            },
        }
    }
    failures, notes = mc.check_memory_budget(good, good)
    assert failures == []
    assert any("argument bytes 744 vs full-width twin 1432" in n for n in notes)

    bad = json.loads(json.dumps(good))
    bad["memory"]["serve@int8/policy_b2"]["argument_bytes"] = 1432
    failures, _ = mc.check_memory_budget(bad, bad)
    assert any("not below the full-width twin" in f for f in failures)

    # an @int8 capture that fell back to f32 (calibration unavailable)
    # never declares int8 and is exempt from the receipt
    undeclared = json.loads(json.dumps(bad))
    undeclared["memory"]["serve@int8/policy_b2"]["declares_int8"] = False
    failures, _ = mc.check_memory_budget(undeclared, undeclared)
    assert failures == []


def test_real_bf16_twin_shows_lower_wide_activation_bytes():
    """The receipt on real programs: the same update traced under a
    bf16-compute policy must shrink its full-width intermediate bytes."""

    def update(w, x):
        h = jnp.tanh(x @ w)
        return (h @ w.T).sum()

    def update_bf16(w, x):
        wb, xb = w.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        h = jnp.tanh(xb @ wb)
        return (h @ wb.T).sum().astype(jnp.float32)

    ex = lambda: (  # noqa: E731
        sds((64, 64), jnp.float32), sds((32, 64), jnp.float32)
    )
    f32 = mc.analyze_entry("twin", _entry("update", jax.jit(update), ex))
    bf16 = mc.analyze_entry(
        "twin@bf16", _entry("update", jax.jit(update_bf16), ex)
    )
    assert bf16.memory["declares_bf16"] and not f32.memory["declares_bf16"]
    assert (
        bf16.memory["wide_activation_bytes"]
        < f32.memory["wide_activation_bytes"]
    )
    derived = {
        "memory": {
            "twin/update": f32.memory,
            "twin@bf16/update": bf16.memory,
        }
    }
    failures, notes = mc.check_memory_budget(derived, derived)
    assert failures == []
    assert any("wide activation bytes" in n for n in notes)


# ---------------------------------------------------------------------------
# ledger persistence + the committed repo ledger
# ---------------------------------------------------------------------------


def test_memory_section_coexists_with_other_sections(tmp_path):
    """sheepmem owns `memory`; the other tools' sections survive its saves
    (and vice versa) in the per-spec dir layout."""
    path = str(tmp_path / "budget.json")
    jits = {
        "version": 1, "jax_version": jax.__version__,
        "tolerance": {"op_count_frac": 0.25},
        "jits": {"fix@led/step": {"op_count": 3, "dtypes": ["float32"]}},
    }
    jc.save_budget(jits, path, sections=("jits",))
    memory = _fixture_budget()
    jc.save_budget(memory, path, sections=("memory",))
    merged = jc.load_budget(path)
    assert merged["jits"] == jits["jits"]
    assert merged["memory"] == memory["memory"]
    assert merged["tolerance"]["op_count_frac"] == 0.25
    assert merged["tolerance"]["peak_bytes_frac"] == 0.25
    # re-saving jits must not clobber memory
    jc.save_budget(jits, path, sections=("jits",))
    assert jc.load_budget(path)["memory"] == memory["memory"]


def test_committed_ledger_carries_memory_for_every_spec():
    """ISSUE acceptance: every capture spec's file carries a `memory`
    section, and the fingerprints have the gated fields."""
    import os

    import sheeprl_tpu

    repo = os.path.dirname(os.path.dirname(sheeprl_tpu.__file__))
    ledger = jc.load_budget(os.path.join(repo, "analysis", "budget.json"))
    memory = ledger.get("memory", {})
    assert len(memory) >= 73, f"only {len(memory)} memory fingerprints"
    specs = {k.split("/", 1)[0] for k in memory}
    for required in (
        "ppo", "sac_ae", "dreamer_v3", "ppo@bf16", "dreamer_v3@bf16",
        "ppo@anakin", "dreamer_v3@anakin", "ppo@mesh8", "dreamer_v3@seq",
        "ppo_decoupled@mesh", "sac_decoupled@mesh", "dreamer_v3_decoupled@mesh",
    ):
        assert required in specs, f"{required} missing from the memory ledger"
    for key, fp in memory.items():
        for field in (
            "peak_bytes", "temp_bytes", "argument_bytes", "aliases",
            "wide_activation_bytes", "num_partitions", "scan_buffers",
        ):
            assert field in fp, (key, field)
    # the committed ledger itself satisfies the bf16 twin receipt
    failures, _ = mc.check_memory_budget(ledger, ledger)
    assert failures == [], failures
    # mesh-bearing specs committed a >1-partition (per-shard) view
    assert memory["ppo@mesh8/train_step"]["num_partitions"] == 8


def test_sheepmem_cli_gate_fails_on_injected_regression(tmp_path):
    """ISSUE acceptance: the CLI exits non-zero on an injected peak-memory
    regression and on a lost realized alias — against a fixture ledger so
    the test stays capture-free (the PR 7/8 gate-verification pattern)."""
    import sys
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "sheepmem_cli",
        jc.os.path.join(
            jc.os.path.dirname(jc.os.path.dirname(jc.os.path.abspath(jc.__file__))),
            jc.os.path.pardir, "tools", "sheepmem.py",
        ),
    )
    # the tool re-execs only when the virtual-device flag is missing; under
    # the test harness it is already set, so import is side-effect-free
    tool = importlib.util.module_from_spec(spec)
    sys.modules["sheepmem_cli"] = tool
    spec.loader.exec_module(tool)

    path = str(tmp_path / "budget.json")
    ledger = _fixture_budget()
    # the committed ledger claims a LOWER peak and an alias the derived
    # sweep will not reproduce -> drift, exit 1
    tampered = json.loads(json.dumps(ledger))
    fp = tampered["memory"]["fix@led/step"]
    fp["peak_bytes"] = max(int(fp["peak_bytes"] * 0.5) - 8192, 1)
    fp["aliases"] = ["out{0}<-arg0", "out{9}<-arg9"]
    failures, _ = mc.check_memory_budget(tampered, ledger)
    assert any("peak bytes grew" in f for f in failures)
    assert any("lost" in f for f in failures)
    jc.save_budget(tampered, path, sections=("memory",))
    # no capture specs resolve from a fixture ledger through the CLI, so
    # drive the gate exactly as main() does: load, filter, check
    loaded = jc.load_budget(path)
    failures2, _ = mc.check_memory_budget(loaded, ledger)
    assert failures2, "gate must fail on the injected regression"


@pytest.mark.timeout(600)
def test_sac_capture_end_to_end(tmp_path):
    """One real capture through the sweep machinery: sac's registered jits
    compile, fingerprint, and come back finding-free (modulo justified
    suppressions) — and the derived entries match the committed ledger
    within the gate's tolerances."""
    algo, extra_argv = mc.resolve_capture("sac")
    plan = jc.capture_plan(algo, str(tmp_path), extra_argv=extra_argv)
    reports = mc.analyze_mem_plan("sac", plan)
    analyzed = [r for r in reports if r.memory is not None]
    assert {r.name for r in analyzed} >= {"train_step", "policy_step"}
    for r in reports:
        assert r.failing == [], [f.format() for f in r.failing]
    derived = mc.build_memory_budget(reports)
    import os

    import sheeprl_tpu

    repo = os.path.dirname(os.path.dirname(sheeprl_tpu.__file__))
    ledger = jc.load_budget(os.path.join(repo, "analysis", "budget.json"))
    committed_sac = {
        k: v for k, v in ledger.get("memory", {}).items()
        if k.startswith("sac/")
    }
    failures, _ = mc.check_memory_budget(
        {**ledger, "memory": committed_sac}, derived
    )
    assert failures == [], failures
