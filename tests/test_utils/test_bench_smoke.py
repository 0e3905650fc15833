"""Smoke-test the bench entry on the CPU: `python bench.py --tiny` prints
exactly one JSON line with the contract keys, stamped with the device it ran
on and labelled as a smoke (never under a device metric's name); without
`--tiny` a run that finds no TPU exits non-zero naming the platform."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.timeout(900)
def test_bench_tiny_prints_contract_json():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = flags
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--tiny"],
        cwd=REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        timeout=850,
    )
    diag = f"stdout: {proc.stdout!r}\nstderr tail: {proc.stderr[-2000:]!r}"
    assert proc.returncode == 0, diag
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"expected ONE JSON line; {diag}"
    payload = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in payload, f"missing contract key {k}"
    # a 0.0 value means every guarded measurement failed (sentinel) — the
    # guarded tracebacks land on stderr, so surface them
    assert payload["value"] > 0, diag
    # the line names its device, and a CPU number never rides a device
    # metric's name or a per-chip unit
    assert payload["platform"] == "cpu" and payload["measured_on"] == "cpu", diag
    assert payload["device_kind"] and payload["device_count"] >= 1, diag
    assert payload["metric"].startswith("cpu_smoke_"), diag
    assert "/chip" not in payload["unit"], diag


def test_bench_without_tpu_exits_nonzero_naming_platform():
    """No chip, no number: the default invocation on a CPU-only box prints no
    result line and fails, saying which platform it found."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")], proc.stdout
    assert "platform='cpu'" in proc.stderr and "not a TPU" in proc.stderr, proc.stderr[-500:]


@pytest.mark.timeout(900)
def test_bench_ledger_partial_emission_and_resume(tmp_path):
    """A bench session that dies mid-run persists its completed phases and
    prints NO result line (exit non-zero); a restart skips them. Run 1 is
    budgeted to ONE phase (the stand-in for a death after phase A). Run 2
    resumes from the sidecar, skips the recorded phase, and completes the
    remaining phases."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    ledger = str(tmp_path / "ledger.json")
    env["SHEEPRL_TPU_BENCH_LEDGER"] = ledger

    # run 1: die after the first completed phase
    env1 = dict(env, SHEEPRL_TPU_BENCH_MAX_PHASES="1")
    p1 = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--tiny"],
        cwd=REPO, env=env1, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=420,
    )
    diag = f"stdout: {p1.stdout!r}\nstderr tail: {p1.stderr[-2000:]!r}"
    assert p1.returncode != 0, diag
    assert "phase_budget_exhausted" in p1.stderr, diag
    assert not [l for l in p1.stdout.splitlines() if l.startswith("{")], diag
    with open(ledger) as fh:
        side = json.load(fh)
    assert "A_wave_all" in side["phases"], side.get("phases", {}).keys()

    # run 2: resume — phase A must be loaded, not re-measured
    p2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--tiny"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=420,
    )
    diag2 = f"stdout: {p2.stdout!r}\nstderr tail: {p2.stderr[-2000:]!r}"
    assert p2.returncode == 0, diag2
    lines2 = [l for l in p2.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines2) == 1, diag2
    final = json.loads(lines2[0])
    assert final["value"] > 0, diag2
    assert "A_wave_all" in final["phases_completed"], diag2
    assert "E_e2e" in final["phases_completed"], diag2
    assert "phase A_wave_all loaded" in p2.stderr, (
        "resume did not skip the recorded phase; " + diag2
    )


def test_interleave_keep_rule_helpers():
    """The ABAB keep-decision primitives (VERDICT r3 #1): pooled medians
    ignore dead segments, and a challenger is kept only when its paired
    advantage exceeds both the observed spread and the 2% floor."""
    import bench

    assert bench._pooled([0.0, 0.0]) == 0.0
    assert bench._pooled([100.0, 0.0, 110.0]) == 105.0

    base = [100.0, 100.0, 100.0, 100.0]
    # clear win: +10% with tight spread
    assert bench._beats([110.0, 110.5, 109.5, 110.0], base)
    # sub-noise win: +1% never kept (margin floor)
    assert not bench._beats([101.0, 101.0, 101.0, 101.0], base)
    # big median win but spread wider than the advantage: not kept
    assert not bench._beats([150.0, 80.0, 150.0, 80.0], base)
    # dead challenger / dead baseline: never kept
    assert not bench._beats([0.0, 0.0, 0.0, 0.0], base)
    assert not bench._beats([110.0] * 4, [0.0] * 4)
    # one dead segment is excluded from pairing, not fatal
    assert bench._beats([110.0, 0.0, 110.0, 110.0], base)


def test_interleave_sps_round_robin_and_guards():
    import bench

    calls = []

    def make_run(name, dt):
        def run(n):
            calls.append(name)
            return dt * n
        return run

    samples = bench._interleave_sps(
        {"a": make_run("a", 0.1), "b": make_run("b", 0.2), "dead": None},
        steps_per_cycle=10, segments=3, cycles_per_segment=2,
        discards=[], tiny=True,
    )
    # round-robin order: a,b,a,b,a,b (dead variant never called)
    assert calls == ["a", "b"] * 3
    assert samples["dead"] == [0.0, 0.0, 0.0]
    assert all(abs(s - 100.0) < 1e-6 for s in samples["a"])
    assert all(abs(s - 50.0) < 1e-6 for s in samples["b"])


def test_paired_ratio_ranking_key():
    """Candidates from different interleaved sessions rank by advantage
    over their OWN session's baseline — never by absolute sps."""
    import bench

    # 20% advantage in a slow-weather session
    assert abs(bench._paired_ratio([120.0, 118.0], [100.0, 100.0]) - 1.19) < 0.02
    # bigger advantage in an even slower session still ranks higher
    fast = bench._paired_ratio([120.0, 120.0], [100.0, 100.0])
    slow = bench._paired_ratio([90.0, 90.0], [70.0, 70.0])
    assert slow > fast
    # dead segments excluded; fewer than 2 valid pairs -> 0.0 sentinel
    assert bench._paired_ratio([0.0, 110.0], [100.0, 100.0]) == 0.0
    assert bench._paired_ratio([0.0] * 4, [100.0] * 4) == 0.0


@pytest.mark.timeout(900)
def test_bench_ppo_telemetry_ab_records_overhead():
    """ISSUE 2 satellite: `--algo ppo --telemetry ab` must run both arms of
    the instrumentation A/B and record the overhead in the artifact. The
    strict <2% bound is asserted on a controlled workload in
    tests/test_utils/test_telemetry.py; here the receipt is that the A/B
    ran, both arms produced real numbers, and the instrumented arm is not
    grossly slower (>15% would mean the subsystem is broken, not noisy)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--algo", "ppo",
         "--telemetry", "ab", "--tiny"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=850,
    )
    diag = f"stdout: {proc.stdout!r}\nstderr tail: {proc.stderr[-2000:]!r}"
    assert proc.returncode == 0, diag
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, diag
    payload = json.loads(lines[0])
    assert payload["telemetry"] == "ab"
    assert payload["telemetry_on_sps"] > 0 and payload["telemetry_off_sps"] > 0, diag
    assert payload["value"] == payload["telemetry_on_sps"]
    assert payload["telemetry_overhead_pct"] < 15.0, (
        f"instrumented arm {payload['telemetry_overhead_pct']}% slower; {diag}"
    )
