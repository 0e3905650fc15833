#!/usr/bin/env python
"""sheepcheck — jaxpr-level whole-program analysis over the CompilePlan
(ISSUE 7), with the compile-cost budget ledger CI gates on.

Usage:
    python tools/sheepcheck.py                      # all 13 mains, SC rules
    python tools/sheepcheck.py ppo sac_ae           # a subset
    python tools/sheepcheck.py --list-rules
    python tools/sheepcheck.py --update-budget      # refresh analysis/budget/
    python tools/sheepcheck.py --check-budget       # the CI drift gate
    python tools/sheepcheck.py --rules SC001,SC002 --json

For every selected algo main, the tool runs the main in SHAPE-CAPTURE mode
(`SHEEPRL_TPU_PLAN_MODE=capture`): setup proceeds on CPU at tiny avals
until `CompilePlan.start()`, which raises instead of compiling — so every
registered hot jit is captured with its exact example avals and NOTHING of
the algorithm executes. Each jit is then abstract-evaled to a ClosedJaxpr
(`jit.trace`) and analyzed (rules SC001-SC005, catalog:
sheeprl_tpu/analysis/jaxpr_check.py + howto/static_analysis.md), and its
compile-cost fingerprint (primitive histogram, op count, dtype set,
donation map, cost_analysis FLOPs/bytes) is compared against — or written
to — the committed ledger: one file per algo/variant under
`analysis/budget/` (the pre-split single-blob `analysis/budget.json` is
still readable for one release). The SPMD/collective half of the ledger
(`comms`/`edges` sections) belongs to tools/sheepshard.py and is preserved
untouched by `--update-budget` here.

Exit codes: 0 clean, 1 findings or budget drift, 2 capture/usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent

# Capture is CPU-by-design (the ledger must not depend on which accelerator
# happens to be attached) and the decoupled topologies need >=2 devices for
# their player/trainer sub-meshes — re-exec once with the virtual-device
# flag before anything imports jax (the same 8-device harness
# tests/conftest.py and CI pin).
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.execv(sys.executable, [sys.executable, *sys.argv])

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(_REPO))

from sheeprl_tpu.analysis import jaxpr_check as jc  # noqa: E402

DEFAULT_BUDGET = str(_REPO / "analysis" / "budget.json")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "algos", nargs="*",
        help="algo mains to capture (default: all registered)",
    )
    ap.add_argument("--rules", default=None, help="comma-separated SC rule ids")
    ap.add_argument(
        "--audit-bf16", action="store_true",
        help="also flag bf16->f32 upcasts (the ROADMAP-5c mixed-precision audit)",
    )
    ap.add_argument(
        "--gate-bf16", action="store_true",
        help="CI gate for declared-bf16 jits (ISSUE 9): capture the @bf16 "
        "variants, count each jit's bf16->f32 upcasts and FAIL when a jit "
        "whose budget entry declares bf16 compute exceeds its committed "
        "fp32-island count (or loses bfloat16 entirely). f32-only jits "
        "stay audit-only. Implies --audit-bf16; SC findings are reported, "
        "not gated, in this mode (the default run gates them)",
    )
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument(
        "--budget", default=DEFAULT_BUDGET,
        help=f"budget ledger path (default {DEFAULT_BUDGET})",
    )
    ap.add_argument(
        "--update-budget", action="store_true",
        help="write the derived fingerprints to the ledger",
    )
    ap.add_argument(
        "--check-budget", action="store_true",
        help="fail on unexplained fingerprint drift vs the ledger (the CI gate)",
    )
    ap.add_argument(
        "--root-dir", default=None,
        help="where capture runs write their (throwaway) run dirs",
    )
    ap.add_argument("--verbose", action="store_true")
    ns = ap.parse_args(argv)

    if ns.list_rules:
        for rule in jc.SC_RULES.values():
            print(f"{rule.id} ({rule.name}) [{rule.severity}]")
            print(f"    {rule.summary}")
            print(f"    fix: {rule.autofix}")
        return 0

    rules = None
    if ns.rules:
        rules = {s.strip().upper() for s in ns.rules.split(",") if s.strip()}
        unknown = rules - set(jc.SC_RULES)
        if unknown:
            print(f"unknown rule ids: {sorted(unknown)}", file=sys.stderr)
            return 2

    import sheeprl_tpu.algos  # noqa: F401 — fire registrations
    from sheeprl_tpu.utils.registry import tasks

    # default sweep: every registered main at its capture argv, plus the
    # named variants (flag combinations that register extra jits — the
    # Anakin `--env_backend jax` rollout collectors and the ISSUE-9
    # `@bf16` mixed-precision traces). --gate-bf16 narrows the default
    # sweep to the bf16 variants (that's the gated population).
    if ns.gate_bf16:
        ns.audit_bf16 = True
        specs = ns.algos or sorted(
            s for s in jc.CAPTURE_VARIANTS if s.endswith("@bf16")
        )
    else:
        specs = ns.algos or [*sorted(tasks), *sorted(jc.CAPTURE_VARIANTS)]
    unknown = set(specs) - set(tasks) - set(jc.CAPTURE_VARIANTS)
    if unknown:
        print(f"unknown algos: {sorted(unknown)}", file=sys.stderr)
        return 2

    root = ns.root_dir or tempfile.mkdtemp(prefix="sheepcheck_")
    reports: list[jc.JitReport] = []
    capture_errors = 0
    for spec in specs:
        algo, extra_argv = jc.resolve_capture(spec)
        try:
            plan = jc.capture_plan(algo, root, extra_argv=extra_argv)
        except BaseException as err:  # CaptureComplete is consumed inside
            if isinstance(err, (KeyboardInterrupt, SystemExit)):
                raise
            print(f"{spec}: CAPTURE FAILED: {type(err).__name__}: {err}",
                  file=sys.stderr)
            capture_errors += 1
            continue
        algo_reports = jc.analyze_plan(
            spec, plan, rules=rules, audit_bf16=ns.audit_bf16
        )
        reports.extend(algo_reports)
        analyzed = [r for r in algo_reports if r.fingerprint is not None]
        print(
            f"{spec}: captured {len(algo_reports)} jits, "
            f"analyzed {len(analyzed)}, "
            f"{sum(len(r.failing) for r in algo_reports)} finding(s)",
            file=sys.stderr,
        )
        if ns.verbose:
            for r in algo_reports:
                if r.error:
                    print(f"  {r.name}: skipped ({r.error})", file=sys.stderr)

    failing = [f for r in reports for f in r.failing]
    suppressed = [f for r in reports for f in r.findings if f.suppressed]

    budget_failures: list[str] = []
    budget_notes: list[str] = []
    derived = jc.build_budget([r for r in reports if r.fingerprint is not None])

    gate_failures: list[str] = []
    if ns.gate_bf16:
        # findings under the gate are the DECLARED islands — report, don't
        # fail; the gate compares each declared-bf16 jit's upcast count to
        # its committed ledger entry
        failing = []
        if not jc.budget_exists(ns.budget):
            print(f"no ledger at {ns.budget} (run --update-budget first)",
                  file=sys.stderr)
            return 2
        committed = jc.load_budget(ns.budget).get("jits", {})
        for key, fp in sorted(derived["jits"].items()):
            entry = committed.get(key)
            if entry is None:
                gate_failures.append(f"{key}: not in the budget ledger")
                continue
            if not jc.declares_bf16(entry):
                continue  # f32-only jit: audit-only by design
            if not jc.declares_bf16(fp):
                gate_failures.append(
                    f"{key}: lost its declared bfloat16 compute"
                )
            elif int(fp.get("bf16_upcasts", 0)) > int(entry.get("bf16_upcasts", 0)):
                gate_failures.append(
                    f"{key}: bf16->f32 upcasts {entry.get('bf16_upcasts')} "
                    f"-> {fp.get('bf16_upcasts')} — undeclared upcast inside "
                    "a declared-bf16 jit"
                )
    if ns.update_budget:
        if ns.algos and jc.budget_exists(ns.budget):
            # partial refresh: replace only the captured specs' entries —
            # a subset run must not drop the other mains from the ledger
            ledger = jc.load_budget(ns.budget)
            prefixes = tuple(f"{s}/" for s in specs)
            merged = {
                k: v
                for k, v in ledger.get("jits", {}).items()
                if not k.startswith(prefixes)
            }
            merged.update(derived["jits"])
            derived = {**ledger, **derived, "jits": merged}
        jc.save_budget(derived, ns.budget)
        print(f"wrote {len(derived['jits'])} fingerprints to {ns.budget}",
              file=sys.stderr)
    elif ns.check_budget:
        if not jc.budget_exists(ns.budget):
            print(f"no ledger at {ns.budget} (run --update-budget first)",
                  file=sys.stderr)
            return 2
        ledger = jc.load_budget(ns.budget)
        if ns.algos:
            # partial capture: gate only the captured algos' entries
            prefixes = tuple(f"{s}/" for s in specs)
            ledger = {
                **ledger,
                "jits": {
                    k: v for k, v in ledger.get("jits", {}).items()
                    if k.startswith(prefixes)
                },
            }
        budget_failures, budget_notes = jc.check_budget(ledger, derived)

    if ns.json:
        print(json.dumps({
            "findings": [f.as_dict() for f in failing],
            "suppressed": [f.as_dict() for f in suppressed],
            "budget_failures": budget_failures,
            "budget_notes": budget_notes,
            "bf16_gate_failures": gate_failures,
            "bf16_upcasts": (
                {
                    k: fp.get("bf16_upcasts")
                    for k, fp in sorted(derived["jits"].items())
                }
                if ns.gate_bf16
                else None
            ),
            "capture_errors": capture_errors,
            "jits": sorted(derived["jits"]),
        }, indent=2))
    else:
        for f in failing:
            print(f.format())
        if ns.verbose:
            for f in suppressed:
                print(f.format())
        for note in budget_notes:
            print(f"budget note: {note}", file=sys.stderr)
        for failure in budget_failures:
            print(f"BUDGET DRIFT: {failure}")
        for failure in gate_failures:
            print(f"BF16 GATE: {failure}")

    if capture_errors:
        return 2
    if failing or budget_failures or gate_failures:
        n = len(failing)
        print(
            f"sheepcheck: {n} finding(s), {len(suppressed)} suppressed, "
            f"{len(budget_failures)} budget drift(s), "
            f"{len(gate_failures)} bf16 gate failure(s)",
            file=sys.stderr,
        )
        return 1
    print(
        f"sheepcheck: clean ({len(derived['jits'])} jits fingerprinted, "
        f"{len(suppressed)} suppressed finding(s))",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
