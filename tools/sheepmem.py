#!/usr/bin/env python
"""sheepmem — static memory & buffer-lifetime analysis over the compiled
plan (ISSUE 10), with the CI-gated HBM budget.

Usage:
    python tools/sheepmem.py                      # the full sweep
    python tools/sheepmem.py sac_ae dreamer_v3    # a subset
    python tools/sheepmem.py --list-rules
    python tools/sheepmem.py --update-budget      # refresh memory sections
    python tools/sheepmem.py --check-budget       # the CI HBM drift gate
    python tools/sheepmem.py --remat              # the remat advisor
    python tools/sheepmem.py --rules SC011,SC012 --json

The sweep re-runs the sheepcheck/sheepshard shape capture over the FULL
population — all 13 mains at their CAPTURE_ARGV, every `@bf16`/Anakin
CAPTURE_VARIANT, and the mesh-bearing SHARD_SWEEP specs (whose mesh argv
wins on name collision: the per-shard peak is the TPU-relevant quantity) —
then `lower().compile()`s every registered jit (CPU virtual mesh, zero
execution) and reads two sources off the executable: XLA's own
`memory_analysis()` (peak/temp/argument/output/generated-code bytes) and
the post-optimization HLO (realized input_output_alias table, embedded
array constants, live-across-scan buffers with known trip counts — the
remat advisor's input). Rules SC010-SC013 (catalog:
sheeprl_tpu/analysis/memory_check.py + howto/static_analysis.md) ride the
sweep; fingerprints live in the committed `analysis/budget/` ledger
(section `memory`, next to `jits`/`comms`/`edges`); `--check-budget`
fails CI on unexplained drift: peak growth >25%, lost realized aliases,
new large embedded constants, per-shard peaks over the HBM budget, or a
`@bf16` variant whose full-width activation bytes do not undercut its f32
twin (the byte-level receipt of the ISSUE-9 mixed-precision contract).

Exit codes: 0 clean, 1 findings or budget drift, 2 capture/usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent

# Same preamble as tools/sheepcheck.py / sheepshard.py: the memory ledger is
# derived on the CPU virtual 8-device harness by design, so re-exec once
# with the virtual-device flag before anything imports jax.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.execv(sys.executable, [sys.executable, *sys.argv])

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(_REPO))

from sheeprl_tpu.analysis import jaxpr_check as jc  # noqa: E402
from sheeprl_tpu.analysis import memory_check as mc  # noqa: E402

DEFAULT_BUDGET = str(_REPO / "analysis" / "budget.json")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "specs", nargs="*",
        help="capture specs to sweep (default: mains + variants + mesh specs)",
    )
    ap.add_argument("--rules", default=None, help="comma-separated SC rule ids")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument(
        "--budget", default=DEFAULT_BUDGET,
        help=f"budget ledger path (default {DEFAULT_BUDGET}; the "
             "analysis/budget/ dir layout is preferred when present)",
    )
    ap.add_argument(
        "--update-budget", action="store_true",
        help="write the derived memory fingerprints to the ledger",
    )
    ap.add_argument(
        "--check-budget", action="store_true",
        help="fail on unexplained memory drift vs the ledger (the CI gate)",
    )
    ap.add_argument(
        "--remat", action="store_true",
        help="print the remat advisor: the largest live-across-scan buffers",
    )
    ap.add_argument(
        "--root-dir", default=None,
        help="where capture runs write their (throwaway) run dirs",
    )
    ap.add_argument("--verbose", action="store_true")
    ns = ap.parse_args(argv)

    if ns.list_rules:
        for rule in mc.MEM_RULES.values():
            print(f"{rule.id} ({rule.name}) [{rule.severity}]")
            print(f"    {rule.summary}")
            print(f"    fix: {rule.autofix}")
        return 0

    rules = None
    if ns.rules:
        rules = {s.strip().upper() for s in ns.rules.split(",") if s.strip()}
        unknown = rules - set(mc.MEM_RULES)
        if unknown:
            print(f"unknown rule ids: {sorted(unknown)}", file=sys.stderr)
            return 2

    import sheeprl_tpu.algos  # noqa: F401 — fire registrations
    from sheeprl_tpu.utils.registry import tasks
    from sheeprl_tpu.analysis import shard_check as sc

    specs = ns.specs or mc.memory_sweep_specs()
    unknown = {
        s for s in specs
        if s not in tasks
        and s not in jc.CAPTURE_VARIANTS
        and s not in sc.SHARD_SWEEP
        and s not in mc.MEM_VARIANTS
    }
    if unknown:
        print(f"unknown specs: {sorted(unknown)}", file=sys.stderr)
        return 2

    root = ns.root_dir or tempfile.mkdtemp(prefix="sheepmem_")
    reports: list[mc.MemReport] = []
    capture_errors = 0
    for spec in specs:
        algo, extra_argv = mc.resolve_capture(spec)
        t0 = time.perf_counter()
        try:
            plan = jc.capture_plan(algo, root, extra_argv=extra_argv)
        except BaseException as err:  # CaptureComplete is consumed inside
            if isinstance(err, (KeyboardInterrupt, SystemExit)):
                raise
            print(f"{spec}: CAPTURE FAILED: {type(err).__name__}: {err}",
                  file=sys.stderr)
            capture_errors += 1
            continue
        spec_reports = mc.analyze_mem_plan(spec, plan, rules=rules)
        reports.extend(spec_reports)
        analyzed = [r for r in spec_reports if r.memory is not None]
        peak = max((r.memory["peak_bytes"] for r in analyzed), default=0)
        print(
            f"{spec}: {len(analyzed)}/{len(spec_reports)} jits compiled, "
            f"max peak {peak} bytes, "
            f"{sum(len(r.failing) for r in spec_reports)} finding(s) "
            f"[{time.perf_counter() - t0:.1f}s]",
            file=sys.stderr,
        )
        if ns.verbose:
            for r in spec_reports:
                if r.error:
                    print(f"  {r.name}: skipped ({r.error})", file=sys.stderr)
                elif r.memory is not None:
                    m = r.memory
                    print(
                        f"  {r.name}: peak={m['peak_bytes']} "
                        f"temp={m['temp_bytes']} args={m['argument_bytes']} "
                        f"aliases={len(m['aliases'])}/{m['donated']} "
                        f"const={m['constant_bytes']}",
                        file=sys.stderr,
                    )

    all_findings = [f for r in reports for f in r.findings]
    failing = [f for f in all_findings if not f.suppressed]
    suppressed = [f for f in all_findings if f.suppressed]

    budget_failures: list[str] = []
    budget_notes: list[str] = []
    derived = mc.build_memory_budget(reports)
    if ns.update_budget:
        if ns.specs and jc.budget_exists(ns.budget):
            # partial refresh: replace only the captured specs' entries
            ledger = jc.load_budget(ns.budget)
            prefixes = tuple(f"{s}/" for s in specs)
            merged = {
                k: v
                for k, v in ledger.get("memory", {}).items()
                if not k.startswith(prefixes)
            }
            merged.update(derived["memory"])
            derived = {**ledger, **derived, "memory": merged}
        jc.save_budget(derived, ns.budget, sections=("memory",))
        print(
            f"wrote {len(derived['memory'])} memory fingerprints to "
            f"{jc.budget_dir_of(ns.budget)}",
            file=sys.stderr,
        )
    elif ns.check_budget:
        if not jc.budget_exists(ns.budget):
            print(f"no ledger at {ns.budget} (run --update-budget first)",
                  file=sys.stderr)
            return 2
        ledger = jc.load_budget(ns.budget)
        if ns.specs:
            # partial capture: gate only the captured specs' entries
            prefixes = tuple(f"{s}/" for s in specs)
            ledger = {
                **ledger,
                "memory": {
                    k: v for k, v in ledger.get("memory", {}).items()
                    if k.startswith(prefixes)
                },
            }
        budget_failures, budget_notes = mc.check_memory_budget(ledger, derived)

    remat = mc.remat_advice(derived["memory"]) if ns.remat else []

    if ns.json:
        print(json.dumps({
            "findings": [f.as_dict() for f in failing],
            "suppressed": [f.as_dict() for f in suppressed],
            "budget_failures": budget_failures,
            "budget_notes": budget_notes,
            "capture_errors": capture_errors,
            "remat": remat,
            "memory": derived["memory"],
        }, indent=2))
    else:
        for f in failing:
            print(f.format())
        if ns.verbose:
            for f in suppressed:
                print(f.format())
        for line in remat:
            print(f"remat: {line}")
        for note in budget_notes:
            print(f"memory note: {note}", file=sys.stderr)
        for failure in budget_failures:
            print(f"MEMORY DRIFT: {failure}")

    if capture_errors:
        return 2
    if failing or budget_failures:
        print(
            f"sheepmem: {len(failing)} finding(s), {len(suppressed)} "
            f"suppressed, {len(budget_failures)} memory drift(s)",
            file=sys.stderr,
        )
        return 1
    print(
        f"sheepmem: clean ({len(derived['memory'])} jits fingerprinted, "
        f"{len(suppressed)} suppressed finding(s))",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
