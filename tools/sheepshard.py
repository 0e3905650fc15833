#!/usr/bin/env python
"""sheepshard — SPMD partitioning & collective-communication analysis over
the lowered CompilePlan (ISSUE 8), with the CI-gated comms budget.

Usage:
    python tools/sheepshard.py                       # the full mesh sweep
    python tools/sheepshard.py ppo@mesh8 ppo@anakin  # a subset
    python tools/sheepshard.py --list-rules
    python tools/sheepshard.py --update-budget       # refresh comms/edges
    python tools/sheepshard.py --check-budget        # the CI comms gate
    python tools/sheepshard.py --source-only         # just the SC009 pass
    python tools/sheepshard.py --rules SC006,SC008 --json

For every sweep spec (analysis/shard_check.py `SHARD_SWEEP` — the mesh-
bearing configurations: data-parallel ppo on the virtual 8-mesh, both
Anakin variants with `shard_env_batch` placement, the (data,seq) context-
parallel dreamer, and the decoupled player/trainer topologies), the tool
runs the main in SHAPE-CAPTURE mode (zero execution), then lowers AND
compiles every mesh-bearing registered jit under its declared mesh on the
CPU virtual-device harness. The post-SPMD-partitioning HLO is parsed into
a per-jit comms ledger (every collective, its bytes, replica groups,
hot-loop placement, estimated bytes-on-the-wire) and checked (SC006-SC008);
declared CompilePlan data edges are resolved producer-output-sharding vs
consumer-input-sharding (SC008); and an AST pass flags eager collectives
in un-jitted host loops (SC009). Fingerprints live in the committed
`analysis/budget/` ledger (sections `comms` + `edges`, next to
sheepcheck's `jits`); `--check-budget` fails CI on unexplained drift: new
collective kinds, new/multiplied hot-loop collectives, comms-bytes growth
>25%, newly replicated large tensors, or a match-edge turning mismatch.

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

# Same preamble as tools/sheepcheck.py: the comms ledger is derived on the
# CPU virtual 8-device harness by design (it must not depend on which
# accelerator happens to be attached), so re-exec once with the
# virtual-device flag before anything imports jax.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.execv(sys.executable, [sys.executable, *sys.argv])

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(_REPO))

from sheeprl_tpu.analysis import jaxpr_check as jc  # noqa: E402
from sheeprl_tpu.analysis import shard_check as sc  # noqa: E402

DEFAULT_BUDGET = str(_REPO / "analysis" / "budget.json")
SOURCE_PATHS = ("sheeprl_tpu", "tools")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "specs", nargs="*",
        help="sweep specs to capture (default: the full SHARD_SWEEP)",
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
        help="write the derived comms/edges fingerprints to the ledger",
    )
    ap.add_argument(
        "--check-budget", action="store_true",
        help="fail on unexplained comms drift vs the ledger (the CI gate)",
    )
    ap.add_argument(
        "--source-only", action="store_true",
        help="run only the SC009 source pass (no capture, no compile)",
    )
    ap.add_argument(
        "--no-source", action="store_true",
        help="skip the SC009 source pass",
    )
    ap.add_argument(
        "--root-dir", default=None,
        help="where capture runs write their (throwaway) run dirs",
    )
    ap.add_argument("--verbose", action="store_true")
    ns = ap.parse_args(argv)

    if ns.list_rules:
        for rule in sc.SHARD_RULES.values():
            print(f"{rule.id} ({rule.name}) [{rule.severity}]")
            print(f"    {rule.summary}")
            print(f"    fix: {rule.autofix}")
        return 0

    rules = None
    if ns.rules:
        rules = {s.strip().upper() for s in ns.rules.split(",") if s.strip()}
        unknown = rules - set(sc.SHARD_RULES)
        if unknown:
            print(f"unknown rule ids: {sorted(unknown)}", file=sys.stderr)
            return 2

    source_findings = []
    if not ns.no_source and (rules is None or "SC009" in rules):
        source_findings = sc.check_source_collectives(
            [str(_REPO / p) for p in SOURCE_PATHS]
        )

    specs = ns.specs or sorted(sc.SHARD_SWEEP)
    unknown = {
        s for s in specs
        if s not in sc.SHARD_SWEEP and s not in jc.CAPTURE_VARIANTS
    }
    if ns.source_only:
        specs = []
    elif unknown:
        import sheeprl_tpu.algos  # noqa: F401 — fire registrations
        from sheeprl_tpu.utils.registry import tasks

        unknown -= set(tasks)
        if unknown:
            print(f"unknown specs: {sorted(unknown)}", file=sys.stderr)
            return 2

    root = ns.root_dir or tempfile.mkdtemp(prefix="sheepshard_")
    reports: list[sc.ShardReport] = []
    edges_by_spec: dict[str, dict[str, dict]] = {}
    edge_findings: list = []
    capture_errors = 0
    for spec in specs:
        algo, extra_argv = sc.resolve_capture(spec)
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
        spec_reports, edge_records, spec_edge_findings = sc.analyze_shard_plan(
            spec, plan, rules=rules
        )
        reports.extend(spec_reports)
        edges_by_spec[spec] = edge_records
        edge_findings.extend(spec_edge_findings)
        analyzed = [r for r in spec_reports if r.comms is not None]
        wire = sum(r.comms["wire_bytes"] for r in analyzed)
        colls = sum(sum(r.comms["collectives"].values()) for r in analyzed)
        print(
            f"{spec}: {len(analyzed)}/{len(spec_reports)} mesh-bearing jits, "
            f"{colls} collective(s), ~{wire} wire bytes/step, "
            f"{len(edge_records)} edge(s), "
            f"{sum(len(r.failing) for r in spec_reports) + sum(1 for f in spec_edge_findings if not f.suppressed)} finding(s) "
            f"[{time.perf_counter() - t0:.1f}s]",
            file=sys.stderr,
        )
        if ns.verbose:
            for r in spec_reports:
                if r.error:
                    print(f"  {r.name}: skipped ({r.error})", file=sys.stderr)
                elif r.comms is not None:
                    print(
                        f"  {r.name}: {r.comms['collectives']} hot="
                        f"{r.comms['hot_collectives']} wire={r.comms['wire_bytes']}",
                        file=sys.stderr,
                    )

    all_findings = [
        *(f for r in reports for f in r.findings),
        *edge_findings,
        *source_findings,
    ]
    failing = [f for f in all_findings if not f.suppressed]
    suppressed = [f for f in all_findings if f.suppressed]

    budget_failures: list[str] = []
    budget_notes: list[str] = []
    derived = sc.build_comms_budget(reports, edges_by_spec)
    if ns.update_budget:
        if (ns.specs or ns.source_only) and jc.budget_exists(ns.budget):
            # partial refresh: replace only the captured specs' comms/edges
            ledger = jc.load_budget(ns.budget)
            prefixes = tuple(f"{s}/" for s in specs)
            for section in ("comms", "edges"):
                merged = {
                    k: v
                    for k, v in ledger.get(section, {}).items()
                    if not k.startswith(prefixes)
                }
                merged.update(derived.get(section, {}))
                derived[section] = merged
        jc.save_budget(derived, ns.budget, sections=("comms", "edges"))
        print(
            f"wrote {len(derived['comms'])} comms fingerprints + "
            f"{len(derived['edges'])} edge contracts to "
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
                "comms": {
                    k: v for k, v in ledger.get("comms", {}).items()
                    if k.startswith(prefixes)
                },
                "edges": {
                    k: v for k, v in ledger.get("edges", {}).items()
                    if k.startswith(prefixes)
                },
            }
        budget_failures, budget_notes = sc.check_comms_budget(ledger, derived)

    if ns.json:
        print(json.dumps({
            "findings": [f.as_dict() for f in failing],
            "suppressed": [f.as_dict() for f in suppressed],
            "budget_failures": budget_failures,
            "budget_notes": budget_notes,
            "capture_errors": capture_errors,
            "comms": derived["comms"],
            "edges": derived["edges"],
        }, indent=2))
    else:
        for f in failing:
            print(f.format())
        if ns.verbose:
            for f in suppressed:
                print(f.format())
        for note in budget_notes:
            print(f"comms note: {note}", file=sys.stderr)
        for failure in budget_failures:
            print(f"COMMS DRIFT: {failure}")

    if capture_errors:
        return 2
    if failing or budget_failures:
        print(
            f"sheepshard: {len(failing)} finding(s), {len(suppressed)} "
            f"suppressed, {len(budget_failures)} comms drift(s)",
            file=sys.stderr,
        )
        return 1
    print(
        f"sheepshard: clean ({len(derived['comms'])} jits fingerprinted, "
        f"{len(derived['edges'])} edge contract(s), "
        f"{len(suppressed)} suppressed finding(s))",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
