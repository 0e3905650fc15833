#!/usr/bin/env python
"""sheepopt — ledger-driven auto-optimization over the committed budget
ledgers (ISSUE 11): the advisor-to-actuator step.

The repo carries three committed static ledgers (compute via sheepcheck,
comms via sheepshard, memory via sheepmem) whose findings a human used to
read and hand-fix. This tool closes the loop:

    python tools/sheepopt.py --propose            # actionable proposals
    python tools/sheepopt.py --propose --json     # the CI artifact
    python tools/sheepopt.py --check SPEC         # verify a landed change
    python tools/sheepopt.py --decisions          # the decision cache

`--propose` is STDLIB-ONLY (no jax import — it runs against the committed
`analysis/budget/` files, so the CI job costs seconds) and derives three
proposal classes:

  - **donations** (the SC010 class): per committed jit, undonated inputs
    whose avals byte-match outputs (the `jits` section's in/out avals, the
    `memory` section's donated/alias counts). Known code sites
    (PROPOSAL_SITES) get the EXACT diff to apply; everything else gets the
    donating_jit instruction. Justified refusals (MEM_SUPPRESSIONS
    mirrors) are skipped.
  - **shardings** (the SC007 class): comms entries whose compiled module
    silently replicates large inputs across the mesh — propose declaring
    the sharding in the jit's registered example (the `ppo._gae_example`
    fix shape from PR 8).
  - **remat**: the memory section's live-across-scan buffers ranked by
    bytes x trip count, pointing dreamer-family train steps at
    `--remat auto` (the measured decision, compile/decisions.py) and
    everything else at `jax.checkpoint` on the scan body.

`--check SPEC` re-runs the capture for one spec through all three budget
gates (subprocesses of sheepcheck/sheepshard/sheepmem with the spec
positional) — the receipt that a landed proposal compiles and keeps every
ledger clean. `--decisions` prints the unified decision cache
(`decisions.json` next to the compile cache): per knob family the
candidates tried, the winner, receipt status and bytes/seconds deltas.

Exit codes: 0 ok (proposals are advisory), 1 --check gate failure,
2 usage/ledger error.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "pred": 1,
}

_AVAL_RE = re.compile(r"^([a-z0-9_]+)\[([0-9, ]*)\]$")

# The justified-refusal mirror of analysis/memory_check.MEM_SUPPRESSIONS
# (kept inline so --propose stays stdlib-only): (spec, jit) pairs whose
# donation opportunities are known-unsafe.
DONATION_SKIP = {
    ("ppo_recurrent", "policy_step"),
    ("ppo_recurrent@bf16", "policy_step"),
}

# Known code sites for the donation class, keyed by jit name: the exact
# diff --propose prints. The dreamer-family player_step donation landed in
# ISSUE 11 for dreamer_v1 (its refreshed ledger no longer proposes it);
# the siblings share the identical call shape.
PROPOSAL_SITES = {
    "player_step": {
        "dreamer_v2": "sheeprl_tpu/algos/dreamer_v2/dreamer_v2.py",
        "dreamer_v3": "sheeprl_tpu/algos/dreamer_v3/dreamer_v3.py",
        "dreamer_v3_decoupled": (
            "sheeprl_tpu/algos/dreamer_v3/dreamer_v3_decoupled.py"
        ),
        "p2e_dv1": "sheeprl_tpu/algos/p2e_dv1/p2e_dv1.py",
        "p2e_dv2": "sheeprl_tpu/algos/p2e_dv2/p2e_dv2.py",
        "_diff": (
            "-    player_step = jax.jit(_player_step)\n"
            "+    player_step = donating_jit(_player_step, donate_argnums=(1,))"
        ),
        "_note": (
            "the caller rebinds player_state to the jit's output every "
            "step (dreamer_v1's landed ISSUE-11 donation is the template; "
            "donating_jit keeps the CPU persistent-cache guard)"
        ),
    },
}


def aval_bytes(aval: str) -> int:
    m = _AVAL_RE.match(aval.strip())
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(m.group(1), 4)


def budget_dir(explicit: str | None = None) -> str:
    return (
        explicit
        or os.environ.get("SHEEPRL_TPU_BUDGET_DIR")
        or str(_REPO / "analysis" / "budget")
    )


def load_ledger(d: str) -> dict:
    """The committed per-spec ledger files merged by section — a stdlib
    twin of analysis/jaxpr_check.load_budget (which needs the package)."""
    out: dict = {"jits": {}, "comms": {}, "edges": {}, "memory": {}}
    if not os.path.isdir(d):
        raise FileNotFoundError(
            f"no budget ledger dir at {d} (run the sheepcheck/sheepshard/"
            "sheepmem --update-budget sweeps first)"
        )
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json") or name == "_meta.json":
            continue
        with open(os.path.join(d, name), encoding="utf-8") as fh:
            blob = json.load(fh)
        for section in out:
            out[section].update(blob.get(section, {}))
    return out


# ---------------------------------------------------------------------------
# proposers
# ---------------------------------------------------------------------------


def propose_donations(ledger: dict, floor: int = 0) -> list[dict]:
    """SC010's matcher over the committed avals: per jit, the multiset of
    input avals byte-matching output avals, minus the donations already
    declared — every remaining match is a buffer pair one `donate_argnums`
    would collapse. Ranked by candidate bytes."""
    proposals = []
    for key, fp in sorted(ledger.get("jits", {}).items()):
        spec, _, jit = key.partition("/")
        if (spec, jit) in DONATION_SKIP:
            continue
        if int(fp.get("donated", 0)) > 0:
            # already-donated jits are out of scope: the ledger records
            # aval COUNTS, so their residual matches are almost always
            # coincidental shape collisions (a conv kernel aval matching
            # another output of the same shape), not open donations —
            # SC010's var-level greedy matcher owns that precision
            continue
        ins = Counter(fp.get("in_avals", []))
        outs = Counter(fp.get("out_avals", []))
        matched = ins & outs
        open_count = sum(matched.values())
        if open_count <= 0:
            continue
        avals = sorted(matched.elements(), key=aval_bytes, reverse=True)
        candidates = avals[:open_count]
        total = sum(aval_bytes(a) for a in candidates)
        if total < floor:
            continue
        mem = ledger.get("memory", {}).get(key, {})
        site = PROPOSAL_SITES.get(jit, {})
        proposal = {
            "kind": "donation",
            "key": key,
            "open_matches": open_count,
            "candidate_avals": candidates,
            "candidate_bytes": total,
            "realized_aliases": len(mem.get("aliases", [])),
            "advice": (
                f"{open_count} undonated input(s) byte-match outputs "
                f"({total} bytes at the capture avals, scales with the "
                "live batch): donate them if the caller discards its "
                "reference (sheeprl_tpu/utils/jit.py:donating_jit)"
            ),
        }
        if spec in site:
            proposal["file"] = site[spec]
            proposal["diff"] = site["_diff"]
            proposal["note"] = site["_note"]
        proposals.append(proposal)
    proposals.sort(key=lambda p: -p["candidate_bytes"])
    return proposals


def propose_shardings(ledger: dict, floor: int = 1 << 20) -> list[dict]:
    """The SC007 class off the committed comms section: compiled modules
    whose post-SPMD HLO replicates undeclared inputs across a >1-device
    mesh. The fix shape is PR 8's: declare the input's sharding in the
    jit's registered example so the partitioner (and the warm AOT path)
    see the live layout."""
    proposals = []
    for key, fp in sorted(ledger.get("comms", {}).items()):
        replicated = fp.get("replicated_inputs") or []
        if not replicated:
            continue
        rep_bytes = int(fp.get("replicated_bytes", 0))
        if rep_bytes < floor and not replicated:
            continue
        proposals.append({
            "kind": "sharding",
            "key": key,
            "replicated_inputs": replicated,
            "replicated_bytes": rep_bytes,
            "mesh": fp.get("mesh", {}),
            "advice": (
                "declare these inputs' shardings in the jit's registered "
                "example (NamedSharding/PartitionSpec — the "
                "ppo._gae_example fix, PR 8): the partitioner stops "
                "materializing a full copy per device and the warm AOT "
                "executable matches the live layout"
            ),
        })
    proposals.sort(key=lambda p: -p["replicated_bytes"])
    return proposals


def propose_remat(ledger: dict, top: int = 8) -> list[dict]:
    """The memory section's live-across-scan buffers ranked by bytes —
    what `jax.checkpoint` on the scan body would stop keeping live for
    the whole trip count. Dreamer-family train steps point at the
    measured actuator (`--remat auto`); everything else at the manual
    wrap."""
    rows = []
    for key, fp in sorted(ledger.get("memory", {}).items()):
        for buf in fp.get("scan_buffers", []) or []:
            rows.append((int(buf.get("bytes", 0)), key, buf))
    rows.sort(key=lambda r: (-r[0], r[1]))
    proposals = []
    for nbytes, key, buf in rows[:top]:
        spec, _, jit = key.partition("/")
        dreamer = spec.split("@", 1)[0].startswith(("dreamer_", "p2e_"))
        proposals.append({
            "kind": "remat",
            "key": key,
            "buffer": buf.get("shape"),
            "bytes": nbytes,
            "trip_count": buf.get("trip_count"),
            "advice": (
                "run with `--remat auto` — the sheepopt measured decision "
                "trial-compiles the off/policy/on ladder at the run's "
                "exact shapes and accepts on peak-bytes reduction at "
                "<=5% exec-time cost with a bit-exactness receipt"
                if dreamer and jit == "train_step"
                else "wrap the scan body in jax.checkpoint "
                "(ops/scan.py:checkpoint_body) and verify with "
                "compile/decisions.py:decide_remat"
            ),
        })
    return proposals


# ---------------------------------------------------------------------------
# the decision cache (shared with compile/decisions.py, read stdlib-only)
# ---------------------------------------------------------------------------


def decision_cache_path(explicit: str | None = None) -> str:
    if explicit:
        return explicit
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from sheeprl_tpu.compile.cache import cache_dir

    return os.path.join(cache_dir(), "decisions.json")


def load_decisions(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return {}


def render_decisions(cache: dict, family: str | None = None) -> list[str]:
    lines = []
    for key, rec in sorted(cache.items()):
        fam = rec.get("family", key.split("|", 1)[0])
        if family and fam != family:
            continue
        if "candidates" in rec:
            winner = rec.get("winner")
            base = rec.get("baseline")
            cands = rec.get("candidates", {})
            wr, br = cands.get(str(winner), {}), cands.get(str(base), {})
            # the quality receipt (ISSUE 20): a non-bit-exact winner that
            # was accepted under a quality bound prints its committed
            # divergence next to the bound it satisfied
            bound = rec.get("quality_bound")
            if wr.get("bit_exact"):
                receipt = "bit-exact"
            elif bound is not None and wr.get("divergence") is not None:
                receipt = (
                    f"divergence {wr['divergence']:.3g} <= bound {bound:g}"
                    if wr.get("within_bound")
                    else f"divergence {wr['divergence']:.3g} > bound {bound:g}"
                )
            elif wr.get("bit_exact") is False:
                receipt = "DISQUALIFIED"
            else:
                receipt = "unmeasured"
            delta = ""
            if wr.get("peak_bytes") is not None and br.get("peak_bytes"):
                delta += f" bytes {wr['peak_bytes'] - br['peak_bytes']:+d}"
            if wr.get("exec_seconds") is not None and br.get("exec_seconds"):
                delta += (
                    f" seconds {wr['exec_seconds'] - br['exec_seconds']:+.4f}"
                )
            lines.append(
                f"[{fam}] {rec.get('name', '?')}: winner={winner} "
                f"(baseline {base}, {len(cands)} candidate(s), {receipt}"
                f"{',' if delta else ''}{delta}) "
                f"{'ACCEPTED' if rec.get('accepted') else 'baseline kept'}"
            )
            if bound is not None:
                for label in sorted(cands):
                    cr = cands[label]
                    if label == str(winner) or cr.get("within_bound") is not False:
                        continue
                    div = cr.get("divergence")
                    lines.append(
                        f"    DISQUALIFIED {label}: divergence "
                        f"{div:.3g} > bound {bound:g}"
                        if div is not None
                        else f"    DISQUALIFIED {label}: "
                        f"{cr.get('error') or 'quality metric failed'}"
                    )
        elif "probe" in rec:
            lines.append(
                f"[{fam}] {rec.get('name', '?')}: measured probe "
                f"({', '.join(sorted(rec['probe']))})"
            )
    return lines


# ---------------------------------------------------------------------------
# --check: one spec through all three budget gates
# ---------------------------------------------------------------------------


def check_spec(spec: str, budget: str | None = None) -> int:
    """Subprocess sheepcheck/sheepshard/sheepmem for `spec` with
    --check-budget. A tool that doesn't know the spec (rc 2 + 'unknown
    specs') is SKIPPED — e.g. sheepshard only sweeps mesh-bearing specs.
    Returns 0 when every applicable gate is clean."""
    rc_total = 0
    for tool in ("sheepcheck", "sheepshard", "sheepmem"):
        cmd = [sys.executable, str(_REPO / "tools" / f"{tool}.py"), spec,
               "--check-budget"]
        if budget:
            cmd += ["--budget", budget]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        out = proc.stdout + proc.stderr
        # sheepcheck says "unknown algos", sheepshard/sheepmem "unknown
        # specs" — either way the spec is outside that tool's population
        if proc.returncode == 2 and ("unknown specs" in out or "unknown algos" in out):
            print(f"{tool}: {spec} not in its sweep population — skipped")
            continue
        tail = [ln for ln in out.strip().splitlines() if ln][-1:]
        print(f"{tool}: rc={proc.returncode} {tail[0] if tail else ''}")
        if proc.returncode != 0:
            sys.stdout.write(out)
            rc_total = 1
    return rc_total


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--propose", action="store_true",
        help="derive donation/sharding/remat proposals from the committed "
             "ledgers (the default mode; stdlib-only, no jax)",
    )
    ap.add_argument(
        "--check", metavar="SPEC", default=None,
        help="re-run one spec's capture through all three budget gates "
             "(the receipt for a landed proposal)",
    )
    ap.add_argument(
        "--decisions", action="store_true",
        help="print the unified decision cache (winners, receipts, deltas)",
    )
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument(
        "--budget", default=None,
        help="budget ledger dir (default analysis/budget, "
             "SHEEPRL_TPU_BUDGET_DIR honored)",
    )
    ap.add_argument(
        "--family", default=None,
        help="with --decisions: only print records of this knob family "
             "(e.g. serve_quant, serve_ladder, remat)",
    )
    ap.add_argument(
        "--decision-cache", default=None,
        help="decision cache path (default: decisions.json next to the "
             "compile cache)",
    )
    ap.add_argument(
        "--floor", type=int, default=0,
        help="minimum candidate bytes for donation proposals (at the "
             "capture avals; they scale with the live batch)",
    )
    ns = ap.parse_args(argv)

    if ns.check:
        return check_spec(ns.check, ns.budget)

    if ns.decisions:
        cache = load_decisions(decision_cache_path(ns.decision_cache))
        if ns.family:
            cache = {
                k: r for k, r in cache.items()
                if r.get("family", k.split("|", 1)[0]) == ns.family
            }
        if ns.json:
            print(json.dumps(cache, indent=2, sort_keys=True))
        elif not cache:
            print("decision cache empty (no measured decisions yet)")
        else:
            for line in render_decisions(cache, family=ns.family):
                print(line)
        return 0

    # default: --propose
    try:
        ledger = load_ledger(budget_dir(ns.budget))
    except FileNotFoundError as err:
        print(err, file=sys.stderr)
        return 2
    donations = propose_donations(ledger, floor=ns.floor)
    shardings = propose_shardings(ledger)
    remat = propose_remat(ledger)
    if ns.json:
        print(json.dumps({
            "donations": donations,
            "shardings": shardings,
            "remat": remat,
        }, indent=2))
        return 0
    for p in donations:
        print(f"DONATION {p['key']}: {p['advice']}")
        for a in p["candidate_avals"]:
            print(f"    candidate {a} ({aval_bytes(a)} bytes)")
        if "diff" in p:
            print(f"    site: {p['file']}")
            for line in p["diff"].splitlines():
                print(f"    {line}")
            print(f"    note: {p['note']}")
    for p in shardings:
        print(
            f"SHARDING {p['key']}: {p['replicated_bytes']} bytes silently "
            f"replicated across {p.get('mesh')} — {p['advice']}"
        )
        for inp in p["replicated_inputs"]:
            print(f"    replicated {inp}")
    for p in remat:
        trip = f"x{p['trip_count']}" if p.get("trip_count") else "unknown trips"
        print(
            f"REMAT {p['key']}: {p['buffer']} ({p['bytes']} bytes, {trip}) "
            f"live across a scan — {p['advice']}"
        )
    print(
        f"sheepopt: {len(donations)} donation, {len(shardings)} sharding, "
        f"{len(remat)} remat proposal(s)",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
