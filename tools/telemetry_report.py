#!/usr/bin/env python
"""Offline run-health report from a `telemetry.jsonl` event log.

    python tools/telemetry_report.py <log_dir-or-telemetry.jsonl>
    python tools/telemetry_report.py --selftest

Reads the structured event log the telemetry subsystem writes
(sheeprl_tpu/telemetry/, schema in howto/observability.md) and prints, for a
finished OR crashed run:

  - run identity + lifecycle (start/end/crash, checkpoints committed,
    profile windows captured);
  - a phase-breakdown table: total seconds and share of accounted time per
    phase (`rollout`, `buffer/sample`, `train/dispatch`, ...), from the
    `Time/<phase>_seconds` series in the `log` events;
  - throughput (mean / last step-per-second) and XLA compile accounting
    (total compiles, compile seconds, recompiles AFTER the first logging
    interval — the retrace-storm signal);
  - health findings: `health.nan` events with the offending metric keys,
    peak device memory;
  - a comms-budget summary (ISSUE 8) sourced from the COMMITTED sheepshard
    ledger (`analysis/budget/`, `comms`/`edges` sections): per mesh-bearing
    jit of the run's algo, its collective histogram, hot-loop collectives,
    and estimated bytes-on-the-wire per dispatch, plus the declared data
    edges' contract status — what the mesh costs per step, next to what the
    run measured;
  - a memory-budget summary (ISSUE 10) sourced from the committed sheepmem
    ledger (`memory` section): per jit of the run's algo, its static
    peak/temp/argument bytes, realized-vs-declared donation aliases,
    embedded-constant bytes and the largest scan-carried buffer — compared
    against the run's `Memory/*` gauges when present;
  - a sheepopt decisions summary (ISSUE 11) sourced from the unified
    decision cache (`decisions.json` next to the compile cache,
    compile/decisions.py): per measured knob decision (scan_unroll, remat,
    batch_chunk, ...) the candidates tried, the winner, its bit-exactness
    receipt status and bytes/seconds deltas vs the baseline.

Pure stdlib + the repo's telemetry package (no jax import), so it runs
anywhere the JSONL can be copied to. `--selftest` synthesizes a small run
via the real Telemetry class, reports on it, and asserts the critical
fields — the CI smoke that the writer and this reader stay in sync.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def load_events(path: str) -> list[dict]:
    """Parse a telemetry.jsonl (or a log_dir containing one). Tolerates a
    truncated final line (crash mid-write). Non-learner processes write
    role shards (`telemetry.<role>.jsonl`, sheepscope ISSUE 17) — a dir
    holding only those (e.g. a serve run) falls back to the first shard;
    merging ALL shards onto one timeline is tools/sheeptrace.py's job."""
    if os.path.isdir(path):
        candidate = os.path.join(path, "telemetry.jsonl")
        if not os.path.exists(candidate):
            import glob as _glob

            shards = sorted(_glob.glob(os.path.join(path, "telemetry.*.jsonl")))
            if shards:
                candidate = shards[0]
        path = candidate
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{path} not found — did the run write telemetry? "
            "(rank 0 only; SHEEPRL_TPU_TELEMETRY=0 disables)"
        )
    events = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                # a crash can truncate the last line; everything before it
                # is still a valid record of the run
                break
    return events


def summarize(events: list[dict]) -> dict:
    """Aggregate an event list into the report's data model."""
    summary: dict = {
        "start": None,
        "end": None,
        "crash": None,
        "checkpoints": [],
        "profile_windows": 0,
        "nan_events": [],
        "log_events": 0,
        "first_ts": None,
        "last_ts": None,
        "last_step": None,
        "phase_seconds": {},
        "sps_series": [],
        "total_compiles": 0.0,
        "total_compile_seconds": 0.0,
        "late_recompiles": 0.0,
        "late_compile_seconds": 0.0,
        "peak_memory_bytes": 0.0,
        "gauges_last": {},
        # ISSUE 5 compile-latency subsystem (compile/plan.py)
        "compile_events": [],       # per-executable `compile` events
        "partition_events": [],     # `compile.partition` heuristic decisions
        "first_update": None,       # the `first_update` stamp event
        "compile_gauges": {},       # last Compile/* gauge values
        "anakin_gauges": {},        # last Anakin/* gauge values (jax envs)
        # ISSUE 12 resilience subsystem (resilience/)
        "fault_injected": [],       # fault.injected events (site/step)
        "fault_recovered": [],      # fault.recovered events (site/action)
        "preempt": None,            # the preempt lifecycle event (rc 75 exit)
        "preempt_signal": None,     # when the grace window opened
        "resume": None,             # the resume-resolution event
        "checkpoint_corrupt": [],   # skipped/failed checkpoint candidates
        "checkpoint_errors": [],    # retried checkpoint writes
        "fault_gauges": {},         # last Fault/* gauge values
        # ISSUE 14 flock subsystem (flock/)
        "flock_started": None,      # flock.started (address/mode)
        "flock_events": [],         # flock.* membership lifecycle events
        "flock_gauges": {},         # last Flock/* gauge values
        "flock_staleness": {},      # actor_id -> list of staleness samples
        # ISSUE 15 serving subsystem (serve/)
        "serve_start": None,        # serve.start (address/algo/rungs)
        "serve_stop": None,         # serve.stop (completed/final version)
        "serve_reloads": [],        # serve.reload timeline (ok/version/seconds)
        "serve_ladder": [],         # serve.ladder rung-sizing decisions
        "serve_gauges": {},         # last Serve/* gauge values
        # ISSUE 16 distributed fault tolerance (sheepchaos)
        "serve_events": [],         # serve.* hardening events (conn_error,
                                    # draining/drained, client_close_error)
        # ISSUE 18 sheepsync runtime thread sanitizer
        "sync_events": [],          # sync.* events (order_violation,
                                    # sanitizer_start/stop)
        "sync_gauges": {},          # last Sync/* gauge values
    }
    for ev in events:
        ts = ev.get("ts")
        if ts is not None:
            summary["first_ts"] = ts if summary["first_ts"] is None else summary["first_ts"]
            summary["last_ts"] = ts
        kind = ev.get("event")
        if kind == "start":
            summary["start"] = ev
        elif kind == "end":
            summary["end"] = ev
        elif kind == "crash":
            summary["crash"] = ev
        elif kind == "checkpoint":
            summary["checkpoints"].append(ev.get("path"))
        elif kind == "profile.start":
            summary["profile_windows"] += 1
        elif kind == "health.nan":
            summary["nan_events"].append(ev)
        elif kind == "compile":
            summary["compile_events"].append(ev)
        elif kind == "compile.partition":
            summary["partition_events"].append(ev)
        elif kind == "first_update":
            summary["first_update"] = ev
        elif kind == "fault.injected":
            summary["fault_injected"].append(ev)
        elif kind == "fault.recovered":
            summary["fault_recovered"].append(ev)
        elif kind == "preempt":
            summary["preempt"] = ev
        elif kind == "preempt.signal":
            summary["preempt_signal"] = ev
        elif kind == "resume":
            summary["resume"] = ev
        elif kind in ("checkpoint.corrupt", "checkpoint.fallback"):
            summary["checkpoint_corrupt"].append(ev)
        elif kind == "checkpoint.error":
            summary["checkpoint_errors"].append(ev)
        elif kind == "flock.started":
            summary["flock_started"] = ev
        elif isinstance(kind, str) and kind.startswith("flock."):
            summary["flock_events"].append(ev)
        elif kind == "serve.start":
            summary["serve_start"] = ev
        elif kind == "serve.stop":
            summary["serve_stop"] = ev
        elif kind == "serve.reload":
            summary["serve_reloads"].append(ev)
        elif kind == "serve.ladder":
            summary["serve_ladder"].append(ev)
        elif isinstance(kind, str) and kind.startswith("serve."):
            summary["serve_events"].append(ev)
        elif isinstance(kind, str) and kind.startswith("sync."):
            summary["sync_events"].append(ev)
        elif kind == "log":
            summary["log_events"] += 1
            if ev.get("step") is not None:
                summary["last_step"] = ev["step"]
            metrics = ev.get("metrics", {})
            for k, v in metrics.items():
                if not isinstance(v, (int, float)):
                    continue
                if k.startswith("Time/") and k.endswith("_seconds"):
                    phase = k[len("Time/"):-len("_seconds")]
                    summary["phase_seconds"][phase] = (
                        summary["phase_seconds"].get(phase, 0.0) + v
                    )
                elif k == "Time/step_per_second":
                    summary["sps_series"].append(v)
                elif k == "XLA/total_compiles":
                    summary["total_compiles"] = v
                elif k == "XLA/total_compile_seconds":
                    summary["total_compile_seconds"] = v
                elif k == "XLA/recompiles" and summary["log_events"] > 1:
                    summary["late_recompiles"] += v
                elif k == "XLA/compile_seconds" and summary["log_events"] > 1:
                    summary["late_compile_seconds"] += v
                elif k.startswith("Memory/") and k.endswith("bytes_in_use"):
                    summary["peak_memory_bytes"] = max(summary["peak_memory_bytes"], v)
                elif k.startswith("Decoupled/"):
                    summary["gauges_last"][k] = v
                elif k.startswith("Compile/"):
                    summary["compile_gauges"][k] = v
                elif k.startswith("Anakin/"):
                    summary["anakin_gauges"][k] = v
                elif k.startswith("Fault/"):
                    summary["fault_gauges"][k] = v
                elif k.startswith("Serve/"):
                    summary["serve_gauges"][k] = v
                elif k.startswith("Sync/"):
                    summary["sync_gauges"][k] = v
                elif k.startswith("Flock/"):
                    summary["flock_gauges"][k] = v
                    parts = k.split("/")
                    if len(parts) == 3 and parts[2] == "staleness_s":
                        summary["flock_staleness"].setdefault(
                            parts[1], []
                        ).append(v)
    # the "end" event carries phase time accumulated after the last interval
    if summary["end"]:
        for phase, secs in (summary["end"].get("phases") or {}).items():
            if isinstance(secs, (int, float)):
                summary["phase_seconds"][phase] = (
                    summary["phase_seconds"].get(phase, 0.0) + secs
                )
    return summary


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_ledger_sections(
    sections: tuple[str, ...], path: str | None = None
) -> list[dict]:
    """The requested sections of the committed `analysis/budget/` ledger —
    per-algo dir layout only (the legacy single-blob fallback is gone,
    ISSUE 11). Stdlib-only (this report must run anywhere the JSONL can
    be copied to); missing ledger -> empty dicts."""
    base = path or os.path.join(_REPO, "analysis", "budget")
    out: list[dict] = [dict() for _ in sections]
    try:
        if os.path.isdir(base):
            for name in sorted(os.listdir(base)):
                if not name.endswith(".json") or name == "_meta.json":
                    continue
                with open(os.path.join(base, name), encoding="utf-8") as fh:
                    blob = json.load(fh)
                for i, section in enumerate(sections):
                    out[i].update(blob.get(section, {}))
    except (OSError, json.JSONDecodeError):
        return [dict() for _ in sections]
    return out


def load_comms_ledger(path: str | None = None) -> tuple[dict, dict]:
    """`(comms, edges)` from the committed sheepshard ledger."""
    comms, edges = load_ledger_sections(("comms", "edges"), path)
    return comms, edges


def load_memory_ledger(path: str | None = None) -> dict:
    """The committed sheepmem `memory` section (ISSUE 10)."""
    (memory,) = load_ledger_sections(("memory",), path)
    return memory


def load_concurrency_ledger(path: str | None = None) -> dict:
    """The committed sheepsync `concurrency` section (ISSUE 18)."""
    (concurrency,) = load_ledger_sections(("concurrency",), path)
    return concurrency


def load_decision_cache(path: str | None = None) -> dict:
    """The unified sheepopt decision cache (`decisions.json` next to the
    compile cache, compile/decisions.py) — stdlib-only, empty dict when
    absent. An explicit path, else the writer's own resolution
    (compile/cache.py:cache_dir)."""
    if path is None:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from sheeprl_tpu.compile.cache import cache_dir

        path = os.path.join(cache_dir(), "decisions.json")
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {}


def render_sheepopt_decisions(cache: dict) -> str:
    """The sheepopt decisions section (ISSUE 11): per measured decision in
    the unified cache, the knob family, candidates tried, winner, receipt
    status and the winner's bytes/seconds deltas vs the baseline."""
    lines = ["== sheepopt decisions (unified decision cache) =="]
    ladders = {k: v for k, v in cache.items() if isinstance(v, dict) and "candidates" in v}
    probes = {k: v for k, v in cache.items() if isinstance(v, dict) and "probe" in v}
    if not ladders and not probes:
        lines.append("decision cache empty (no measured decisions this host)")
        return "\n".join(lines)
    for key in sorted(ladders):
        rec = ladders[key]
        cands = rec.get("candidates", {})
        winner = str(rec.get("winner"))
        base = str(rec.get("baseline"))
        wr, br = cands.get(winner, {}), cands.get(base, {})
        disq = sorted(
            lbl for lbl, c in cands.items() if c.get("bit_exact") is False
        )
        receipt = "bit-exact" if wr.get("bit_exact") else "baseline"
        deltas = []
        if wr.get("peak_bytes") is not None and br.get("peak_bytes"):
            d = int(wr["peak_bytes"]) - int(br["peak_bytes"])
            deltas.append(f"bytes {d:+d} ({d / max(br['peak_bytes'], 1):+.0%})")
        if wr.get("exec_seconds") is not None and br.get("exec_seconds"):
            d = float(wr["exec_seconds"]) - float(br["exec_seconds"])
            deltas.append(
                f"seconds {d:+.4f} ({d / max(br['exec_seconds'], 1e-12):+.1%})"
            )
        lines.append(
            f"[{rec.get('family', '?')}] {rec.get('name', '?')}: "
            f"{len(cands)} candidate(s) tried, winner={winner} "
            f"({'ACCEPTED' if rec.get('accepted') else 'baseline kept'}, "
            f"{receipt}"
            + (f", disqualified: {','.join(disq)}" if disq else "")
            + (f"; {' '.join(deltas)}" if deltas else "")
            + ")"
        )
    for key in sorted(probes):
        rec = probes[key]
        lines.append(
            f"[{rec.get('family', '?')}] {rec.get('name', '?')}: measured "
            f"probe cached ({', '.join(sorted(rec['probe']))})"
        )
    return "\n".join(lines)


def _fmt_wire(n: float) -> str:
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f}MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KiB"
    return f"{n:.0f}B"


def _hist(h: dict) -> str:
    return (
        ",".join(f"{k}x{v}" for k, v in sorted(h.items())) if h else "-"
    )


def render_comms_budget(comms: dict, edges: dict, algo: str | None = None) -> str:
    """The comms-budget section: the committed per-jit collective ledger
    filtered to `algo`'s mesh specs (a spec key is `<algo>[@variant]/<jit>`)."""

    def of_algo(key: str) -> bool:
        return algo is None or key.split("/", 1)[0].split("@", 1)[0] == algo

    lines = ["== comms budget (committed sheepshard ledger) =="]
    rows = [(k, v) for k, v in sorted(comms.items()) if of_algo(k)]
    if not rows:
        lines.append(
            f"no mesh-bearing specs in the ledger for algo={algo!r} "
            "(see howto/static_analysis.md, sheepshard)"
        )
        return "\n".join(lines)
    widths = (
        max(len("spec/jit"), *(len(k) for k, _ in rows)) + 2, 6, 26, 22, 12,
    )
    lines.append(
        _fmt_row(("spec/jit", "parts", "collectives", "hot(in-loop)", "wire/step"), widths)
    )
    for key, fp in rows:
        lines.append(_fmt_row(
            (
                key,
                fp.get("num_partitions", 1),
                _hist(fp.get("collectives", {})),
                _hist(fp.get("hot_collectives", {})),
                _fmt_wire(fp.get("wire_bytes", 0)),
            ),
            widths,
        ))
        for item in fp.get("replicated_inputs", []):
            lines.append(f"  SILENTLY REPLICATED input {item}")
    for key, rec in sorted(edges.items()):
        if not of_algo(key):
            continue
        status = rec.get("status", "?")
        flag = " <- RESHARD THRASH" if status == "mismatch" else ""
        lines.append(
            f"edge {key}: expect={rec.get('expect', '?')} status={status}{flag}"
        )
    return "\n".join(lines)


def render_memory_budget(
    memory: dict, algo: str | None = None, runtime_peak_bytes: float = 0.0
) -> str:
    """The memory-budget section (ISSUE 10): the committed per-jit sheepmem
    ledger filtered to `algo`'s specs — static peak/temp/alias/constant
    bytes per jit — plus the static-vs-runtime comparison when the run
    recorded `Memory/*` gauges."""

    def of_algo(key: str) -> bool:
        return algo is None or key.split("/", 1)[0].split("@", 1)[0] == algo

    lines = ["== memory budget (committed sheepmem ledger) =="]
    rows = [(k, v) for k, v in sorted(memory.items()) if of_algo(k)]
    if not rows:
        lines.append(
            f"no memory fingerprints in the ledger for algo={algo!r} "
            "(run tools/sheepmem.py --update-budget)"
        )
        return "\n".join(lines)
    widths = (
        max(len("spec/jit"), *(len(k) for k, _ in rows)) + 2,
        10, 10, 10, 12, 10,
    )
    lines.append(_fmt_row(
        ("spec/jit", "peak", "temp", "args", "aliases", "const"), widths
    ))
    static_peak = 0
    for key, fp in rows:
        static_peak = max(static_peak, int(fp.get("peak_bytes", 0)))
        lines.append(_fmt_row(
            (
                key,
                _fmt_wire(fp.get("peak_bytes", 0)),
                _fmt_wire(fp.get("temp_bytes", 0)),
                _fmt_wire(fp.get("argument_bytes", 0)),
                f"{len(fp.get('aliases', []))}/{fp.get('donated', 0)}",
                _fmt_wire(fp.get("constant_bytes", 0)),
            ),
            widths,
        ))
        for item in fp.get("large_constants", []):
            lines.append(f"  LARGE EMBEDDED CONSTANT {item}")
        for buf in fp.get("scan_buffers", [])[:1]:
            trip = buf.get("trip_count")
            lines.append(
                f"  largest scan-carried buffer: {buf.get('shape')} "
                f"({_fmt_wire(buf.get('bytes', 0))}"
                + (f" x{trip} iterations)" if trip else ")")
            )
    if runtime_peak_bytes and static_peak:
        ratio = runtime_peak_bytes / static_peak
        lines.append(
            f"runtime peak (Memory/* gauges) {_fmt_wire(runtime_peak_bytes)} "
            f"vs static max peak {_fmt_wire(static_peak)} "
            f"({ratio:.1f}x — buffers + executables beyond any single jit)"
        )
    return "\n".join(lines)


def render_concurrency(conc: dict, summary: dict) -> str:
    """The sheepsync concurrency section (ISSUE 18): the committed lock
    graph, guard map and thread inventory from the ledger, merged with the
    run's live `Sync/*` sanitizer gauges and any `sync.order_violation`
    timeline. Either side may be empty — ledger-only (no sanitized run) and
    run-only (ledger not committed yet) both render."""
    lines = ["== sheepsync concurrency (lock graph / thread sanitizer) =="]
    if conc:
        lines.append(
            f"ledger fingerprint {conc.get('fingerprint', '?')}  "
            f"(analysis/budget/concurrency.json)"
        )
        roles = conc.get("roles", {})
        for role in sorted(roles):
            locks = roles[role].get("locks", {})
            if not locks:
                continue
            lines.append(f"  [{role}] locks:")
            for ident, ld in sorted(locks.items()):
                backing = f" on {ld['backing']}" if ld.get("backing") else ""
                lines.append(
                    f"    {ident:52s} {ld.get('kind', '?'):9s}{backing} "
                    f"({ld.get('site', '?')})"
                )
        edges = conc.get("lock_order", {}).get("edges", [])
        chains = conc.get("lock_order", {}).get("chains", {})
        lines.append("  lock-order edges (outer -> inner):")
        if not edges:
            lines.append("    (none)")
        for a, b in edges:
            lines.append(f"    {a} -> {b}")
            chain = chains.get(f"{a} -> {b}")
            if chain:
                lines.append(f"        {chain}")
        for cyc in conc.get("lock_order", {}).get("cycles", []):
            lines.append(f"    CYCLE: {cyc[0]} <-> {cyc[1]}")
        guarded = []
        for role in sorted(roles):
            for attr, guard in sorted(
                (roles[role].get("guards") or {}).items()
            ):
                guarded.append(
                    f"    {role}:{attr:40s} "
                    + (guard if guard else "UNGUARDED")
                )
        if guarded:
            lines.append("  shared-write guard map:")
            lines.extend(guarded)
        threads = [
            (role, t)
            for role in sorted(roles)
            for t in roles[role].get("threads", [])
        ]
        if threads:
            lines.append("  declared threads:")
            for role, t in threads:
                d = {True: "daemon", False: "non-daemon"}.get(
                    t.get("daemon"), "daemon?"
                )
                j = "joined" if t.get("joined") else "unjoined"
                lines.append(
                    f"    [{role}] {t.get('name', '?'):26s} "
                    f"target={t.get('target', '?'):34s} {d:11s} {j}"
                )
    gauges = summary.get("sync_gauges", {})
    if gauges:
        lines.append("  runtime sanitizer (last Sync/* gauges):")
        acq = gauges.get("Sync/acquisitions", 0.0)
        lines.append(
            f"    acquisitions {acq:.0f}  contended "
            f"{gauges.get('Sync/contended', 0.0):.0f}  "
            f"hold max {gauges.get('Sync/hold_ms_max', 0.0):.1f}ms "
            f"avg {gauges.get('Sync/hold_ms_avg', 0.0):.3f}ms  "
            f"wait max {gauges.get('Sync/wait_ms_max', 0.0):.1f}ms"
        )
        lines.append(
            f"    observed edges {gauges.get('Sync/observed_edges', 0.0):.0f} "
            f"(undeclared {gauges.get('Sync/undeclared_edges', 0.0):.0f})  "
            f"order violations "
            f"{gauges.get('Sync/order_violations', 0.0):.0f}"
        )
    first_ts = summary.get("first_ts")
    violations = [
        ev
        for ev in summary.get("sync_events", [])
        if ev.get("event") == "sync.order_violation"
    ]
    if violations:
        lines.append("  ORDER VIOLATIONS (runtime inversions of the DAG):")
        for ev in violations:
            rel = ""
            if first_ts is not None and ev.get("ts") is not None:
                rel = f"t+{ev['ts'] - first_ts:7.2f}s  "
            lines.append(
                f"    {rel}[{ev.get('thread', '?')}] acquired "
                f"{ev.get('acquiring', '?')} while holding "
                f"{ev.get('held', '?')}"
            )
    elif gauges or conc:
        lines.append("  no lock-order violations recorded")
    return "\n".join(lines)


def _fmt_row(cols, widths):
    return "  ".join(str(c).ljust(w) for c, w in zip(cols, widths)).rstrip()


# Distributed fault-tolerance lifecycle (ISSUE 16): which events mark a
# failure being NOTICED vs SURVIVED, per tier. The timeline pairs each
# recovery with the nearest preceding detection on the same scope (actor id
# for flock, the whole server for serve) to print recovery latencies.
_DETECT_EVENTS = {
    "flock.conn_error": "flock",
    "flock.actor_stale": "flock",
    "flock.actor_disconnected": "flock",
    "serve.conn_error": "serve",
    "serve.client_close_error": "serve",
    "serve.draining": "serve",
}
_RECOVER_EVENTS = {
    "flock.actor_rejoined": "flock",
    "flock.actor_adopted": "flock",
    "flock.actor_respawned": "flock",
    "flock.resumed": "flock",
    "serve.drained": "serve",
}


def recovery_timeline(summary: dict) -> list[str]:
    """Per-tier fault/recovery timeline: every injection, detection and
    recovery event in one chronological view, recoveries annotated with
    the latency since the matching detection."""
    entries: list[tuple[float, str, str, str]] = []  # ts, tier, verb, detail

    def _detail(ev, skip=("event", "ts", "step")):
        return " ".join(
            f"{k}={v}" for k, v in ev.items() if k not in skip and v is not None
        )

    for ev in summary["fault_injected"]:
        site = str(ev.get("site", "?"))
        tier = (
            "net" if site.startswith("net.")
            else "peer" if site.startswith("peer.")
            else "train"
        )
        param = "" if ev.get("param") is None else f":{ev['param']:g}"
        entries.append(
            (ev.get("ts") or 0.0, tier, "INJECT",
             f"{site}@{ev.get('step')}{param}")
        )

    pool = summary["flock_events"] + summary["serve_events"]
    detections: list[dict] = []
    for ev in sorted(pool, key=lambda e: e.get("ts") or 0.0):
        kind = ev["event"]
        ts = ev.get("ts") or 0.0
        if kind in _DETECT_EVENTS:
            detections.append(ev)
            verb = {
                "flock.actor_stale": "EVICT",
                "serve.draining": "DRAIN",
            }.get(kind, "DETECT")
            entries.append(
                (ts, _DETECT_EVENTS[kind], verb,
                 f"{kind.split('.', 1)[1]} {_detail(ev)}")
            )
        elif kind in _RECOVER_EVENTS:
            # latency: nearest preceding detection on the same scope
            scope = ev.get("actor_id")
            prior = [
                d for d in detections
                if (d.get("ts") or 0.0) <= ts
                and (scope is None or d.get("actor_id") in (None, scope))
                and _DETECT_EVENTS[d["event"]] == _RECOVER_EVENTS[kind]
            ]
            lat = (
                f" (+{ts - (prior[-1].get('ts') or 0.0):.2f}s after "
                f"{prior[-1]['event'].split('.', 1)[1]})"
                if prior else ""
            )
            entries.append(
                (ts, _RECOVER_EVENTS[kind], "RECOVER",
                 f"{kind.split('.', 1)[1]} {_detail(ev)}{lat}")
            )

    if not entries:
        return []
    t0 = summary["first_ts"] or 0.0
    lines = ["distributed recovery timeline (per tier):"]
    for ts, tier, verb, detail in sorted(entries, key=lambda e: e[0]):
        lines.append(f"t+{ts - t0:7.2f}s  [{tier:<5}] {verb:<7} {detail}")
    return lines


def render(summary: dict) -> str:
    """The human-readable report."""
    lines: list[str] = []
    start = summary["start"] or {}
    lines.append("== run ==")
    lines.append(
        f"algo={start.get('algo', '?')} env={start.get('env_id', '?')} "
        f"seed={start.get('seed', '?')} platform={start.get('platform', '?')} "
        f"device_kind={start.get('device_kind', '?')} "
        f"devices={start.get('local_devices', '?')}"
    )
    if summary["first_ts"] is not None and summary["last_ts"] is not None:
        lines.append(
            f"wall_clock={summary['last_ts'] - summary['first_ts']:.1f}s "
            f"log_events={summary['log_events']} last_step={summary['last_step']}"
        )
    if summary["crash"]:
        lines.append(f"OUTCOME: CRASHED — {summary['crash'].get('error')}")
    elif summary["preempt"]:
        p = summary["preempt"]
        lines.append(
            f"OUTCOME: PREEMPTED at step {p.get('step')} "
            f"({p.get('signal', '?')}, resumable rc {p.get('rc')}) — "
            "restart with --resume auto"
        )
    elif summary["end"]:
        lines.append("OUTCOME: completed (clean end event)")
    else:
        lines.append("OUTCOME: unknown (no end/crash event — log truncated or run live)")
    lines.append(
        f"checkpoints={len(summary['checkpoints'])} "
        f"profile_windows={summary['profile_windows']}"
    )

    lines.append("")
    lines.append("== phase breakdown ==")
    phases = summary["phase_seconds"]
    if phases:
        total = sum(phases.values())
        widths = (max(len("total (accounted)"), *(len(p) for p in phases)) + 2, 12, 8)
        lines.append(_fmt_row(("phase", "seconds", "share"), widths))
        for name, secs in sorted(phases.items(), key=lambda kv: -kv[1]):
            share = f"{100 * secs / total:.1f}%" if total > 0 else "-"
            lines.append(_fmt_row((name, f"{secs:.3f}", share), widths))
        lines.append(_fmt_row(("total (accounted)", f"{total:.3f}", "100%"), widths))
    else:
        lines.append("no phase timings recorded")

    lines.append("")
    lines.append("== throughput / compiles ==")
    if summary["sps_series"]:
        sps = summary["sps_series"]
        lines.append(
            f"step_per_second: mean={sum(sps) / len(sps):.1f} last={sps[-1]:.1f}"
        )
    lines.append(
        f"xla_compiles={summary['total_compiles']:.0f} "
        f"({summary['total_compile_seconds']:.1f}s total)"
    )
    lines.append(
        f"recompiles after first interval: {summary['late_recompiles']:.0f} "
        f"({summary['late_compile_seconds']:.1f}s) "
        + ("<- RETRACE STORM?" if summary["late_recompiles"] > 0 else "(clean)")
    )

    lines.append("")
    lines.append("== compile breakdown (warm-start subsystem) ==")
    g = summary["compile_gauges"]
    fu = summary["first_update"]
    if fu is not None:
        lines.append(
            f"time_to_first_update={fu.get('seconds', 0):.1f}s "
            f"(warm_compile={fu.get('warm_compile', '?')})"
        )
    if summary["compile_events"] or g:
        warm = [e for e in summary["compile_events"] if e.get("mode") == "warm"]
        falls = [
            e for e in summary["compile_events"] if e.get("mode") == "aot_fallback"
        ]
        if warm:
            widths = (max(len("executable"), *(len(str(e.get("jit"))) for e in warm)) + 2, 12, 8, 8)
            lines.append(_fmt_row(("executable", "compile_s", "hits", "misses"), widths))
            for e in warm:
                lines.append(_fmt_row(
                    (e.get("jit"), f"{e.get('seconds', 0):.2f}",
                     e.get("cache_hits", 0), e.get("cache_misses", 0)),
                    widths,
                ))
        if g:
            lines.append(
                f"plan: entries={g.get('Compile/plan_entries', 0):.0f} "
                f"compiled={g.get('Compile/plan_compiled', 0):.0f} "
                f"aot_calls={g.get('Compile/aot_calls', 0):.0f} "
                f"fallbacks={g.get('Compile/aot_fallbacks', 0):.0f} "
                f"cache {g.get('Compile/cache_hits', 0):.0f} hit / "
                f"{g.get('Compile/cache_misses', 0):.0f} miss"
            )
        for e in falls:
            lines.append(f"AOT FALLBACK {e.get('jit')}: {e.get('error', '')}")
        for e in summary["partition_events"]:
            lines.append(
                f"partition {e.get('jit')}: chunk={e.get('chunk')} "
                f"({e.get('reason', '')})"
            )
    else:
        lines.append("no warm-start compile telemetry (cold path or pre-round-6 log)")

    a = summary["anakin_gauges"]
    if a:
        lines.append("")
        lines.append("== anakin collection (on-device jax envs) ==")
        lines.append(
            f"env_steps_per_second: last={a.get('Anakin/env_steps_per_second', 0):,.0f} "
            f"avg={a.get('Anakin/env_steps_per_second_avg', 0):,.0f}"
        )
        lines.append(
            f"scan_span={a.get('Anakin/scan_span', 0):.0f} "
            f"env_batch={a.get('Anakin/env_batch', 0):.0f} "
            f"devices={a.get('Anakin/devices', 0):.0f} "
            f"rollouts={a.get('Anakin/rollouts', 0):.0f} "
            f"env_steps_total={a.get('Anakin/env_steps_total', 0):,.0f}"
        )

    fg = summary["flock_gauges"]
    if fg or summary["flock_started"] or summary["flock_events"]:
        lines.append("")
        lines.append("== flock (actor-learner runtime) ==")
        started = summary["flock_started"] or {}
        lines.append(
            f"service: address={started.get('address', '?')} "
            f"mode={started.get('mode', '?')}"
        )
        lines.append(
            f"fleet: actors_alive={fg.get('Flock/actors_alive', 0):.0f} "
            f"weight_version={fg.get('Flock/weight_version', 0):.0f} "
            f"rows_total={fg.get('Flock/rows_total', 0):,.0f} "
            f"chunks_dropped={fg.get('Flock/chunks_dropped', 0):.0f}"
        )
        # Per-actor table from the Flock/actor{N}/<field> gauge namespace.
        actors = sorted(
            {
                k.split("/")[1]
                for k in fg
                if k.count("/") == 2 and k.split("/")[1].startswith("actor")
            },
            key=lambda a: (len(a), a),
        )
        if actors:
            headers = (
                "actor", "steps/s", "env_steps", "wv", "lag",
                "stale_s", "hb_s", "fill", "gen", "up",
            )
            widths = (8, 10, 12, 5, 5, 9, 7, 7, 5, 4)
            lines.append(_fmt_row(headers, widths))
            for a in actors:
                def g(field, _a=a):
                    return fg.get(f"Flock/{_a}/{field}")

                def num(field, fmt, _g=g):
                    v = _g(field)
                    return format(v, fmt) if isinstance(v, (int, float)) else "-"

                lines.append(_fmt_row(
                    (
                        a,
                        num("env_steps_s", ",.0f"),
                        num("env_steps", ",.0f"),
                        num("weight_version", ".0f"),
                        num("version_lag", ".0f"),
                        num("staleness_s", ".2f"),
                        num("heartbeat_age_s", ".2f"),
                        num("shard_fill", ".2f"),
                        num("generation", ".0f"),
                        "yes" if g("connected") else "no",
                    ),
                    widths,
                ))
        # Staleness distribution across every logged interval, not just the
        # last gauge value.
        all_stale = [v for vs in summary["flock_staleness"].values() for v in vs]
        if all_stale:
            s = sorted(all_stale)
            lines.append(
                f"weight staleness (all actors, {len(s)} samples): "
                f"min={s[0]:.2f}s p50={s[len(s) // 2]:.2f}s "
                f"p90={s[min(len(s) - 1, int(len(s) * 0.9))]:.2f}s "
                f"max={s[-1]:.2f}s"
            )
        if summary["flock_events"]:
            counts: dict = {}
            for ev in summary["flock_events"]:
                counts[ev["event"]] = counts.get(ev["event"], 0) + 1
            lines.append(
                "membership: "
                + " ".join(f"{k.split('.', 1)[1]}={v}" for k, v in sorted(counts.items()))
            )
            t0 = summary["first_ts"] or 0.0
            for ev in summary["flock_events"]:
                ts = ev.get("ts")
                rel = f"t+{ts - t0:7.2f}s" if isinstance(ts, (int, float)) else "t+      ?"
                what = ev["event"].split(".", 1)[1].upper()
                detail = " ".join(
                    f"{k}={v}"
                    for k, v in ev.items()
                    if k not in ("event", "ts", "step")
                )
                lines.append(f"{rel}  {what:<12} {detail}")

    sg = summary["serve_gauges"]
    if sg or summary["serve_start"] or summary["serve_ladder"]:
        lines.append("")
        lines.append("== serving (batched inference tier) ==")
        started = summary["serve_start"] or {}
        lines.append(
            f"server: algo={started.get('algo', '?')} "
            f"address={started.get('address', '?')} "
            f"rungs={started.get('rungs', '?')} "
            f"ckpt={started.get('ckpt') or '-'}"
        )
        if summary["serve_ladder"]:
            lines.append("batch ladder (ledger-first sizing):")
            for d in summary["serve_ladder"]:
                status = "accepted" if d.get("accepted") else "REJECTED"
                peak = d.get("peak_bytes")
                peak_s = _fmt_wire(peak) if isinstance(peak, (int, float)) else "-"
                lines.append(
                    f"  rung {d.get('rung', '?'):>4}  {status:<9} "
                    f"{str(d.get('source', '?')):<7} peak={peak_s:<10} "
                    f"{d.get('reason', '')}"
                )
        if sg:
            lines.append(
                f"load: qps={sg.get('Serve/qps', 0):.1f} "
                f"latency p50={sg.get('Serve/latency_p50_ms', 0):.2f}ms "
                f"p99={sg.get('Serve/latency_p99_ms', 0):.2f}ms "
                f"batch_occupancy={sg.get('Serve/batch_occupancy', 0):.2f}"
            )
            lines.append(
                f"requests: served={sg.get('Serve/served_total', 0):,.0f} "
                f"shed={sg.get('Serve/shed_total', 0):.0f} "
                f"oversized={sg.get('Serve/oversized_total', 0):.0f} "
                f"failed={sg.get('Serve/failed_total', 0):.0f} "
                f"dispatches={sg.get('Serve/dispatches', 0):,.0f}"
            )
            lines.append(
                f"params: version={sg.get('Serve/params_version', 0):.0f} "
                f"reloads={sg.get('Serve/reloads', 0):.0f} "
                f"reload_failures={sg.get('Serve/reload_failures', 0):.0f}"
            )
        # Hot-reload timeline: every swap (and every refused swap) with the
        # version the server kept serving.
        t0 = summary["first_ts"] or 0.0
        for ev in summary["serve_reloads"]:
            ts = ev.get("ts")
            rel = f"t+{ts - t0:7.2f}s" if isinstance(ts, (int, float)) else "t+      ?"
            if ev.get("ok"):
                lines.append(
                    f"{rel}  RELOAD  -> v{ev.get('version')} "
                    f"({ev.get('seconds', 0):.2f}s) {ev.get('path', '')}"
                )
            else:
                lines.append(
                    f"{rel}  RELOAD-FAILED kept v{ev.get('version')}: "
                    f"{(ev.get('error') or '')[:80]}"
                )
        if summary["serve_stop"]:
            st = summary["serve_stop"]
            lines.append(
                f"stopped: completed={st.get('completed')} "
                f"final_version={st.get('version')}"
            )

    # distributed detections/recoveries (ISSUE 16) open the section too:
    # a partition that only shows up as flock.conn_error + actor_rejoined
    # still belongs in the fault/recovery story
    timeline = recovery_timeline(summary)
    resil_any = (
        summary["fault_injected"]
        or summary["fault_recovered"]
        or summary["preempt"]
        or summary["resume"]
        or summary["checkpoint_corrupt"]
        or summary["checkpoint_errors"]
        or summary["fault_gauges"]
        or timeline
    )
    if resil_any:
        lines.append("")
        lines.append("== resilience (faults / recovery) ==")
        t0 = summary["first_ts"] or 0.0

        def rel(ev):
            ts = ev.get("ts")
            return f"t+{ts - t0:7.2f}s" if isinstance(ts, (int, float)) else "t+      ?"

        if summary["resume"]:
            r = summary["resume"]
            lines.append(
                f"{rel(r)}  RESUME  {r.get('mode')} -> {r.get('checkpoint')}"
                + (
                    f" ({r.get('fallbacks')} fallback candidate(s))"
                    if r.get("fallbacks") is not None
                    else ""
                )
            )
        for ev in summary["fault_injected"]:
            param = "" if ev.get("param") is None else f":{ev['param']:g}"
            lines.append(
                f"{rel(ev)}  INJECT  {ev.get('site')}@{ev.get('step')}{param}"
            )
        for ev in summary["fault_recovered"]:
            lines.append(
                f"{rel(ev)}  RECOVER {ev.get('site')} -> {ev.get('action')}"
            )
        for ev in summary["checkpoint_errors"]:
            lines.append(
                f"{rel(ev)}  CKPT-RETRY attempt {ev.get('attempt')}: "
                f"{ev.get('error', '')[:80]}"
            )
        for ev in summary["checkpoint_corrupt"]:
            what = ev.get("reason") or f"fell back to {ev.get('checkpoint')}"
            lines.append(
                f"{rel(ev)}  CORRUPT {ev.get('path') or ev.get('failed')}: {what}"
            )
        if summary["preempt_signal"]:
            lines.append(
                f"{rel(summary['preempt_signal'])}  PREEMPT "
                f"{summary['preempt_signal'].get('signal')} received "
                "(grace window opened)"
            )
        if summary["preempt"]:
            lines.append(
                f"{rel(summary['preempt'])}  EXIT    grace checkpoint committed, "
                f"rc {summary['preempt'].get('rc')}"
            )
        if summary["fault_gauges"]:
            gauges = " ".join(
                f"{k.split('/', 1)[1]}={v:.0f}"
                for k, v in sorted(summary["fault_gauges"].items())
            )
            lines.append(f"Fault gauges: {gauges}")
        if timeline:
            lines.append("")
            lines.extend(timeline)

    lines.append("")
    lines.append("== health ==")
    if summary["nan_events"]:
        keys: set = set()
        for ev in summary["nan_events"]:
            keys.update(ev.get("keys", []))
        lines.append(
            f"NON-FINITE metrics in {len(summary['nan_events'])} interval(s): "
            f"{sorted(keys)}"
        )
    else:
        lines.append("no non-finite metrics observed")
    if summary["peak_memory_bytes"]:
        lines.append(f"peak_device_memory={summary['peak_memory_bytes'] / 2**30:.2f}GiB")
    for k, v in sorted(summary["gauges_last"].items()):
        lines.append(f"{k}={v:.2f}")
    return "\n".join(lines)


def report(path: str) -> dict:
    """Load + summarize + print; returns the summary (tests use it)."""
    summary = summarize(load_events(path))
    print(render(summary))
    algo = (summary["start"] or {}).get("algo")
    comms, edges = load_comms_ledger()
    if comms or edges:
        print()
        print(render_comms_budget(comms, edges, algo=algo))
    memory = load_memory_ledger()
    if memory:
        print()
        print(render_memory_budget(
            memory, algo=algo,
            runtime_peak_bytes=summary["peak_memory_bytes"],
        ))
    decisions = load_decision_cache()
    if decisions:
        print()
        print(render_sheepopt_decisions(decisions))
    conc = load_concurrency_ledger()
    if conc or summary["sync_gauges"] or summary["sync_events"]:
        print()
        print(render_concurrency(conc, summary))
    return summary


def selftest() -> int:
    """Synthesize a run through the REAL Telemetry writer, then assert this
    reader recovers the critical facts from it."""
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from sheeprl_tpu.telemetry import Telemetry

    d = tempfile.mkdtemp(prefix="telemetry_selftest_")
    telem = Telemetry(d, rank=0, algo="selftest")
    telem.event("start", algo="selftest", env_id="dummy", seed=0)
    for step in (10, 20, 30):
        telem.mark("rollout")
        telem.mark("train/dispatch")
        telem.mark("log")
        metrics = {"Loss/x": 0.5}
        if step == 20:
            metrics["Loss/bad"] = float("inf")
        telem.interval(metrics, step, sps=123.0)
    telem.event("checkpoint", path=os.path.join(d, "ckpt_30"))
    # the warm-start subsystem's events ride the same writer (compile/plan.py)
    telem.event(
        "compile", jit="train_step", mode="warm", seconds=3.25,
        cache_hits=0, cache_misses=1, error=None,
    )
    telem.event("first_update", seconds=7.5, warm_compile="on")
    telem.close()

    summary = report(d)
    assert summary["start"] and summary["start"]["algo"] == "selftest"
    assert summary["end"] is not None and summary["crash"] is None
    assert summary["log_events"] == 3 and summary["last_step"] == 30
    assert "rollout" in summary["phase_seconds"], summary["phase_seconds"]
    assert "train/dispatch" in summary["phase_seconds"]
    assert len(summary["checkpoints"]) == 1
    assert len(summary["nan_events"]) == 1
    assert summary["nan_events"][0]["keys"] == ["Loss/bad"]
    assert summary["first_update"] and summary["first_update"]["seconds"] == 7.5
    assert len(summary["compile_events"]) == 1
    assert summary["compile_events"][0]["jit"] == "train_step"
    assert summary["compile_events"][0]["cache_misses"] == 1

    # comms-budget section: writer (sheepshard ledger schema) and this
    # reader stay in sync — rendered from a synthetic ledger, and the
    # committed repo ledger must load without error wherever it exists
    section = render_comms_budget(
        {
            "selftest@mesh8/train_step": {
                "num_partitions": 8,
                "collectives": {"all-reduce": 5},
                "hot_collectives": {"all-reduce": 5},
                "wire_bytes": 4 << 20,
                "replicated_inputs": ["3:float32[1024,1024]"],
            }
        },
        {"selftest@mesh8/rollout->train_step": {"expect": "match", "status": "mismatch"}},
        algo="selftest",
    )
    assert "all-reducex5" in section and "4.0MiB" in section, section
    assert "SILENTLY REPLICATED" in section and "RESHARD THRASH" in section
    comms, edges = load_comms_ledger()
    if comms:
        assert all("/" in k for k in comms), "comms keys must be spec/jit"
        assert all(r.get("status") for r in edges.values())

    # memory-budget section (ISSUE 10): writer (sheepmem ledger schema) and
    # this reader stay in sync — rendered from a synthetic ledger with a
    # runtime gauge to compare against, and the committed repo ledger must
    # load without error wherever it exists
    mem_section = render_memory_budget(
        {
            "selftest/train_step": {
                "peak_bytes": 8 << 20,
                "temp_bytes": 3 << 20,
                "argument_bytes": 4 << 20,
                "donated": 12,
                "aliases": ["out{0}<-arg0"] * 12,
                "constant_bytes": 2048,
                "large_constants": ["f32[4096,64]:1048576"],
                "scan_buffers": [
                    {"shape": "f32[64,64]", "bytes": 16384, "trip_count": 16}
                ],
            }
        },
        algo="selftest",
        runtime_peak_bytes=float(24 << 20),
    )
    assert "8.0MiB" in mem_section and "12/12" in mem_section, mem_section
    assert "LARGE EMBEDDED CONSTANT f32[4096,64]:1048576" in mem_section
    assert "x16 iterations" in mem_section
    assert "runtime peak" in mem_section and "3.0x" in mem_section
    memory = load_memory_ledger()
    if memory:
        assert all("/" in k for k in memory), "memory keys must be spec/jit"
        assert all("peak_bytes" in fp for fp in memory.values())

    # sheepopt decisions section (ISSUE 11): writer schema
    # (compile/decisions.py Decision.as_dict + measured_probe records) and
    # this renderer stay in sync — a ladder with a disqualified rung, an
    # accepted bytes-objective winner, and a cached probe
    fake_cache = {
        "remat|selftest.step|f32[4]|jax0|cpu": {
            "family": "remat", "name": "selftest.step",
            "winner": "on", "baseline": "off", "objective": "bytes",
            "accepted": True, "source": "measured",
            "candidates": {
                "off": {"exec_seconds": 1.0, "compile_seconds": 0.5,
                        "bit_exact": True, "peak_bytes": 100 << 20,
                        "temp_bytes": 90 << 20},
                "policy": {"exec_seconds": 1.0, "compile_seconds": 0.6,
                           "bit_exact": False, "peak_bytes": 80 << 20,
                           "temp_bytes": 70 << 20},
                "on": {"exec_seconds": 1.04, "compile_seconds": 0.5,
                       "bit_exact": True, "peak_bytes": 70 << 20,
                       "temp_bytes": 60 << 20},
            },
        },
        "batch_chunk|selftest.recon[batch=8]|f32[8]|jax0|cpu": {
            "family": "batch_chunk", "name": "selftest.recon[batch=8]",
            "probe": {"counts": {"convolutions": 23}, "trial": True,
                      "trial_seconds": 2.0, "temp_bytes": 1 << 20},
        },
    }
    opt_section = render_sheepopt_decisions(fake_cache)
    assert "winner=on" in opt_section and "ACCEPTED" in opt_section, opt_section
    assert "disqualified: policy" in opt_section, opt_section
    assert "bytes -31457280" in opt_section, opt_section
    assert "3 candidate(s) tried" in opt_section, opt_section
    assert "measured probe cached" in opt_section, opt_section
    import tempfile as _tf

    opt_dir = _tf.mkdtemp(prefix="telemetry_selftest_dec_")
    with open(os.path.join(opt_dir, "decisions.json"), "w") as fh:
        json.dump(fake_cache, fh)
    loaded = load_decision_cache(os.path.join(opt_dir, "decisions.json"))
    assert loaded == fake_cache
    assert load_decision_cache(os.path.join(opt_dir, "absent.json")) == {}

    # resilience section (ISSUE 12): a preempted run with injected faults,
    # recoveries, a corrupt-checkpoint skip and Fault/* gauges must render
    # as the fault/recovery timeline, and the preempt outcome must win over
    # "unknown" — written through the REAL Telemetry writer like the rest
    d2 = tempfile.mkdtemp(prefix="telemetry_selftest_resil_")
    telem2 = Telemetry(d2, rank=0, algo="resil")
    telem2.event("start", algo="resil", env_id="dummy", seed=0)
    telem2.event("resume", mode="auto", checkpoint="/run/checkpoints/ckpt_4", fallbacks=1)
    telem2.event("fault.injected", site="nan.grad", step=6, param=None)
    telem2.event("fault.recovered", site="nan", action="updates_skipped")
    telem2.event("fault.injected", site="sigterm", step=9, param=None)
    telem2.event("checkpoint.corrupt", path="/run/checkpoints/ckpt_2", reason="missing args.json sidecar")
    telem2.event("checkpoint.error", path="/run/checkpoints/ckpt_8", attempt=1, error="InjectedFault: boom")
    telem2.event("fault.recovered", site="ckpt.write", action="ckpt_retried")
    telem2.event("preempt.signal", signal="SIGTERM")
    telem2.interval({"Loss/x": 1.0, "Fault/injected": 2.0, "Fault/updates_skipped": 1.0}, step=9)
    telem2.event("preempt", step=9, signal="SIGTERM", rc=75)
    telem2.close()
    summary2 = summarize(load_events(d2))
    out2 = render(summary2)
    assert "OUTCOME: PREEMPTED at step 9" in out2 and "rc 75" in out2, out2
    assert "RESUME  auto -> /run/checkpoints/ckpt_4 (1 fallback candidate(s))" in out2
    assert "INJECT  nan.grad@6" in out2 and "INJECT  sigterm@9" in out2
    assert "RECOVER nan -> updates_skipped" in out2
    assert "RECOVER ckpt.write -> ckpt_retried" in out2
    assert "CKPT-RETRY attempt 1" in out2
    assert "CORRUPT /run/checkpoints/ckpt_2: missing args.json sidecar" in out2
    assert "PREEMPT SIGTERM received" in out2
    assert "Fault gauges: injected=2 updates_skipped=1" in out2, out2

    # flock section (ISSUE 14): a 2-actor run with a death + rejoin must
    # render the service line, the per-actor table, the staleness
    # distribution and the membership timeline — written through the REAL
    # Telemetry writer like the rest
    d3 = tempfile.mkdtemp(prefix="telemetry_selftest_flock_")
    telem3 = Telemetry(d3, rank=0, algo="flock")
    telem3.event("start", algo="flock", env_id="dummy", seed=0)
    telem3.event("flock.started", address="unix:/tmp/svc.sock", mode="buffer")
    telem3.event("flock.actor_joined", actor_id=0, pid=111)
    telem3.event("flock.actor_joined", actor_id=1, pid=222)
    telem3.interval(
        {
            "Flock/actors_alive": 2.0, "Flock/weight_version": 3.0,
            "Flock/rows_total": 1024.0, "Flock/chunks_dropped": 0.0,
            "Flock/actor0/env_steps_s": 512.0, "Flock/actor0/env_steps": 600.0,
            "Flock/actor0/weight_version": 3.0, "Flock/actor0/version_lag": 0.0,
            "Flock/actor0/staleness_s": 0.25, "Flock/actor0/heartbeat_age_s": 0.1,
            "Flock/actor0/shard_fill": 0.5, "Flock/actor0/generation": 0.0,
            "Flock/actor0/connected": 1.0,
            "Flock/actor1/env_steps_s": 480.0, "Flock/actor1/env_steps": 424.0,
            "Flock/actor1/weight_version": 2.0, "Flock/actor1/version_lag": 1.0,
            "Flock/actor1/staleness_s": 0.75, "Flock/actor1/heartbeat_age_s": 0.2,
            "Flock/actor1/shard_fill": 0.4, "Flock/actor1/generation": 0.0,
            "Flock/actor1/connected": 1.0,
        },
        step=10,
    )
    telem3.event("flock.actor_disconnected", actor_id=1, rows=424, env_steps=424)
    telem3.event("flock.actor_died", actor_id=1, rc=-9)
    telem3.event("flock.actor_respawned", actor_id=1, attempt=1)
    telem3.event("flock.actor_rejoined", actor_id=1, generation=1, weight_version=4)
    telem3.interval(
        {
            "Flock/actors_alive": 2.0, "Flock/weight_version": 4.0,
            "Flock/rows_total": 2048.0, "Flock/chunks_dropped": 0.0,
            "Flock/actor0/staleness_s": 0.30, "Flock/actor0/connected": 1.0,
            "Flock/actor1/staleness_s": 0.05, "Flock/actor1/connected": 1.0,
            "Flock/actor1/generation": 1.0,
        },
        step=20,
    )
    telem3.close()
    summary3 = summarize(load_events(d3))
    out3 = render(summary3)
    assert "== flock (actor-learner runtime) ==" in out3, out3
    assert "address=unix:/tmp/svc.sock mode=buffer" in out3
    assert "actors_alive=2 weight_version=4 rows_total=2,048" in out3, out3
    assert "actor0" in out3 and "actor1" in out3
    assert "weight staleness (all actors, 4 samples)" in out3, out3
    assert "min=0.05s" in out3 and "max=0.75s" in out3, out3
    assert (
        "membership: actor_died=1 actor_disconnected=1 actor_joined=2 "
        "actor_rejoined=1 actor_respawned=1" in out3
    ), out3
    assert "DIED" in out3 and "rc=-9" in out3
    assert "REJOINED" in out3 and "generation=1" in out3
    assert summary3["flock_staleness"]["actor1"] == [0.75, 0.05]

    # serving section (ISSUE 15): ladder sizing decisions, traffic gauges,
    # and a hot-reload timeline with one success and one refused swap must
    # render — written through the REAL Telemetry writer like the rest
    d4 = tempfile.mkdtemp(prefix="telemetry_selftest_serve_")
    telem4 = Telemetry(d4, rank=0, algo="serve")
    telem4.event("start", algo="serve", env_id="dummy", seed=0)
    telem4.event(
        "serve.ladder", rung=1, accepted=True, source="ledger",
        peak_bytes=2048, reason="ledger serve/policy_b1 x1.05",
    )
    telem4.event(
        "serve.ladder", rung=8, accepted=False, source="ledger",
        peak_bytes=1 << 30, reason="predicted peak exceeds budget",
    )
    telem4.event(
        "serve.start", address="unix:/tmp/serve.sock", algo="sac",
        rungs=[1], version=1, ckpt="/run/checkpoints/ckpt_1",
    )
    telem4.event(
        "serve.reload", ok=True, version=2, path="/run/checkpoints/ckpt_2",
        seconds=0.12, error=None,
    )
    telem4.event(
        "serve.reload", ok=False, version=2, path="/run/checkpoints/ckpt_bad",
        seconds=0.01, error="FileNotFoundError: no such checkpoint",
    )
    telem4.interval(
        {
            "Serve/qps": 180.5, "Serve/latency_p50_ms": 2.4,
            "Serve/latency_p99_ms": 9.8, "Serve/batch_occupancy": 0.81,
            "Serve/served_total": 1200.0, "Serve/shed_total": 3.0,
            "Serve/oversized_total": 1.0, "Serve/failed_total": 0.0,
            "Serve/dispatches": 400.0, "Serve/params_version": 2.0,
            "Serve/reloads": 1.0, "Serve/reload_failures": 1.0,
        },
        step=1200,
    )
    telem4.event("serve.stop", completed=1200, version=2)
    telem4.close()
    summary4 = summarize(load_events(d4))
    out4 = render(summary4)
    assert "== serving (batched inference tier) ==" in out4, out4
    assert "algo=sac address=unix:/tmp/serve.sock rungs=[1]" in out4, out4
    assert "rung    1  accepted  ledger" in out4, out4
    assert "rung    8  REJECTED" in out4, out4
    assert "qps=180.5" in out4 and "p50=2.40ms" in out4 and "p99=9.80ms" in out4
    assert "batch_occupancy=0.81" in out4, out4
    assert "served=1,200 shed=3 oversized=1 failed=0" in out4, out4
    assert "version=2 reloads=1 reload_failures=1" in out4, out4
    assert "RELOAD  -> v2 (0.12s) /run/checkpoints/ckpt_2" in out4, out4
    assert "RELOAD-FAILED kept v2: FileNotFoundError" in out4, out4
    assert "stopped: completed=1200 final_version=2" in out4, out4
    assert len(summary4["serve_ladder"]) == 2
    assert [r["ok"] for r in summary4["serve_reloads"]] == [True, False]

    # distributed recovery timeline (ISSUE 16): a chaos-shaped run — a net
    # partition detected as a flock conn_error + disconnect and survived by
    # a rejoin, a learner resume, and a serve drain — must render one
    # chronological per-tier timeline with recovery latencies
    d5 = tempfile.mkdtemp(prefix="telemetry_selftest_chaos_")
    telem5 = Telemetry(d5, rank=0, algo="chaos")
    telem5.event("start", algo="chaos", env_id="dummy", seed=0)
    telem5.event("fault.injected", site="net.partition", step=30, param=1.0)
    telem5.event(
        "flock.conn_error", actor_id=0, role="data",
        error="FrameError: bad magic b'XXXX'",
    )
    telem5.event("flock.actor_disconnected", actor_id=0, rows=96, env_steps=96)
    telem5.event("flock.actor_rejoined", actor_id=0, generation=1, weight_version=3)
    telem5.event("flock.resumed", rows_total=96, weight_version=3, n_actors=2)
    telem5.event("serve.conn_error", peer="c1", error="FrameError: oversize")
    telem5.event("serve.draining", pending=2)
    telem5.event("serve.drained", completed=60)
    telem5.close()
    summary5 = summarize(load_events(d5))
    assert len(summary5["serve_events"]) == 3, summary5["serve_events"]
    tl = recovery_timeline(summary5)
    assert tl and tl[0] == "distributed recovery timeline (per tier):", tl
    body = "\n".join(tl)
    assert "[net  ] INJECT  net.partition@30:1" in body, body
    assert "[flock] DETECT  conn_error" in body and "FrameError" in body, body
    assert "[flock] DETECT  actor_disconnected" in body, body
    assert "[flock] RECOVER actor_rejoined" in body, body
    assert "[flock] RECOVER resumed" in body, body
    assert "[serve] DETECT  conn_error" in body, body
    assert "[serve] DRAIN   draining" in body, body
    assert "[serve] RECOVER drained" in body, body
    # recoveries carry the latency back to their matching detection
    assert "s after actor_disconnected)" in body or "s after conn_error)" in body, body
    assert "s after draining)" in body, body
    out5 = render(summary5)
    assert "== resilience (faults / recovery) ==" in out5, out5
    assert "distributed recovery timeline (per tier):" in out5, out5
    # the flock selftest's membership churn alone must ALSO open the section
    assert "distributed recovery timeline (per tier):" in out3, out3

    # sheepsync concurrency section (ISSUE 18): writer (the runtime thread
    # sanitizer's sync.* events + Sync/* gauges, and the sheepsync ledger
    # schema) and this reader stay in sync
    d6 = tempfile.mkdtemp(prefix="telemetry_selftest_sync_")
    telem6 = Telemetry(d6, rank=0, algo="selftest")
    telem6.event("start", algo="selftest", env_id="dummy", seed=0)
    telem6.event("sync.sanitizer_start", committed_edges=2, known_sites=16, pid=1)
    telem6.event(
        "sync.order_violation",
        acquiring="flock.service.ReplayService._lock",
        held="flock.service.ReplayService._shard_locks[*]",
        thread="flock-monitor",
    )
    telem6.interval(
        {
            "Sync/acquisitions": 420.0,
            "Sync/contended": 3.0,
            "Sync/order_violations": 1.0,
            "Sync/undeclared_edges": 2.0,
            "Sync/observed_edges": 5.0,
            "Sync/hold_ms_avg": 0.021,
            "Sync/hold_ms_max": 4.5,
            "Sync/wait_ms_max": 1.25,
        },
        10,
    )
    telem6.close()
    summary6 = summarize(load_events(d6))
    assert len(summary6["sync_events"]) == 2, summary6["sync_events"]
    assert summary6["sync_gauges"]["Sync/order_violations"] == 1.0
    fake_conc = {
        "fingerprint": "feedfacecafebeef",
        "lock_order": {
            "edges": [["A._lock", "A._shard[*]"]],
            "chains": {"A._lock -> A._shard[*]": "f holds A._lock, acquires A._shard[*]"},
            "cycles": [],
        },
        "roles": {
            "flock": {
                "locks": {
                    "A._lock": {"kind": "RLock", "site": "a.py:1", "backing": None}
                },
                "threads": [
                    {
                        "role": "flock", "path": "a.py", "line": 9,
                        "target": "A._loop", "name": "flock-monitor",
                        "daemon": True, "joined": True,
                    }
                ],
                "guards": {"A.count": "A._lock", "A.naked": None},
            }
        },
    }
    sync_section = render_concurrency(fake_conc, summary6)
    assert "feedfacecafebeef" in sync_section, sync_section
    assert "A._lock -> A._shard[*]" in sync_section, sync_section
    assert "UNGUARDED" in sync_section and "A.count" in sync_section
    assert "flock-monitor" in sync_section and "joined" in sync_section
    assert "acquisitions 420" in sync_section, sync_section
    assert "ORDER VIOLATIONS" in sync_section, sync_section
    assert "while holding" in sync_section, sync_section
    # ledger-only render (no sanitized run) stays valid + committed ledger
    # loads wherever it exists
    ledger_only = render_concurrency(fake_conc, {"sync_gauges": {}, "sync_events": []})
    assert "no lock-order violations recorded" in ledger_only
    conc = load_concurrency_ledger()
    if conc:
        assert conc.get("fingerprint") and "lock_order" in conc
        assert "roles" in conc and "flock" in conc["roles"]

    print("\nselftest OK", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "path", nargs="?", help="run log_dir or telemetry.jsonl path"
    )
    parser.add_argument(
        "--selftest", action="store_true",
        help="synthesize a run and verify writer/reader agreement",
    )
    opts = parser.parse_args(argv)
    if opts.selftest:
        return selftest()
    if not opts.path:
        parser.error("path required (or --selftest)")
    report(opts.path)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `| head` closed the pipe; not an error
        os._exit(0)
