"""Session-end straggler sweep (VERDICT r4 #4).

SIGTERMs any `tools/*_learning_run.py` process still alive — the bounded harness (tools/runner_common.py) turns SIGTERM into the
graceful checkpoint-then-eval path, so a swept runner lands a
partial/resumable receipt instead of dying silently. After a grace window,
survivors (stuck in native code) get SIGKILL; their mid-run checkpoints
remain resumable and runner_common's hard timer has usually already written
a stub.

Usage: python tools/sweep_runners.py [--grace-s 900] [--dry-run]
Intended caller: any operator ending a work session.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import time

PATTERNS = ("learning_run.py",)


def _is_runner_cmd(cmd: str) -> bool:
    """True only for a python interpreter executing a runner SCRIPT: the
    first token must be a python binary and a later token must be a path
    whose basename matches a runner pattern (ADVICE r5: a plain substring
    match also SIGKILLed `tail -f dv1_learning_run.py`, editors with the
    file open, and greps over the tools tree)."""
    tokens = cmd.split()
    if len(tokens) < 2:
        return False
    interp = os.path.basename(tokens[0])
    if not interp.startswith("python"):
        return False
    if "sweep_runners" in cmd:
        return False
    for tok in tokens[1:]:
        if tok.startswith("-"):
            continue  # interpreter flags (-u, -X, ...)
        # first non-flag token is the script path (a `python -m pkg` runner
        # would not match the .py patterns, correctly)
        base = os.path.basename(tok)
        return any(base.endswith(p) for p in PATTERNS)
    return False


def find_runners() -> dict[int, str]:
    out = subprocess.run(
        ["ps", "-e", "-o", "pid=,args="], capture_output=True, text=True
    ).stdout
    procs = {}
    for line in out.splitlines():
        pid_s, _, cmd = line.strip().partition(" ")
        if _is_runner_cmd(cmd.strip()):
            procs[int(pid_s)] = cmd.strip()
    return procs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grace-s", type=float, default=900.0,
                    help="wait this long for graceful receipts before SIGKILL")
    ap.add_argument("--dry-run", action="store_true")
    ns = ap.parse_args()

    procs = find_runners()
    if not procs:
        print("sweep: no runner processes found")
        return
    for pid, cmd in procs.items():
        print(f"sweep: SIGTERM {pid}: {cmd}")
        if not ns.dry_run:
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
    if ns.dry_run:
        return
    deadline = time.time() + ns.grace_s
    while time.time() < deadline:
        alive = [pid for pid in procs if _alive(pid)]
        if not alive:
            print("sweep: all runners exited gracefully")
            return
        time.sleep(10)
    for pid in procs:
        if _alive(pid):
            print(f"sweep: SIGKILL {pid} (stuck past grace; checkpoint "
                  "remains resumable)")
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


if __name__ == "__main__":
    main()
