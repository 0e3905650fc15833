#!/usr/bin/env python
"""sheeplint — static JAX/TPU hazard linter for this repo (ISSUE 3).

Usage:
    python tools/sheeplint.py sheeprl_tpu/ tools/
    python tools/sheeplint.py --list-rules
    python tools/sheeplint.py --select SL001,SL002 sheeprl_tpu/
    python tools/sheeplint.py --format json sheeprl_tpu/ | jq .

Exit codes: 0 clean, 1 violations found, 2 usage/parse error.

The rule catalog, severities, and suppression syntax
(`# sheeplint: disable=SL002 — why`) live in sheeprl_tpu/analysis/rules.py
and howto/static_analysis.md. CI runs this over `sheeprl_tpu/ tools/` and
fails the build on any new violation.

Pure AST analysis: no jax import, no module execution — safe to run
anywhere, including pre-commit.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from sheeprl_tpu.analysis.linter import lint_file, iter_python_files  # noqa: E402
from sheeprl_tpu.analysis.rules import RULES  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="files or directories to lint")
    ap.add_argument(
        "--select", default=None,
        help="comma-separated rule ids to run (default: all)",
    )
    ap.add_argument("--format", choices=["text", "json"], default="text")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument(
        "--statistics", action="store_true",
        help="print a per-rule violation count summary",
    )
    ns = ap.parse_args(argv)

    if ns.list_rules:
        for rule in RULES.values():
            print(f"{rule.id} ({rule.name}) [{rule.severity}]")
            print(f"    {rule.summary}")
            print(f"    fix: {rule.autofix}")
        return 0
    if not ns.paths:
        ap.error("no paths given (or use --list-rules)")

    select = None
    if ns.select:
        select = {s.strip().upper() for s in ns.select.split(",") if s.strip()}
        unknown = select - set(RULES)
        if unknown:
            print(f"unknown rule ids: {sorted(unknown)}", file=sys.stderr)
            return 2

    violations = []
    parse_errors = 0
    for path in iter_python_files(ns.paths):
        try:
            violations.extend(lint_file(path, select=select))
        except ValueError as exc:
            print(str(exc), file=sys.stderr)
            parse_errors += 1

    if ns.format == "json":
        print(json.dumps([v.as_dict() for v in violations], indent=2))
    else:
        for v in violations:
            print(v.format())
    if ns.statistics:
        counts = Counter(v.rule.id for v in violations)
        for rid in sorted(counts):
            print(f"{rid}: {counts[rid]}", file=sys.stderr)
    if parse_errors:
        return 2
    if violations:
        n_err = sum(1 for v in violations if v.rule.severity == "error")
        print(
            f"sheeplint: {len(violations)} violation(s) "
            f"({n_err} error, {len(violations) - n_err} warning)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
