"""Wall-clock-bounded learning-runner harness (VERDICT r4 #4).

Round 4 left a receipt runner alive 2h18m past the end-of-round snapshot at
10.7 GB RSS on the 1-core box, contending with the next session's work and
producing neither a receipt nor a checkpoint. Every `tools/*_learning_run.py`
now runs its training phase through `run_bounded`, which guarantees the
session ends in one of exactly three states by a known deadline:

1. **receipt** — training finished inside the budget; eval ran; receipt JSON
   written.
2. **partial_receipt_resumable** — the soft deadline (SIGALRM or SIGTERM,
   so the session-end sweep composes with this) interrupted training; the
   latest mid-run checkpoint was evaluated and the receipt says so. A later
   session resumes from that checkpoint.
3. **stub_hard_deadline** — the process was stuck in uninterruptible native
   code (e.g. the XLA:CPU conv-gradient compile pathology, ~16 min for the
   SAC-AE recon jit) past the hard deadline; a daemon timer writes a stub
   sidecar and hard-exits so no orphan survives the session.

The soft handler uses SIGALRM/SIGTERM -> Python exception, which only fires
between bytecodes — a long native call defers it, hence the separate hard
timer with a grace window sized to one pathological compile.

Runners share the package's persistent compilation cache
(sheeprl_tpu/compile/cache.py) so a pathological compile is paid once
across bounded sessions, not once per resume.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time


class BudgetExpired(Exception):
    """Soft deadline (or SIGTERM from the session-end sweep) hit."""


def bounded_runner_main(
    default_root: str,
    train,
    evaluate,
    recipe: dict,
    tag: str,
    default_budget_s: float = 5400.0,
) -> None:
    """Shared CLI entry for the learning-receipt runners: --root / --eval-only
    / --budget-s, training bounded by `run_bounded`, receipt at <root>.json.
    `train(root)` must auto-resume from the latest checkpoint under root;
    `evaluate(root)` must read the latest checkpoint (see run_bounded)."""
    import argparse
    from pathlib import Path

    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=default_root)
    ap.add_argument("--eval-only", action="store_true")
    ap.add_argument(
        "--budget-s", type=float, default=default_budget_s,
        help="wall-clock training budget (VERDICT r4 #4); on expiry the "
        "latest mid-run checkpoint is evaluated and the receipt marked "
        "partial/resumable",
    )
    ap.add_argument(
        "--eval-budget-s", type=float, default=1800.0,
        help="wall-clock bound on the evaluation phase (both --eval-only "
        "and the post-training eval share it; ADVICE r5: a bare eval "
        "session must not outlive the session unbounded either)",
    )
    ns = ap.parse_args()
    root = Path(ns.root)
    out = str(root) + ".json"
    if ns.eval_only:
        result = run_eval_bounded(
            lambda: evaluate(root), out, {"recipe": recipe},
            eval_budget_s=ns.eval_budget_s,
        )
        if "mean_return" in result:
            print(json.dumps(
                {k: result[k] for k in ("mean_return", "returns") if k in result}
            ))
        print(f"[{tag}] receipt written to {out}", flush=True)
        return
    run_bounded(
        ns.budget_s,
        lambda: train(root),
        lambda: evaluate(root),
        out,
        {"recipe": recipe},
        eval_budget_s=ns.eval_budget_s,
    )


def _write_receipt(receipt_path: str, payload: dict, suffix: str = "") -> None:
    path = receipt_path + suffix
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".tmp", "w") as fh:
        json.dump(payload, fh, indent=2)
    os.replace(path + ".tmp", path)


def run_eval_bounded(
    eval_fn,
    receipt_path: str,
    meta: dict,
    *,
    eval_budget_s: float = 1800.0,
    hard_grace_s: float = 1500.0,
) -> dict:
    """`--eval-only` twin of run_bounded's eval half (ADVICE r5): the same
    SIGALRM soft bound plus a daemon hard timer, so a bare evaluation
    session stuck in a pathological XLA compile ends by a known deadline
    with a stub receipt instead of surviving as an orphan."""
    t0 = time.time()

    def _hard_exit() -> None:
        _write_receipt(
            receipt_path,
            {
                **meta,
                "status": "stub_hard_deadline",
                "note": "eval stuck in native code past the hard deadline",
                "eval_budget_s": eval_budget_s,
                "elapsed_s": round(time.time() - t0, 1),
            },
            suffix=".stub",
        )
        print(f"[runner] HARD deadline; stub written to {receipt_path}.stub",
              flush=True)
        os._exit(3)

    hard_timer = threading.Timer(eval_budget_s + hard_grace_s, _hard_exit)
    hard_timer.daemon = True
    hard_timer.start()

    def _raise(_sig, _frm):
        raise BudgetExpired

    signal.signal(signal.SIGALRM, _raise)
    signal.signal(signal.SIGTERM, _raise)  # session-end sweep -> graceful
    signal.alarm(max(1, int(eval_budget_s)))

    result = {**meta, "eval_budget_s": eval_budget_s}
    try:
        result.update(eval_fn())
        result["status"] = "eval_receipt"
    except BudgetExpired:
        result["status"] = "stub_eval_timeout"
    except Exception as exc:
        result["status"] = "stub_no_eval"
        result["eval_error"] = repr(exc)
    finally:
        signal.alarm(0)
        hard_timer.cancel()
    result["elapsed_s"] = round(time.time() - t0, 1)
    result["train_plus_eval_seconds"] = result["elapsed_s"]  # legacy key
    _write_receipt(receipt_path, result)
    print(json.dumps({k: result.get(k) for k in ("status", "mean_return")}),
          flush=True)
    return result


def run_bounded(
    budget_s: float,
    train_fn,
    eval_fn,
    receipt_path: str,
    meta: dict,
    *,
    eval_budget_s: float = 1800.0,
    hard_grace_s: float = 1500.0,
) -> dict:
    """Run `train_fn` under a wall-clock budget, then `eval_fn`; always leave
    a receipt (or stub) at `receipt_path` and return the receipt dict.

    `eval_fn` must evaluate the LATEST CHECKPOINT (not in-memory state): the
    partial path relies on mid-run checkpoints for resumability, so a run
    killed at the soft deadline is evaluated exactly as a resumed session
    would see it.
    """
    t0 = time.time()

    def _write(payload: dict, suffix: str = "") -> None:
        _write_receipt(receipt_path, payload, suffix)

    def _hard_exit() -> None:
        _write(
            {
                **meta,
                "status": "stub_hard_deadline",
                "note": (
                    "stuck in native code past the hard deadline (likely a "
                    "pathological XLA compile); any mid-run checkpoint is "
                    "resumable by the next session"
                ),
                "budget_s": budget_s,
                "elapsed_s": round(time.time() - t0, 1),
            },
            suffix=".stub",
        )
        print(f"[runner] HARD deadline; stub written to {receipt_path}.stub",
              flush=True)
        os._exit(3)

    hard_timer = threading.Timer(budget_s + hard_grace_s, _hard_exit)
    hard_timer.daemon = True
    hard_timer.start()

    def _raise(_sig, _frm):
        raise BudgetExpired

    signal.signal(signal.SIGALRM, _raise)
    signal.signal(signal.SIGTERM, _raise)  # session-end sweep -> graceful
    signal.alarm(max(1, int(budget_s)))

    completed = True
    train_error = None
    try:
        train_fn()
    except BudgetExpired:
        completed = False
        print(f"[runner] soft deadline after {time.time() - t0:.0f}s; "
              "evaluating latest checkpoint", flush=True)
    except Exception as exc:  # training crash still lands a stub
        completed = False
        train_error = repr(exc)
    finally:
        signal.alarm(0)

    # fresh bound for eval: the hard timer above may be nearly spent
    hard_timer.cancel()
    hard_timer = threading.Timer(eval_budget_s + hard_grace_s, _hard_exit)
    hard_timer.daemon = True
    hard_timer.start()
    signal.alarm(int(eval_budget_s))

    result = {
        **meta,
        "completed_training": completed,
        "budget_s": budget_s,
    }
    if train_error:
        result["train_error"] = train_error
    try:
        result.update(eval_fn())
        result["status"] = "receipt" if completed else "partial_receipt_resumable"
    except BudgetExpired:
        result["status"] = "stub_eval_timeout"
    except Exception as exc:
        # e.g. no checkpoint yet: resumable is still the honest outcome
        result["status"] = "stub_no_eval"
        result["eval_error"] = repr(exc)
    finally:
        signal.alarm(0)
        hard_timer.cancel()
    result["elapsed_s"] = round(time.time() - t0, 1)
    _write(result)
    print(json.dumps({k: result.get(k) for k in
                      ("status", "mean_return", "elapsed_s")}), flush=True)
    print(f"[runner] receipt written to {receipt_path}", flush=True)
    return result
