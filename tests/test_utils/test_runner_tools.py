"""Unit receipts for the ISSUE 3 satellite fixes in tools/: process matching
in the session-end sweep and the bounded --eval-only path."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))


# ---------------------------------------------------------------------------
# sweep_runners: only real python processes running runner scripts
# ---------------------------------------------------------------------------


def test_sweep_matches_only_python_runner_processes():
    from sweep_runners import _is_runner_cmd

    # real runners, in the shapes an operator spawns them
    assert _is_runner_cmd("python tools/dv1_learning_run.py --root logs/x")
    assert _is_runner_cmd("python3 -u /root/repo/tools/dv3_pixel_learning_run.py")
    assert _is_runner_cmd("/usr/bin/python3.10 tools/sac_ae_pixel_learning_run.py")

    # ADVICE r5: these used to be SIGKILLed by the substring match
    assert not _is_runner_cmd("tail -f logs/dv1_learning_run.py.out")
    assert not _is_runner_cmd("vim tools/dv1_learning_run.py")
    assert not _is_runner_cmd("grep -r dv3_pixel_learning_run.py tools/")
    assert not _is_runner_cmd("less dv3_pixel_learning_run.py")
    # the sweep itself, and unrelated python work
    assert not _is_runner_cmd("python tools/sweep_runners.py --dry-run")
    assert not _is_runner_cmd("python -m benchmark.run --workload x")
    assert not _is_runner_cmd("python -m pytest tests/")
    assert not _is_runner_cmd("")


# ---------------------------------------------------------------------------
# runner_common: --eval-only rides the same bounds as run_bounded
# ---------------------------------------------------------------------------


def test_run_eval_bounded_receipt(tmp_path):
    from runner_common import run_eval_bounded

    out = str(tmp_path / "receipt.json")
    result = run_eval_bounded(
        lambda: {"mean_return": 12.5, "returns": [12.5]},
        out, {"recipe": {"algo": "x"}}, eval_budget_s=60.0,
    )
    assert result["status"] == "eval_receipt"
    assert result["mean_return"] == 12.5
    with open(out) as fh:
        on_disk = json.load(fh)
    assert on_disk["recipe"] == {"algo": "x"}
    assert on_disk["eval_budget_s"] == 60.0
    assert "train_plus_eval_seconds" in on_disk  # legacy consumer key


def test_run_eval_bounded_soft_timeout(tmp_path):
    from runner_common import run_eval_bounded

    out = str(tmp_path / "receipt.json")

    def slow_eval():
        import time

        time.sleep(30)
        return {"mean_return": 0.0}

    result = run_eval_bounded(
        slow_eval, out, {}, eval_budget_s=1.0, hard_grace_s=600.0,
    )
    assert result["status"] == "stub_eval_timeout"
    assert os.path.exists(out)


def test_run_eval_bounded_crash_lands_stub(tmp_path):
    from runner_common import run_eval_bounded

    out = str(tmp_path / "receipt.json")
    result = run_eval_bounded(
        lambda: (_ for _ in ()).throw(RuntimeError("no checkpoint")),
        out, {}, eval_budget_s=30.0,
    )
    assert result["status"] == "stub_no_eval"
    assert "no checkpoint" in result["eval_error"]
