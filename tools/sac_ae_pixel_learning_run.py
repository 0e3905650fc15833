"""SAC-AE pixel learning receipt (bonus beyond VERDICT r3 #4).

The DV3 swingup run covers the model-based pixel path; this covers the
OTHER pixel family — SAC-AE's autoencoder + detached-encoder actor
(reference sac_ae.py:50-130) — on the same dmc_cartpole_swingup pixels
(random ~27, shaped reward). Evaluation goes through the framework's own
`--eval_only` capability (fresh process path: checkpoint restore + greedy
episodes), and the per-episode returns are read back from the eval run's
TB events — so this receipt also exercises eval_only on a pixel checkpoint.

Usage: MUJOCO_GL=egl python tools/sac_ae_pixel_learning_run.py [--eval-only]
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("MUJOCO_GL", "egl")

import argparse
import glob
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import sheeprl_tpu.algos  # noqa: F401 - fire registrations
from sheeprl_tpu.utils.checkpoint import latest_checkpoint
from sheeprl_tpu.utils.registry import tasks

RECIPE = dict(
    env_id="dmc_cartpole_swingup",
    seed=5,
    total_steps=8192,  # cut from 16384: collection already hit ~100 by episode 3, and the 1-core box can't fit the full budget in-session
    learning_starts=1000,
    # batch 32 / 128-unit heads, NOT 64/256: the single-jit SAC-AE pixel
    # update (5 optimizers + conv enc/dec fwd+bwd) triggers an XLA:CPU
    # compile blowup at the larger sizes (>25 min observed; fine on TPU,
    # where this jit compiles in tens of seconds) — the receipt must fit
    # the 1-core box's session budget
    per_rank_batch_size=32,
    buffer_size=100000,
    actor_hidden_size=128,
    critic_hidden_size=128,
    dense_units=128,
    action_repeat=4,  # the reference's DMC SAC-AE convention
    # the round-4 fix for the XLA:CPU compile pathology: four per-model jits
    # instead of the fused update (parity unit-tested vs the fused path)
    split_update=True,
)


def _train(root: Path) -> None:
    argv = [
        "--num_devices", "1",
        "--num_envs", "1",
        "--sync_env",
        "--root_dir", str(root),
        "--run_name", "learn",
        "--cnn_keys", "rgb",
        "--checkpoint_every", "1024",
    ]
    for k, v in RECIPE.items():
        if isinstance(v, bool):
            argv += [f"--{k}" if v else f"--no_{k}"]
        else:
            argv += [f"--{k}", str(v)]
    resume = latest_checkpoint(str(root / "learn" / "checkpoints"))
    if resume is not None:
        print(f"[sac-ae-pixel] resuming from {resume}", flush=True)
        argv += ["--checkpoint_path", resume]
    tasks["sac_ae"](argv)


def _evaluate(root: Path, episodes: int = 10) -> dict:
    """Evaluate through the framework's own --eval_only path and read the
    per-episode returns back from the eval run's TB events."""
    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator,
    )

    ckpt = latest_checkpoint(str(root / "learn" / "checkpoints"))
    assert ckpt is not None, "no checkpoint to evaluate"
    eval_root = str(root) + "_eval"
    tasks["sac_ae"]([
        "--eval_only",
        "--checkpoint_path", ckpt,
        "--test_episodes", str(episodes),
        "--seed", "1000",
        "--root_dir", eval_root,
        "--run_name", "eval",
    ])
    events = glob.glob(os.path.join(eval_root, "**", "events.*"), recursive=True)
    assert events, f"no TB events under {eval_root}"
    returns: list[float] = []
    # newest first: a resumed run's re-evaluation must not pick a stale file
    for f in sorted(events, key=os.path.getmtime, reverse=True):
        ea = EventAccumulator(f)
        ea.Reload()
        if "Test/episode_reward" in ea.Tags()["scalars"]:
            returns = [e.value for e in ea.Scalars("Test/episode_reward")]
            break
    assert returns, "eval run logged no Test/episode_reward"
    return {
        "checkpoint": ckpt,
        "returns": [round(r, 1) for r in returns],
        "mean_return": float(np.mean(returns)),
        "random_baseline": "swingup random 18.5-35.7 over 3 episodes (measured 2026-08-02)",
    }


def main() -> None:
    from runner_common import run_bounded

    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="logs/sac_ae_pixel_r5")
    ap.add_argument("--eval-only", action="store_true")
    ap.add_argument("--budget-s", type=float, default=5400.0,
                    help="wall-clock training budget (VERDICT r4 #4); on "
                    "expiry the latest mid-run checkpoint is evaluated and "
                    "the receipt marked partial/resumable")
    ns = ap.parse_args()
    root = Path(ns.root)
    out = str(root) + ".json"
    if ns.eval_only:
        t0 = time.time()
        result = _evaluate(root)
        result["recipe"] = RECIPE
        result["train_plus_eval_seconds"] = round(time.time() - t0, 1)
        Path(out).write_text(json.dumps(result, indent=2))
        print(json.dumps({k: result[k] for k in ("mean_return", "returns")}))
        print(f"[sac-ae-pixel] receipt written to {out}", flush=True)
        return
    run_bounded(
        ns.budget_s,
        lambda: _train(root),
        lambda: _evaluate(root),
        out,
        {"recipe": RECIPE},
    )


if __name__ == "__main__":
    main()
