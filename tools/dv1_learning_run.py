"""Budget-proofed DreamerV1 learning receipt (VERDICT r3 next-round #3).

The round-3 attempt reused the DV2/DV3 CartPole recipe verbatim and died to
the session budget (>75 min on the 1-core box, no checkpoint). This runner
fixes both failure modes:

- **Shrunk recipe**: DV1 needs fewer imagination FLOPs than DV3 (Gaussian
  latent, no discrete head) — 4096 total steps, 200-unit nets, horizon 10
  (vs DV3's 6144 / 256 / 15).
- **Mid-run checkpoints + resume**: `--checkpoint_every 1024` writes a
  checkpoint every ~1k env steps, and on restart the runner auto-resumes
  from the latest one (DV1's `--checkpoint_path` restore path,
  dreamer_v1.py:382-404), so a timeout costs at most 1k steps, not the run.
- **Eval-from-checkpoint**: after training (or on `--eval-only` against a
  partial run) the latest checkpoint is restored and greedily evaluated for
  10 episodes; the result is written to logs/dv1_learn_r4.json.

Reference scope: /root/reference/sheeprl/algos/dreamer_v1/dreamer_v1.py:40-358
(the training loop this receipt certifies our redesign of).

Usage: python tools/dv1_learning_run.py [--eval-only] [--root DIR]
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax

jax.config.update("jax_platforms", "cpu")

import gymnasium as gym
import jax.numpy as jnp
import numpy as np

import sheeprl_tpu.algos  # noqa: F401 - fire registrations
from sheeprl_tpu.algos.dreamer_v1.agent import PlayerDV1, build_models
from sheeprl_tpu.algos.dreamer_v1.args import DreamerV1Args
from sheeprl_tpu.algos.dreamer_v1.dreamer_v1 import make_optimizers
from sheeprl_tpu.algos.ppo.agent import one_hot_to_env_actions
from sheeprl_tpu.utils.checkpoint import latest_checkpoint, load_checkpoint
from sheeprl_tpu.utils.registry import tasks

# Attempt 1 (CartPole, 4096 steps, DV1 defaults use_continues=False/expl 0.3)
# trained fine (world losses converged, 897 updates, 7 min) but learned
# nothing (greedy 18.9 ~= random): with no continue predictor the imagined
# rollouts never terminate, and CartPole's ONLY learning signal is
# termination. Attempt 2 (CartPole, continues on, 6144 steps) collapsed
# below random (9.8): DV1's actor trains by PURE dynamics backprop of
# imagined values — no reinforce term, no entropy bonus (the reference
# DV1 loss has neither; DV2 added both) — and the straight-through discrete
# policy saturated into always-left. The reference does support discrete
# DV1 (OneHotCategoricalStraightThrough via the shared Actor); whether its
# torch implementation also collapses on tiny-CartPole is unverified here.
# Attempt 3 moves to DV1's native regime: continuous control with dense
# rewards (Pendulum swing-up, the SAC/DroQ receipt env), tanh_normal actor
# + additive Gaussian exploration noise, no continue head (no termination).
# Attempt 3 (Pendulum, reference lrs, expl 0.3 constant, 12288 then resumed
# to 28672 steps) plateaued at greedy -1066..-1213 across every checkpoint
# vs measured same-protocol random -1287 — within noise, not a receipt.
# Diagnosis: DV1's reference actor/critic lr (8e-5) is calibrated for its
# 100-updates-per-1000-steps x 5M-step regime (~500k updates); our receipt
# budget delivers ~3.5k updates, so the actor barely moves. Attempt 4
# keeps the reference ALGORITHM and scales the receipt recipe: 4x
# actor/critic lr and exploration decay (0.3 -> 0.05) so late collection
# exploits what the world model knows.
RECIPE = dict(
    env_id="Pendulum-v1",
    seed=5,
    total_steps=24576,  # extended once: 12288 still improving (rew_avg -1464 -> -883)
    learning_starts=1024,
    train_every=4,
    gradient_steps=1,  # DV1 default is 100 (train_every=1000 regime)
    per_rank_batch_size=16,
    per_rank_sequence_length=32,
    buffer_size=100000,
    dense_units=200,
    hidden_size=200,
    recurrent_state_size=200,
    stochastic_size=30,
    mlp_layers=2,
    horizon=15,
    action_repeat=1,
    checkpoint_every=2048,
    use_continues=False,
    expl_amount=0.3,
    expl_decay=True,
    expl_min=0.05,
    max_step_expl_decay=2000,
    actor_lr=3e-4,
    critic_lr=3e-4,
)


def _train(root: Path) -> None:
    argv = [
        "--num_devices", "1",
        "--num_envs", "1",
        "--sync_env",
        "--root_dir", str(root),
        "--run_name", "learn",
        "--mlp_keys", "state",
    ]
    for k, v in RECIPE.items():
        if isinstance(v, bool):
            argv += [f"--{k}" if v else f"--no_{k}"]
        else:
            argv += [f"--{k}", str(v)]
    resume = latest_checkpoint(str(root / "learn" / "checkpoints"))
    if resume is not None:
        print(f"[dv1] resuming from {resume}", flush=True)
        argv += ["--checkpoint_path", resume]
    tasks["dreamer_v1"](argv)


def _evaluate(root: Path) -> dict:
    ckpt = latest_checkpoint(str(root / "learn" / "checkpoints"))
    assert ckpt is not None, "no checkpoint to evaluate"
    env = gym.make(RECIPE["env_id"])
    is_continuous = hasattr(env.action_space, "high")
    act_dim = (
        int(np.prod(env.action_space.shape)) if is_continuous else env.action_space.n
    )
    args = DreamerV1Args(env_id=RECIPE["env_id"], seed=5)
    args.cnn_keys, args.mlp_keys = [], ["state"]
    for k in (
        "dense_units", "hidden_size", "recurrent_state_size",
        "stochastic_size", "mlp_layers", "horizon", "action_repeat",
        "use_continues",
    ):
        setattr(args, k, RECIPE[k])
    wm, actor, critic = build_models(
        jax.random.PRNGKey(0), [act_dim], is_continuous, args,
        {"state": env.observation_space}, [], ["state"],
    )
    wopt, aopt, copt = make_optimizers(args)
    restored = load_checkpoint(ckpt, {
        "world_model": wm, "actor": actor, "critic": critic,
        "world_optimizer": wopt.init(wm), "actor_optimizer": aopt.init(actor),
        "critic_optimizer": copt.init(critic),
        "expl_decay_steps": 0, "global_step": 0, "batch_size": 0,
    })
    player = PlayerDV1(
        encoder=restored["world_model"].encoder,
        rssm=restored["world_model"].rssm,
        actor=restored["actor"],
        actions_dim=(act_dim,),
        stochastic_size=RECIPE["stochastic_size"],
        recurrent_state_size=RECIPE["recurrent_state_size"],
        is_continuous=is_continuous,
    )
    step = jax.jit(
        lambda p, s, o, k: p.step(s, o, k, jnp.float32(0.0), is_training=False)
    )
    returns = []
    for episode in range(10):
        obs, _ = env.reset(seed=1000 + episode)
        state = player.init_states(1)
        key = jax.random.PRNGKey(episode)
        done, ep_return = False, 0.0
        while not done:
            dobs = {"state": jnp.asarray(obs, jnp.float32)[None]}
            key, sub = jax.random.split(key)
            state, actions = step(player, state, dobs, sub)
            if is_continuous:
                obs, reward, terminated, truncated, _ = env.step(
                    np.asarray(actions)[0]
                )
            else:
                act = one_hot_to_env_actions(
                    np.asarray(actions), (act_dim,), False
                )[0]
                obs, reward, terminated, truncated, _ = env.step(act.item())
            ep_return += float(reward)
            done = terminated or truncated
        returns.append(ep_return)
    env.close()
    return {
        "checkpoint": ckpt,
        "returns": returns,
        "mean_return": float(np.mean(returns)),
        "global_step_restored": int(restored["global_step"]),
    }


def main() -> None:
    from runner_common import bounded_runner_main

    bounded_runner_main(
        "logs/dv1_learn_r4d", _train, _evaluate, RECIPE, "dv1"
    )


if __name__ == "__main__":
    main()
