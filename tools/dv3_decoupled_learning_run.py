"""Decoupled-DV3 learning receipt (VERDICT r3 next-round #6).

Round 3 proved the decoupled plumbing (0.999x coupled parity on the virtual
mesh, cross-task checkpoint eval) but nothing showed the decoupled loop
itself LEARNS — the player runs one update behind the trainers
(stale-weights overlap, sheeprl_tpu/algos/dreamer_v3/dreamer_v3_decoupled.py),
and that staleness tolerance was untested against returns. This runner
trains the SAME tiny-CartPole recipe as the coupled DV3 learning regression
(tests/test_algos/test_learning.py::test_dreamer_v3_learns_cartpole,
validated greedy mean 408.5) through `dreamer_v3_decoupled` on a 2-device
virtual CPU mesh (1 player + 1 trainer), then greedily evaluates the
checkpoint. A learning result here certifies that the one-update weight lag
does not break imagination training.

Usage: python tools/dv3_decoupled_learning_run.py [--eval-only]
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2"
    ).strip()

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
from sheeprl_tpu import ops
from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_models
from sheeprl_tpu.algos.dreamer_v3.args import DreamerV3Args
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_optimizers
from sheeprl_tpu.algos.ppo.agent import one_hot_to_env_actions
from sheeprl_tpu.utils.checkpoint import latest_checkpoint, load_checkpoint
from sheeprl_tpu.utils.registry import tasks

# identical to the coupled regression's recipe (test_learning.py) so any
# return gap is attributable to the decoupled topology, not the config
RECIPE = dict(
    env_id="CartPole-v1",
    seed=5,
    total_steps=6144,
    learning_starts=512,
    train_every=4,
    per_rank_batch_size=16,
    per_rank_sequence_length=32,
    buffer_size=100000,
    dense_units=256,
    hidden_size=256,
    recurrent_state_size=256,
    stochastic_size=16,
    discrete_size=16,
    mlp_layers=2,
    horizon=15,
    action_repeat=1,
    checkpoint_every=2048,
)


def _train(root: Path) -> None:
    argv = [
        "--num_devices", "2",  # 1 player + 1 trainer sub-mesh
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
        print(f"[dv3-decoupled] resuming from {resume}", flush=True)
        argv += ["--checkpoint_path", resume]
    tasks["dreamer_v3_decoupled"](argv)


def _evaluate(root: Path) -> dict:
    ckpt = latest_checkpoint(str(root / "learn" / "checkpoints"))
    assert ckpt is not None, "no checkpoint to evaluate"
    env = gym.make("CartPole-v1")
    args = DreamerV3Args(env_id="CartPole-v1", seed=5)
    args.cnn_keys, args.mlp_keys = [], ["state"]
    for k in (
        "dense_units", "hidden_size", "recurrent_state_size",
        "stochastic_size", "discrete_size", "mlp_layers", "horizon",
        "action_repeat",
    ):
        setattr(args, k, RECIPE[k])
    wm, actor, critic, tcritic = build_models(
        jax.random.PRNGKey(0), [2], False, args,
        {"state": env.observation_space}, [], ["state"],
    )
    wopt, aopt, copt = make_optimizers(args)
    restored = load_checkpoint(ckpt, {
        "world_model": wm, "actor": actor, "critic": critic,
        "target_critic": tcritic,
        "world_optimizer": wopt.init(wm), "actor_optimizer": aopt.init(actor),
        "critic_optimizer": copt.init(critic),
        "moments": ops.Moments.init(args.moments_decay, args.moment_max),
        "expl_decay_steps": 0, "global_step": 0, "batch_size": 0,
    })
    player = PlayerDV3(
        encoder=restored["world_model"].encoder,
        rssm=restored["world_model"].rssm,
        actor=restored["actor"],
        actions_dim=(2,),
        stochastic_size=RECIPE["stochastic_size"],
        discrete_size=RECIPE["discrete_size"],
        recurrent_state_size=RECIPE["recurrent_state_size"],
        is_continuous=False,
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
            act = one_hot_to_env_actions(np.asarray(actions), (2,), False)[0]
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
        "coupled_twin_result": "greedy mean 408.5 (same recipe)",
    }


def main() -> None:
    from runner_common import bounded_runner_main

    bounded_runner_main(
        "logs/dv3_decoupled_learn_r5", _train, _evaluate, RECIPE,
        "dv3-decoupled",
    )


if __name__ == "__main__":
    main()
