"""First pixel-obs learning receipt (VERDICT r3 next-round #4).

Every prior return receipt is vector-obs; the north star is pixel IQM
parity, so this runner trains tiny DreamerV3 on **dmc_cartpole_balance
pixels** (64x64 rgb through the real DMC wrapper + conv encoder/decoder,
the DMC pixel recipe's shape at CartPole scale) long enough to beat the
random policy by a wide margin, then greedily evaluates the checkpoint.

Env choice (revised after the balance attempts): **swingup**, not balance.
Balance's reward landscape is flat for the actor at tiny scale (random
already collects ~350/1000 because the pole starts upright; the world
model converged, recon 2376->37, but the greedy policy drifted DEGENERATE
— 292 at 8192 steps, 168 at 20480, below random — while stochastic
collection stayed ~300: the trunc-normal mean wandered on a flat imagined
value surface). Swingup's cos-angle shaped reward gives the imagination
gradient signal everywhere and random scores only ~27, so ANY learning is
a wide-margin receipt. Mid-run checkpoints + auto-resume, same
budget-proofing as tools/dv1_learning_run.py.

Reference scope: /root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py:316-707
(pixel Dreamer training is the reference's flagship use).

Usage: MUJOCO_GL=egl python tools/dv3_pixel_learning_run.py [--eval-only]
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("MUJOCO_GL", "egl")  # osmesa is broken in this image

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

import sheeprl_tpu.algos  # noqa: F401 - fire registrations
from sheeprl_tpu import ops
from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3, build_models
from sheeprl_tpu.algos.dreamer_v3.args import DreamerV3Args
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_optimizers
from sheeprl_tpu.utils.checkpoint import latest_checkpoint, load_checkpoint
from sheeprl_tpu.utils.env import make_dict_env
from sheeprl_tpu.utils.registry import tasks

RECIPE = dict(
    env_id="dmc_cartpole_swingup",
    seed=5,
    total_steps=12288,
    learning_starts=1024,
    train_every=4,
    per_rank_batch_size=8,
    per_rank_sequence_length=16,
    buffer_size=100000,
    dense_units=128,
    hidden_size=128,
    recurrent_state_size=128,
    stochastic_size=16,
    discrete_size=16,
    cnn_channels_multiplier=8,
    mlp_layers=2,
    horizon=15,
    action_repeat=2,
    checkpoint_every=2048,
)


def _train(root: Path) -> None:
    argv = [
        "--num_devices", "1",
        "--num_envs", "1",
        "--sync_env",
        "--root_dir", str(root),
        "--run_name", "learn",
        "--cnn_keys", "rgb",
    ]
    for k, v in RECIPE.items():
        if isinstance(v, bool):
            argv += [f"--{k}" if v else f"--no_{k}"]
        else:
            argv += [f"--{k}", str(v)]
    resume = latest_checkpoint(str(root / "learn" / "checkpoints"))
    if resume is not None:
        print(f"[dv3-pixel] resuming from {resume}", flush=True)
        argv += ["--checkpoint_path", resume]
    tasks["dreamer_v3"](argv)


def _evaluate(root: Path, episodes: int = 5) -> dict:
    ckpt = latest_checkpoint(str(root / "learn" / "checkpoints"))
    assert ckpt is not None, "no checkpoint to evaluate"
    args = DreamerV3Args(env_id=RECIPE["env_id"], seed=5, num_envs=1)
    args.cnn_keys, args.mlp_keys = ["rgb"], []
    for k in (
        "dense_units", "hidden_size", "recurrent_state_size",
        "stochastic_size", "discrete_size", "cnn_channels_multiplier",
        "mlp_layers", "horizon", "action_repeat",
    ):
        setattr(args, k, RECIPE[k])
    env = make_dict_env(
        RECIPE["env_id"], 1000, rank=0, args=args, run_name="eval",
        vector_env_idx=0,
    )()
    act_dim = int(np.prod(env.action_space.shape))
    obs_space = {"rgb": env.observation_space["rgb"]}
    wm, actor, critic, tcritic = build_models(
        jax.random.PRNGKey(0), [act_dim], True, args, obs_space, ["rgb"], [],
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
        actions_dim=(act_dim,),
        stochastic_size=RECIPE["stochastic_size"],
        discrete_size=RECIPE["discrete_size"],
        recurrent_state_size=RECIPE["recurrent_state_size"],
        is_continuous=True,
    )
    from sheeprl_tpu.algos.dreamer_v3.utils import make_device_preprocess

    _prep = make_device_preprocess(["rgb"])
    step = jax.jit(
        lambda p, s, o, k: p.step(s, _prep(o), k, jnp.float32(0.0), is_training=False)
    )
    returns = []
    for episode in range(episodes):
        obs, _ = env.reset(seed=2000 + episode)
        state = player.init_states(1)
        key = jax.random.PRNGKey(episode)
        done, ep_return = False, 0.0
        while not done:
            dobs = {"rgb": jnp.asarray(obs["rgb"])[None]}
            key, sub = jax.random.split(key)
            state, actions = step(player, state, dobs, sub)
            obs, reward, terminated, truncated, _ = env.step(
                np.asarray(actions)[0]
            )
            ep_return += float(reward)
            done = terminated or truncated
        returns.append(round(ep_return, 1))
        print(f"[dv3-pixel] eval episode {episode}: {ep_return:.1f}", flush=True)
    env.close()
    return {
        "checkpoint": ckpt,
        "returns": returns,
        "mean_return": float(np.mean(returns)),
        "global_step_restored": int(restored["global_step"]),
        "random_baseline": "swingup random 18.5-35.7 over 3 episodes (measured 2026-08-02)",
    }


def main() -> None:
    from runner_common import bounded_runner_main

    bounded_runner_main(
        "logs/dv3_pixel_swingup_r5", _train, _evaluate, RECIPE, "dv3-pixel"
    )


if __name__ == "__main__":
    main()
