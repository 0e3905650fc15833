"""DreamerV3, decoupled player/trainer — a capability BEYOND the reference
(which decouples only PPO and SAC: /root/reference/sheeprl/algos/ppo/
ppo_decoupled.py, sac/sac_decoupled.py; its Dreamer family is coupled-only).

Topology (sheeprl_tpu/parallel/decoupled.py): the player device owns the
envs, the replay buffer and `PlayerDV3` inference (encoder + RSSM + actor
weights only); the trainer mesh runs the SAME single-jit DreamerV3 update
as the coupled task with the sampled `[T, B]` sequence batches sharded on
their batch axis. Double-buffered overlap like the other decoupled tasks:
the trainer computes update N while the player keeps stepping envs with
(at most one update) stale policy weights — the standard async-actor
staleness of off-policy Dreamer — and swaps in refreshed weights when the
async transfer lands instead of blocking the env loop on trainer compute.

Why this helps: in the coupled task a single device serializes the policy
steps behind the train step, so env interaction stalls for the full update
latency every `train_every` steps. Here the policy runs on its own device
while the trainer mesh updates — the duty-cycle/end-to-end gap closes with
hardware instead of batching tricks.
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ... import ops
from ...data import AsyncReplayBuffer, stage_batch
from ...envs import make_vector_env
from ...envs.wrappers import RestartOnException
from ...parallel import (
    Pipeline,
    distributed_setup,
    make_decoupled_meshes,
    process_index,
)
from ...telemetry import Telemetry
from ... import resilience
from ...analysis import Sanitizer
from ...utils.checkpoint import load_checkpoint, load_checkpoint_args, save_checkpoint
from ...utils.env import make_dict_env
from ...utils.logger import create_logger
from ...utils.metric import MetricAggregator
from ...utils.parser import DataclassArgumentParser
from ...utils.profiler import StepProfiler
from ...utils.registry import register_algorithm
from ..ppo.agent import (
    buffer_actions,
    env_action_indices,
    indices_to_env_actions,
)
from ...compile import CompilePlan, dict_obs_spec
from ..ppo.ppo import actions_dim_of, validate_obs_keys
from ..dreamer_v2.utils import maybe_autotune_scan_unroll, maybe_decide_remat
from .agent import PlayerDV3, build_models
from .args import DreamerV3Args
from .dreamer_v3 import (
    DV3TrainState,
    _random_actions,
    make_optimizers,
    make_train_step,
)
from .utils import make_device_preprocess, test


@register_algorithm()
@resilience.crashsafe
def main(argv: Sequence[str] | None = None) -> None:
    parser = DataclassArgumentParser(DreamerV3Args)
    (args,) = parser.parse_args_into_dataclasses(argv)
    if args.eval_only:
        # A single-stream greedy evaluation has no player/trainer split to
        # exercise, and decoupled checkpoints share the coupled twin's key
        # contract, so route through the coupled evaluator natively
        # (VERDICT r3 #7).
        from .dreamer_v3 import main as coupled_main

        return coupled_main(argv)
    resilience.prepare_run(args, "dreamer_v3_decoupled")
    if args.checkpoint_path:
        saved = load_checkpoint_args(args.checkpoint_path)
        if saved:
            saved.update(checkpoint_path=args.checkpoint_path)
            (args,) = parser.parse_dict(saved)
    args.screen_size = 64
    args.frame_stack = -1
    if args.seq_devices > 1:
        raise ValueError(
            "--seq_devices is not supported by the decoupled topology: the "
            "trainer mesh is 1-D data-parallel (use the coupled dreamer_v3 "
            "task for context parallelism)"
        )

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    np.random.seed(args.seed)
    distributed_setup()
    rank, world = process_index(), jax.process_count()
    key = jax.random.PRNGKey(args.seed)
    meshes = make_decoupled_meshes(args.num_devices)
    # the per-process batch shards over the trainer mesh; an indivisible
    # batch wrap-pads in to_trainers (DistributedSampler semantics,
    # parallel/decoupled.py:62-71), so no divisibility requirement here

    logger, log_dir, run_name = create_logger(
        args, "dreamer_v3_decoupled", process_index=rank
    )
    logger.log_hyperparams(args.as_dict())
    profiler = StepProfiler.from_args(args, log_dir, rank)
    telem = Telemetry.from_args(args, log_dir, rank, algo="dreamer_v3_decoupled")
    guard = resilience.RunGuard.install(telem)
    sanitizer = Sanitizer.from_args(args, telem)
    telem.add_gauges(sanitizer.gauges)
    pipe = Pipeline.from_args(args, telem)
    plan = CompilePlan.from_args(args, telem)
    telem.add_gauges(plan.gauges)
    telem.add_gauges(meshes.telemetry_gauges)

    envs = make_vector_env(
        [
            partial(
                RestartOnException,
                partial(
                    make_dict_env(
                        args.env_id, args.seed + rank * args.num_envs + i,
                        rank=rank, args=args, run_name=log_dir, vector_env_idx=i,
                    )
                ),
            )
            for i in range(args.num_envs)
        ],
        sync=args.sync_env or args.num_envs == 1,
    )
    cnn_keys, mlp_keys = validate_obs_keys(envs.single_observation_space, args)
    obs_keys = [*cnn_keys, *mlp_keys]
    actions_dim, is_continuous = actions_dim_of(envs.single_action_space)

    key, model_key = jax.random.split(key)
    world_model, actor, critic, target_critic = build_models(
        model_key, actions_dim, is_continuous, args,
        envs.single_observation_space.spaces, cnn_keys, mlp_keys,
    )
    # SHEEPRL_TPU_SCAN_UNROLL=auto / --remat auto: measured decisions on
    # this run's RSSM shapes before the trainer jit traces (shared cache)
    maybe_autotune_scan_unroll(
        "dreamer_v3_decoupled", world_model, args, int(sum(actions_dim)), telem
    )
    maybe_decide_remat(
        "dreamer_v3_decoupled", world_model, args, int(sum(actions_dim)), telem
    )
    world_optimizer, actor_optimizer, critic_optimizer = make_optimizers(args)
    state = DV3TrainState(
        world_model=world_model,
        actor=actor,
        critic=critic,
        target_critic=target_critic,
        world_opt=world_optimizer.init(world_model),
        actor_opt=actor_optimizer.init(actor),
        critic_opt=critic_optimizer.init(critic),
        moments=ops.Moments.init(
            args.moments_decay, args.moment_max,
            args.moments_percentile_low, args.moments_percentile_high,
        ),
    )
    expl_decay_steps = 0
    start_step = 1
    if args.checkpoint_path:
        template = {
            "world_model": state.world_model,
            "actor": state.actor,
            "critic": state.critic,
            "target_critic": state.target_critic,
            "world_optimizer": state.world_opt,
            "actor_optimizer": state.actor_opt,
            "critic_optimizer": state.critic_opt,
            "moments": state.moments,
            "expl_decay_steps": 0,
            "global_step": 0,
            "batch_size": 0,
        }
        ckpt = load_checkpoint(args.checkpoint_path, template)
        state = DV3TrainState(
            world_model=ckpt["world_model"],
            actor=ckpt["actor"],
            critic=ckpt["critic"],
            target_critic=ckpt["target_critic"],
            world_opt=ckpt["world_optimizer"],
            actor_opt=ckpt["actor_optimizer"],
            critic_opt=ckpt["critic_optimizer"],
            moments=ckpt["moments"],
        )
        expl_decay_steps = int(ckpt["expl_decay_steps"])
        start_step = int(ckpt["global_step"]) + 1

    # trainers hold the replicated full train state; the player holds only
    # the inference weights (encoder + RSSM + actor)
    state = meshes.replicated_on_trainers(state)
    player_weights = meshes.to_player(
        (state.world_model.encoder, state.world_model.rssm, state.actor),
        deadline_s=float("inf"),
    )
    meshes.note_weights_applied()  # the setup copy is, by definition, applied

    def make_player(weights) -> PlayerDV3:
        encoder, rssm, p_actor = weights
        return PlayerDV3(
            encoder=encoder,
            rssm=rssm,
            actor=p_actor,
            actions_dim=tuple(actions_dim),
            stochastic_size=args.stochastic_size,
            discrete_size=args.discrete_size,
            recurrent_state_size=args.recurrent_state_size,
            is_continuous=is_continuous,
            compute_dtype=args.precision,
        )

    _dev_preprocess = make_device_preprocess(cnn_keys)

    def _player_step(p, s, o, k, expl, mask):
        new_s, acts = p.step(
            s, _dev_preprocess(o), k, expl, is_training=True, mask=mask
        )
        # per-head env indices computed on device: the per-step d2h pull is
        # a few ints (see dreamer_v3.py)
        return new_s, acts, env_action_indices(acts, actions_dim, is_continuous)

    player_step = jax.jit(_player_step)

    train_step = make_train_step(
        args,
        world_optimizer,
        actor_optimizer,
        critic_optimizer,
        cnn_keys,
        mlp_keys,
        actions_dim,
        is_continuous,
        mesh=meshes.trainer_mesh,
    )

    buffer_size = (
        args.buffer_size // (args.num_envs * world) if not args.dry_run else 2
    )
    rb = AsyncReplayBuffer(
        max(buffer_size, args.per_rank_sequence_length),
        args.num_envs,
        storage="host" if args.memmap_buffer else "device",
        memmap_dir=(
            os.path.join(log_dir, "memmap_buffer") if args.memmap_buffer else None
        ),
        sequential=True,
        obs_keys=tuple(obs_keys),
        seed=args.seed,
    )
    buffer_ckpt = (
        os.path.abspath(args.checkpoint_path) + "_buffer.npz"
        if args.checkpoint_path
        else None
    )
    if buffer_ckpt and args.checkpoint_buffer and os.path.exists(buffer_ckpt):
        rb.load(buffer_ckpt)

    aggregator = MetricAggregator()
    single_global_step = args.num_envs
    step_before_training = args.train_every // single_global_step
    num_updates = args.total_steps // single_global_step if not args.dry_run else 1
    learning_starts = (
        args.learning_starts // single_global_step if not args.dry_run else 0
    )
    if args.checkpoint_path and not args.checkpoint_buffer:
        learning_starts += start_step
    if args.dry_run:
        # V3 row layout: the first training fires with step_before_training
        # rows per env ring (no pre-loop add) — clamp the sampled window so
        # the smoke runs on DEFAULT flags
        args.per_rank_sequence_length = min(
            args.per_rank_sequence_length,
            max(args.train_every // args.num_envs, 1),
        )
    max_step_expl_decay = args.max_step_expl_decay // args.gradient_steps
    expl_amount = args.expl_amount
    if args.checkpoint_path and max_step_expl_decay > 0:
        expl_amount = ops.polynomial_decay(
            expl_decay_steps,
            initial=args.expl_amount,
            final=args.expl_min,
            max_decay_steps=max_step_expl_decay,
        )

    obs, _ = envs.reset(seed=args.seed)
    step_data = {k: np.asarray(obs[k]) for k in obs_keys}
    step_data["dones"] = np.zeros((args.num_envs, 1), np.float32)
    step_data["rewards"] = np.zeros((args.num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((args.num_envs, 1), np.float32)
    player = make_player(player_weights)
    player_state = player.init_states(args.num_envs)

    # ---- warm-start shape capture (ISSUE 5): zero example batches run
    # through the SAME trainer-mesh placement as the live loop, so the AOT
    # executables compile for the exact shardings the updates use
    act_sum = int(sum(actions_dim))
    obs_space = envs.single_observation_space

    def _train_example():
        T, B = args.per_rank_sequence_length, args.per_rank_batch_size
        sample = {
            k: np.zeros(
                (T, B) + tuple(obs_space[k].shape),
                np.uint8 if k in cnn_keys else np.float32,
            )
            for k in obs_keys
        }
        sample["actions"] = np.zeros((T, B, act_sum), np.float32)
        for k in ("rewards", "dones", "is_first"):
            sample[k] = np.zeros((T, B, 1), np.float32)
        sample = meshes.to_trainers(sample, axis=1)
        return (state, sample, key, jnp.float32(1.0))

    train_step = plan.register(
        "train_step", train_step, example=_train_example, role="update"
    )
    player_step = plan.register(
        "player_step", player_step,
        example=lambda: (
            player, player.init_states(args.num_envs),
            dict_obs_spec(obs_space, obs_keys, cnn_keys, (args.num_envs,)),
            key, jnp.float32(0.0), None,
        ),
    )
    # data edge (ISSUE 8): player rollouts reach the update through the
    # replay buffer + the explicit meshes.to_trainers put — the sharding
    # change across the edge is the decoupled contract.
    plan.declare_edge(
        "player_step", "train_step", expect="reshard",
        note="replay buffer + meshes.to_trainers: player -> trainer mesh",
    )
    plan.start()

    gradient_steps = 0
    pending_weights = None
    prev_metrics = None
    start_time = time.perf_counter()
    for global_step in range(start_step, num_updates + 1):
        guard.tick(global_step)  # fires injected sig* faults for this step
        telem.mark("rollout")
        # ---- player: swap in refreshed weights if the transfer landed -------
        if pending_weights is not None:
            leaves = jax.tree_util.tree_leaves(pending_weights)
            if global_step == num_updates or all(
                leaf.is_ready() for leaf in leaves if hasattr(leaf, "is_ready")
            ):
                player_weights = pending_weights
                player = make_player(player_weights)
                pending_weights = None
                meshes.note_weights_applied()

        # ---- player: action selection ---------------------------------------
        if (
            global_step <= learning_starts
            and args.checkpoint_path is None
            and "minedojo" not in args.env_id
        ):
            pairs = [
                _random_actions(envs.single_action_space, actions_dim, is_continuous)
                for _ in range(args.num_envs)
            ]
            actions = np.stack([p[0] for p in pairs])
            env_actions = [p[1] for p in pairs]
        else:
            device_obs = {k: jnp.asarray(np.asarray(obs[k])) for k in obs_keys}
            mask = {k: v for k, v in device_obs.items() if k.startswith("mask")} or None
            key, step_key = jax.random.split(key)
            player_state, actions_dev, env_idx_dev = player_step(
                player, player_state, device_obs, step_key,
                jnp.float32(expl_amount), mask,
            )
            env_idx = pipe.action.fetch(env_idx_dev)  # the ONLY per-step d2h pull
            env_actions = list(
                indices_to_env_actions(env_idx, actions_dim, is_continuous)
            )
            # host rows throughout (see rb.add below): rebuilt from the
            # tiny index pull instead of pulling the full one-hot
            actions = buffer_actions(
                env_idx, actions_dev, actions_dim, is_continuous, host=True
            )

        step_data["actions"] = actions.astype(np.float32)
        # host rows throughout: the buffer lives on the player device and the
        # policy puts are committed there — rb's packed add keeps the
        # transfer count low without cross-sub-mesh placement hazards
        rb.add({k: v[None] for k, v in step_data.items()})

        next_obs, rewards, terms, truncs, infos = envs.step(env_actions)
        dones = np.logical_or(terms, truncs).astype(np.float32)

        step_data["is_first"] = np.zeros((args.num_envs, 1), np.float32)
        for i, info in enumerate(infos):
            if info.get("restart_on_exception") and not dones[i]:
                env_rb = rb.buffer[i]
                last_idx = (env_rb.pos - 1) % env_rb.buffer_size
                env_rb.set_at("dones", last_idx, np.ones((1, 1), np.float32))
                env_rb.set_at("is_first", last_idx, np.zeros((1, 1), np.float32))
                step_data["is_first"][i] = 1.0
            if "episode" in info:
                aggregator.update("Rewards/rew_avg", float(info["episode"]["r"]))
                aggregator.update("Game/ep_len_avg", float(info["episode"]["l"]))

        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
        for i, info in enumerate(infos):
            if "final_observation" in info:
                for k in obs_keys:
                    real_next_obs[k][i] = info["final_observation"][k]

        for k in obs_keys:
            step_data[k] = np.asarray(next_obs[k])
        obs = next_obs
        step_data["dones"] = dones[:, None]
        step_data["rewards"] = (
            np.tanh(rewards)[:, None] if args.clip_rewards else rewards[:, None]
        ).astype(np.float32)

        dones_idxes = np.nonzero(dones)[0].tolist()
        if dones_idxes:
            n_reset = len(dones_idxes)
            reset_data = {k: real_next_obs[k][dones_idxes][None] for k in obs_keys}
            reset_data["dones"] = np.ones((1, n_reset, 1), np.float32)
            reset_data["actions"] = np.zeros(
                (1, n_reset, int(sum(actions_dim))), np.float32
            )
            reset_data["rewards"] = step_data["rewards"][dones_idxes][None]
            reset_data["is_first"] = np.zeros((1, n_reset, 1), np.float32)
            rb.add(reset_data, dones_idxes)
            step_data["rewards"][dones_idxes] = 0.0
            step_data["dones"][dones_idxes] = 0.0
            step_data["is_first"][dones_idxes] = 1.0
            reset_mask = np.zeros((args.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0
            player_state = player.reset_states(player_state, jnp.asarray(reset_mask))

        step_before_training -= 1

        # ---- player samples; trainers update (overlapped) --------------------
        if global_step >= learning_starts and step_before_training <= 0:
            n_samples = (
                args.pretrain_steps
                if global_step == learning_starts
                else args.gradient_steps
            )
            telem.mark("buffer/sample")
            local_data = pipe.sampler(rb).sample(
                args.per_rank_batch_size,
                sequence_length=args.per_rank_sequence_length,
                n_samples=n_samples,
            )
            staged = stage_batch(local_data, to_host=jax.process_count() > 1)
            telem.mark("host_to_device")
            # ship the block's n_samples [T, B] rows to the trainer mesh,
            # batch axis sharded (the data path — ICI, typed pytree)
            staged = meshes.to_trainers(staged, axis=1)
            telem.mark("train/dispatch")
            for i in range(n_samples):
                if gradient_steps % args.critic_target_network_update_freq == 0:
                    tau = 1.0 if gradient_steps == 0 else args.critic_tau
                else:
                    tau = 0.0
                sample = staged[i]
                key, train_key = jax.random.split(key)
                sample = resilience.poison_batch(sample, global_step)  # nan.* sites
                state, metrics = train_step(state, sample, train_key, jnp.float32(tau))
                resilience.update_skipped(metrics, args.on_nonfinite)
                gradient_steps += 1
                # log the PREVIOUS update's metrics — pulling this update's
                # scalars would block the host on the trainer mesh and kill
                # the overlap
                if prev_metrics is not None:
                    for name, val in prev_metrics.items():
                        aggregator.update(name, val)
                profiler.tick()
                prev_metrics = metrics
            # the weight path: refreshed inference weights stream back to
            # the player device behind the update; consumed when ready. A
            # deadline-dropped transfer (None) keeps the player on stale
            # weights — graceful degradation instead of deadlock (ISSUE 12)
            shipped_weights = meshes.to_player(
                (state.world_model.encoder, state.world_model.rssm, state.actor)
            )
            if shipped_weights is not None:
                pending_weights = shipped_weights
            step_before_training = args.train_every // single_global_step
            if args.expl_decay:
                expl_decay_steps += 1
                expl_amount = ops.polynomial_decay(
                    expl_decay_steps,
                    initial=args.expl_amount,
                    final=args.expl_min,
                    max_decay_steps=max_step_expl_decay,
                )
            aggregator.update("Params/exploration_amount", expl_amount)

        telem.mark("log")
        sps = (global_step - start_step + 1) * args.num_envs / (
            time.perf_counter() - start_time
        )
        for drained, dstep in pipe.drain_metrics(aggregator, global_step):
            logger.log_dict(telem.interval(drained, dstep, sps), dstep)
        logger.log("Time/step_per_second", sps, global_step)

        # ---- checkpoint ------------------------------------------------------
        if (
            (args.checkpoint_every > 0 and global_step % args.checkpoint_every == 0)
            or args.dry_run
            or global_step == num_updates
            or guard.preempted
        ):
            ckpt_path = os.path.join(log_dir, "checkpoints", f"ckpt_{global_step}")
            save_checkpoint(
                ckpt_path,
                {
                    "world_model": state.world_model,
                    "actor": state.actor,
                    "critic": state.critic,
                    "target_critic": state.target_critic,
                    "world_optimizer": state.world_opt,
                    "actor_optimizer": state.actor_opt,
                    "critic_optimizer": state.critic_opt,
                    "moments": state.moments,
                    "expl_decay_steps": expl_decay_steps,
                    "global_step": global_step,
                    "batch_size": args.per_rank_batch_size,
                },
                args=args,
                block=args.dry_run or global_step == num_updates or guard.preempted,
            )
            if args.checkpoint_buffer:
                rb.save(ckpt_path + "_buffer.npz")

        if guard.preempted:
            # the in-flight step finished and its grace checkpoint
            # committed: exit with the distinct resumable rc
            raise resilience.Preempted(global_step, guard.preempt_signal or "")
    for drained, dstep in pipe.flush_metrics():
        logger.log_dict(telem.interval(drained, dstep, None), dstep)
    profiler.close()
    envs.close()
    # the final update's refreshed weights may still be in flight: swap them
    # in so the end-of-run evaluation sees the trained policy, not a
    # one-burst-stale one (the coupled task rebuilds its player from the
    # post-update state before test())
    if pending_weights is not None:
        player = make_player(pending_weights)
    # drain the pipeline: final update's metrics
    if prev_metrics is not None:
        for name, val in prev_metrics.items():
            aggregator.update(name, val)
        logger.log_dict(aggregator.compute(), num_updates)
        aggregator.reset()
    test(player, logger, args, cnn_keys, mlp_keys, log_dir, sample_actions=True)
    plan.close()
    sanitizer.close()
    telem.close()
    logger.close()


if __name__ == "__main__":
    main()
