"""DreamerV1 (arXiv:1912.01603), coupled — capability parity with
/root/reference/sheeprl/algos/dreamer_v1/dreamer_v1.py.

Same TPU-first structure as the V2/V3 tasks (one jitted train step: Gaussian
RSSM `lax.scan`, imagination scan, three optimizer updates), with the V1
semantics: Normal(x, 1) likelihoods, KL with free nats on the mean, pure
dynamics-backpropagation actor loss `-mean(discount * lambda)`, no target
critic, no `is_first` tracking in the buffer rows.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ... import nn, ops
from ...data import AsyncReplayBuffer, stage_batch
from ...envs import make_vector_env
from ...ops.distributions import Bernoulli, Independent, Normal
from ...parallel import (
    Pipeline,
    assert_divisible,
    distributed_setup,
    make_mesh,
    process_index,
    replicate,
    constrain_scan_inputs,
    constrain_time_batch,
    make_constrain,
    scan_batch_spec,
    shard_time_batch,
)
from ...telemetry import Telemetry
from ... import resilience
from ...analysis import Sanitizer
from ...compile import CompilePlan, dict_obs_spec, dreamer_sample_spec, remat_mode
from ...utils.jit import donating_jit
from ...utils.checkpoint import load_checkpoint, load_checkpoint_args, save_checkpoint
from ...utils.evaluation import (
    apply_eval_overrides,
    run_test_episodes,
    validate_eval_args,
)
from ...utils.env import make_dict_env
from ...utils.logger import create_logger
from ...utils.metric import MetricAggregator
from ...utils.profiler import StepProfiler
from ...utils.parser import DataclassArgumentParser
from ...utils.registry import register_algorithm
from ..ppo.agent import (
    buffer_actions,
    env_action_indices,
    indices_to_env_actions,
)
from ..ppo.ppo import actions_dim_of, validate_obs_keys
from ..dreamer_v2.utils import (
    make_device_preprocess,
    make_row_codec,
    maybe_autotune_scan_unroll,
    maybe_decide_remat,
    substitute_step_obs,
    test,
)
from ..dreamer_v3.agent import WorldModel
from ..dreamer_v3.dreamer_v3 import _random_actions
from .agent import PlayerDV1, build_models
from .args import DreamerV1Args
from .loss import actor_loss as actor_loss_fn_v1
from .loss import critic_loss as critic_loss_fn_v1
from .loss import reconstruction_loss


class DV1TrainState(nn.Module):
    world_model: WorldModel
    actor: object
    critic: nn.MLP
    world_opt: object
    actor_opt: object
    critic_opt: object


def make_optimizers(args: DreamerV1Args):
    """Plain Adam with shared clipping (reference dreamer_v1.py:475-477)."""

    def chain(lr):
        steps = []
        if args.clip_gradients is not None and args.clip_gradients > 0:
            steps.append(optax.clip_by_global_norm(args.clip_gradients))
        steps.append(optax.adam(lr))
        return optax.chain(*steps)

    return chain(args.world_lr), chain(args.actor_lr), chain(args.critic_lr)


def make_train_step(
    args: DreamerV1Args,
    world_optimizer,
    actor_optimizer,
    critic_optimizer,
    cnn_keys: Sequence[str],
    mlp_keys: Sequence[str],
    mesh=None,
):
    """Build the single-jit DreamerV1 update (reference train(),
    dreamer_v1.py:40-356)."""
    constrain = make_constrain(mesh)
    horizon = args.horizon
    # --precision bfloat16: model forwards run in bf16, params stay f32,
    # Gaussian means/stds, losses and lambda-return math stay f32
    # (ops/precision.py — the shared mixed-precision policy)
    compute_dtype = ops.precision.compute_dtype(args.precision)
    use_remat = remat_mode(args.remat)

    def train_step(state: DV1TrainState, data: dict, key):
        T, B = data["dones"].shape[:2]
        scan_spec = scan_batch_spec(mesh, B)
        k_wm, k_img = jax.random.split(key)
        obs_targets = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        obs_targets.update({k: data[k] for k in mlp_keys})
        batch_obs = {k: v.astype(compute_dtype) for k, v in obs_targets.items()}

        # ---- world model -----------------------------------------------------
        def world_loss_fn(wm: WorldModel):
            embedded = constrain_scan_inputs(constrain, scan_spec, wm.encoder(batch_obs))
            posterior0 = jnp.zeros((B, args.stochastic_size), compute_dtype)
            recurrent0 = jnp.zeros((B, args.recurrent_state_size), compute_dtype)
            recurrent_states, posteriors, post_means, post_stds, prior_means, prior_stds = (
                wm.rssm.scan_dynamic(
                    posterior0,
                    recurrent0,
                    constrain_scan_inputs(
                        constrain, scan_spec, data["actions"].astype(compute_dtype)
                    ),
                    embedded,
                    k_wm,
                    remat=use_remat,
                )
            )
            (recurrent_states, posteriors, post_means, post_stds,
             prior_means, prior_stds) = constrain_time_batch(
                constrain,
                recurrent_states, posteriors, post_means, post_stds,
                prior_means, prior_stds,
                from_spec=scan_spec,
            )
            latent_states = jnp.concatenate([posteriors, recurrent_states], axis=-1)
            # fp32 island: likelihood/KL math runs full width
            decoded = {
                k: v.astype(jnp.float32)
                for k, v in wm.observation_model(latent_states).items()
            }
            qo = {
                k: Independent(
                    base=Normal(loc=decoded[k], scale=jnp.ones_like(decoded[k])),
                    event_ndims=len(decoded[k].shape[2:]),
                )
                for k in decoded
            }
            qr_mean = wm.reward_model(latent_states).astype(jnp.float32)
            qr = Independent(
                base=Normal(loc=qr_mean, scale=jnp.ones_like(qr_mean)), event_ndims=1
            )
            if args.use_continues:
                qc = Independent(
                    base=Bernoulli(
                        logits=wm.continue_model(latent_states).astype(jnp.float32)
                    ),
                    event_ndims=1,
                )
                continue_targets = (1.0 - data["dones"]) * args.gamma
            else:
                qc = continue_targets = None
            losses = reconstruction_loss(
                qo,
                obs_targets,
                qr,
                data["rewards"],
                (post_means, post_stds),
                (prior_means, prior_stds),
                args.kl_free_nats,
                args.kl_regularizer,
                qc,
                continue_targets,
                args.continue_scale_factor,
            )
            aux = (losses, recurrent_states, posteriors, post_means, post_stds, prior_means, prior_stds)
            return losses[0], aux

        (_, (wm_losses, recurrent_states, posteriors, post_means, post_stds, prior_means, prior_stds)), wm_grads = (
            jax.value_and_grad(world_loss_fn, has_aux=True)(state.world_model)
        )
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = wm_losses
        wm_updates, world_opt = world_optimizer.update(
            wm_grads, state.world_opt, state.world_model
        )
        world_model = optax.apply_updates(state.world_model, wm_updates)

        # ---- behaviour: imagination + actor ---------------------------------
        imagined_prior0 = constrain(
            jnp.swapaxes(jax.lax.stop_gradient(posteriors), 0, 1).reshape(T * B, args.stochastic_size),
            ("data", "seq"),
        )
        recurrent0 = constrain(
            jnp.swapaxes(jax.lax.stop_gradient(recurrent_states), 0, 1).reshape(
                T * B, args.recurrent_state_size
            ),
            ("data", "seq"),
        )
        img_keys = jax.random.split(k_img, horizon)

        def actor_loss_fn(actor):
            def img_step(carry, k):
                prior, recurrent = carry
                latent = jnp.concatenate([prior, recurrent], axis=-1)
                k_act, k_trans = jax.random.split(k)
                acts, _ = actor(jax.lax.stop_gradient(latent), key=k_act)
                # actions sample from f32 logits; the imagination recurrence
                # runs in the compute dtype
                action = jnp.concatenate(acts, axis=-1).astype(prior.dtype)
                new_prior, new_recurrent = world_model.rssm.imagination(
                    prior, recurrent, action, k_trans
                )
                new_latent = jnp.concatenate([new_prior, new_recurrent], axis=-1)
                return (new_prior, new_recurrent), new_latent

            img_step = ops.checkpoint_body(img_step, use_remat)
            # H imagination steps; trajectory entries are the POST-step
            # latents (reference dreamer_v1.py:252-258 — no entry for z0)
            _, imagined_trajectories = jax.lax.scan(
                img_step, (imagined_prior0, recurrent0), img_keys,
                unroll=ops.scan_unroll(),
            )  # [H, T*B, L]

            predicted_values = state.critic(imagined_trajectories).astype(jnp.float32)
            predicted_rewards = world_model.reward_model(
                imagined_trajectories
            ).astype(jnp.float32)
            if args.use_continues:
                predicted_continues = Independent(
                    base=Bernoulli(
                        logits=world_model.continue_model(
                            imagined_trajectories
                        ).astype(jnp.float32)
                    ),
                    event_ndims=1,
                ).mean
            else:
                predicted_continues = (
                    jnp.ones_like(jax.lax.stop_gradient(predicted_rewards)) * args.gamma
                )

            lambda_values = ops.lambda_values(
                predicted_rewards,
                predicted_values,
                predicted_continues,
                predicted_values[-1],
                horizon=horizon,
                lmbda=args.lmbda,
            )  # [H-1, T*B, 1]
            discount = jax.lax.stop_gradient(
                jnp.cumprod(
                    jnp.concatenate(
                        [jnp.ones_like(predicted_continues[:1]), predicted_continues[:-2]],
                        axis=0,
                    ),
                    axis=0,
                )
            )  # [H-1, T*B, 1]
            policy_loss = actor_loss_fn_v1(discount * lambda_values)
            return policy_loss, (imagined_trajectories, lambda_values, discount)

        (policy_loss, (imagined_trajectories, lambda_values, discount)), actor_grads = (
            jax.value_and_grad(actor_loss_fn, has_aux=True)(state.actor)
        )
        actor_updates, actor_opt = actor_optimizer.update(
            actor_grads, state.actor_opt, state.actor
        )
        actor = optax.apply_updates(state.actor, actor_updates)

        # ---- critic ----------------------------------------------------------
        traj_sg = jax.lax.stop_gradient(imagined_trajectories)
        lambda_sg = jax.lax.stop_gradient(lambda_values)

        def critic_loss_fn(critic):
            qv_mean = critic(traj_sg).astype(jnp.float32)[:-1]
            qv = Independent(
                base=Normal(loc=qv_mean, scale=jnp.ones_like(qv_mean)), event_ndims=1
            )
            return critic_loss_fn_v1(qv, lambda_sg, discount[..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state.critic)
        critic_updates, critic_opt = critic_optimizer.update(
            critic_grads, state.critic_opt, state.critic
        )
        critic = optax.apply_updates(state.critic, critic_updates)

        post_entropy = (
            Independent(base=Normal(loc=post_means, scale=post_stds), event_ndims=1)
            .entropy()
            .mean()
        )
        prior_entropy = (
            Independent(base=Normal(loc=prior_means, scale=prior_stds), event_ndims=1)
            .entropy()
            .mean()
        )
        new_state = DV1TrainState(
            world_model=world_model,
            actor=actor,
            critic=critic,
            world_opt=world_opt,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
        )
        metrics = {
            "Loss/reconstruction_loss": rec_loss,
            "Loss/observation_loss": observation_loss,
            "Loss/reward_loss": reward_loss,
            "Loss/state_loss": state_loss,
            "Loss/continue_loss": continue_loss,
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
            "State/kl": kl,
            "State/post_entropy": post_entropy,
            "State/prior_entropy": prior_entropy,
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/actor": optax.global_norm(actor_grads),
            "Grads/critic": optax.global_norm(critic_grads),
        }
        return new_state, metrics

    # --on_nonfinite skip/rollback: donation-safe nonfinite select around
    # the unjitted body (default 'warn' is identity - zero jaxpr drift)
    train_step = resilience.guard_nonfinite(train_step, args.on_nonfinite)
    return donating_jit(train_step, donate_argnums=(0,))


@register_algorithm()
@resilience.crashsafe
def main(argv: Sequence[str] | None = None) -> None:
    parser = DataclassArgumentParser(DreamerV1Args)
    (args,) = parser.parse_args_into_dataclasses(argv)
    validate_eval_args(args)
    resilience.prepare_run(args, "dreamer_v1")
    if args.checkpoint_path:
        saved = load_checkpoint_args(args.checkpoint_path)
        if saved:
            saved.update(checkpoint_path=args.checkpoint_path)
            apply_eval_overrides(saved, args)
            (args,) = parser.parse_dict(saved)
    args.screen_size = 64
    args.frame_stack = -1

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    np.random.seed(args.seed)
    distributed_setup()
    rank, world = process_index(), jax.process_count()
    key = jax.random.PRNGKey(args.seed)
    mesh = make_mesh(args.num_devices, seq_devices=args.seq_devices)
    n_dev = mesh.devices.size
    # the global batch (per-process batch x world) shards over the global mesh
    assert_divisible(
        args.per_rank_batch_size * world,
        mesh.shape["data"],
        "per_rank_batch_size*world",
    )
    assert_divisible(
        args.per_rank_sequence_length, args.seq_devices, "per_rank_sequence_length"
    )

    logger, log_dir, run_name = create_logger(args, "dreamer_v1", process_index=rank)
    logger.log_hyperparams(args.as_dict())
    profiler = StepProfiler.from_args(args, log_dir, rank)
    telem = Telemetry.from_args(args, log_dir, rank, algo="dreamer_v1")
    guard = resilience.RunGuard.install(telem)
    sanitizer = Sanitizer.from_args(args, telem)
    telem.add_gauges(sanitizer.gauges)
    pipe = Pipeline.from_args(args, telem)
    plan = CompilePlan.from_args(args, telem)
    telem.add_gauges(plan.gauges)

    envs = make_vector_env(
        [
            make_dict_env(
                args.env_id, args.seed + rank * args.num_envs + i, rank=rank, args=args,
                run_name=log_dir, vector_env_idx=i,
            )
            for i in range(args.num_envs)
        ],
        sync=args.sync_env or args.num_envs == 1,
    )
    cnn_keys, mlp_keys = validate_obs_keys(envs.single_observation_space, args)
    obs_keys = [*cnn_keys, *mlp_keys]
    actions_dim, is_continuous = actions_dim_of(envs.single_action_space)

    key, model_key = jax.random.split(key)
    world_model, actor, critic = build_models(
        model_key,
        actions_dim,
        is_continuous,
        args,
        envs.single_observation_space.spaces,
        cnn_keys,
        mlp_keys,
    )
    # SHEEPRL_TPU_SCAN_UNROLL=auto / --remat auto: both measured decisions
    # run on this run's RSSM shapes BEFORE the train jit traces, through
    # the shared decision cache (compile/decisions.py)
    maybe_autotune_scan_unroll(
        "dreamer_v1", world_model, args, int(sum(actions_dim)), telem
    )
    maybe_decide_remat(
        "dreamer_v1", world_model, args, int(sum(actions_dim)), telem
    )
    world_optimizer, actor_optimizer, critic_optimizer = make_optimizers(args)
    state = DV1TrainState(
        world_model=world_model,
        actor=actor,
        critic=critic,
        world_opt=world_optimizer.init(world_model),
        actor_opt=actor_optimizer.init(actor),
        critic_opt=critic_optimizer.init(critic),
    )
    expl_decay_steps = 0
    start_step = 1
    if args.checkpoint_path:
        template = {
            "world_model": state.world_model,
            "actor": state.actor,
            "critic": state.critic,
            "world_optimizer": state.world_opt,
            "actor_optimizer": state.actor_opt,
            "critic_optimizer": state.critic_opt,
            "expl_decay_steps": 0,
            "global_step": 0,
            "batch_size": 0,
        }
        ckpt = load_checkpoint(args.checkpoint_path, template)
        state = DV1TrainState(
            world_model=ckpt["world_model"],
            actor=ckpt["actor"],
            critic=ckpt["critic"],
            world_opt=ckpt["world_optimizer"],
            actor_opt=ckpt["actor_optimizer"],
            critic_opt=ckpt["critic_optimizer"],
        )
        expl_decay_steps = int(ckpt["expl_decay_steps"])
        start_step = int(ckpt["global_step"]) + 1
    state = replicate(state, mesh)

    def make_player(st: DV1TrainState) -> PlayerDV1:
        return PlayerDV1(
            encoder=st.world_model.encoder,
            rssm=st.world_model.rssm,
            actor=st.actor,
            actions_dim=tuple(actions_dim),
            stochastic_size=args.stochastic_size,
            recurrent_state_size=args.recurrent_state_size,
            is_continuous=is_continuous,
            compute_dtype=args.precision,
        )

    player = make_player(state)

    # raw obs puts (uint8 pixels), normalized inside the jit; the same
    # device arrays feed rb.add (see dreamer_v3.py — V2 row layout here:
    # the stored obs is real_next_obs, which equals the NEXT policy obs
    # whenever no env finished, so the put is shared across both uses)
    _dev_preprocess = make_device_preprocess(cnn_keys)

    def _player_step(p, s, o, k, expl, mask):
        new_s, acts = p.step(
            s, _dev_preprocess(o), k, expl, is_training=True, mask=mask
        )
        # per-head env indices computed on device: the per-step d2h pull is
        # a few ints; the one-hot stays device-resident for rb.add
        return new_s, acts, env_action_indices(acts, actions_dim, is_continuous)

    # sheepopt auto-donation (ISSUE 11, SC010 over the committed ledger):
    # the caller rebinds player_state to this jit's output every step and
    # never touches the old state again — donating it lets XLA alias the
    # state buffers in place instead of holding both copies per dispatch
    player_step = donating_jit(_player_step, donate_argnums=(1,))
    train_step = make_train_step(
        args, world_optimizer, actor_optimizer, critic_optimizer, cnn_keys,
        mlp_keys, mesh=mesh,
    )

    if args.dry_run:
        # the dry run adds ~2 rows before its single update fires
        # (step_before_training=0): clamp the sampled window so the smoke
        # runs on DEFAULT flags instead of raising "too long
        # sequence_length" from a 2-row ring
        args.per_rank_sequence_length = min(args.per_rank_sequence_length, 2)
    buffer_size = args.buffer_size // (args.num_envs * world) if not args.dry_run else 4
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
    if buffer_ckpt and args.checkpoint_buffer and os.path.exists(buffer_ckpt) and not args.eval_only:
        rb.load(buffer_ckpt)

    # ---- warm-start shape capture (ISSUE 5): AOT-compile the train step
    # and the interaction jit concurrently with the learning_starts window
    act_sum = int(sum(actions_dim))
    train_step = plan.register(
        "train_step", train_step,
        example=lambda: (
            state,
            dreamer_sample_spec(
                envs.single_observation_space, obs_keys, cnn_keys,
                args.per_rank_sequence_length, args.per_rank_batch_size,
                act_sum, extra=("rewards", "dones"),
                mesh=mesh if n_dev > 1 else None,
            ),
            key,
        ),
        role="update",
    )
    player_step = plan.register(
        "player_step", player_step,
        example=lambda: (
            player, player.init_states(args.num_envs),
            dict_obs_spec(
                envs.single_observation_space, obs_keys, cnn_keys,
                (args.num_envs,),
            ),
            key, jnp.float32(0.0), None,
        ),
    )
    plan.start()

    aggregator = MetricAggregator()
    single_global_step = args.num_envs * args.action_repeat
    step_before_training = (
        args.train_every // single_global_step if not args.dry_run else 0
    )
    num_updates = args.total_steps // single_global_step if not args.dry_run else 1
    learning_starts = args.learning_starts // single_global_step if not args.dry_run else 0
    if args.checkpoint_path and not args.checkpoint_buffer:
        learning_starts += start_step
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
    step_data["actions"] = np.zeros((args.num_envs, int(sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((args.num_envs, 1), np.float32)
    rb.add({k: v[None] for k, v in step_data.items()})
    player_state = player.init_states(args.num_envs)
    device_next_obs = None  # this step's obs put, shared policy<->rb.add
    use_blob = (
        not rb.prefers_host_adds
        and os.environ.get("SHEEPRL_TPU_STEP_BLOB", "1") != "0"
    )
    if use_blob:
        blob_add = make_row_codec(obs, obs_keys, args.num_envs, ("rewards", "dones"))
        use_blob = blob_add is not None  # live-backend roundtrip check

    gradient_steps = 0
    start_time = time.perf_counter()
    if args.eval_only:
        num_updates = start_step - 1  # empty training loop: fall through to test
    for global_step in range(start_step, num_updates + 1):
        guard.tick(global_step)  # fires injected sig* faults for this step
        telem.mark("rollout")
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
            if device_next_obs is None:
                device_next_obs = {
                    k: jnp.asarray(np.asarray(obs[k])) for k in obs_keys
                }
            device_obs = device_next_obs
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
            actions = buffer_actions(
                env_idx, actions_dev, actions_dim, is_continuous,
                host=rb.prefers_host_adds,
            )

        next_obs, rewards, terms, truncs, infos = envs.step(env_actions)
        dones = np.logical_or(terms, truncs).astype(np.float32)

        for i, info in enumerate(infos):
            if "episode" in info:
                aggregator.update("Rewards/rew_avg", float(info["episode"]["r"]))
                aggregator.update("Game/ep_len_avg", float(info["episode"]["l"]))

        real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
        for i, info in enumerate(infos):
            if "final_observation" in info:
                for k in obs_keys:
                    real_next_obs[k][i] = info["final_observation"][k]

        for k in obs_keys:
            step_data[k] = real_next_obs[k]
        obs = next_obs
        step_data["dones"] = dones[:, None]
        step_data["actions"] = (
            actions if isinstance(actions, jax.Array)
            else np.asarray(actions, np.float32)
        )
        step_data["rewards"] = (
            np.tanh(rewards)[:, None] if args.clip_rewards else rewards[:, None]
        ).astype(np.float32)
        if use_blob and isinstance(actions, jax.Array):
            # ONE transfer for obs + row floats + ring write indices;
            # returns the obs the next policy step reuses (data/blob.py)
            device_next_obs = blob_add(rb, real_next_obs, step_data, actions)
        else:
            add_data = {k: v[None] for k, v in step_data.items()}
            # one put for this step's obs: the add consumes it now and the
            # next policy step reuses it (unless an env resets below)
            device_next_obs = substitute_step_obs(add_data, rb, real_next_obs, obs_keys)
            rb.add(add_data)

        dones_idxes = np.nonzero(dones)[0].tolist()
        if dones_idxes:
            n_reset = len(dones_idxes)
            reset_data = {k: np.asarray(obs[k])[dones_idxes] for k in obs_keys}
            reset_data["dones"] = np.zeros((n_reset, 1), np.float32)
            reset_data["actions"] = np.zeros(
                (n_reset, int(sum(actions_dim))), np.float32
            )
            reset_data["rewards"] = np.zeros((n_reset, 1), np.float32)
            rb.add({k: v[None] for k, v in reset_data.items()}, dones_idxes)
            # finished envs observe their RESET obs next, not the stored
            # final obs: drop the shared put and re-put next iteration
            device_next_obs = None
            step_data["dones"][dones_idxes] = 0.0
            reset_mask = np.zeros((args.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0
            player_state = player.reset_states(player_state, jnp.asarray(reset_mask))

        step_before_training -= 1

        if global_step >= learning_starts and step_before_training <= 0:
            telem.mark("buffer/sample")
            local_data = pipe.sampler(rb).sample(
                args.per_rank_batch_size,
                sequence_length=args.per_rank_sequence_length,
                n_samples=args.gradient_steps if not args.dry_run else 1,
            )
            n_samples = next(iter(local_data.values())).shape[0]
            staged = stage_batch(local_data, to_host=jax.process_count() > 1)
            telem.mark("train/dispatch")
            for i in range(n_samples):
                sample = staged[i]
                if n_dev > 1:
                    sample = shard_time_batch(sample, mesh, time_axis=0, batch_axis=1)
                key, train_key = jax.random.split(key)
                sample = resilience.poison_batch(sample, global_step)  # nan.* sites
                state, metrics = train_step(state, sample, train_key)
                resilience.update_skipped(metrics, args.on_nonfinite)
                gradient_steps += 1
                for name, val in metrics.items():
                    aggregator.update(name, val)
                profiler.tick()
            player = make_player(state)
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
        sps = (global_step - start_step + 1) * single_global_step / (
            time.perf_counter() - start_time
        )
        for drained, dstep in pipe.drain_metrics(aggregator, global_step):
            logger.log_dict(telem.interval(drained, dstep, sps), dstep)
        logger.log("Time/step_per_second", sps, global_step)

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
                    "world_optimizer": state.world_opt,
                    "actor_optimizer": state.actor_opt,
                    "critic_optimizer": state.critic_opt,
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
    run_test_episodes(
        lambda: test(player, logger, args, cnn_keys, mlp_keys, log_dir),
        args, logger,
    )
    plan.close()
    sanitizer.close()
    telem.close()
    logger.close()


if __name__ == "__main__":
    main()
