"""DreamerV2 (arXiv:2010.02193), coupled — capability parity with
/root/reference/sheeprl/algos/dreamer_v2/dreamer_v2.py.

Same TPU-first structure as the DreamerV3 task (one jitted train step:
RSSM `lax.scan`, imagination scan, three optimizer updates, hard
target-critic copy as a traced tau), with the V2 semantics:
  - Normal(x, 1) observation/reward likelihoods and a plain 1-dim critic
    (no two-hot), alpha-KL balancing with optional free-nats averaging;
  - actor objective mixes REINFORCE with dynamics backpropagation via
    `objective_mix` (reference dreamer_v2.py:324-339);
  - lambda-returns bootstrapped from the TARGET critic
    (reference dreamer_v2.py:279-299);
  - `buffer_type`: `sequential` (AsyncReplayBuffer) or `episode`
    (EpisodeBuffer with `prioritize_ends`), reference dreamer_v2.py:532-553.
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
from ...data import AsyncReplayBuffer, EpisodeBuffer, stage_batch
from ...ops.distributions import (
    Bernoulli,
    Independent,
    Normal,
    OneHotCategorical,
    TanhNormal,
)
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
from ...envs import make_vector_env
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
from ..dreamer_v3.agent import WorldModel
from ..dreamer_v3.dreamer_v3 import _random_actions
from .agent import PlayerDV2, build_models
from .args import DreamerV2Args
from .loss import reconstruction_loss
from .utils import (
    make_device_preprocess,
    make_row_codec,
    maybe_autotune_scan_unroll,
    maybe_decide_remat,
    substitute_step_obs,
    test,
)


class DV2TrainState(nn.Module):
    world_model: WorldModel
    actor: object
    critic: nn.MLP
    target_critic: nn.MLP
    world_opt: object
    actor_opt: object
    critic_opt: object


def make_optimizers(args: DreamerV2Args):
    """Three Adam chains with shared clipping and the reference's L2
    weight decay folded into the gradient (torch Adam weight_decay=1e-6,
    reference dreamer_v2.py:496-498)."""

    def chain(lr):
        steps = []
        if args.clip_gradients is not None and args.clip_gradients > 0:
            steps.append(optax.clip_by_global_norm(args.clip_gradients))
        steps.append(optax.add_decayed_weights(1e-6))
        steps.append(optax.adam(lr, eps=1e-5))
        return optax.chain(*steps)

    return chain(args.world_lr), chain(args.actor_lr), chain(args.critic_lr)


def _policy_entropy(dist):
    if isinstance(dist, TanhNormal):
        return None
    return dist.entropy()


def make_train_step(
    args: DreamerV2Args,
    world_optimizer,
    actor_optimizer,
    critic_optimizer,
    cnn_keys: Sequence[str],
    mlp_keys: Sequence[str],
    actions_dim: Sequence[int],
    is_continuous: bool,
    mesh=None,
):
    """Build the single-jit DreamerV2 update (reference train(),
    dreamer_v2.py:45-374). With a 2-D (data, seq) mesh the step is
    context-parallel like dreamer_v3.make_train_step: time-sharded conv/head
    stages, batch-only resharding around the RSSM scan."""
    stoch_size = args.stochastic_size * args.discrete_size
    horizon = args.horizon
    action_splits = np.cumsum(actions_dim)[:-1]
    # --precision bfloat16: model forwards run in bf16, params stay f32,
    # logits/losses stay f32 (same policy as dreamer_v3.make_train_step)
    compute_dtype = ops.precision.compute_dtype(args.precision)
    use_remat = remat_mode(args.remat)

    constrain = make_constrain(mesh)

    def train_step(state: DV2TrainState, data: dict, key, tau):
        T, B = data["dones"].shape[:2]
        scan_spec = scan_batch_spec(mesh, B)
        k_wm, k_img = jax.random.split(key)

        # hard target-critic copy gated by traced tau in {0, 1}
        # (reference host loop, dreamer_v2.py:726-728)
        target_critic = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1.0 - tau) * t, state.critic, state.target_critic
        )

        obs_targets = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        obs_targets.update({k: data[k] for k in mlp_keys})
        batch_obs = {k: v.astype(compute_dtype) for k, v in obs_targets.items()}
        is_first = data["is_first"].at[0].set(1.0)

        # ---- world model -----------------------------------------------------
        def world_loss_fn(wm: WorldModel):
            # context parallelism: encoder runs (seq, data)-sharded; the
            # scan inputs reshard along the batch axis (data-only per
            # scan_batch_spec), its outputs back to
            # time-sharded for the decoder/heads (same scheme as dreamer_v3)
            embedded = constrain_scan_inputs(constrain, scan_spec, wm.encoder(batch_obs))
            posterior0 = jnp.zeros(
                (B, args.stochastic_size, args.discrete_size), compute_dtype
            )
            recurrent0 = jnp.zeros((B, args.recurrent_state_size), compute_dtype)
            recurrent_states, priors_logits, posteriors, posteriors_logits = (
                wm.rssm.scan_dynamic(
                    posterior0,
                    recurrent0,
                    constrain_scan_inputs(constrain, scan_spec, data["actions"].astype(compute_dtype)),
                    embedded,
                    constrain_scan_inputs(constrain, scan_spec, is_first),
                    k_wm,
                    remat=use_remat,
                )
            )
            recurrent_states, priors_logits, posteriors, posteriors_logits = (
                constrain_time_batch(
                    constrain,
                    recurrent_states, priors_logits, posteriors, posteriors_logits,
                from_spec=scan_spec,
            )
            )
            latent_states = jnp.concatenate(
                [posteriors.reshape(T, B, -1), recurrent_states], axis=-1
            )
            decoded = {
                k: v.astype(jnp.float32)
                for k, v in wm.observation_model(latent_states).items()
            }
            po = {
                k: Independent(
                    base=Normal(loc=decoded[k], scale=jnp.ones_like(decoded[k])),
                    event_ndims=len(decoded[k].shape[2:]),
                )
                for k in decoded
            }
            pr_mean = wm.reward_model(latent_states).astype(jnp.float32)
            pr = Independent(
                base=Normal(loc=pr_mean, scale=jnp.ones_like(pr_mean)), event_ndims=1
            )
            if args.use_continues:
                pc = Independent(
                    base=Bernoulli(
                        logits=wm.continue_model(latent_states).astype(jnp.float32)
                    ),
                    event_ndims=1,
                )
                continue_targets = (1.0 - data["dones"]) * args.gamma
            else:
                pc = continue_targets = None
            shaped = (T, B, args.stochastic_size, args.discrete_size)
            losses = reconstruction_loss(
                po,
                obs_targets,
                pr,
                data["rewards"],
                priors_logits.reshape(shaped),
                posteriors_logits.reshape(shaped),
                args.kl_balancing_alpha,
                args.kl_free_nats,
                args.kl_free_avg,
                args.kl_regularizer,
                pc,
                continue_targets,
                args.continue_scale_factor,
            )
            return losses[0], (losses, recurrent_states, posteriors, priors_logits, posteriors_logits)

        (_, (wm_losses, recurrent_states, posteriors, priors_logits, posteriors_logits)), wm_grads = (
            jax.value_and_grad(world_loss_fn, has_aux=True)(state.world_model)
        )
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = wm_losses
        wm_updates, world_opt = world_optimizer.update(
            wm_grads, state.world_opt, state.world_model
        )
        world_model = optax.apply_updates(state.world_model, wm_updates)

        # ---- behaviour: imagination + actor ---------------------------------
        imagined_prior0 = constrain(
            jnp.swapaxes(jax.lax.stop_gradient(posteriors), 0, 1).reshape(T * B, stoch_size),
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
            latent0 = jnp.concatenate([imagined_prior0, recurrent0], axis=-1)

            def img_step(carry, k):
                prior, recurrent = carry
                latent = jnp.concatenate([prior, recurrent], axis=-1)
                k_act, k_trans = jax.random.split(k)
                acts, _ = actor(jax.lax.stop_gradient(latent), key=k_act)
                action = jnp.concatenate(acts, axis=-1).astype(prior.dtype)
                new_prior, new_recurrent = world_model.rssm.imagination(
                    prior, recurrent, action, k_trans
                )
                new_latent = jnp.concatenate([new_prior, new_recurrent], axis=-1)
                return (new_prior, new_recurrent), (new_latent, action)

            # H imagination steps; trajectory entry i is reached BY action i
            # (imagined_actions[0] is the zero action, reference
            # dreamer_v2.py:243-276)
            img_step = ops.checkpoint_body(img_step, use_remat)
            _, (new_latents, actions_h) = jax.lax.scan(
                img_step, (imagined_prior0, recurrent0), img_keys,
                unroll=ops.scan_unroll(),
            )
            imagined_trajectories = jnp.concatenate(
                [latent0[None], new_latents], axis=0
            )  # [H+1, T*B, L]
            imagined_actions = jnp.concatenate(
                [jnp.zeros_like(actions_h[:1]), actions_h], axis=0
            )  # [H+1, T*B, A]

            predicted_target_values = target_critic(imagined_trajectories).astype(
                jnp.float32
            )
            predicted_rewards = world_model.reward_model(
                imagined_trajectories
            ).astype(jnp.float32)
            if args.use_continues:
                continues = Independent(
                    base=Bernoulli(
                        logits=world_model.continue_model(
                            imagined_trajectories
                        ).astype(jnp.float32)
                    ),
                    event_ndims=1,
                ).mean
                true_continue0 = constrain(
                    jnp.swapaxes(1.0 - data["dones"], 0, 1).reshape(1, T * B, 1),
            None, ("data", "seq"),
                ) * args.gamma
                continues = jnp.concatenate([true_continue0, continues[1:]], axis=0)
            else:
                continues = (
                    jnp.ones_like(jax.lax.stop_gradient(predicted_rewards)) * args.gamma
                )

            lambda_values = ops.lambda_values_dv2(
                predicted_rewards[:-1],
                predicted_target_values[:-1],
                continues[:-1],
                bootstrap=predicted_target_values[-1:],
                lmbda=args.lmbda,
            )  # [H, T*B, 1]
            discount = jax.lax.stop_gradient(
                jnp.cumprod(
                    jnp.concatenate(
                        [jnp.ones_like(continues[:1]), continues[:-1]], axis=0
                    ),
                    axis=0,
                )
            )

            policies = actor.dists(
                jax.lax.stop_gradient(imagined_trajectories[:-2])
            )
            dynamics = lambda_values[1:]
            advantage = jax.lax.stop_gradient(
                lambda_values[1:] - predicted_target_values[:-2]
            )
            per_head_actions = jnp.split(
                jax.lax.stop_gradient(imagined_actions[1:-1]), action_splits, axis=-1
            )
            reinforce = (
                sum(
                    p.log_prob(a)[..., None]
                    for p, a in zip(policies, per_head_actions)
                )
                * advantage
            )
            objective = (
                args.objective_mix * reinforce + (1 - args.objective_mix) * dynamics
            )
            entropies = [_policy_entropy(p) for p in policies]
            if any(e is None for e in entropies):
                entropy = jnp.zeros_like(objective)
            else:
                entropy = args.actor_ent_coef * sum(entropies)[..., None]
            policy_loss = -jnp.mean(discount[:-2] * (objective + entropy))
            return policy_loss, (imagined_trajectories, lambda_values, discount)

        (policy_loss, (imagined_trajectories, lambda_values, discount)), actor_grads = (
            jax.value_and_grad(actor_loss_fn, has_aux=True)(state.actor)
        )
        actor_updates, actor_opt = actor_optimizer.update(
            actor_grads, state.actor_opt, state.actor
        )
        actor = optax.apply_updates(state.actor, actor_updates)

        # ---- critic ----------------------------------------------------------
        traj_sg = jax.lax.stop_gradient(imagined_trajectories[:-1])
        lambda_sg = jax.lax.stop_gradient(lambda_values)

        def critic_loss_fn(critic):
            qv_mean = critic(traj_sg).astype(jnp.float32)
            qv = Independent(
                base=Normal(loc=qv_mean, scale=jnp.ones_like(qv_mean)), event_ndims=1
            )
            return -jnp.mean(discount[:-1, :, 0] * qv.log_prob(lambda_sg))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state.critic)
        critic_updates, critic_opt = critic_optimizer.update(
            critic_grads, state.critic_opt, state.critic
        )
        critic = optax.apply_updates(state.critic, critic_updates)

        shaped = (T, B, args.stochastic_size, args.discrete_size)
        post_entropy = (
            OneHotCategorical.from_logits(posteriors_logits.reshape(shaped))
            .entropy()
            .sum(-1)
            .mean()
        )
        prior_entropy = (
            OneHotCategorical.from_logits(priors_logits.reshape(shaped))
            .entropy()
            .sum(-1)
            .mean()
        )
        new_state = DV2TrainState(
            world_model=world_model,
            actor=actor,
            critic=critic,
            target_critic=target_critic,
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
            "State/kl": kl.mean(),
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
    parser = DataclassArgumentParser(DreamerV2Args)
    (args,) = parser.parse_args_into_dataclasses(argv)
    validate_eval_args(args)
    resilience.prepare_run(args, "dreamer_v2")
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

    logger, log_dir, run_name = create_logger(args, "dreamer_v2", process_index=rank)
    logger.log_hyperparams(args.as_dict())
    profiler = StepProfiler.from_args(args, log_dir, rank)
    telem = Telemetry.from_args(args, log_dir, rank, algo="dreamer_v2")
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
    world_model, actor, critic, target_critic = build_models(
        model_key,
        actions_dim,
        is_continuous,
        args,
        envs.single_observation_space.spaces,
        cnn_keys,
        mlp_keys,
    )
    maybe_autotune_scan_unroll(
        "dreamer_v2", world_model, args, int(sum(actions_dim)), telem
    )
    maybe_decide_remat(
        "dreamer_v2", world_model, args, int(sum(actions_dim)), telem
    )
    world_optimizer, actor_optimizer, critic_optimizer = make_optimizers(args)
    state = DV2TrainState(
        world_model=world_model,
        actor=actor,
        critic=critic,
        target_critic=target_critic,
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
            "target_critic": state.target_critic,
            "world_optimizer": state.world_opt,
            "actor_optimizer": state.actor_opt,
            "critic_optimizer": state.critic_opt,
            "expl_decay_steps": 0,
            "global_step": 0,
            "batch_size": 0,
        }
        ckpt = load_checkpoint(args.checkpoint_path, template)
        state = DV2TrainState(
            world_model=ckpt["world_model"],
            actor=ckpt["actor"],
            critic=ckpt["critic"],
            target_critic=ckpt["target_critic"],
            world_opt=ckpt["world_optimizer"],
            actor_opt=ckpt["actor_optimizer"],
            critic_opt=ckpt["critic_optimizer"],
        )
        expl_decay_steps = int(ckpt["expl_decay_steps"])
        start_step = int(ckpt["global_step"]) + 1
    state = replicate(state, mesh)

    def make_player(st: DV2TrainState) -> PlayerDV2:
        return PlayerDV2(
            encoder=st.world_model.encoder,
            rssm=st.world_model.rssm,
            actor=st.actor,
            actions_dim=tuple(actions_dim),
            stochastic_size=args.stochastic_size,
            discrete_size=args.discrete_size,
            recurrent_state_size=args.recurrent_state_size,
            is_continuous=is_continuous,
            compute_dtype=args.precision,
        )

    player = make_player(state)

    # raw obs puts (uint8 pixels), normalized inside the jit in the V2
    # [-0.5, 0.5] convention; with the sequential buffer the same device
    # arrays feed rb.add (V2 row layout: the stored obs is real_next_obs,
    # which equals the NEXT policy obs whenever no env finished)
    _dev_preprocess = make_device_preprocess(cnn_keys)

    def _player_step(p, s, o, k, expl, mask):
        new_s, acts = p.step(
            s, _dev_preprocess(o), k, expl, is_training=True, mask=mask
        )
        # per-head env indices computed on device: the per-step d2h pull is
        # a few ints; the one-hot stays device-resident for rb.add
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
        mesh=mesh,
    )

    if args.dry_run:
        # the dry run adds ~2 rows before its single update fires
        # (step_before_training=0): clamp the sampled window so the smoke
        # runs on DEFAULT flags instead of raising "too long
        # sequence_length" from a 2-row ring
        args.per_rank_sequence_length = min(args.per_rank_sequence_length, 2)
    buffer_size = args.buffer_size // (args.num_envs * world) if not args.dry_run else 4
    buffer_type = args.buffer_type.lower()
    if buffer_type == "sequential":
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
    elif buffer_type == "episode":
        rb = EpisodeBuffer(
            max(buffer_size, args.per_rank_sequence_length),
            sequence_length=args.per_rank_sequence_length,
            memmap_dir=(
                os.path.join(log_dir, "memmap_buffer") if args.memmap_buffer else None
            ),
            seed=args.seed,
        )
    else:
        raise ValueError(
            f"unrecognized buffer type {buffer_type!r}: must be `sequential` or `episode`"
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
                act_sum, extra=("rewards", "dones", "is_first"),
                mesh=mesh if n_dev > 1 else None,
            ),
            key, jnp.float32(1.0),
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

    # per-env episode accumulators for the episode buffer
    episode_steps: list[list[dict]] = [[] for _ in range(args.num_envs)]
    obs, _ = envs.reset(seed=args.seed)
    step_data = {k: np.asarray(obs[k]) for k in obs_keys}
    step_data["dones"] = np.zeros((args.num_envs, 1), np.float32)
    step_data["actions"] = np.zeros((args.num_envs, int(sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((args.num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((args.num_envs, 1), np.float32)
    if buffer_type == "sequential":
        rb.add({k: v[None] for k, v in step_data.items()})
    else:
        for i in range(args.num_envs):
            episode_steps[i].append({k: v[i] for k, v in step_data.items()})
    player_state = player.init_states(args.num_envs)
    device_next_obs = None  # this step's obs put, shared policy<->rb.add
    use_blob = (
        buffer_type == "sequential"
        and not rb.prefers_host_adds
        and os.environ.get("SHEEPRL_TPU_STEP_BLOB", "1") != "0"
    )
    if use_blob:
        blob_add = make_row_codec(obs, obs_keys, args.num_envs, ("rewards", "dones", "is_first"))
        use_blob = blob_add is not None  # live-backend roundtrip check

    gradient_steps = 0
    start_time = time.perf_counter()
    if args.eval_only:
        num_updates = start_step - 1  # empty training loop: fall through to test
    for global_step in range(start_step, num_updates + 1):
        guard.tick(global_step)  # fires injected sig* faults for this step
        telem.mark("rollout")
        # ---- action selection ----------------------------------------------
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
                host=buffer_type == "episode" or rb.prefers_host_adds,
            )

        # row layout: (o_t, a_t, r_t, d_t) where a_t leads TO o_t
        # (reference dreamer_v2.py:636-681; V3 shifts actions instead)
        step_data["is_first"] = step_data["dones"].copy()
        next_obs, rewards, terms, truncs, infos = envs.step(env_actions)
        dones = np.logical_or(terms, truncs).astype(np.float32)
        if args.dry_run and buffer_type == "episode":
            dones = np.ones_like(dones)

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
        if buffer_type == "sequential":
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
        else:
            # the episode accumulator keeps host rows; re-put next step
            device_next_obs = None
            for i in range(args.num_envs):
                episode_steps[i].append({k: v[i] for k, v in step_data.items()})

        dones_idxes = np.nonzero(dones)[0].tolist()
        if dones_idxes:
            n_reset = len(dones_idxes)
            reset_data = {k: np.asarray(obs[k])[dones_idxes] for k in obs_keys}
            reset_data["dones"] = np.zeros((n_reset, 1), np.float32)
            reset_data["actions"] = np.zeros(
                (n_reset, int(sum(actions_dim))), np.float32
            )
            reset_data["rewards"] = np.zeros((n_reset, 1), np.float32)
            reset_data["is_first"] = np.ones((n_reset, 1), np.float32)
            if buffer_type == "episode":
                for col, d in enumerate(dones_idxes):
                    if len(episode_steps[d]) >= args.per_rank_sequence_length:
                        ep = {
                            k: np.stack([s[k] for s in episode_steps[d]])
                            for k in episode_steps[d][0]
                        }
                        rb.add(ep)
                    episode_steps[d] = [
                        {k: v[col] for k, v in reset_data.items()}
                    ]
            else:
                rb.add({k: v[None] for k, v in reset_data.items()}, dones_idxes)
            # finished envs observe their RESET obs next, not the stored
            # final obs: drop the shared put and re-put next iteration
            device_next_obs = None
            step_data["dones"][dones_idxes] = 0.0
            reset_mask = np.zeros((args.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0
            player_state = player.reset_states(player_state, jnp.asarray(reset_mask))

        step_before_training -= 1

        # ---- training --------------------------------------------------------
        can_sample = (
            rb.buffer is not None and len(rb.buffer) > 0
            if buffer_type == "episode"
            else True
        )
        if global_step >= learning_starts and step_before_training <= 0 and can_sample:
            telem.mark("buffer/sample")
            n_samples = (
                args.pretrain_steps
                if global_step == learning_starts
                else args.gradient_steps
            )
            if buffer_type == "sequential":
                local_data = pipe.sampler(rb).sample(
                    args.per_rank_batch_size,
                    sequence_length=args.per_rank_sequence_length,
                    n_samples=n_samples,
                )
            else:
                local_data = pipe.sampler(rb).sample(
                    args.per_rank_batch_size,
                    n_samples=n_samples,
                    prioritize_ends=args.prioritize_ends,
                )
            staged = stage_batch(local_data, to_host=jax.process_count() > 1)
            telem.mark("train/dispatch")
            for i in range(n_samples):
                tau = 1.0 if gradient_steps % args.critic_target_network_update_freq == 0 else 0.0
                sample = staged[i]
                if n_dev > 1:
                    sample = shard_time_batch(sample, mesh, time_axis=0, batch_axis=1)
                key, train_key = jax.random.split(key)
                sample = resilience.poison_batch(sample, global_step)  # nan.* sites
                state, metrics = train_step(state, sample, train_key, jnp.float32(tau))
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
