"""DreamerV3 (arXiv:2301.04104), coupled — capability parity with
/root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py.

TPU-first structure:
  - ONE jitted train step contains the whole update: the RSSM
    dynamic-learning recurrence as `lax.scan` over T (the reference's Python
    loop, dreamer_v3.py:117-124), the reconstruction loss, the imagination
    rollout as `lax.scan` over the horizon (reference loop :217-223), the
    Moments percentile-EMA update, three optimizer applications and the EMA
    target-critic update — zero host round-trips inside an update;
  - the EMA/no-EMA target update is a traced `tau` scalar (1 on the first
    step, `critic_tau` when due, 0 to skip), so the schedule never
    recompiles (reference host loop, dreamer_v3.py:642-645);
  - the interaction hot loop is a jitted `PlayerDV3.step` feeding host
    vector envs; transitions land in an `AsyncReplayBuffer` whose per-env
    rings are HBM-resident by default (host/memmap for >HBM pixel runs);
  - data parallelism: params replicated over the mesh, the batch axis
    sharded — XLA inserts the gradient all-reduce and the Moments
    cross-device percentile reduction (the reference's `fabric.all_gather`
    inside the loss, dreamer_v3/utils.py:35-42).
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ... import nn, ops
from ...data import AsyncReplayBuffer, StepBlobCodec, stage_batch
from ...data.blob import verify_blob_roundtrip
from ...envs import make_vector_env
from ...envs.jax import (
    DreamerCollectorCarry,
    VecJaxEnv,
    make_dreamer_collector,
    make_jax_env,
)
from ...envs.wrappers import RestartOnException
from ...ops.distributions import (
    Bernoulli,
    Independent,
    OneHotCategorical,
    TanhNormal,
    TwoHotEncodingDistribution,
    MSEDistribution,
    SymlogDistribution,
)
from ...parallel import (
    AnakinStats,
    Pipeline,
    assert_divisible,
    shard_env_batch,
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
from ...compile import CompilePlan, dict_obs_spec, dreamer_sample_spec, remat_mode, sds
from ...utils.jit import donating_jit
from ...utils.checkpoint import load_checkpoint, load_checkpoint_args, save_checkpoint
from ...utils.evaluation import (
    apply_eval_overrides,
    run_test_episodes,
    validate_eval_args,
)
from ...utils.env import make_dict_env
from ...utils.logger import create_logger
from ...utils.metric import MetricAggregator, packed_metrics
from ...utils.profiler import StepProfiler
from ...utils.parser import DataclassArgumentParser
from ...utils.registry import register_algorithm
from ..ppo.agent import (
    buffer_actions,
    env_action_indices,
    indices_to_env_actions,
)
from ..ppo.ppo import actions_dim_of, validate_obs_keys
from .agent import PlayerDV3, WorldModel, build_models
from .args import DreamerV3Args
from .loss import reconstruction_loss
from ..dreamer_v2.utils import maybe_autotune_scan_unroll, maybe_decide_remat
from .utils import make_device_preprocess, test


# the named regions of the train step, in the order they run
TRAIN_STEP_SCOPES = (
    "wm/encoder", "wm/rssm_scan", "wm/decoder", "wm/heads", "wm/loss", "wm/opt",
    "imagine", "moments", "actor/loss", "actor/opt", "critic/loss", "critic/opt",
)


class DV3TrainState(nn.Module):
    world_model: WorldModel
    actor: object
    critic: nn.MLP
    target_critic: nn.MLP
    world_opt: object
    actor_opt: object
    critic_opt: object
    moments: ops.Moments


def make_optimizers(args: DreamerV3Args):
    """Three Adam chains with per-module gradient-norm clipping (reference
    optimizer setup, dreamer_v3.py:435-444 + clip calls in train)."""

    def chain(clip, lr, eps):
        steps = []
        if clip is not None and clip > 0:
            steps.append(optax.clip_by_global_norm(clip))
        steps.append(optax.adam(lr, eps=eps))
        return optax.chain(*steps)

    return (
        chain(args.world_clip_gradients, args.world_lr, 1e-8),
        chain(args.actor_clip_gradients, args.actor_lr, 1e-5),
        chain(args.critic_clip_gradients, args.critic_lr, 1e-5),
    )


def _policy_entropy(dist) -> jax.Array | None:
    """Per-head entropy; None for distributions without one (the reference
    catches NotImplementedError from tanh-normal, dreamer_v3.py:275-278)."""
    if isinstance(dist, TanhNormal):
        return None
    return dist.entropy()


def make_train_step(
    args: DreamerV3Args,
    world_optimizer,
    actor_optimizer,
    critic_optimizer,
    cnn_keys: Sequence[str],
    mlp_keys: Sequence[str],
    actions_dim: Sequence[int],
    is_continuous: bool,
    mesh=None,
):
    """Build the single-jit DreamerV3 update (reference train(),
    dreamer_v3.py:48-313).

    With a 2-D `(data, seq)` mesh (`--seq_devices`), the step is
    context-parallel: the `[T, B]` batch arrives time-sharded over "seq" and
    batch-sharded over "data"; the per-timestep stages (conv encoder/decoder,
    reward/continue heads, imagination over the T*B flattened axis) compute
    in that layout, while sharding constraints reshard the RSSM scan's
    inputs/outputs to batch-only — GSPMD inserts the all-gather/slice
    collectives over ICI at the two phase boundaries.

    Its regions carry `jax.named_scope`s (TRAIN_STEP_SCOPES): a scope reaches
    the `op_name` of every operation traced under it, and of its backward
    (`jvp(wm/encoder)`, `transpose(jvp(wm/encoder))`), so a device trace can
    be split by region. Metadata only: the compiled program is the same."""
    stoch_size = args.stochastic_size * args.discrete_size
    horizon = args.horizon
    action_splits = np.cumsum(actions_dim)[:-1]
    # --precision bfloat16: model forwards (conv trunks, RSSM scan,
    # imagination) run in bf16 — params stay f32 (every layer casts its
    # weights to the input dtype), normalizations/logits/losses stay f32
    compute_dtype = ops.precision.compute_dtype(args.precision)
    use_remat = remat_mode(args.remat)

    constrain = make_constrain(mesh)

    def train_step(state: DV3TrainState, data: dict, key, tau):
        T, B = data["dones"].shape[:2]
        scan_spec = scan_batch_spec(mesh, B)
        k_wm, k_img = jax.random.split(key)

        # EMA target-critic update happens before the gradient step with the
        # pre-update critic, matching the reference host-loop ordering
        # (dreamer_v3.py:642-645); tau==0 is a no-op.
        target_critic = jax.tree_util.tree_map(
            lambda c, t: tau * c + (1.0 - tau) * t, state.critic, state.target_critic
        )

        obs_targets = {k: data[k] / 255.0 for k in cnn_keys}
        obs_targets.update({k: data[k] for k in mlp_keys})
        batch_obs = {k: v.astype(compute_dtype) for k, v in obs_targets.items()}
        is_first = data["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        ).astype(compute_dtype)
        continue_targets = 1.0 - data["dones"]

        # ---- world model -----------------------------------------------------
        def world_loss_fn(wm: WorldModel):
            # encoder computes on the (seq, data)-sharded input layout; the
            # scan needs full T per shard, so its inputs reshard to
            # batch-over-"data" with the seq groups replicating the scan
            # (scan_batch_spec explains why this beats the fully-sharded
            # alternative under GSPMD)
            with jax.named_scope("wm/encoder"):
                embedded = constrain_scan_inputs(
                    constrain, scan_spec, wm.encoder(batch_obs)
                )
            posterior0 = jnp.zeros(
                (B, args.stochastic_size, args.discrete_size), compute_dtype
            )
            recurrent0 = jnp.zeros((B, args.recurrent_state_size), compute_dtype)
            with jax.named_scope("wm/rssm_scan"):
                recurrent_states, priors_logits, posteriors, posteriors_logits = (
                    wm.rssm.scan_dynamic(
                        posterior0,
                        recurrent0,
                        constrain_scan_inputs(constrain, scan_spec, batch_actions),
                        embedded,
                        constrain_scan_inputs(constrain, scan_spec, is_first),
                        k_wm,
                        remat=use_remat,
                    )
                )
            # back to time-sharded for the decoder/reward/continue heads
            # (a local T-slice out of the replicated-scan layout)
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
            with jax.named_scope("wm/decoder"):
                reconstructed = {
                    k: v.astype(jnp.float32)
                    for k, v in wm.observation_model(latent_states).items()
                }
            po = {
                k: MSEDistribution(_mode=reconstructed[k], dims=3) for k in cnn_keys
            }
            po.update(
                {k: SymlogDistribution(_mode=reconstructed[k], dims=1) for k in mlp_keys}
            )
            with jax.named_scope("wm/heads"):
                pr = TwoHotEncodingDistribution(
                    logits=wm.reward_model(latent_states).astype(jnp.float32), dims=1
                )
                pc = Independent(
                    base=Bernoulli(
                        logits=wm.continue_model(latent_states).astype(jnp.float32)
                    ),
                    event_ndims=1,
                )
            shaped = (T, B, args.stochastic_size, args.discrete_size)
            with jax.named_scope("wm/loss"):
                losses = reconstruction_loss(
                    po,
                    obs_targets,
                    pr,
                    data["rewards"],
                    priors_logits.reshape(shaped),
                    posteriors_logits.reshape(shaped),
                    args.kl_dynamic,
                    args.kl_representation,
                    args.kl_free_nats,
                    args.kl_regularizer,
                    pc,
                    continue_targets,
                    args.continue_scale_factor,
                )
            rec_loss = losses[0]
            return rec_loss, (losses, recurrent_states, posteriors, priors_logits, posteriors_logits)

        (_, (wm_losses, recurrent_states, posteriors, priors_logits, posteriors_logits)), wm_grads = (
            jax.value_and_grad(world_loss_fn, has_aux=True)(state.world_model)
        )
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = wm_losses
        with jax.named_scope("wm/opt"):
            wm_updates, world_opt = world_optimizer.update(
                wm_grads, state.world_opt, state.world_model
            )
            world_model = optax.apply_updates(state.world_model, wm_updates)

        # ---- behaviour: imagination + actor ---------------------------------
        # imagination flattens [T, B] -> rows; a (seq, data)-sharded [T, B]
        # flattens to rows sharded over the full device grid, so the
        # imagination scan, actor and critic parallelize over all devices
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
        true_continue0 = constrain(
            jnp.swapaxes(1.0 - data["dones"], 0, 1).reshape(1, T * B, 1),
            None, ("data", "seq"),
        )
        img_keys = jax.random.split(k_img, horizon + 1)

        def actor_loss_fn(actor):
            def img_step(carry, k):
                prior, recurrent = carry
                latent = jnp.concatenate([prior, recurrent], axis=-1)
                k_act, k_trans = jax.random.split(k)
                acts, _ = actor(jax.lax.stop_gradient(latent), key=k_act)
                action = jnp.concatenate(acts, axis=-1).astype(prior.dtype)
                new_prior, new_recurrent = world_model.rssm.imagination(
                    prior, recurrent, action, k_trans
                )
                return (new_prior, new_recurrent), (latent, action)

            # --remat also covers the imagination backward: recompute the
            # actor/transition activations of each horizon step instead of
            # storing them across all H steps (same mode as the RSSM scan)
            img_step = ops.checkpoint_body(img_step, use_remat)
            # H imagination steps emitting the pre-step latent, plus the final
            # latent/action pair outside the scan: H+1 trajectory entries from
            # exactly H RSSM transitions (reference loop, dreamer_v3.py:217-223)
            with jax.named_scope("imagine"):
                (prior_h, recurrent_h), (latents, actions_h) = jax.lax.scan(
                    img_step,
                    (imagined_prior0, recurrent0),
                    img_keys[:horizon],
                    unroll=ops.scan_unroll(),
                )
                latent_h = jnp.concatenate([prior_h, recurrent_h], axis=-1)
                last_acts, _ = actor(jax.lax.stop_gradient(latent_h), key=img_keys[horizon])
            with jax.named_scope("actor/loss"):
                imagined_trajectories = jnp.concatenate(
                    [latents, latent_h[None]], axis=0
                )  # [H+1, T*B, L]
                imagined_actions = jnp.concatenate(
                    [actions_h, jnp.concatenate(last_acts, axis=-1)[None]], axis=0
                )  # [H+1, T*B, A]

                predicted_values = TwoHotEncodingDistribution(
                    logits=state.critic(imagined_trajectories).astype(jnp.float32),
                    dims=1,
                ).mean
                predicted_rewards = TwoHotEncodingDistribution(
                    logits=world_model.reward_model(imagined_trajectories).astype(
                        jnp.float32
                    ),
                    dims=1,
                ).mean
                continues = Independent(
                    base=Bernoulli(
                        logits=world_model.continue_model(imagined_trajectories).astype(
                            jnp.float32
                        )
                    ),
                    event_ndims=1,
                ).mode
                continues = jnp.concatenate([true_continue0, continues[1:]], axis=0)

                lambda_values = ops.lambda_values_dv3(
                    predicted_rewards[1:],
                    predicted_values[1:],
                    continues[1:] * args.gamma,
                    lmbda=args.lmbda,
                )
                discount = jax.lax.stop_gradient(
                    jnp.cumprod(continues * args.gamma, axis=0) / args.gamma
                )

            with jax.named_scope("moments"):
                new_moments, (offset, invscale) = state.moments.update(lambda_values)
            with jax.named_scope("actor/loss"):
                normed_lambda_values = (lambda_values - offset) / invscale
                normed_baseline = (predicted_values[:-1] - offset) / invscale
                advantage = normed_lambda_values - normed_baseline

                policies = actor.dists(jax.lax.stop_gradient(imagined_trajectories))
                if is_continuous:
                    objective = advantage
                else:
                    per_head_actions = jnp.split(
                        jax.lax.stop_gradient(imagined_actions), action_splits, axis=-1
                    )
                    log_probs = sum(
                        p.log_prob(a)[..., None]
                        for p, a in zip(policies, per_head_actions)
                    )
                    objective = log_probs[:-1] * jax.lax.stop_gradient(advantage)
                entropies = [_policy_entropy(p) for p in policies]
                if any(e is None for e in entropies):
                    entropy = jnp.zeros_like(objective)
                else:
                    entropy = args.actor_ent_coef * sum(entropies)[..., None][:-1]
                policy_loss = -jnp.mean(discount[:-1] * (objective + entropy))
            return policy_loss, (
                imagined_trajectories,
                lambda_values,
                discount,
                new_moments,
            )

        (policy_loss, (imagined_trajectories, lambda_values, discount, new_moments)), actor_grads = (
            jax.value_and_grad(actor_loss_fn, has_aux=True)(state.actor)
        )
        with jax.named_scope("actor/opt"):
            actor_updates, actor_opt = actor_optimizer.update(
                actor_grads, state.actor_opt, state.actor
            )
            actor = optax.apply_updates(state.actor, actor_updates)

        # ---- critic ----------------------------------------------------------
        traj_sg = jax.lax.stop_gradient(imagined_trajectories[:-1])

        with jax.named_scope("critic/loss"):
            target_values = TwoHotEncodingDistribution(
                logits=target_critic(traj_sg).astype(jnp.float32), dims=1
            ).mean

        @jax.named_scope("critic/loss")
        def critic_loss_fn(critic):
            qv = TwoHotEncodingDistribution(
                logits=critic(traj_sg).astype(jnp.float32), dims=1
            )
            value_loss = -qv.log_prob(jax.lax.stop_gradient(lambda_values))
            value_loss = value_loss - qv.log_prob(jax.lax.stop_gradient(target_values))
            return jnp.mean(value_loss * discount[:-1, :, 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state.critic)
        with jax.named_scope("critic/opt"):
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
        new_state = DV3TrainState(
            world_model=world_model,
            actor=actor,
            critic=critic,
            target_critic=target_critic,
            world_opt=world_opt,
            actor_opt=actor_opt,
            critic_opt=critic_opt,
            moments=new_moments,
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
    # the metric scalars leave the program as ONE f32 vector: the host pulls
    # one array a train step, not one per metric (utils/metric.py); the skip
    # flag stays its own output for `update_skipped`'s lagged read
    train_step = packed_metrics(train_step, loose=(resilience.SKIP_FLAG,))
    return donating_jit(train_step, donate_argnums=(0,))


def _random_actions(action_space, actions_dim, is_continuous: bool):
    sample = action_space.sample()
    if is_continuous:
        return np.asarray(sample, np.float32).reshape(-1), sample
    idxs = np.asarray(sample).reshape(-1)
    one_hot = np.concatenate(
        [np.eye(dim, dtype=np.float32)[i] for i, dim in zip(idxs, actions_dim)]
    )
    return one_hot, sample


def make_blob_step(codec, obs_keys, dev_preprocess, actions_dim, is_continuous):
    """Blob transport (data/blob.py): the whole interaction step — policy
    obs, the replay row's floats, the ring write indices — rides ONE
    host->device transfer; this jit unpacks it, runs the policy, and
    returns the device-resident replay row for `rb.add_direct` (zero
    further transfers). Disable with `SHEEPRL_TPU_STEP_BLOB=0` (the
    separate-puts path remains the host/memmap route)."""

    def _blob_step(p, s, blob, k, expl):
        u8, f32, idx = codec.unpack(blob)
        o = {**u8, **{kk: f32[kk] for kk in obs_keys if kk in f32}}
        mask = {kk: v for kk, v in o.items() if kk.startswith("mask")} or None
        with jax.named_scope("player/step"):
            new_s, acts = p.step(
                s, dev_preprocess(o), k, expl, is_training=True, mask=mask
            )
        row = {kk: v[None] for kk, v in o.items()}
        row["actions"] = acts[None].astype(jnp.float32)
        for kk in ("rewards", "dones", "is_first"):
            row[kk] = f32[kk][None]
        return (
            new_s,
            env_action_indices(acts, actions_dim, is_continuous),
            row,
            idx,
        )

    return jax.jit(_blob_step)


@register_algorithm()
@resilience.crashsafe
def main(argv: Sequence[str] | None = None) -> None:
    parser = DataclassArgumentParser(DreamerV3Args)
    (args,) = parser.parse_args_into_dataclasses(argv)
    validate_eval_args(args)
    resilience.prepare_run(args, "dreamer_v3")
    if args.checkpoint_path:
        saved = load_checkpoint_args(args.checkpoint_path)
        if saved:
            saved.update(checkpoint_path=args.checkpoint_path)
            apply_eval_overrides(saved, args)
            (args,) = parser.parse_dict(saved)
    # fixed by the 4-stage 64x64 conv trunk (reference dreamer_v3.py:321-323)
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
    # the global batch (per-process batch x world) shards over the data axis;
    # the sequence length shards over the seq axis when context parallelism
    # is on
    assert_divisible(
        args.per_rank_batch_size * world,
        mesh.shape["data"],
        "per_rank_batch_size*world",
    )
    assert_divisible(
        args.per_rank_sequence_length, args.seq_devices, "per_rank_sequence_length"
    )

    logger, log_dir, run_name = create_logger(args, "dreamer_v3", process_index=rank)
    logger.log_hyperparams(args.as_dict())
    profiler = StepProfiler.from_args(args, log_dir, rank)
    telem = Telemetry.from_args(args, log_dir, rank, algo="dreamer_v3")
    if rank == 0:
        from ...telemetry.trace import install_profile_signal

        # sheepscope: SIGUSR2 opens a bounded on-demand profile window
        install_profile_signal(log_dir)
    guard = resilience.RunGuard.install(telem)
    sanitizer = Sanitizer.from_args(args, telem)
    telem.add_gauges(sanitizer.gauges)
    pipe = Pipeline.from_args(args, telem)
    plan = CompilePlan.from_args(args, telem)
    telem.add_gauges(plan.gauges)

    use_jax_env = args.env_backend == "jax"
    use_flock = args.flock != "off" and not args.eval_only
    if use_flock and use_jax_env:
        raise ValueError(
            "--flock runs host envs in actor processes; drop --env_backend jax"
        )
    if use_flock:
        # flock (ISSUE 14): the envs live in the actor processes — the
        # learner builds ONE probe env to read the spaces, then closes it
        probe = make_dict_env(
            args.env_id, args.seed, rank=rank, args=args,
            run_name=log_dir, vector_env_idx=0,
        )()
        observation_space = probe.observation_space
        action_space = probe.action_space
        probe.close()
        envs = None
    elif use_jax_env:
        # Anakin arrangement (ISSUE 6): env + player co-reside on chip; the
        # collection window is chunked jitted scans writing straight into
        # the device replay ring via reserve()/add_direct()
        if args.memmap_buffer:
            raise ValueError(
                "--env_backend jax writes rollouts into the device replay "
                "ring; drop --memmap_buffer"
            )
        assert_divisible(args.num_envs, mesh.shape["data"], "num_envs")
        jax_env = make_jax_env(args.env_id)
        venv = VecJaxEnv(env=jax_env, num_envs=args.num_envs)
        envs = None
        observation_space = venv.single_observation_space
        action_space = venv.single_action_space
    else:
        envs = make_vector_env(
            [
                partial(
                    RestartOnException,
                    partial(
                        make_dict_env(
                            args.env_id, args.seed + rank * args.num_envs + i, rank=rank, args=args,
                            run_name=log_dir, vector_env_idx=i,
                        )
                    ),
                )
                for i in range(args.num_envs)
            ],
            sync=args.sync_env or args.num_envs == 1,
        )
        observation_space = envs.single_observation_space
        action_space = envs.single_action_space
    cnn_keys, mlp_keys = validate_obs_keys(observation_space, args)
    obs_keys = [*cnn_keys, *mlp_keys]
    actions_dim, is_continuous = actions_dim_of(action_space)

    key, model_key = jax.random.split(key)
    world_model, actor, critic, target_critic = build_models(
        model_key,
        actions_dim,
        is_continuous,
        args,
        observation_space.spaces,
        cnn_keys,
        mlp_keys,
    )
    # SHEEPRL_TPU_SCAN_UNROLL=auto: measure the unroll ladder on this run's
    # RSSM scan shapes and install the winner before any train jit traces
    maybe_autotune_scan_unroll(
        "dreamer_v3", world_model, args, int(sum(actions_dim)), telem
    )
    maybe_decide_remat(
        "dreamer_v3", world_model, args, int(sum(actions_dim)), telem
    )
    world_optimizer, actor_optimizer, critic_optimizer = make_optimizers(args)
    moments = ops.Moments.init(
        args.moments_decay,
        args.moment_max,
        args.moments_percentile_low,
        args.moments_percentile_high,
    )
    state = DV3TrainState(
        world_model=world_model,
        actor=actor,
        critic=critic,
        target_critic=target_critic,
        world_opt=world_optimizer.init(world_model),
        actor_opt=actor_optimizer.init(actor),
        critic_opt=critic_optimizer.init(critic),
        moments=moments,
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
    state = replicate(state, mesh)

    def make_player(st: DV3TrainState) -> PlayerDV3:
        """Player sharing the training graph's current parameters
        (reference agent.py:469-498)."""
        return PlayerDV3(
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

    # pixels normalize INSIDE the jit: the host puts raw obs (uint8 -> 4x
    # less transfer volume than pre-normalized f32) and the same device
    # array is reused by rb.add below — one obs transfer per env step total
    _dev_preprocess = make_device_preprocess(cnn_keys)

    def _player_step(p, s, o, k, expl, mask):
        with jax.named_scope("player/step"):
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
        # the V3 row layout has no pre-loop add, so the first (and in a dry
        # run only) training fires with exactly step_before_training rows
        # per env ring: clamp the sampled window so the smoke runs on
        # DEFAULT flags instead of raising "too long sequence_length"
        args.per_rank_sequence_length = min(
            args.per_rank_sequence_length,
            max(args.train_every // args.num_envs, 1),
        )
        # the divisibility check at mesh build time saw the PRE-clamp value;
        # a clamped window that no longer divides the seq axis would shard-
        # fail at trace time (sheepshard found this via the train_step
        # example spec) — fail loudly at config time instead
        assert_divisible(
            args.per_rank_sequence_length,
            args.seq_devices,
            "per_rank_sequence_length (dry-run clamped to train_every/num_envs)",
        )
    buffer_size = (
        args.buffer_size // (args.num_envs * world) if not args.dry_run else 2
    )
    rb = None
    service = fleet = flock_assembler = None
    if use_flock:
        from ... import flock as _flock
        from ...data.wire import tree_nbytes

        # sigkill/net.* clauses retarget onto actor 0: killing the learner
        # tests nothing about elastic membership, and under flock the
        # interesting frame sends are the actor's (peer.crash stays here)
        _, actor_faults = _flock.retarget_sigkill(args)
        _row = {
            k: np.zeros(
                (args.num_envs, *observation_space[k].shape),
                np.uint8 if k in cnn_keys else np.float32,
            )
            for k in obs_keys
        }
        _row.update(
            actions=np.zeros((args.num_envs, int(sum(actions_dim))), np.float32),
            rewards=np.zeros((args.num_envs, 1), np.float32),
            dones=np.zeros((args.num_envs, 1), np.float32),
            is_first=np.zeros((args.num_envs, 1), np.float32),
        )
        capacity = _flock.shard_capacity(
            "dreamer_v3", int(args.flock), tree_nbytes(_row),
            floor_rows=max(64, 4 * args.per_rank_sequence_length),
        )

        def _make_shard(cap):
            # one ordinary AsyncReplayBuffer per actor, host storage (the
            # wire lands host arrays; sampling stages to device afterwards)
            return AsyncReplayBuffer(
                cap, args.num_envs, storage="host", sequential=True,
                obs_keys=tuple(obs_keys), seed=args.seed,
            )

        service = _flock.ReplayService(
            algo="dreamer_v3", n_actors=int(args.flock), mode="buffer",
            capacity_rows=capacity, make_shard=_make_shard, telem=telem,
        )
        # crash-resume: the sidecar riding the checkpoint carries the shard
        # contents and membership table, and pins the pre-crash address so
        # surviving actors reconnect instead of re-collecting from scratch
        flock_restored = bool(
            args.checkpoint_path
            and service.restore_sidecar(args.checkpoint_path)
        )
        addr = service.start()
        telem.add_gauges(service.gauges)
        # actors block on the initial snapshot: version 1 is published
        # BEFORE the first actor spawns (on resume this bumps PAST the
        # restored version: weight versions stay monotonic across the crash)
        service.publish(jax.tree_util.tree_leaves(player))
        service.set_random_phase(
            args.checkpoint_path is None and not args.dry_run
        )
        fleet = _flock.ActorFleet(
            algo="dreamer_v3", args=args, address=addr, log_dir=log_dir,
            telem=telem, actor_faults=actor_faults,
        )
        service.on_evict = fleet.handle_eviction
        flock_skip: set[int] = set()
        if flock_restored:
            # adoption window: actors that outlived the crash are already
            # re-dialing this address; don't double-spawn their ids
            service.wait_for_actors(n=int(args.flock), timeout=10.0)
            flock_skip = service.connected_ids()
            for aid in flock_skip:
                fleet.adopt(aid, service.actor_pid(aid))
        fleet.start(skip=flock_skip)
        if not service.wait_for_actors(n=1, timeout=180.0):
            fleet.close()
            service.close()
            raise RuntimeError("flock: no actor registered within 180 s")
        # the learner samples the service directly: local shard reads, no
        # socket on the sample path. Under --pipeline on the assembler
        # pre-draws the next batch's shard slices on worker threads while
        # the train step runs (flock/assemble.py — the SamplePrefetcher
        # contract generalized across shards, same epoch guard + PRNG
        # rewind, so assembly on/off stays bit-exact)
        sampler = service
        if pipe.enabled:
            flock_assembler = _flock.BatchAssembler(
                service, max_staleness=pipe.max_staleness, stats=pipe.stats,
            )
            sampler = flock_assembler
    else:
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
        sampler = pipe.sampler(rb)

    aggregator = MetricAggregator()
    single_global_step = args.num_envs
    step_before_training = args.train_every // single_global_step
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

    player_state = player.init_states(args.num_envs)
    device_step_obs = None  # the policy step's obs puts, reused by rb.add
    expl_dev = jnp.float32(expl_amount)  # re-put only when the decay ticks
    obs = step_data = None
    use_blob = False
    anakin = jcarry = None
    anakin_chunk = 0
    if use_jax_env:
        # ---- Anakin collection setup (ISSUE 6): the collection window is
        # chunked at the train cadence — one jitted scan per train_every
        # window of env steps, writing straight into the device ring
        anakin_chunk = max(
            min(
                args.train_every // single_global_step,
                num_updates - start_step + 1,
            ),
            1,
        )
        key, jreset_key = jax.random.split(key)
        vec_state, jax_obs = jax.jit(venv.reset)(jreset_key)
        jcarry = DreamerCollectorCarry(
            vec=vec_state,
            obs=jax_obs,
            prev_reward=jnp.zeros((args.num_envs, 1), jnp.float32),
            prev_done=jnp.zeros((args.num_envs, 1), jnp.float32),
            is_first=jnp.ones((args.num_envs, 1), jnp.float32),
        )
        # env batch sharded over the mesh's data axis, player replicated —
        # zero cross-device traffic inside the rollout scan
        jcarry = shard_env_batch(jcarry, mesh)
        player_state = shard_env_batch(player_state, mesh)
        collect = donating_jit(
            make_dreamer_collector(
                venv, anakin_chunk, actions_dim, is_continuous,
                _dev_preprocess, clip_rewards=args.clip_rewards,
            ),
            donate_argnums=(2,),
        )
        collect_random = donating_jit(
            make_dreamer_collector(
                venv, anakin_chunk, actions_dim, is_continuous,
                _dev_preprocess, clip_rewards=args.clip_rewards,
                random_actions=True,
            ),
            donate_argnums=(2,),
        )
        anakin = AnakinStats(
            scan_span=anakin_chunk, env_batch=args.num_envs, devices=n_dev
        )
        telem.add_gauges(anakin.gauges)
    elif not use_flock:
        obs, _ = envs.reset(seed=args.seed)
        step_data = {k: np.asarray(obs[k]) for k in obs_keys}
        step_data["dones"] = np.zeros((args.num_envs, 1), np.float32)
        step_data["rewards"] = np.zeros((args.num_envs, 1), np.float32)
        step_data["is_first"] = np.ones((args.num_envs, 1), np.float32)

        # blob transport (device buffers): obs + replay-row floats + write
        # indices ride ONE transfer per step; shapes/dtypes from the first obs
        use_blob = (
            not rb.prefers_host_adds
            and os.environ.get("SHEEPRL_TPU_STEP_BLOB", "1") != "0"
        )
    if use_blob:
        codec, u8_keys, f32_obs_keys = StepBlobCodec.for_step(
            obs, obs_keys, args.num_envs, ("rewards", "dones", "is_first")
        )
        # live-backend roundtrip check: fall back to separate puts rather
        # than ship corrupt rows if a backend disagrees on the bitcasts
        use_blob = verify_blob_roundtrip(codec)
    if use_blob:
        blob_step = make_blob_step(
            codec, tuple(obs_keys), _dev_preprocess, actions_dim, is_continuous
        )

    # ---- warm-start shape capture (ISSUE 5): the full-scale DV3 train step
    # compiles in ~30-40 s per config — AOT-compile it (and the interaction
    # jit actually in use: blob or player step) concurrently with the
    # learning_starts collection window
    act_sum = int(sum(actions_dim))

    def _train_example():
        return (
            state,
            dreamer_sample_spec(
                observation_space, obs_keys, cnn_keys,
                args.per_rank_sequence_length, args.per_rank_batch_size,
                act_sum, extra=("rewards", "dones", "is_first"),
                mesh=mesh if n_dev > 1 else None,
            ),
            key, jnp.float32(1.0),
        )

    train_step = plan.register(
        "train_step", train_step, example=_train_example, role="update"
    )
    if use_jax_env:
        # the rollout jit is the interaction-critical executable on this
        # path: register it so --warm_compile on AOT-builds it during setup
        collect_w = plan.register(
            "anakin_rollout", collect,
            example=lambda: (player, player_state, jcarry, key, expl_dev),
        )
        collect_random_w = collect_random
        if learning_starts >= start_step and args.checkpoint_path is None:
            collect_random_w = plan.register(
                "anakin_rollout_random", collect_random,
                example=lambda: (player, player_state, jcarry, key, expl_dev),
            )
    elif use_blob:
        blob_step = plan.register(
            "blob_step", blob_step,
            example=lambda: (
                player, player.init_states(args.num_envs),
                sds((codec.blob_len,), jnp.int32), key, jnp.float32(0.0),
            ),
        )
    elif not use_flock:
        # flock: the actors own the player jit; the learner has no
        # interaction-critical executable to warm
        player_step = plan.register(
            "player_step", player_step,
            example=lambda: (
                player, player.init_states(args.num_envs),
                dict_obs_spec(
                    observation_space, obs_keys, cnn_keys,
                    (args.num_envs,),
                ),
                key, jnp.float32(0.0), None,
            ),
        )
    # data edges (ISSUE 8): collection reaches the train step through the
    # replay ring + sampler on every backend — the reshuffle is the
    # documented contract, recorded so sheepshard keeps drift visible.
    if use_jax_env:
        plan.declare_edge(
            "anakin_rollout", "train_step", expect="reshard",
            note="device replay ring (reserve/add_direct) + sequence sampler",
        )
    elif use_blob:
        plan.declare_edge(
            "blob_step", "train_step", expect="reshard",
            note="replay buffer + sequence sampler",
        )
    elif use_flock:
        # declared only when the flock is ON so default capture runs keep
        # the committed shard ledgers byte-stable; both endpoints resolve
        # as "unresolved" records (host-side, outside any compiled jit)
        plan.declare_edge(
            "flock_actors", "flock_replay", expect="reshard",
            note="actor buffer ops over the socket transport (host-side)",
        )
        plan.declare_edge(
            "flock_replay", "train_step", expect="reshard",
            note="learner-local shard sample: no socket on the sample path",
        )
    else:
        plan.declare_edge(
            "player_step", "train_step", expect="reshard",
            note="replay buffer + sequence sampler",
        )
    plan.start()

    gradient_steps = 0
    start_time = time.perf_counter()
    if args.eval_only:
        num_updates = start_step - 1  # empty training loop: fall through to test
    if use_jax_env:
        # each iteration collects anakin_chunk steps per env in one scan;
        # global_step names the last step of the chunk (a trailing partial
        # chunk is dropped — sub-chunk remainders are below the cadence)
        steps_iter = range(
            start_step + anakin_chunk - 1, num_updates + 1, anakin_chunk
        )
    else:
        steps_iter = range(start_step, num_updates + 1)
    for global_step in steps_iter:
        guard.tick(global_step)  # fires injected sig* faults for this step
        # the loop body is one `iteration` span; its spans (howto/
        # observability.md has the table) are opened where the work is, and
        # what none covers is the iteration's self time. `phase=` names the
        # `Time/*` sum a finer span is logged under: the four the loop always
        # had, because every scalar logged here costs the device idle time
        telem.iteration(global_step)
        blob_added = False
        if use_flock:
            telem.mark("rollout")
            # actors collect; one loop iteration corresponds to ONE replay
            # row landing fleet-wide (num_envs env steps — the same
            # global_step unit as the in-process path). The wait is the
            # drain: how far training runs ahead of collection.
            service.set_random_phase(
                global_step <= learning_starts
                and args.checkpoint_path is None
                and "minedojo" not in args.env_id
            )
            target_rows = global_step - start_step + 1
            while service.rows_total() < target_rows:
                if guard.preempted:
                    break
                if service.actors_alive() == 0 and fleet.alive() == 0:
                    raise RuntimeError(
                        "flock: every actor is dead and the respawn budget "
                        "is spent"
                    )
                time.sleep(0.01)
        elif use_jax_env:
            # ---- Anakin collection: one jitted scan per chunk ---------------
            telem.mark("rollout")
            key, roll_key = jax.random.split(key)
            random_phase = (
                global_step <= learning_starts and args.checkpoint_path is None
            )
            fn = collect_random_w if random_phase else collect_w
            t0 = time.perf_counter()
            idx = rb.reserve(anakin_chunk)
            player_state, jcarry, traj, ep = sanitizer.checked(
                "anakin/rollout", fn,
                player, player_state, jcarry, roll_key, expl_dev,
            )
            # rows are already device-resident: the ring scatter is the
            # zero-transfer half of the blob transport, fed by the scan
            rb.add_direct(traj, jnp.asarray(idx), data_len=anakin_chunk)
            jax.block_until_ready(traj["dones"])
            anakin.note(anakin_chunk * args.num_envs, time.perf_counter() - t0)
            ep_np = jax.device_get(ep)  # one pull per chunk, not per step
            if ep_np["episodes"] > 0:
                aggregator.update(
                    "Rewards/rew_avg",
                    float(ep_np["return_sum"] / ep_np["episodes"]),
                )
                aggregator.update(
                    "Game/ep_len_avg",
                    float(ep_np["length_sum"] / ep_np["episodes"]),
                )
        # ---- action selection (host envs) -----------------------------------
        elif (
            global_step <= learning_starts
            and args.checkpoint_path is None
            and "minedojo" not in args.env_id
        ):
            telem.mark("rollout")
            pairs = [
                _random_actions(action_space, actions_dim, is_continuous)
                for _ in range(args.num_envs)
            ]
            actions = np.stack([p[0] for p in pairs])
            env_actions = [p[1] for p in pairs]
        elif use_blob:
            # ONE transfer for the whole step: obs + prev rewards/dones/
            # is_first + ring write indices; the jit returns the device
            # replay row and add_direct scatters it transfer-free
            telem.mark("rollout/pack", phase="rollout")
            idx = rb.reserve(1)
            blob = codec.pack(
                {k: np.asarray(obs[k]) for k in u8_keys},
                {
                    **{k: np.asarray(obs[k]) for k in f32_obs_keys},
                    "rewards": step_data["rewards"],
                    "dones": step_data["dones"],
                    "is_first": step_data["is_first"],
                },
                idx,
            )
            telem.mark("rollout/policy_dispatch", phase="rollout")
            key, step_key = jax.random.split(key)
            player_state, env_idx_dev, row, idx_dev = blob_step(
                player, player_state, jnp.asarray(blob), step_key, expl_dev
            )
            # the d2h copy of the action indices starts NOW and lands while
            # the replay scatter dispatches (ActionPipeline; with --pipeline
            # off the handle is a plain deferred np.asarray)
            idx_handle = pipe.action.dispatch(env_idx_dev)
            telem.mark("rollout/add_dispatch", phase="rollout")
            rb.add_direct(row, idx_dev)
            blob_added = True
            # the host blocked on the policy step
            telem.mark("rollout/action_wait", phase="rollout")
            env_idx = idx_handle.get()  # the ONLY per-step d2h pull
            telem.mark(None)
            env_actions = list(
                indices_to_env_actions(env_idx, actions_dim, is_continuous)
            )
        else:
            telem.mark("rollout")
            # raw puts (uint8 for pixels): normalization happens inside the
            # jitted player step, and these same device arrays feed rb.add
            device_obs = {k: jnp.asarray(np.asarray(obs[k])) for k in obs_keys}
            mask = {k: v for k, v in device_obs.items() if k.startswith("mask")} or None
            key, step_key = jax.random.split(key)
            player_state, actions_dev, env_idx_dev = player_step(
                player, player_state, device_obs, step_key,
                expl_dev, mask,
            )
            env_idx = pipe.action.fetch(env_idx_dev)  # the ONLY per-step d2h pull
            env_actions = list(
                indices_to_env_actions(env_idx, actions_dim, is_continuous)
            )
            device_step_obs = device_obs
            actions = buffer_actions(
                env_idx, actions_dev, actions_dim, is_continuous,
                host=rb.prefers_host_adds,
            )

        if not use_jax_env and not use_flock:
            if not blob_added:
                step_data["actions"] = (
                    actions if isinstance(actions, jax.Array)
                    else np.asarray(actions, np.float32)
                )
                add_data = {k: v[None] for k, v in step_data.items()}
                if device_step_obs is not None and not rb.prefers_host_adds:
                    # reuse the policy step's obs puts instead of re-transferring
                    # (host/memmap storage and staged buffers want host numpy)
                    for k in obs_keys:
                        add_data[k] = device_step_obs[k][None]
                rb.add(add_data)
            device_step_obs = None

            telem.mark("rollout/env_step", phase="rollout")
            next_obs, rewards, terms, truncs, infos = envs.step(env_actions)
            dones = np.logical_or(terms, truncs).astype(np.float32)
            telem.mark(None)

            step_data["is_first"] = np.zeros((args.num_envs, 1), np.float32)
            for i, info in enumerate(infos):
                # env crash+restart: close the episode retroactively in the ring
                # (reference dreamer_v3.py:565-573)
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
                # terminal rows carry the true final observation and zero actions
                # (reference dreamer_v3.py:609-628)
                telem.mark("rollout/reset", phase="rollout")
                n_reset = len(dones_idxes)
                telem.count(rows=n_reset)
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
                telem.mark(None)

        step_before_training -= anakin_chunk if use_jax_env else 1

        # ---- training --------------------------------------------------------
        if global_step >= learning_starts and step_before_training <= 0:
            # chunked collection never lands exactly ON learning_starts: the
            # first chunk at/after it is the pretrain moment
            first_training = (
                global_step - anakin_chunk < learning_starts
                if use_jax_env
                else global_step == learning_starts
            )
            n_samples = (
                args.pretrain_steps if first_training else args.gradient_steps
            )
            telem.mark("buffer/sample")
            local_data = sampler.sample(
                args.per_rank_batch_size,
                sequence_length=args.per_rank_sequence_length,
                n_samples=n_samples,
            )
            telem.mark("buffer/stage", phase="buffer/sample")
            staged = stage_batch(local_data, to_host=jax.process_count() > 1)
            for i in range(n_samples):
                if gradient_steps % args.critic_target_network_update_freq == 0:
                    tau = 1.0 if gradient_steps == 0 else args.critic_tau
                else:
                    tau = 0.0
                # two spans a train step: what is left to do on its row apart
                # from the step's own enqueue. `stage_batch` cut every row on
                # the device in one program (inside `buffer/stage`), so this
                # one enqueues nothing on one device and holds the row's
                # re-sharding on several
                telem.mark("train/slice", phase="train/dispatch")
                sample = staged[i]
                if n_dev > 1:
                    sample = shard_time_batch(sample, mesh, time_axis=0, batch_axis=1)
                telem.mark("train/dispatch")
                key, train_key = jax.random.split(key)
                sample = resilience.poison_batch(sample, global_step)  # nan.* sites
                state, metrics = train_step(state, sample, train_key, jnp.float32(tau))
                resilience.update_skipped(metrics, args.on_nonfinite)
                gradient_steps += 1
                for name, val in metrics.items():
                    aggregator.update(name, val)
                profiler.tick()
            telem.mark(None)
            player = make_player(state)
            if use_flock:
                telem.mark("flock/publish")
                # sheepscope publish span: dv3's buffer mode has no per-chunk
                # drain chain, so the publish span is the learner-side anchor
                # actor pushes parent onto via the WEIGHTS meta
                pub = telem.tracer.begin("publish")
                version = service.publish(
                    jax.tree_util.tree_leaves(player),
                    span=None if pub is None else pub.id,
                )
                telem.tracer.end(pub, version=version)
                telem.mark(None)
            step_before_training = args.train_every // single_global_step
            if args.expl_decay:
                expl_decay_steps += 1
                expl_amount = ops.polynomial_decay(
                    expl_decay_steps,
                    initial=args.expl_amount,
                    final=args.expl_min,
                    max_decay_steps=max_step_expl_decay,
                )
                expl_dev = jnp.float32(expl_amount)
            aggregator.update("Params/exploration_amount", expl_amount)

        sps = (global_step - start_step + 1) * args.num_envs / (
            time.perf_counter() - start_time
        )
        # deferred drain: with --pipeline on this resolves the PREVIOUS
        # interval's snapshot (its d2h copies landed during this step) and
        # costs zero synchronous round trips; off mode computes eagerly,
        # which blocks on the iteration's last train step
        telem.mark("log/pull", phase="log")
        drains = pipe.drain_metrics(aggregator, global_step)
        telem.count(arrays=aggregator.arrays)
        telem.mark("log/write", phase="log")
        # `Time/step_per_second` rides the event of its own step: an iteration
        # is one event for the logger's writer, not two (--pipeline on drains
        # the step before, or nothing: the rate then goes alone)
        rate = {"Time/step_per_second": sps}
        scalars = 1
        for drained, dstep in drains:
            merged = telem.interval(drained, dstep, sps)
            scalars += len(merged)
            if dstep == global_step:
                merged, rate = {**merged, **rate}, {}
            logger.log_dict(merged, dstep)
        logger.log_dict(rate, global_step)
        telem.count(scalars=scalars, backlog=logger.backlog)
        telem.mark(None)

        # ---- checkpoint ------------------------------------------------------
        if (
            (args.checkpoint_every > 0 and global_step % args.checkpoint_every == 0)
            or args.dry_run
            or global_step == num_updates
            or guard.preempted
        ):
            ckpt_path = os.path.join(log_dir, "checkpoints", f"ckpt_{global_step}")
            telem.mark("checkpoint", phase="log")
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
            if args.checkpoint_buffer and rb is not None:
                rb.save(ckpt_path + "_buffer.npz")
            if use_flock:
                # flock mode: the shard contents ride a service sidecar
                # (bit-exact buffer wire codecs, sampler PRNG included) so a
                # restarted learner resumes with zero committed rows lost
                service.save_sidecar(ckpt_path)
            telem.mark(None)

        if guard.preempted:
            # the in-flight step finished and its grace checkpoint
            # committed: exit with the distinct resumable rc
            raise resilience.Preempted(global_step, guard.preempt_signal or "")
    telem.iteration(None)
    for drained, dstep in pipe.flush_metrics():
        logger.log_dict(telem.interval(drained, dstep, None), dstep)
    profiler.close()
    if envs is not None:
        envs.close()
    if flock_assembler is not None:
        flock_assembler.close()
    if fleet is not None:
        fleet.close()
    if service is not None:
        service.close()
    run_test_episodes(
        lambda: test(player, logger, args, cnn_keys, mlp_keys, log_dir, sample_actions=True),
        args, logger,
    )
    plan.close()
    sanitizer.close()
    telem.close()
    logger.close()


if __name__ == "__main__":
    main()
