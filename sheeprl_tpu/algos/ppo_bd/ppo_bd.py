"""`ppo_bd`: clipped policy gradient over a block-diffusion language model's own
denoising trajectories (howto/learn_token_tasks.md).

A policy step is ONE DENOISING STEP for every environment, not one token: the
current block's `block_length` positions are read against the cache of clean
keys and values, an id is sampled at each still-masked position, and the
`block_length / denoise_steps` of highest confidence are committed. Those ids
are the action; their log-probabilities are the stored `logprob_old`. After a
block's last step one more pass over the now clean block writes its keys and
values to the cache. An environment step is one denoising step of one sequence.

The reward comes with the episode's end. Environments run in groups that
share a prompt; the advantage of a sequence, the same for all its steps, is
its reward normalised over its group (no critic, no reference model). When
`update_sequences` sequences are finished an update trains on them once, in
minibatches of whole sequences at one fixed shape (`layout.py`): the clean
sequence beside one noisy copy a denoising step, the clipped surrogate of
`algos/ppo/loss.py` over the ids each step committed.

Every program of the loop has one shape whatever a step brings: the prefill
takes a group's prompts padded to the longest (a group's members end on the
same step, so resets arrive a group at a time; a step that ends three groups
calls it three times), the cache commit takes a mask over the
environments, the train step always `per_rank_batch_size` sequences.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ... import nn, resilience
from ...analysis import Sanitizer
from ...compile import CompilePlan, sds
from ...envs import make_vector_env
from ...envs import token_task  # noqa: F401  (registers the token-task ids)
from ...ops import precision
from ...parallel import Pipeline, distributed_setup, process_index, pull
from ...telemetry import Telemetry
from ...utils.checkpoint import load_checkpoint, load_checkpoint_args, save_checkpoint
from ...utils.env import make_dict_env
from ...utils.jit import donating_jit
from ...utils.logger import create_logger
from ...utils.metric import MetricAggregator
from ...utils.parser import DataclassArgumentParser
from ...utils.registry import register_algorithm
from ..ppo.loss import policy_loss
from .agent import BDPolicy, build_policy, layout_attend, player_copy
from .args import PPOBDArgs
from .layout import Dims, Record, build_batch


class TrainState(nn.Module):
    model: BDPolicy
    opt_state: object


def make_optimizer(args: PPOBDArgs) -> optax.GradientTransformation:
    """Global-norm clip then Adam, as PPO has it; the rate is applied in the step."""
    steps = [optax.scale_by_adam(eps=args.eps)]
    if args.max_grad_norm > 0:
        steps.insert(0, optax.clip_by_global_norm(args.max_grad_norm))
    return optax.chain(*steps)


def build_models(key, args: PPOBDArgs, vocab_size: int) -> BDPolicy:
    """The float32 master model (the benchmark wraps this to hand in its own weights)."""
    if args.experts_held is None:
        args.experts_held = args.num_experts - args.first_expert
    if args.block_length % args.denoise_steps:
        raise ValueError("block_length must be a multiple of denoise_steps")
    return build_policy(key, args, vocab_size)


def make_policy_step(args: PPOBDArgs):
    """-> jitted (player, state, key) -> (the block's ids after the step,
    packed [envs, 2 * block_length] int32: the committed ids (-1 elsewhere)
    then their log-probabilities' bits, the step's logits)."""
    n_commit = args.block_length // args.denoise_steps

    def bd_policy_step(player: BDPolicy, state, key):
        with jax.named_scope("bd/policy_step"):
            hidden, _ = player.cached_block(state, state.block_ids)
            logits = player.logits(hidden) / args.temperature
            masked = state.block_ids == player.mask_token_id
            ids = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), ids[..., None], axis=-1)[..., 0]
            _, top = jax.lax.top_k(jnp.where(masked, logp, -jnp.inf), n_commit)  # confidence: the sampled id's probability
            chosen = jnp.zeros(masked.shape, bool).at[jnp.arange(masked.shape[0])[:, None], top].set(True) & masked
            actions = jnp.where(chosen, ids, -1)
            bits = jax.lax.bitcast_convert_type(jnp.where(chosen, logp, 0.0), jnp.int32)
            return jnp.where(chosen, ids, state.block_ids), jnp.concatenate([actions, bits], axis=-1), logits

    return jax.jit(bd_policy_step)


def make_train_step(args: PPOBDArgs, optimizer, n_clean: int):
    """-> jitted (state, player, batch) -> (state, player, metrics, tokens per
    held expert [layers, held]); `batch` as `layout.build_batch` makes it, its
    first `n_clean` positions the prompt and the clean response.

    The gradient is taken with respect to `player`, the compute copy the policy
    steps read (`agent.player_copy`: the matrices in the compute dtype, router
    and norm scales float32). It arrives as it would through the layers' own
    cast, a bfloat16 cotangent, but no second compute copy and no float32
    gradient of every matrix stand beside the master weights and Adam's
    moments, which read it in float32. The step hands back the copy of the
    weights it has just made, in the buffers of the one it was given: the next
    minibatch and the next policy step read the new weights."""
    policy = precision.policy(args.precision)

    def loss_fn(player: BDPolicy, batch):
        attend = layout_attend(batch["copy"], batch["block"], n_clean, args.block_length)
        hidden, counts, _ = player.layout_hidden(batch["ids"], batch["positions"], batch["copy"] >= 0, attend, policy.compute)
        at_loss = jnp.take_along_axis(hidden, batch["loss_pos"][..., None], axis=1)  # the head at the loss positions alone

        def one(seq):  # a sequence's logits at a time
            h, targets, old, adv, live = seq
            logits = player.logits(h) / args.temperature
            with jax.named_scope("bd/loss"):
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]
                per_token = policy_loss(logp, old, adv, args.clip_coef, "none")
                return jnp.sum(per_token * live), jnp.sum(jnp.abs(jnp.exp(logp - old) - 1.0) * live)

        sums, gaps = jax.lax.map(jax.checkpoint(one), (at_loss, batch["targets"], batch["logprob_old"], batch["advantages"], batch["loss_mask"]))
        n = jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0)
        return jnp.sum(sums) / n, (counts, jnp.sum(gaps) / n)

    def bd_train_step(state: TrainState, player: BDPolicy, batch):
        (loss, (counts, ratio_gap)), grads = jax.value_and_grad(loss_fn, has_aux=True)(player, batch)
        with jax.named_scope("bd/opt"):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            updates, opt_state = optimizer.update(grads, state.opt_state, state.model)
            model = optax.apply_updates(state.model, jax.tree_util.tree_map(lambda u: -args.lr * u, updates))
        metrics = {"Loss/policy_loss": loss, "Policy/ratio_gap": ratio_gap, "Grads/global_norm": optax.global_norm(grads)}
        return TrainState(model=model, opt_state=opt_state), player_copy(model, policy.compute), metrics, counts

    return donating_jit(bd_train_step, donate_argnums=(0, 1))


@register_algorithm()
@resilience.crashsafe
def main(argv: Sequence[str] | None = None) -> None:
    parser = DataclassArgumentParser(PPOBDArgs)
    (args,) = parser.parse_args_into_dataclasses(argv)
    resilience.prepare_run(args, "ppo_bd")
    if args.checkpoint_path:
        saved = load_checkpoint_args(args.checkpoint_path)
        if saved:
            saved.update(checkpoint_path=args.checkpoint_path)
            (args,) = parser.parse_dict(saved)
    if args.num_devices != 1:
        raise ValueError("ppo_bd runs on one device: parallel/mesh.py has no expert axis yet (ROADMAP R-a1)")
    if args.num_envs % args.group_size or args.update_sequences % args.group_size:
        raise ValueError("num_envs and update_sequences are multiples of group_size")
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    np.random.seed(args.seed)
    distributed_setup()
    rank = process_index()
    key = jax.random.PRNGKey(args.seed)

    logger, log_dir, run_name = create_logger(args, "ppo_bd", process_index=rank)
    logger.log_hyperparams(args.as_dict())
    telem = Telemetry.from_args(args, log_dir, rank, algo="ppo_bd")
    guard = resilience.RunGuard.install(telem)
    sanitizer = Sanitizer.from_args(args, telem)
    telem.add_gauges(sanitizer.gauges)
    pipe = Pipeline.from_args(args, telem)
    plan = CompilePlan.from_args(args, telem)
    telem.add_gauges(plan.gauges)

    # the members of a group are given one seed: they pose the same prompts in the same order
    seeds = [args.seed + rank * args.num_envs + i // args.group_size for i in range(args.num_envs)]
    envs = make_vector_env(
        [make_dict_env(args.env_id, seeds[i], rank=rank, args=args, run_name=log_dir, vector_env_idx=i) for i in range(args.num_envs)],
        sync=args.sync_env or args.num_envs == 1,
    )
    space = envs.single_observation_space
    env_vocab = int(space["prompt"].high.max()) + 1
    vocab_size = args.vocab_size or env_vocab
    if vocab_size < env_vocab:
        raise ValueError(f"the environment's ids reach {env_vocab - 1}; --vocab_size {vocab_size} holds fewer")
    if envs.single_action_space.shape != (args.block_length,):
        raise ValueError(f"the environment takes {envs.single_action_space.shape} ids a step, --block_length is {args.block_length}")
    dims = Dims(
        p_max=space["prompt"].shape[0], r_max=int(space["response_len"].high.max()),
        block_length=args.block_length, denoise_steps=args.denoise_steps, mask_id=vocab_size - 1,
    )

    key, model_key = jax.random.split(key)
    model = build_models(model_key, args, vocab_size)
    optimizer = make_optimizer(args)
    state = TrainState(model=model, opt_state=optimizer.init(model))
    start_step = 1
    if args.checkpoint_path:
        ckpt = load_checkpoint(args.checkpoint_path, {"model": state.model, "optimizer": state.opt_state, "global_step": 0})
        state = TrainState(model=ckpt["model"], opt_state=ckpt["optimizer"])
        start_step = int(ckpt["global_step"]) + 1
    del model

    compute = precision.compute_dtype(args.precision)

    def bd_player_copy(m):
        return player_copy(m, compute)

    def bd_prefill(p, s, prompts, lengths, idx):
        return p.prefill(s, prompts, lengths, idx)

    def bd_cache_commit(p, s, done):
        return p.commit(s, done)

    def bd_reset_states(p, s, done):
        return p.reset_states(s, done)

    make_player = jax.jit(bd_player_copy)
    bd_prefill, bd_cache_commit, reset_states = (donating_jit(f, donate_argnums=(1,)) for f in (bd_prefill, bd_cache_commit, bd_reset_states))
    player = make_player(state.model)
    pstate = player.init_states(args.num_envs, dims.s_max, compute)

    def batch_spec():
        B, S, R = args.per_rank_batch_size, dims.layout, dims.r_max
        ints, floats = ("ids", "positions", "copy", "block"), ("loss_mask", "logprob_old")
        spec = {k: sds((B, S), jnp.int32) for k in ints}
        spec.update({k: sds((B, R), jnp.int32) for k in ("loss_pos", "targets")}, **{k: sds((B, R), jnp.float32) for k in floats})
        return {**spec, "advantages": sds((B,), jnp.float32)}

    policy_step = plan.register("policy_step", make_policy_step(args), example=lambda: (player, pstate, key))
    train_step = plan.register("train_step", make_train_step(args, optimizer, dims.s_max), example=lambda: (state, player, batch_spec()), role="update")
    plan.start()

    record = Record(args.num_envs, args.group_size, dims)
    aggregator = MetricAggregator()

    def prefill(pstate, obs, which: np.ndarray):
        """Reset the slots `which` and write their prompts' keys and values, a group's prompts a call."""
        mask = np.zeros(args.num_envs, np.float32)
        mask[which] = 1.0
        pstate = reset_states(player, pstate, jnp.asarray(mask))
        for lo in range(0, len(which), args.group_size):
            idx = np.full(args.group_size, args.num_envs, np.int32)  # past the last slot: written nowhere
            part = which[lo : lo + args.group_size]
            idx[: len(part)] = part
            rows = np.minimum(idx, args.num_envs - 1)
            pstate = bd_prefill(
                player, pstate, jnp.asarray(obs["prompt"][rows].astype(np.int32)),
                jnp.asarray(obs["prompt_len"][rows, 0].astype(np.int32)), jnp.asarray(idx),
            )
        for i in which:
            record.start(int(i), obs["prompt"][i], int(obs["prompt_len"][i, 0]))
        return pstate

    obs, _ = envs.reset(seed=seeds)
    pstate = prefill(pstate, obs, np.arange(args.num_envs))

    num_updates = args.total_steps // args.num_envs if not args.dry_run else 10**9  # a dry run ends with its first update
    update_sequences = args.update_sequences if not args.dry_run else args.group_size
    updates_done = 0
    start_time = time.perf_counter()
    bl = args.block_length
    for global_step in range(start_step, num_updates + 1):
        guard.tick(global_step)
        telem.iteration(global_step)
        # ---- one denoising step of every environment ------------------------
        telem.mark("rollout/denoise_dispatch", phase="rollout")
        key, step_key = jax.random.split(key)
        block_ids, packed_dev, _ = policy_step(player, pstate, step_key)
        pstate = pstate.replace(block_ids=block_ids)
        handle = pipe.action.dispatch(packed_dev)
        telem.mark("rollout/action_wait", phase="rollout")
        packed = np.asarray(handle.get())  # the ONLY per-step d2h pull: the action and its log-probabilities
        telem.mark("rollout/pack", phase="rollout")
        actions = packed[:, :bl]
        clean = record.commit(actions, np.ascontiguousarray(packed[:, bl:]).view(np.float32))
        telem.count(tokens_committed=int((actions >= 0).sum()))
        telem.mark("rollout/env_step", phase="rollout")
        obs, rewards, terms, truncs, infos = envs.step(list(actions))
        dones = np.logical_or(terms, truncs)
        telem.mark(None)
        for info in infos:
            if "episode" in info:
                aggregator.update("Rewards/rew_avg", float(info["episode"]["r"]))
                aggregator.update("Game/ep_len_avg", float(info["episode"]["l"]))
        if (clean & ~dones).any():
            # the blocks that are clean now: one more pass writes their keys and values
            telem.mark("rollout/cache_commit", phase="rollout")
            pstate = bd_cache_commit(player, pstate, jnp.asarray((clean & ~dones).astype(np.float32)))
        if dones.any():
            telem.mark("rollout/prefill", phase="rollout")
            which = np.nonzero(dones)[0]
            for i in which:
                record.finish(int(i), float(rewards[i]))
            pstate = prefill(pstate, obs, which)
            telem.count(cache_slots_reset=len(which))
        telem.mark(None)

        # ---- the update: one epoch over the finished sequences --------------
        if len(record.ready) >= update_sequences:
            with telem.phase("update"):
                seqs, record.ready = record.ready[:update_sequences], record.ready[update_sequences:]
                pad = trained = 0
                counts, lengths = [], []
                for lo in range(0, len(seqs), args.per_rank_batch_size):
                    telem.mark("train/build_batch", phase="train")
                    part = seqs[lo : lo + args.per_rank_batch_size]
                    batch = build_batch(part, args.per_rank_batch_size, dims)
                    pad += int((batch["copy"] < 0).sum())
                    trained += len(part)
                    lengths.append([[len(s["prompt"]), len(s["ids"])] for s in part])
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                    telem.mark("train/dispatch", phase="train")
                    batch = resilience.poison_batch(batch, global_step)  # nan.* sites
                    state, player, metrics, step_counts = train_step(state, player, batch)
                    counts.append(step_counts)
                    for name, val in metrics.items():
                        aggregator.update(name, val)
                telem.mark("log/pull", phase="log")
                counts = pull(counts, telem)  # waits for the update's last train step
                telem.mark(None)
                telem.count(
                    sequences_trained=trained, pad_positions=pad, positions=len(counts) * args.per_rank_batch_size * dims.layout,
                    moe_assignments=[int(c.sum()) for c in counts], moe_load_max=[int(c.max()) for c in counts],
                    moe_load_mean=[float(c.mean()) for c in counts], lengths=lengths,
                )
            updates_done += 1

        sps = (global_step - start_step + 1) * args.num_envs / (time.perf_counter() - start_time)
        telem.mark("log/pull", phase="log")
        drains = pipe.drain_metrics(aggregator, global_step)
        telem.mark("log/write", phase="log")
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

        last = global_step == num_updates or (args.dry_run and updates_done > 0)
        if (
            (args.checkpoint_every > 0 and global_step % args.checkpoint_every == 0)
            or last
            or (guard.preempted and args.grace_checkpoint)
        ):
            telem.mark("checkpoint", phase="log")
            save_checkpoint(
                os.path.join(log_dir, "checkpoints", f"ckpt_{global_step}"),
                {"model": state.model, "optimizer": state.opt_state, "global_step": global_step},
                args=args, block=last or guard.preempted,
            )
            telem.mark(None)
        if guard.preempted:
            raise resilience.Preempted(global_step, guard.preempt_signal or "")
        if last:
            break
    telem.iteration(None)
    for drained, dstep in pipe.flush_metrics():
        logger.log_dict(telem.interval(drained, dstep, None), dstep)
    envs.close()
    plan.close()
    sanitizer.close()
    telem.close()
    logger.close()


if __name__ == "__main__":
    main()
