"""`ppo_lm`: clipped policy gradient over an autoregressive language model's
own samples (howto/learn_token_tasks.md).

A policy step is ONE TOKEN for every environment: the token sampled last (the
prompt's last at first) is fed at its position through `total_ut_steps`
passes of the layer stack, each pass with a key/value cache of its own; its
keys and values go into all of them, and the next token is sampled at
temperature `--temperature` from the exit loop's logits (`agent.py`). That
token, its log-probability and its exit loop come back in the one action pull.
An environment step is one token of one sequence.

The reward comes with the episode's end. Environments run in groups that
share a prompt; the advantage of a sequence, the same for all its tokens, is
its reward normalised over its group (no critic, no reference model, no KL).
When `update_sequences` sequences are finished an update trains on them once,
in minibatches of whole sequences at one fixed shape (`layout.py`), with the
clipped surrogate of `algos/ppo/loss.py` over the sampled tokens.

Every program of the loop has one shape whatever a step brings: the prefill
takes a group's prompts padded to the longest (a group's members end on the
same step, so resets arrive a group at a time), the train step always
`per_rank_batch_size` sequences of `P_max + R_max` positions.

One policy step is always queued ahead of the host's read: step t + 1 needs
nothing from the host (its fed token is step t's sample, already on the
device), so it is dispatched on the state step t left before step t's action
is pulled, and the device runs it while the host packs, steps the
environments and logs. Where step t ended a group (its rows are prefilled) or
an update runs (the player changes), the step queued ahead read what the host
is about to change: it is dropped (the state rolled back a position), and
dispatched again with the same key behind the prefill and the train steps.
The samples, the trained batches and the weights are the plain order's, bit
for bit; only when a step is enqueued moves.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ... import resilience
from ...analysis import Sanitizer
from ...compile import CompilePlan, sds
from ...envs import make_vector_env
from ...envs import token_task  # noqa: F401  (registers the token-task ids)
from ...ops import precision
from ...parallel import Pipeline, distributed_setup, process_index
from ...parallel.pipeline import start_copies
from ...telemetry import Telemetry
from ...utils.checkpoint import load_checkpoint, load_checkpoint_args, save_checkpoint
from ...utils.env import make_dict_env
from ...utils.jit import donating_jit
from ...utils.logger import create_logger
from ...utils.metric import MetricAggregator
from ...utils.parser import DataclassArgumentParser
from ...utils.registry import register_algorithm
from ..ppo.loss import policy_loss
from ..ppo_bd.ppo_bd import TrainState, make_optimizer
from .agent import LoopedLM, build_policy, player_copy, read_block
from .args import PPOLMArgs
from .layout import Dims, Record, build_batch


def build_models(key, args: PPOLMArgs, vocab_size: int) -> LoopedLM:
    """The float32 master model (the benchmark wraps this to hand in its own weights)."""
    if args.total_ut_steps < 1:
        raise ValueError("total_ut_steps is at least 1")
    return build_policy(key, args, vocab_size)


def make_policy_step(args: PPOLMArgs):
    """-> jitted (player, state, key) -> (the state with the token fed and the
    sampled one next, packed [envs, 3] int32: the token, its log-probability's
    bits, its exit loop from 0, the step's logits). The state is donated: the
    caches are written where they lie."""
    def lm_policy_step(player: LoopedLM, state, key):
        with jax.named_scope("lm/policy_step"):
            state, logits, exits = player.decode(state)
            logits = logits / args.temperature
            ids = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), ids[:, None], axis=-1)[:, 0]
            packed = jnp.stack([ids, jax.lax.bitcast_convert_type(logp, jnp.int32), exits], axis=-1)
            return state.replace(last=ids), packed, logits

    return donating_jit(lm_policy_step, donate_argnums=(1,))


def make_rollback():
    """-> jitted (pos, packed) -> (pos, last): the positions and the fed tokens
    that the policy step before the last one left, from the last one's
    positions and that earlier step's packed action (its sampled ids are fed
    again, a position back). The caches are not touched: the slot at that
    position keeps what the dropped step wrote, which the next policy step
    writes before it reads it. `pos` is donated."""
    def lm_rollback(pos, packed):
        return pos - 1, packed[:, 0]

    return donating_jit(lm_rollback, donate_argnums=(0,))


def make_train_step(args: PPOLMArgs, optimizer):
    """-> jitted (state, player, batch) -> (state, player, metrics); `batch` as
    `layout.build_batch` makes it. As `ppo_bd.make_train_step`: the gradient
    is taken with respect to `player`, the compute copy the policy steps read,
    Adam reads it in float32 against the float32 master weights, and the step
    hands back the new copy in the buffers of the one it was given."""
    policy = precision.policy(args.precision)

    def loss_fn(player: LoopedLM, batch):
        hidden, _, _ = player.hidden(batch["ids"], policy.compute)
        at_loss = jnp.take_along_axis(hidden, batch["loss_pos"][..., None], axis=1)  # the head at the loss positions alone

        def one(seq):  # a sequence's logits at a time
            h, targets, old, adv, live = seq
            logits = player.logits(h) / args.temperature
            with jax.named_scope("lm/loss"):
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[:, None], axis=-1)[:, 0]
                per_token = policy_loss(logp, old, adv, args.clip_coef, "none")
                return jnp.sum(per_token * live), jnp.sum(jnp.abs(jnp.exp(logp - old) - 1.0) * live)

        sums, gaps = jax.lax.map(jax.checkpoint(one), (at_loss, batch["targets"], batch["logprob_old"], batch["advantages"], batch["loss_mask"]))
        n = jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0)
        return jnp.sum(sums) / n, jnp.sum(gaps) / n

    def lm_train_step(state: TrainState, player: LoopedLM, batch):
        (loss, ratio_gap), grads = jax.value_and_grad(loss_fn, has_aux=True)(player, batch)
        with jax.named_scope("lm/opt"):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            updates, opt_state = optimizer.update(grads, state.opt_state, state.model)
            model = optax.apply_updates(state.model, jax.tree_util.tree_map(lambda u: -args.lr * u, updates))
        metrics = {"Loss/policy_loss": loss, "Policy/ratio_gap": ratio_gap, "Grads/global_norm": optax.global_norm(grads)}
        return TrainState(model=model, opt_state=opt_state), player_copy(model, policy.compute), metrics

    return donating_jit(lm_train_step, donate_argnums=(0, 1))


@register_algorithm()
@resilience.crashsafe
def main(argv: Sequence[str] | None = None) -> None:
    parser = DataclassArgumentParser(PPOLMArgs)
    (args,) = parser.parse_args_into_dataclasses(argv)
    resilience.prepare_run(args, "ppo_lm")
    if args.checkpoint_path:
        saved = load_checkpoint_args(args.checkpoint_path)
        if saved:
            saved.update(checkpoint_path=args.checkpoint_path)
            (args,) = parser.parse_dict(saved)
    if args.num_devices != 1:
        raise ValueError("ppo_lm runs on one device: no layer is sharded over devices yet (ROADMAP R-a1)")
    if args.num_envs % args.group_size or args.update_sequences % args.group_size:
        raise ValueError("num_envs and update_sequences are multiples of group_size")
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    np.random.seed(args.seed)
    distributed_setup()
    rank = process_index()
    key = jax.random.PRNGKey(args.seed)

    logger, log_dir, run_name = create_logger(args, "ppo_lm", process_index=rank)
    logger.log_hyperparams(args.as_dict())
    telem = Telemetry.from_args(args, log_dir, rank, algo="ppo_lm")
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
    if envs.single_action_space.shape != (1,):
        raise ValueError(f"the environment takes {envs.single_action_space.shape} ids a step; ppo_lm gives one (env id TokenTaskAR-v0)")
    dims = Dims(p_max=space["prompt"].shape[0], r_max=int(space["response_len"].high.max()))

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

    def lm_player_copy(m):
        return player_copy(m, compute)

    def lm_prefill(p, s, prompts, lengths, idx):
        return p.prefill(s, prompts, lengths, idx)

    make_player = jax.jit(lm_player_copy)
    lm_prefill = donating_jit(lm_prefill, donate_argnums=(1,))
    player = make_player(state.model)
    pstate = player.init_states(args.num_envs, dims.s_max, compute)

    def batch_spec():
        B, S, R = args.per_rank_batch_size, dims.s_max, dims.r_max
        spec = {"ids": sds((B, S), jnp.int32), "loss_pos": sds((B, R), jnp.int32), "targets": sds((B, R), jnp.int32)}
        spec.update(loss_mask=sds((B, R), jnp.float32), logprob_old=sds((B, R), jnp.float32), advantages=sds((B,), jnp.float32))
        return spec

    policy_step = plan.register("policy_step", make_policy_step(args), example=lambda: (player, pstate, key))
    rollback = plan.register("rollback", make_rollback(), example=lambda: (sds((args.num_envs,), jnp.int32), sds((args.num_envs, 3), jnp.int32)))
    train_step = plan.register("train_step", make_train_step(args, optimizer), example=lambda: (state, player, batch_spec()), role="update")
    plan.start()

    record = Record(args.num_envs, args.group_size, dims)
    aggregator = MetricAggregator()
    R, N = args.total_ut_steps, args.num_hidden_layers
    block = read_block(player.layers[0].attn, dims.s_max)  # the cache slots a policy step reads a row's cache in

    def prefill(pstate, obs, which: np.ndarray):
        """Write the prompts of the slots `which`, a group's prompts a call."""
        for lo in range(0, len(which), args.group_size):
            idx = np.full(args.group_size, args.num_envs, np.int32)  # past the last slot: written nowhere
            part = which[lo : lo + args.group_size]
            idx[: len(part)] = part
            rows = np.minimum(idx, args.num_envs - 1)
            pstate = lm_prefill(
                player, pstate, jnp.asarray(obs["prompt"][rows].astype(np.int32)),
                jnp.asarray(obs["prompt_len"][rows, 0].astype(np.int32)), jnp.asarray(idx),
            )
        for i in which:
            record.start(int(i), obs["prompt"][i], int(obs["prompt_len"][i, 0]))
        return pstate

    def dispatch(pstate, step_key):
        """A policy step queued on the device, the copy of its packed action
        issued before the step after it is queued (with the pipeline off too)."""
        pstate, packed_dev, _ = policy_step(player, pstate, step_key)
        start_copies(packed_dev)
        return pstate, packed_dev, pipe.action.dispatch(packed_dev)

    obs, _ = envs.reset(seed=seeds)
    pstate = prefill(pstate, obs, np.arange(args.num_envs))
    key, ahead_key = jax.random.split(key)
    pstate, ahead_dev, ahead = dispatch(pstate, ahead_key)
    kept = 1  # the step read next was queued ahead and kept (1), or dispatched again after a drop (0)

    num_updates = args.total_steps // args.num_envs if not args.dry_run else 10**9  # a dry run ends with its first update
    update_sequences = args.update_sequences if not args.dry_run else args.group_size
    updates_done = 0
    start_time = time.perf_counter()
    for global_step in range(start_step, num_updates + 1):
        guard.tick(global_step)
        telem.iteration(global_step)
        # ---- one token of every environment: step t read, step t + 1 queued behind it
        telem.mark("rollout/decode_dispatch", phase="rollout")
        packed_dev, handle = ahead_dev, ahead
        key, ahead_key = jax.random.split(key)
        pstate, ahead_dev, ahead = dispatch(pstate, ahead_key)
        telem.mark("rollout/action_wait", phase="rollout")
        packed = np.asarray(handle.get())  # the ONLY per-step d2h pull: the token, its log-probability and exit loop
        telem.mark("rollout/pack", phase="rollout")
        tokens = packed[:, 0]
        fed = record.prompt_len - 1 + record.written  # the position each row's token was fed at
        read = np.minimum(-(-(fed + 1) // block) * block, dims.s_max)
        record.add(tokens, np.ascontiguousarray(packed[:, 1]).view(np.float32))
        telem.count(tokens_committed=len(tokens), loop_applications=len(tokens) * R * N, exit_loop=np.bincount(packed[:, 2], minlength=R).tolist(),
                    attn_slots_read=int(read.sum()), attn_slots_held=args.num_envs * dims.s_max, ahead_kept=kept)
        telem.mark("rollout/env_step", phase="rollout")
        obs, rewards, terms, truncs, infos = envs.step(list(tokens[:, None]))
        dones = np.logical_or(terms, truncs)
        telem.mark(None)
        for info in infos:
            if "episode" in info:
                aggregator.update("Rewards/rew_avg", float(info["episode"]["r"]))
                aggregator.update("Game/ep_len_avg", float(info["episode"]["l"]))
        which = np.flatnonzero(dones)
        for i in which:
            record.finish(int(i), float(rewards[i]))
        update = len(record.ready) >= update_sequences
        kept = int(not (len(which) or update))
        if not kept:  # step t + 1 read slots the prefill rewrites, or the player the update replaces
            telem.mark("rollout/ahead_drop", phase="rollout")
            pos, last = rollback(pstate.pos, packed_dev)
            pstate = pstate.replace(pos=pos, last=last)
            telem.count(ahead_dropped=1)
            telem.mark(None)
        if len(which):
            telem.mark("rollout/prefill", phase="rollout")
            pstate = prefill(pstate, obs, which)
            telem.count(cache_slots_reset=len(which))
            telem.mark(None)

        # ---- the update: one epoch over the finished sequences --------------
        if update:
            with telem.phase("update"):
                seqs, record.ready = record.ready[:update_sequences], record.ready[update_sequences:]
                pad = trained = 0
                lengths = []
                for lo in range(0, len(seqs), args.per_rank_batch_size):
                    telem.mark("train/build_batch", phase="train")
                    part = seqs[lo : lo + args.per_rank_batch_size]
                    batch = build_batch(part, args.per_rank_batch_size, dims)
                    pad += args.per_rank_batch_size * dims.s_max - sum(len(s["prompt"]) + len(s["ids"]) for s in part)
                    trained += len(part)
                    lengths.append([[len(s["prompt"]), len(s["ids"])] for s in part])
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                    telem.mark("train/dispatch", phase="train")
                    batch = resilience.poison_batch(batch, global_step)  # nan.* sites
                    state, player, metrics = train_step(state, player, batch)
                    for name, val in metrics.items():
                        aggregator.update(name, val)
                telem.mark(None)
                telem.count(sequences_trained=trained, pad_positions=pad, positions=len(lengths) * args.per_rank_batch_size * dims.s_max, lengths=lengths)
            updates_done += 1
        if not kept:  # step t + 1 again, with the key it was given, queued behind the prefill and the train steps
            telem.mark("rollout/decode_dispatch", phase="rollout")
            pstate, ahead_dev, ahead = dispatch(pstate, ahead_key)
            telem.mark(None)

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
