"""`ppo_lm`, the autoregressive policy-gradient main over a looped language
model, against the plain reference (`benchmark/reference/ouro_loop.py`) at a
tiny preset on seeded weights: prefill and decoding through the per-loop
caches, one loop as a plain causal stack, the exit rule, one train step's loss
and gradients, the record and layout, and the CLI's dry run."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import ouro_names
from benchmark.reference import ouro_loop as ref
from sheeprl_tpu.algos.ppo_lm import ppo_lm
from sheeprl_tpu.algos.ppo_lm.agent import exit_loop
from sheeprl_tpu.algos.ppo_lm.args import PPOLMArgs
from sheeprl_tpu.algos.ppo_lm.layout import Dims, Record, build_batch
from sheeprl_tpu.cli import run as cli_run
from sheeprl_tpu.envs.token_task import TokenTask
from sheeprl_tpu.ops import pallas_kernels as pk
from sheeprl_tpu.utils.registry import tasks

MODEL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96, rope_theta=1e6,
             rms_norm_eps=1e-6, num_hidden_layers=2, total_ut_steps=4, early_exit_threshold=1.0)
TRAIN = dict(clip_coef=0.2, lr=1e-3, max_grad_norm=1.0, adam_eps=1e-8, temperature=1.0)
DIMS = Dims(p_max=16, r_max=8)
V = 64
SEED = 2147483949  # more than 32 signed bits hold


def config(**changes):
    return {**MODEL, **TRAIN, "vocab_size": V, **changes}


def program(c):
    args = PPOLMArgs(**{k: c[k] for k in MODEL}, lr=c["lr"], clip_coef=c["clip_coef"], max_grad_norm=c["max_grad_norm"], eps=c["adam_eps"], precision="float32")
    params = ref.make_params(SEED, c)
    model = ouro_names.from_reference(ppo_lm.build_models(jax.random.PRNGKey(0), args, V), params.__getitem__)
    return args, jax.tree_util.tree_map(jnp.copy, model), params  # a copy: the train step donates, the reference keeps its own


@pytest.mark.parametrize("loops", [1, 2, 4])
def test_prefill_and_decoding_through_the_per_loop_caches_equal_the_full_forward(loops):
    c = config(total_ut_steps=loops)
    _, model, params = program(c)
    rng = np.random.default_rng(loops)
    lengths, steps = np.array([9, 16, 5, 12]), 6
    seqs = rng.integers(0, V, (4, DIMS.s_max)).astype(np.int32)
    state = model.init_states(5, DIMS.s_max, jnp.float32)
    with jax.default_matmul_precision("highest"):
        # the slots are given out of order, and slot 4 is left empty: a prefill writes its own rows alone
        state = model.prefill(state, jnp.asarray(seqs[:, : DIMS.p_max]), jnp.asarray(lengths), jnp.asarray([3, 0, 2, 1]))
        got, exits = [], []
        for k in range(steps):
            state, logits, ex = model.decode(state)
            got.append(np.asarray(logits)[[3, 0, 2, 1]])
            exits.append(np.asarray(ex)[[3, 0, 2, 1]])
            nxt = np.append(seqs[[1, 3, 2, 0], lengths[[1, 3, 2, 0]] + k], 0)  # the next token of each slot's own sequence; slot 4 idles
            state = state.replace(last=jnp.asarray(nxt))
        assert np.asarray(state.pos).tolist() == [lengths[1] - 1 + steps, lengths[3] - 1 + steps, lengths[2] - 1 + steps, lengths[0] - 1 + steps, steps]
        for j in range(4):
            want, want_exit = ref.followed_logits(params, seqs[j, : DIMS.p_max + steps], int(lengths[j]), steps, c)
            np.testing.assert_allclose(np.stack(got)[:, j], np.asarray(want), rtol=1e-4, atol=1e-4)
            assert (np.stack(exits)[:, j] == np.asarray(want_exit)).all()
    assert len(state.cache_k) == loops * c["num_hidden_layers"]  # one cache a (loop, layer)


def test_one_loop_is_a_plain_causal_transformer():
    c = config(total_ut_steps=1)
    _, model, params = program(c)
    ids = np.random.default_rng(0).integers(0, V, 12).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        hidden, exits, _ = model.hidden(jnp.asarray(ids)[None], jnp.float32)
        got = model.logits(hidden)[0]
        # written out: embedding, the layers under a causal mask, the final norm, the head; no gate is read
        x = params["embed"][ids]
        mask = jnp.asarray(np.tril(np.ones((12, 12), bool)))
        for i in range(c["num_hidden_layers"]):
            x, _ = ref.layer(params, f"layers.{i}.", x, mask, c, None)
        want = ref.rms_norm(x, params["final_norm"], c["rms_norm_eps"]) @ params["lm_head"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert (np.asarray(exits) == 0).all()


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_the_exit_rule_picks_what_the_reference_picks(threshold):
    c = config(early_exit_threshold=threshold)
    _, model, params = program(c)
    ids = np.random.default_rng(1).integers(0, V, 20).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        hidden, exits, _ = model.hidden(jnp.asarray(ids)[None], jnp.float32)
        want, want_exits = ref.forward(params, jnp.asarray(ids), c)
        np.testing.assert_allclose(np.asarray(model.logits(hidden)[0]), np.asarray(want), rtol=1e-4, atol=1e-4)
    assert (np.asarray(exits)[0] == np.asarray(want_exits)).all()
    if threshold == 1.0:
        assert (np.asarray(exits) == c["total_ut_steps"] - 1).all()  # at 1 the last loop, barring rounding
    else:
        assert (np.asarray(exits) < c["total_ut_steps"] - 1).any()  # an earlier exit, per token


def test_the_exit_rule_by_hand():
    lams = jnp.asarray([[0.6, 0.2, 0.1], [0.5, 0.5, 0.1], [0.9, 0.9, 0.1], [0.5, 0.5, 0.5]])  # [R, tokens]
    # cumulative exit probability: token 0 0.6 at once; token 1 0.2, then 0.2 + 0.5 * 0.8 = 0.6; token 2 0.1, 0.19, 0.271, and the last loop takes the rest
    assert exit_loop(lams, 0.5).tolist() == [0, 1, 3]
    assert exit_loop(lams, 1.0).tolist() == [3, 3, 3]
    assert (exit_loop(lams, 0.5) == ref.exit_loops(lams, 0.5)).all() and (exit_loop(lams, 0.99) == ref.exit_loops(lams, 0.99)).all()


def sequences(rng, n):
    out = []
    for _ in range(n):
        p, r = int(rng.integers(2, DIMS.p_max + 1)), int(rng.integers(1, DIMS.r_max + 1))
        out.append({"prompt": rng.integers(0, V, p).astype(np.int32), "ids": rng.integers(0, V, r).astype(np.int32),
                    "logprob": rng.normal(-4.0, 0.3, r).astype(np.float32), "advantage": float(rng.normal())})
    return out


def test_the_train_steps_loss_and_gradients_match_the_reference():
    c = config()
    args, model, params = program(c)
    batch = {k: jnp.asarray(v) for k, v in build_batch(sequences(np.random.default_rng(2), 3), 4, DIMS).items()}
    optimizer = ppo_lm.make_optimizer(args)
    from sheeprl_tpu.algos.ppo_bd.ppo_bd import TrainState
    from sheeprl_tpu.algos.ppo_lm.agent import player_copy

    state = TrainState(model=model, opt_state=optimizer.init(model))
    with jax.default_matmul_precision("highest"):
        new_state, _, metrics = ppo_lm.make_train_step(args, optimizer)(state, player_copy(model, jnp.float32), batch)
        (loss, _), grads = jax.value_and_grad(lambda p: ref.batch_loss(p, batch, c), has_aux=True)(params)
        want = ref.adam(ref.init_state(params), grads, c)
    assert float(metrics["Loss/policy_loss"]) == pytest.approx(float(loss), rel=1e-4, abs=1e-6)
    mu = ouro_names.to_reference(next(x for x in jax.tree_util.tree_leaves(new_state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")).mu)
    after = ouro_names.to_reference(new_state.model)
    for name in params:
        np.testing.assert_allclose(np.asarray(mu[name]), np.asarray(want["mu"][name]), rtol=2e-3, atol=1e-7, err_msg=name)
        np.testing.assert_allclose(np.asarray(after[name]), np.asarray(want["params"][name]), rtol=1e-5, atol=2e-6, err_msg=name)
    assert float(jnp.abs(grads["gate_w"]).max()) == 0.0 and float(jnp.abs(grads["layers.0.wq"]).max()) > 0  # no gradient reaches the gate through the pick


def test_the_record_and_the_layout_of_one_sequence():
    record = Record(num_envs=4, group_size=4, dims=DIMS)
    prompt = np.arange(1, 6, dtype=np.int32)
    for i in range(4):
        record.start(i, np.pad(prompt, (0, DIMS.p_max - 5)), 5)
    for t in range(3):
        record.add(np.array([10 + t] * 4, np.int32), np.full(4, -1.5 - t, np.float32))
    for i, reward in enumerate([0.0, 0.5, 0.5, 1.0]):
        record.finish(i, reward)
    assert len(record.ready) == 4 and sum(s["advantage"] for s in record.ready) == pytest.approx(0.0, abs=1e-5)
    batch = build_batch(record.ready[:1], 2, DIMS)
    assert batch["ids"][0].tolist() == [1, 2, 3, 4, 5, 10, 11, 12] + [0] * 16
    assert batch["loss_pos"][0, :3].tolist() == [4, 5, 6] and batch["targets"][0, :3].tolist() == [10, 11, 12]  # read a position earlier
    assert batch["loss_mask"][0].tolist() == [1.0] * 3 + [0.0] * 5 and batch["logprob_old"][0, :3].tolist() == [-1.5, -2.5, -3.5]
    assert batch["loss_mask"][1].sum() == 0 and (batch["ids"][1] == 0).all()  # a row past the sequences is padding


def test_a_dry_run_through_the_cli_trains_once_and_leaves_a_checkpoint_and_spans(tmp_path):
    assert "ppo_lm" in tasks
    cli_run(["ppo_lm", "--dry_run", "--num_envs=4", "--sync_env", f"--root_dir={tmp_path}", "--run_name=test", "--num_hidden_layers=1",
             "--hidden_size=32", "--head_dim=8", "--intermediate_size=64", "--total_ut_steps=3"])
    run = os.path.join(tmp_path, "test")
    assert any(e.startswith("ckpt_") for e in os.listdir(os.path.join(run, "checkpoints")))
    with open(os.path.join(run, "telemetry.jsonl")) as f:
        spans = [e for e in map(json.loads, filter(str.strip, f)) if e.get("event") == "span"]
    names = {s["name"] for s in spans}
    assert {"iteration", "rollout/decode_dispatch", "rollout/action_wait", "rollout/pack", "rollout/env_step", "rollout/prefill",
            "train/build_batch", "train/dispatch", "update", "log/pull", "log/write"} <= names
    update = next(s for s in spans if s["name"] == "update")
    assert update["sequences_trained"] == 4 and update["pad_positions"] < update["positions"] and len(update["lengths"]) == 1
    packs = [s for s in spans if s["name"] == "rollout/pack"]
    assert all(s["tokens_committed"] == 4 and s["loop_applications"] == 4 * 3 and s["exit_loop"] == [0, 0, 4] for s in packs)
    assert sum(s.get("cache_slots_reset", 0) for s in spans if s["name"] == "rollout/prefill") >= 4


def test_the_main_refuses_what_it_cannot_run(tmp_path):
    with pytest.raises(ValueError, match="R-a1"):
        ppo_lm.main(["--dry_run", "--num_devices=2", f"--root_dir={tmp_path}", "--run_name=a"])
    with pytest.raises(ValueError, match="group_size"):
        ppo_lm.main(["--dry_run", "--num_envs=6", f"--root_dir={tmp_path}", "--run_name=b"])
    with pytest.raises(ValueError, match="ppo_lm gives one"):
        ppo_lm.main(["--dry_run", "--num_envs=4", "--sync_env", "--env_id=TokenTask-v0", f"--root_dir={tmp_path}", "--run_name=c"])


# ---- the cache read at each row's own position: the `decode_attn` kernel (interpret mode) against the XLA twin
WIDE = dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=2, head_dim=128)  # inside the kernel's contract
LONG = Dims(p_max=2 * pk.DECODE_BLOCK - 8, r_max=8)  # 2 blocks of cache slots


@pytest.fixture
def kernel_interpret():
    pk.set_pallas(True, interpret=True)
    yield
    pk.set_pallas(None, interpret=False)


def test_prefill_and_decoding_read_through_the_kernel_equal_the_full_forward(kernel_interpret):
    c = config(**WIDE)
    _, model, params = program(c)
    rng = np.random.default_rng(7)
    lengths, steps = np.array([3, LONG.p_max, pk.DECODE_BLOCK, pk.DECODE_BLOCK + 1]), 3  # positions either side of a block's end
    seqs = rng.integers(0, V, (4, LONG.s_max)).astype(np.int32)
    state = model.init_states(4, LONG.s_max, jnp.float32)
    with jax.default_matmul_precision("highest"):
        state = model.prefill(state, jnp.asarray(seqs[:, : LONG.p_max]), jnp.asarray(lengths), jnp.arange(4))
        got = []
        for k in range(steps):
            state, logits, _ = model.decode(state)
            got.append(np.asarray(logits))
            state = state.replace(last=jnp.asarray(seqs[np.arange(4), lengths + k]))
        for j in range(4):
            want, _ = ref.followed_logits(params, seqs[j, : LONG.p_max + steps], int(lengths[j]), steps, c)
            np.testing.assert_allclose(np.stack(got)[:, j], np.asarray(want), rtol=1e-4, atol=1e-4)


def test_decoding_through_the_kernel_samples_what_the_twin_samples(kernel_interpret):
    """Several policy steps at one key, a group's prefill between them: the
    kernel and the twin sample the same tokens, give the same logits and leave
    the same caches."""
    c = config(**WIDE)
    args, model, _ = program(c)
    rng = np.random.default_rng(11)
    prompts = rng.integers(0, V, (2, 4, LONG.p_max)).astype(np.int32)
    lengths = np.array([[5, LONG.p_max, 130, 127], [200, 9, 128, 64]])

    def rollout(kernel: bool):
        pk.set_pallas(kernel, interpret=kernel)
        step = ppo_lm.make_policy_step(args)
        state = model.init_states(4, LONG.s_max, jnp.float32)
        state = model.prefill(state, jnp.asarray(prompts[0]), jnp.asarray(lengths[0]), jnp.arange(4))
        key, out = jax.random.PRNGKey(3), []
        for t in range(5):
            if t == 3:  # the group of slots 2 and 3 ends and takes new prompts
                state = model.prefill(state, jnp.asarray(prompts[1, 2:]), jnp.asarray(lengths[1, 2:]), jnp.asarray([2, 3]))
            key, k = jax.random.split(key)
            state, packed, logits = step(model, state, k)
            out.append((np.asarray(packed[:, 0]), np.asarray(logits)))
        return out, state

    with jax.default_matmul_precision("highest"):
        twin, twin_state = rollout(False)
        kern, kern_state = rollout(True)
    for (ids_t, logits_t), (ids_k, logits_k) in zip(twin, kern):
        assert (ids_t == ids_k).all()
        np.testing.assert_allclose(logits_k, logits_t, rtol=1e-4, atol=1e-4)
    assert np.asarray(kern_state.pos).tolist() == np.asarray(twin_state.pos).tolist() == [lengths[0, 0] + 4, lengths[0, 1] + 4, lengths[1, 2] + 1, lengths[1, 3] + 1]
    for a, b in zip(kern_state.cache_k + kern_state.cache_v, twin_state.cache_k + twin_state.cache_v):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "twin"])
def test_the_policy_steps_count_the_cache_slots_they_read(tmp_path, monkeypatch, kernel):
    """A dry run, four groups on prompts of 8 to 248 ids (caches of 2 blocks): every
    `rollout/pack` counts the slots read, sum of ceil((pos + 1) / block) x block
    over the rows at the positions the step was fed, against all it holds; the
    twin reads every slot. The decision is one `kernel.select` event."""
    import gymnasium as gym

    env_id = "TokenTaskARLong-v0"
    if env_id not in gym.registry:
        gym.register(env_id, entry_point=TokenTask, kwargs={"block_length": 1, "max_prompt": LONG.p_max, "max_response": LONG.r_max})
    fed, real = [], ppo_lm.make_policy_step

    def recording(*a, **kw):
        step = real(*a, **kw)

        def call(player, state, key):
            fed.append(np.asarray(state.pos))
            return step(player, state, key)

        return call

    monkeypatch.setattr(ppo_lm, "make_policy_step", recording)
    pk.set_pallas(kernel, interpret=kernel)
    try:
        cli_run(["ppo_lm", "--dry_run", "--num_envs=8", "--group_size=2", "--sync_env", f"--env_id={env_id}", f"--root_dir={tmp_path}", "--run_name=test",
                 "--num_hidden_layers=1", "--hidden_size=32", "--num_attention_heads=1", "--num_key_value_heads=1", "--head_dim=128",
                 "--intermediate_size=64", "--total_ut_steps=2"])
    finally:
        pk.set_pallas(None, interpret=False)
    with open(os.path.join(tmp_path, "test", "telemetry.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    packs = [e for e in events if e.get("event") == "span" and e["name"] == "rollout/pack"]
    block = pk.DECODE_BLOCK if kernel else LONG.s_max
    assert len(packs) >= LONG.r_max
    # the call each pack read: one step is queued ahead of every read, and a dropped one is dispatched again
    drops = {e["step"] for e in events if e.get("event") == "span" and e["name"] == "rollout/ahead_drop"}
    packed_calls, calls = [], 0
    for span in packs:
        packed_calls.append(calls)
        calls += 1 + (span["step"] in drops)
    assert len(fed) == calls + 1  # the last step queued is never read
    for span, pos in zip(packs, [fed[i] for i in packed_calls]):
        assert span["attn_slots_held"] == 8 * LONG.s_max
        assert span["attn_slots_read"] == int(np.minimum(-(-(pos + 1) // block) * block, LONG.s_max).sum())
    if kernel:
        assert max(s["attn_slots_read"] for s in packs) < 8 * LONG.s_max  # some row's cache read short of its end
    picks = [(e["selected"], e["reason"]) for e in events if e.get("event") == "kernel.select" and e["family"] == "decode_attn"]
    assert picks == [(True, "ok") if kernel else (False, "disabled")]


# ---- one policy step queued ahead of the host's read: dropped, rolled back and run again where the host changes what it read
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "twin"])
def test_a_step_rolled_back_and_run_again_equals_one_step(kernel_interpret, kernel):
    """A step, a second one dropped (rolled back a position), two rows given
    new prompts (one of them fed where its last block holds the slot past the
    prompts' that the dropped step wrote), and the second step run again at
    its key: the packed action, the logits, the positions and every (loop,
    layer) cache's slots up to each row's position are those of the plain
    order, bit for bit."""
    c = config(**WIDE)
    args, model, _ = program(c)
    pk.set_pallas(kernel, interpret=kernel)
    step = ppo_lm.make_policy_step(args)
    rollback = ppo_lm.make_rollback()
    prefill = jax.jit(lambda s, prompts, lengths, idx: model.prefill(s, prompts, lengths, idx))
    rng = np.random.default_rng(13)
    prompts = rng.integers(0, V, (2, 4, LONG.p_max)).astype(np.int32)
    # row 1's dropped step writes slot 248, which no prefill writes; it restarts fed at 199, in the same block
    lengths = np.array([[5, LONG.p_max, 130, 127], [0, 200, 129, 0]])
    state = prefill(model.init_states(4, LONG.s_max, jnp.float32), jnp.asarray(prompts[0]), jnp.asarray(lengths[0]), jnp.arange(4))
    state, first, _ = step(model, state, jax.random.PRNGKey(1))
    copy = lambda s: jax.tree_util.tree_map(jnp.copy, s)
    renew = lambda s: prefill(s, jnp.asarray(prompts[1, 1:3]), jnp.asarray(lengths[1, 1:3]), jnp.asarray([1, 2]))

    plain, packed, logits = step(model, renew(copy(state)), jax.random.PRNGKey(2))
    ahead, _, _ = step(model, copy(state), jax.random.PRNGKey(2))
    pos, last = rollback(ahead.pos, first)
    again, packed_again, logits_again = step(model, renew(ahead.replace(pos=pos, last=last)), jax.random.PRNGKey(2))

    assert np.array_equal(np.asarray(packed_again), np.asarray(packed)) and np.array_equal(np.asarray(logits_again), np.asarray(logits))
    pos = np.asarray(plain.pos)
    assert pos.tolist() == np.asarray(again.pos).tolist() == [6, 200, 129, 128]
    assert np.array_equal(np.asarray(again.last), np.asarray(plain.last))
    for a, b in zip(again.cache_k + again.cache_v, plain.cache_k + plain.cache_v):
        a, b = np.asarray(a), np.asarray(b)
        for row, p in enumerate(pos):
            assert np.array_equal(a[row, :p], b[row, :p])
        assert np.array_equal(a[[0, 2, 3]], b[[0, 2, 3]])  # the rows that went on: the slot written again, all equal
        assert not np.array_equal(a[1, LONG.p_max], b[1, LONG.p_max])  # the restarted row keeps the dropped step's slot past its position


class ScheduledTask(TokenTask):
    """The one-id-a-step token task with the response lengths given by the
    seed a group shares, episode after episode."""

    def __init__(self, lengths: dict, **kw):
        super().__init__(block_length=1, max_prompt=16, min_prompt=4, max_response=8, min_response=1, **kw)
        self.lengths, self.schedule = lengths, iter(())

    def reset(self, *, seed=None, options=None):
        if seed is not None:
            self.schedule = iter(self.lengths[seed])
        return super().reset(seed=seed, options=options)

    def pose(self):
        prompt, _ = super().pose()
        return prompt, next(self.schedule)


def test_the_loop_one_step_ahead_trains_what_the_plain_order_trains(tmp_path, monkeypatch):
    """Ten iterations through the CLI, 4 groups of 2 whose first episodes end
    at steps 3, 5, 5 and 5 (three groups on one step) and two of whose second
    ones end together at step 8, an update per 4 finished sequences in 2 train
    steps: a group end with no update (step 3), updates with a group end and
    one with none (step 6). Every trained batch (`ids`, `targets`,
    `loss_pos`, `logprob_old` as bits) and the weights after every train step
    equal those of the plain order (step t dispatched after the host's turn
    at t - 1), written here from the module's own programs on the same
    weights and keys. Each packed step was queued ahead and kept, or
    dispatched again after a drop."""
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo_bd.ppo_bd import TrainState
    from sheeprl_tpu.algos.ppo_lm.agent import player_copy
    from sheeprl_tpu.envs import make_vector_env
    from sheeprl_tpu.ops import precision
    from sheeprl_tpu.utils.env import make_dict_env

    seed, envs_n, group, iterations = 7, 8, 2, 10
    lengths = {seed: [3, 4, 8], seed + 1: [5, 3, 8], seed + 2: [5, 3, 8], seed + 3: [5, 5, 8]}  # by group, episode after episode
    env_id = "TokenTaskARScheduled-v0"
    if env_id in gym.registry:
        del gym.registry[env_id]
    gym.register(env_id, entry_point=ScheduledTask, kwargs={"lengths": lengths})
    flags = dict(num_envs=envs_n, group_size=group, update_sequences=4, per_rank_batch_size=2, num_hidden_layers=1, hidden_size=32, head_dim=8,
                 intermediate_size=64, total_ut_steps=2, seed=seed, total_steps=envs_n * iterations)

    def trained(record):
        return lambda batch, new: record.append(({k: np.asarray(batch[k]) for k in ("ids", "targets", "loss_pos", "logprob_old")},
                                                 [np.asarray(x) for x in jax.tree_util.tree_leaves(new.model)]))

    got, calls, real_train, real_policy = [], [0], ppo_lm.make_train_step, ppo_lm.make_policy_step

    def recording_train(*a, **kw):
        step, keep = real_train(*a, **kw), trained(got)

        def call(state, player, batch):
            out = step(state, player, batch)
            keep(batch, out[0])
            return out

        return call

    def counting_policy(*a, **kw):
        step = real_policy(*a, **kw)

        def call(*args):
            calls[0] += 1
            return step(*args)

        return call

    monkeypatch.setattr(ppo_lm, "make_train_step", recording_train)
    monkeypatch.setattr(ppo_lm, "make_policy_step", counting_policy)
    try:
        cli_run(["ppo_lm", "--sync_env", f"--env_id={env_id}", f"--root_dir={tmp_path}", "--run_name=test", *(f"--{k}={v}" for k, v in flags.items())])
    finally:
        monkeypatch.undo()

    # the plain order: dispatch step t, read it, step the environments, prefill, update; then step t + 1
    args = PPOLMArgs(env_id=env_id, sync_env=True, **flags)
    envs = make_vector_env([make_dict_env(env_id, seed + i // group, rank=0, args=args, run_name=str(tmp_path), vector_env_idx=i) for i in range(envs_n)])
    space = envs.single_observation_space
    dims = Dims(p_max=space["prompt"].shape[0], r_max=int(space["response_len"].high.max()))
    key = jax.random.PRNGKey(seed)
    key, model_key = jax.random.split(key)
    model = ppo_lm.build_models(model_key, args, int(space["prompt"].high.max()) + 1)
    optimizer = ppo_lm.make_optimizer(args)
    state = TrainState(model=model, opt_state=optimizer.init(model))
    compute = precision.compute_dtype(args.precision)
    player = jax.jit(lambda m: player_copy(m, compute))(state.model)
    pstate = player.init_states(envs_n, dims.s_max, compute)
    policy_step, train_step = ppo_lm.make_policy_step(args), ppo_lm.make_train_step(args, optimizer)
    prefill_step = jax.jit(lambda p, s, prompts, n, idx: p.prefill(s, prompts, n, idx), donate_argnums=(1,))
    record, want = Record(envs_n, group, dims), []
    keep = trained(want)

    def prefill(pstate, obs, which):
        for lo in range(0, len(which), group):
            idx = np.full(group, envs_n, np.int32)
            idx[: len(which[lo : lo + group])] = which[lo : lo + group]
            rows = np.minimum(idx, envs_n - 1)
            pstate = prefill_step(player, pstate, jnp.asarray(obs["prompt"][rows].astype(np.int32)), jnp.asarray(obs["prompt_len"][rows, 0].astype(np.int32)), jnp.asarray(idx))
        for i in which:
            record.start(int(i), obs["prompt"][i], int(obs["prompt_len"][i, 0]))
        return pstate

    obs, _ = envs.reset(seed=[seed + i // group for i in range(envs_n)])
    pstate = prefill(pstate, obs, np.arange(envs_n))
    for _ in range(iterations):
        key, step_key = jax.random.split(key)
        pstate, packed, _ = policy_step(player, pstate, step_key)
        packed = np.asarray(packed)
        record.add(packed[:, 0], np.ascontiguousarray(packed[:, 1]).view(np.float32))
        obs, rewards, terms, truncs, _ = envs.step(list(packed[:, 0][:, None]))
        which = np.flatnonzero(np.logical_or(terms, truncs))
        for i in which:
            record.finish(int(i), float(rewards[i]))
        if len(which):
            pstate = prefill(pstate, obs, which)
        if len(record.ready) >= args.update_sequences:
            seqs, record.ready = record.ready[: args.update_sequences], record.ready[args.update_sequences :]
            for lo in range(0, len(seqs), args.per_rank_batch_size):
                batch = {k: jnp.asarray(v) for k, v in build_batch(seqs[lo : lo + args.per_rank_batch_size], args.per_rank_batch_size, dims).items()}
                state, player, _ = train_step(state, player, batch)
                keep(batch, state)
    envs.close()

    assert len(got) == len(want) == 8  # updates at steps 5, 6 (no group ends), 8 and 10, two train steps each
    for (batch, weights), (want_batch, want_weights) in zip(got, want):
        for k in ("ids", "targets", "loss_pos"):
            assert np.array_equal(batch[k], want_batch[k]), k
        assert np.array_equal(batch["logprob_old"].view(np.int32), want_batch["logprob_old"].view(np.int32))
        assert all(np.array_equal(a.view(np.uint32), b.view(np.uint32)) for a, b in zip(weights, want_weights))

    with open(os.path.join(tmp_path, "test", "telemetry.jsonl")) as f:
        spans = [e for e in map(json.loads, filter(str.strip, f)) if e.get("event") == "span"]
    packs = [s for s in spans if s["name"] == "rollout/pack"]
    drops = [s["step"] for s in spans if s["name"] == "rollout/ahead_drop"]
    prefilled = {s["step"]: s["cache_slots_reset"] for s in spans if s["name"] == "rollout/prefill"}
    assert len(packs) == iterations and drops == [3, 5, 6, 7, 8, 10] and prefilled == {3: 2, 5: 6, 7: 2, 8: 4, 10: 2}
    assert len(packs) == sum(s["ahead_kept"] for s in packs) + len([d for d in drops if d < iterations])  # kept, or dispatched again
    assert calls[0] == 1 + len(packs) + len(drops)  # one queued before the loop, one ahead an iteration, one again a drop
    assert all(s["ahead_dropped"] == 1 for s in spans if s["name"] == "rollout/ahead_drop")
