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
    assert len(packs) == len(fed) >= LONG.r_max
    for span, pos in zip(packs, fed):
        assert span["attn_slots_held"] == 8 * LONG.s_max
        assert span["attn_slots_read"] == int(np.minimum(-(-(pos + 1) // block) * block, LONG.s_max).sum())
    if kernel:
        assert max(s["attn_slots_read"] for s in packs) < 8 * LONG.s_max  # some row's cache read short of its end
    picks = [(e["selected"], e["reason"]) for e in events if e.get("event") == "kernel.select" and e["family"] == "decode_attn"]
    assert picks == [(True, "ok") if kernel else (False, "disabled")]
