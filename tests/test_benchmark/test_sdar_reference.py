"""The program's block-diffusion MoE policy against the plain reference
(`benchmark/reference/sdar_moe.py`) at a tiny preset on seeded weights: the
block under the update's layout, the cached denoising path, one train step's
loss and gradients, and the test that ties the expert share to the model."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import sdar_names
from benchmark.reference import sdar_moe as ref
from sheeprl_tpu.algos.ppo_bd import ppo_bd
from sheeprl_tpu.algos.ppo_bd.agent import layout_attend, layout_mask, mask_attend
from sheeprl_tpu.algos.ppo_bd.args import PPOBDArgs
from sheeprl_tpu.algos.ppo_bd.layout import Dims, Record, build_batch
from sheeprl_tpu.nn.moe import RoutedExperts

TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, norm_topk_prob=True, num_hidden_layers=2,
    first_expert=0, experts_held=8, vocab_size=64, block_length=4, denoise_steps=2,
)
TRAIN = dict(clip_coef=0.2, lr=1e-3, max_grad_norm=1.0, adam_eps=1e-8)
DIMS = Dims(p_max=16, r_max=8, block_length=4, denoise_steps=2, mask_id=63)
SEED = 2147483949  # more than 32 signed bits hold


def config(**changes):
    return {**TINY, **TRAIN, "mask_token_id": TINY["vocab_size"] - 1, **changes}


def program(c, params=None):
    args = PPOBDArgs(**{k: c[k] for k in TINY if k != "vocab_size"}, lr=c["lr"], clip_coef=c["clip_coef"], max_grad_norm=c["max_grad_norm"], eps=c["adam_eps"])
    model = ppo_bd.build_models(jax.random.PRNGKey(0), args, c["vocab_size"])
    params = ref.make_params(SEED, c) if params is None else params
    # a copy: the train step donates its state, and the reference keeps its weights
    return args, jax.tree_util.tree_map(jnp.copy, sdar_names.from_reference(model, params.__getitem__)), params


def sequences(rng, n, c):
    """n finished sequences as the main's record closes them, with made-up log-probabilities and advantages."""
    out = []
    for _ in range(n):
        p, r = 4 * int(rng.integers(1, DIMS.p_max // 4 + 1)), 4 * int(rng.integers(1, DIMS.r_max // 4 + 1))
        step = np.concatenate([rng.permutation([1, 1, 2, 2]) for _ in range(r // 4)])
        out.append({
            "prompt": rng.integers(0, c["vocab_size"] - 1, p).astype(np.int32), "ids": rng.integers(0, c["vocab_size"] - 1, r).astype(np.int32),
            "step": step.astype(np.int32), "logprob": rng.normal(-4.0, 0.3, r).astype(np.float32), "advantage": float(rng.normal()),
        })
    return out


@pytest.mark.parametrize("how", ["mask", "layout"])  # the mask in full, and the same computed the short way the update takes
def test_the_layout_block_matches_the_reference(how):
    c = config()
    args, model, params = program(c)
    batch = build_batch(sequences(np.random.default_rng(0), 3, c), 4, DIMS)
    copy, block = jnp.asarray(batch["copy"]), jnp.asarray(batch["block"])
    attend = {"mask": mask_attend(lambda b: layout_mask(copy[b], block[b])), "layout": layout_attend(copy, block, DIMS.s_max, 4)}[how]
    hidden, counts, _ = model.layout_hidden(jnp.asarray(batch["ids"]), jnp.asarray(batch["positions"]), copy >= 0, attend, jnp.float32)
    logits = np.asarray(model.logits(hidden))
    assert int(counts.sum()) == c["num_hidden_layers"] * int((batch["copy"] >= 0).sum()) * c["num_experts_per_tok"]  # every expert held: no token dropped, no padding routed
    with jax.default_matmul_precision("highest"):
        for b in range(3):
            seq = {k: jnp.asarray(v[b]) for k, v in batch.items()}
            want = np.asarray(ref.forward(params, seq["ids"], seq["positions"], ref.layout_mask(seq["copy"], seq["block"]), c))
            live = batch["copy"][b] >= 0
            keep = np.arange(c["vocab_size"]) != c["mask_token_id"]
            np.testing.assert_allclose(logits[b][live][:, keep], want[live][:, keep], rtol=2e-4, atol=2e-4)
    assert np.isneginf(logits[..., c["mask_token_id"]]).all()  # the mask token is never a choice


def test_prefill_and_cached_denoising_steps_match_the_reference_forward_pass():
    c = config()
    args, model, params = program(c)
    rng = np.random.default_rng(1)
    lengths = np.array([8, 16, 4], np.int32)
    prompts = np.zeros((3, DIMS.p_max), np.int32)
    for i, n in enumerate(lengths):
        prompts[i, :n] = rng.integers(0, 63, n)
    state = model.init_states(3, DIMS.s_max, jnp.float32)
    state = model.prefill(state, jnp.asarray(prompts), jnp.asarray(lengths), jnp.arange(3))
    step = ppo_bd.make_policy_step(args)
    clean = [list(prompts[i, :n]) for i, n in enumerate(lengths)]
    key = jax.random.PRNGKey(3)
    for it in range(4):  # two blocks, two denoising steps each
        before = np.asarray(state.block_ids)
        key, k = jax.random.split(key)
        block_ids, packed, logits = step(model, state, k)
        with jax.default_matmul_precision("highest"):
            for i in range(3):
                ids = np.zeros(DIMS.s_max + 4, np.int32)
                n = len(clean[i]) + 4
                ids[:n] = clean[i] + list(before[i])
                want = np.asarray(ref.policy_logits(params, ids, n, c))
                got = np.asarray(logits[i])
                keep = np.arange(64) != 63
                np.testing.assert_allclose(got[:, keep], want[:, keep], rtol=2e-4, atol=2e-4)
        actions = np.asarray(packed)[:, :4]
        assert ((actions >= 0).sum(1) == 2).all()  # two of four a step, only where masked
        assert (before[actions >= 0] == 63).all()
        state = state.replace(block_ids=block_ids)
        if it % 2 == 1:
            for i in range(3):
                clean[i] += list(np.asarray(block_ids[i]))
            state = model.commit(state, jnp.ones(3))
            assert (np.asarray(state.block_ids) == 63).all()
    assert list(np.asarray(state.pos)) == [16, 24, 12]


def test_a_train_steps_loss_and_gradients_match_the_reference():
    c = config()
    args, model, params = program(c)
    batch = build_batch(sequences(np.random.default_rng(2), 4, c), 4, DIMS)
    optimizer = ppo_bd.make_optimizer(args)
    state = ppo_bd.TrainState(model=model, opt_state=optimizer.init(model))
    new_state, _, metrics, counts = ppo_bd.make_train_step(args, optimizer, DIMS.s_max)(state, jax.tree_util.tree_map(jnp.copy, model), {k: jnp.asarray(v) for k, v in batch.items()})
    seen = {}
    final, outs = ref.run_steps(params, [{k: jnp.asarray(v) for k, v in batch.items()}], c, on_step=lambda i, s, g: seen.update(g))
    np.testing.assert_allclose(float(metrics["Loss/policy_loss"]), float(outs[0]["loss"]), rtol=1e-4, atol=1e-5)
    mu = sdar_names.to_reference(new_state.opt_state[1].mu)  # Adam's first moment after one step: (1 - b1) x the clipped gradient
    norm = np.sqrt(sum(float(jnp.sum(g * g)) for g in seen.values()))
    clip = min(1.0, c["max_grad_norm"] / norm)
    for name, g in seen.items():
        np.testing.assert_allclose(np.asarray(mu[name]) / (1 - ref.ADAM_B1), clip * np.asarray(g), rtol=2e-3, atol=2e-6, err_msg=name)
    after = sdar_names.to_reference(new_state.model)
    for name in after:
        # Adam's first step moves every element by about lr: the two results lie within a twentieth of that, as norms
        gap = np.linalg.norm(np.asarray(after[name]) - np.asarray(final["params"][name]))
        assert gap < 0.05 * c["lr"] * np.sqrt(after[name].size), name


def test_logprob_old_recomputed_by_the_update_at_unchanged_weights_gives_ratio_one():
    """Generate through prefill, cache and commit; train on the record: the update's layout reads what the policy read."""
    c = config()
    args, model, params = program(c)
    rng = np.random.default_rng(4)
    n, lengths, r_len = 4, np.array([8, 12, 4, 16], np.int32), [8, 4, 8, 8]
    prompts = np.zeros((n, DIMS.p_max), np.int32)
    for i, m in enumerate(lengths):
        prompts[i, :m] = rng.integers(0, 63, m)
    state = model.prefill(model.init_states(n, DIMS.s_max, jnp.float32), jnp.asarray(prompts), jnp.asarray(lengths), jnp.arange(n))
    record = Record(n, 4, DIMS)
    for i in range(n):
        record.start(i, prompts[i], int(lengths[i]))
    step, key, open_ = ppo_bd.make_policy_step(args), jax.random.PRNGKey(5), set(range(n))
    for _ in range(4):
        key, k = jax.random.split(key)
        block_ids, packed, _ = step(model, state, k)
        packed = np.asarray(packed)
        actions = packed[:, :4].copy()
        for i in set(range(n)) - open_:
            actions[i] = -1
        clean = record.commit(actions, np.ascontiguousarray(packed[:, 4:]).view(np.float32))
        state = model.commit(state.replace(block_ids=block_ids), jnp.asarray(clean.astype(np.float32)))
        for i in list(open_):
            if record.written[i] >= r_len[i]:
                record.finish(i, float(rng.random()))
                open_.discard(i)
    assert len(record.ready) == 4 and abs(sum(s["advantage"] for s in record.ready)) < 1e-4
    batch = {k: jnp.asarray(v) for k, v in build_batch(record.ready, 4, DIMS).items()}
    optimizer = ppo_bd.make_optimizer(args)
    _, _, metrics, _ = ppo_bd.make_train_step(args, optimizer, DIMS.s_max)(ppo_bd.TrainState(model=jax.tree_util.tree_map(jnp.copy, model), opt_state=optimizer.init(model)), jax.tree_util.tree_map(jnp.copy, model), batch)
    assert float(metrics["Policy/ratio_gap"]) < 1e-4  # mean |ratio - 1| over the committed tokens
    with jax.default_matmul_precision("highest"):
        logp = np.asarray(jax.lax.map(lambda s: ref.sequence_logprobs(params, s, c), batch))
    live = np.asarray(batch["loss_mask"]) > 0
    np.testing.assert_allclose(logp[live], np.asarray(batch["logprob_old"])[live], rtol=1e-4, atol=1e-4)


def test_the_shares_partial_results_add_up_to_the_uncut_layer():
    """8 shares of one expert each: what they give adds up to the reference's whole expert layer."""
    c = config()
    params = ref.make_params(SEED, c)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(40, c["hidden_size"])).astype(np.float32))
    pre = "layers.0."
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.experts(params, pre, x, c, None)) - np.asarray(x)
        r = ref.rms_norm(x, params[pre + "mlp_norm"], c["rms_norm_eps"])
        total, tokens = np.zeros_like(whole), 0
        for first in range(8):
            share = RoutedExperts(
                router=params[pre + "router"], w_gate=params[pre + "w_gate"][first : first + 1], w_up=params[pre + "w_up"][first : first + 1],
                w_down=params[pre + "w_down"][first : first + 1], num_experts=8, top_k=2, first_expert=first,
            )
            y, counts = share(r)
            total += np.asarray(y)
            tokens += int(counts.sum())
            cut = {**c, "first_expert": first, "experts_held": 1}
            cut_params = {**params, **{pre + k: params[pre + k][first : first + 1] for k in ("w_gate", "w_up", "w_down")}}
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref.experts(cut_params, pre, x, cut, None)) - np.asarray(x), rtol=1e-4, atol=1e-5)
    assert tokens == 40 * 2  # every assignment is some share's
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tokens", [40, 20000])  # one chunk; the sorted list walked in chunks
def test_no_token_is_dropped_when_every_token_picks_one_expert(tokens):
    hidden, ff = 16, 8
    layer = RoutedExperts.init(jax.random.PRNGKey(0), hidden, ff, num_experts=4, top_k=2, first_expert=1, held=2)
    router = np.zeros((hidden, 4), np.float32)
    router[0] = [0.0, 9.0, 5.0, 1.0]  # every token: expert 1 first, expert 2 second
    layer = layer.replace(router=jnp.asarray(router))
    x = np.abs(np.random.default_rng(7).normal(size=(tokens, hidden))).astype(np.float32) + 0.5
    y, counts = jax.jit(lambda m, v: m(v))(layer, jnp.asarray(x))
    assert list(np.asarray(counts)) == [tokens, tokens]
    weights, picks = layer.route(jnp.asarray(x))
    assert (np.asarray(picks) == [1, 2]).all()
    expert = lambda e, v: (jax.nn.silu(v @ layer.w_gate[e]) * (v @ layer.w_up[e])) @ layer.w_down[e]
    with jax.default_matmul_precision("highest"):
        want = weights[:, :1] * expert(0, jnp.asarray(x)) + weights[:, 1:] * expert(1, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_the_planted_faults_and_the_control_change_the_references_logits():
    c = config()
    params = ref.make_params(SEED, c)
    ids = np.array(list(range(8)) + [63, 5, 63, 63] + [0] * 4, np.int32)
    with jax.default_matmul_precision("highest"):
        base = np.asarray(ref.policy_logits(params, ids, 12, c))
    for kw in ({"fault": "drop_expert"}, {"fault": "causal"}, {"quant": jnp.float8_e4m3fn}):
        other = np.asarray(ref.policy_logits(params, ids, 12, c, **kw))
        assert np.sqrt(np.mean((other - base) ** 2)) / np.sqrt(np.mean(base**2)) > 1e-2, kw


def test_the_references_picks_are_the_rows_a_left_out_expert_moves():
    """In one layer nothing carries a row's change to another row: the expert left out moves the rows that pick it, and those alone."""
    c = config(num_hidden_layers=1, experts_held=4)
    params = ref.make_params(SEED, c)
    ids = np.array(list(range(20, 32)) + [63, 5, 63, 63] + [0] * 4, np.int32)
    with jax.default_matmul_precision("highest"):
        base, picks = map(np.asarray, ref.policy_logits(params, ids, 16, c, with_picks=True))
        np.testing.assert_array_equal(base, np.asarray(ref.policy_logits(params, ids, 16, c)))
        _, all_picks = map(np.asarray, ref.forward(params, jnp.asarray(ids), jnp.arange(20), ref.block_mask(jnp.arange(20), c), c, with_picks=True))
        dropped = np.asarray(ref.forward(params, jnp.asarray(ids), jnp.arange(20), ref.block_mask(jnp.arange(20), c), c, fault="drop_expert"))
        whole = np.asarray(ref.forward(params, jnp.asarray(ids), jnp.arange(20), ref.block_mask(jnp.arange(20), c), c))
    assert picks.shape == (4, 4) and picks.dtype == bool and (picks == all_picks[12:16]).all()
    assert all_picks.sum(axis=1).max() <= c["num_experts_per_tok"] and 0 < all_picks[:, -1].sum() < 20
    moved = np.abs(dropped - whole).max(axis=1) > 1e-6
    np.testing.assert_array_equal(moved, all_picks[:, -1])


def test_make_leaf_is_the_same_alone_and_in_make_params():
    c = config()
    params = ref.make_params(SEED, c)
    for name in ("embed", "layers.1.w_down", "layers.0.q_norm"):
        np.testing.assert_array_equal(np.asarray(params[name]), np.asarray(ref.make_leaf(SEED, name, ref.param_spec(c)[name])))
    assert not np.array_equal(np.asarray(params["layers.0.wq"]), np.asarray(ref.make_params(SEED + 1, c)["layers.0.wq"]))


def test_padding_is_routed_nowhere():
    layer = RoutedExperts.init(jax.random.PRNGKey(0), 16, 8, num_experts=4, top_k=2, first_expert=0, held=4)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(24, 16)).astype(np.float32))
    valid = jnp.arange(24) % 3 != 0
    y, counts = layer(x, valid)
    whole, all_counts = layer(x)
    assert int(counts.sum()) == 16 * 2 and int(all_counts.sum()) == 24 * 2
    assert (np.asarray(y)[::3] == 0).all()
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)], np.asarray(whole)[np.asarray(valid)], rtol=1e-5, atol=1e-6)
