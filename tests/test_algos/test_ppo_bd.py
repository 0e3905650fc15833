"""`ppo_bd`, the block-diffusion policy-gradient main, through its own entry
points: the CLI's dry run, the token task, the host-side record and layout,
and the player's per-environment state."""

import json
import os

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.ppo_bd import ppo_bd
from sheeprl_tpu.algos.ppo_bd.agent import layout_mask
from sheeprl_tpu.algos.ppo_bd.args import PPOBDArgs
from sheeprl_tpu.algos.ppo_bd.layout import Dims, Record, build_batch
from sheeprl_tpu.cli import run as cli_run
from sheeprl_tpu.envs.token_task import ENV_ID, TokenTask
from sheeprl_tpu.utils.registry import tasks

DIMS = Dims(p_max=16, r_max=8, block_length=4, denoise_steps=2, mask_id=63)


def test_a_dry_run_through_the_cli_trains_once_and_leaves_a_checkpoint_and_spans(tmp_path):
    assert "ppo_bd" in tasks
    cli_run(["ppo_bd", "--dry_run", "--num_envs=4", "--sync_env", f"--root_dir={tmp_path}", "--run_name=test", "--num_hidden_layers=1", "--hidden_size=32", "--head_dim=8"])
    run = os.path.join(tmp_path, "test")
    assert any(e.startswith("ckpt_") for e in os.listdir(os.path.join(run, "checkpoints")))
    with open(os.path.join(run, "telemetry.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    spans = [e for e in events if e.get("event") == "span"]
    names = {s["name"] for s in spans}
    assert {"iteration", "rollout/denoise_dispatch", "rollout/action_wait", "rollout/pack", "rollout/env_step", "rollout/cache_commit",
            "rollout/prefill", "train/build_batch", "train/dispatch", "update", "log/pull", "log/write"} <= names
    update = next(s for s in spans if s["name"] == "update")
    assert update["sequences_trained"] == 4 and len(update["moe_assignments"]) == 1 and update["pad_positions"] < update["positions"]
    assert update["lengths"][0] and all(p % 4 == 0 and r % 4 == 0 for p, r in update["lengths"][0])
    packs = [s for s in spans if s["name"] == "rollout/pack"]
    assert all(s["tokens_committed"] == 4 * 2 for s in packs)  # two of four ids a step, every environment
    assert sum(s.get("cache_slots_reset", 0) for s in spans if s["name"] == "rollout/prefill") >= 4


def test_the_main_refuses_what_it_cannot_run(tmp_path):
    with pytest.raises(ValueError, match="one device"):
        ppo_bd.main(["--dry_run", "--num_devices=2", f"--root_dir={tmp_path}", "--run_name=a"])
    with pytest.raises(ValueError, match="group_size"):
        ppo_bd.main(["--dry_run", "--num_envs=6", f"--root_dir={tmp_path}", "--run_name=b"])


def test_the_token_task_rewards_the_classes_and_ends_with_its_response():
    env = gym.make(ENV_ID, vocab_size=32, max_prompt=16, max_response=8, classes=2)
    obs, _ = env.reset(seed=11)
    twin = TokenTask(vocab_size=32, max_prompt=16, max_response=8, classes=2)
    obs2, _ = twin.reset(seed=11)
    assert (obs["prompt"] == obs2["prompt"]).all() and obs["prompt_len"][0] % 4 == 0  # one seed, one prompt: how a group shares it
    n, r = int(obs["prompt_len"][0]), int(obs["response_len"][0])
    assert obs["prompt"][:n].max() < 31 and (obs["prompt"][n:] == 0).all()  # never the mask id; padded
    want = twin.targets(obs["prompt"][:n].astype(np.int32), r)
    total, done, steps = 0.0, False, 0
    while not done:
        block, cols = steps // 2, ([0, 3] if steps % 2 == 0 else [1, 2])
        action = np.full(4, -1, np.int32)
        for j in cols:
            action[j] = 4 + want[4 * block + j] if block else 5 - want[4 * block + j]  # the first block all wrong, the rest right
        _, reward, done, _, _ = env.step(action)
        total += reward
        steps += 1
    assert steps == r // 2 and total == pytest.approx((r - 4) / r)


def test_the_token_task_ends_at_an_end_of_sequence_id_where_it_has_one():
    env = TokenTask(vocab_size=32, max_prompt=8, max_response=16, min_response=16, eos_token_id=7)
    env.reset(seed=3)
    _, _, done, _, _ = env.step(np.array([7, -1, 2, -1]))
    assert not done  # the block is not whole yet
    _, reward, done, _, _ = env.step(np.array([-1, 2, -1, 2]))
    assert done and 0.0 <= reward <= 4 / 16


def test_the_record_and_the_layout_of_one_sequence():
    record = Record(num_envs=4, group_size=4, dims=DIMS)
    prompt = np.arange(1, 9, dtype=np.int32)
    for i in range(4):
        record.start(i, np.pad(prompt, (0, 8)), 8)
    steps = [np.array([[10, -1, 11, -1]] * 4), np.array([[-1, 12, -1, 13]] * 4), np.array([[-1, -1, 14, 15]] * 4), np.array([[16, 17, -1, -1]] * 4)]
    cleans = [record.commit(a, np.full((4, 4), -1.5, np.float32)) for a in steps]
    assert [c.tolist() for c in cleans] == [[False] * 4, [True] * 4, [False] * 4, [True] * 4]
    for i, reward in enumerate([0.0, 0.5, 0.5, 1.0]):
        record.finish(i, reward)
    assert len(record.ready) == 4 and sum(s["advantage"] for s in record.ready) == pytest.approx(0.0, abs=1e-5)
    assert record.ready[0]["advantage"] < 0 < record.ready[3]["advantage"]
    batch = build_batch(record.ready[:1], 2, DIMS)
    ids, copy, pos = batch["ids"][0], batch["copy"][0], batch["positions"][0]
    assert ids[:8].tolist() == prompt.tolist() and copy[:8].tolist() == [0] * 8 and copy[8:16].tolist() == [-1] * 8
    assert ids[16:24].tolist() == [10, 12, 11, 13, 16, 17, 14, 15] and pos[16:24].tolist() == list(range(8, 16))
    assert ids[24:32].tolist() == [63] * 8 and copy[24:32].tolist() == [1] * 8  # step 1 saw every block all mask
    assert ids[32:40].tolist() == [10, 63, 11, 63, 63, 63, 14, 15] and copy[32:40].tolist() == [2] * 8  # step 2: what step 1 committed
    assert pos[24:32].tolist() == pos[32:40].tolist() == list(range(8, 16))  # a copy stands at its token's position
    assert batch["loss_pos"][0].tolist() == [24, 33, 26, 35, 36, 37, 30, 31] and batch["loss_mask"][0].tolist() == [1.0] * 8
    assert (batch["copy"][1] == -1).all() and batch["loss_mask"][1].sum() == 0  # a row past the sequences is padding
    mask = np.asarray(layout_mask(jnp.asarray(copy), jnp.asarray(batch["block"][0])))
    assert mask[24, :8].all() and not mask[24, 16:24].any() and mask[24, 24:28].all() and not mask[24, 28:].any()  # copy 1, block 0: the prompt and its own block
    assert mask[36, 16:20].all() and not mask[36, 20:24].any() and mask[36, 36:40].all() and not mask[36, 24:36].any()  # copy 2, block 1: clean block 0 too
    assert mask[17, 16:20].all() and not mask[17, 20:].any() and not mask[8:16].any() and not mask[:, 8:16].any()  # clean: bidirectional in its block; padding nothing


def test_a_cache_reset_touches_only_the_finished_environments():
    args = PPOBDArgs(hidden_size=32, head_dim=8, num_hidden_layers=1)
    model = ppo_bd.build_models(jax.random.PRNGKey(0), args, 64)
    state = model.init_states(4, DIMS.s_max, jnp.float32)
    prompts = jnp.asarray(np.random.default_rng(0).integers(0, 63, (4, 16)).astype(np.int32))
    state = model.prefill(state, prompts, jnp.asarray([8, 16, 4, 12]), jnp.arange(4))
    state = state.replace(block_ids=jnp.asarray([[1, 63, 2, 63]] * 4))
    stacked = lambda caches: np.stack([np.asarray(c) for c in caches])  # a layer's cache is an array of its own
    before = state.replace(cache_k=stacked(state.cache_k), cache_v=stacked(state.cache_v), block_ids=np.asarray(state.block_ids))
    after = model.reset_states(state, jnp.asarray([0.0, 1.0, 0.0, 0.0]))
    assert np.asarray(after.pos).tolist() == [8, 0, 4, 12] and (np.asarray(after.block_ids)[1] == 63).all()
    assert (np.asarray(after.block_ids)[[0, 2, 3]] == before.block_ids[[0, 2, 3]]).all()
    again = model.prefill(after, prompts[:2] + 1, jnp.asarray([4, 8]), jnp.asarray([1, 4]))  # slot 4 is past the last: written nowhere
    assert np.asarray(again.pos).tolist() == [8, 4, 4, 12]
    for i in (0, 2, 3):
        assert (stacked(again.cache_k)[:, i] == before.cache_k[:, i]).all() and (stacked(again.cache_v)[:, i] == before.cache_v[:, i]).all()
    assert not (stacked(again.cache_k)[:, 1, :4] == before.cache_k[:, 1, :4]).all()
    committed = model.commit(again, jnp.asarray([1.0, 0.0, 0.0, 0.0]))
    assert np.asarray(committed.pos).tolist() == [12, 4, 4, 12] and (stacked(committed.cache_k)[:, 1:] == stacked(again.cache_k)[:, 1:]).all()
    assert not (stacked(committed.cache_k)[:, 0, 8:12] == stacked(again.cache_k)[:, 0, 8:12]).all()
