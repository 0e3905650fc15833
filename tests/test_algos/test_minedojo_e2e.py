"""DreamerV3 + MinedojoActor end-to-end on the mocked MineDojo backend:
drives the full pipeline — make_dict_env minedojo dispatch, the wrapper's
3-head MultiDiscrete actions and mask_* obs, the masked actor at play time —
through one real training update."""

import os

import pytest

import sheeprl_tpu.algos  # noqa: F401 - fire registrations
import sheeprl_tpu.envs.minedojo as minedojo_mod
from sheeprl_tpu.envs.minedojo_mock import FakeMineDojoBackend
from sheeprl_tpu.utils.registry import tasks


@pytest.mark.timeout(600)
def test_dreamer_v3_minedojo_mocked(tmp_path, monkeypatch):
    monkeypatch.setattr(minedojo_mod, "MineDojoBackend", FakeMineDojoBackend)
    tasks["dreamer_v3"]([
        "--dry_run",
        "--num_devices=1",
        "--env_id=minedojo_harvest_milk",
        "--num_envs=1",
        "--sync_env",
        "--per_rank_batch_size=1",
        "--per_rank_sequence_length=1",
        "--buffer_size=8",
        "--learning_starts=0",
        "--gradient_steps=1",
        "--horizon=4",
        "--dense_units=8",
        "--cnn_channels_multiplier=2",
        "--recurrent_state_size=8",
        "--hidden_size=8",
        "--stochastic_size=4",
        "--discrete_size=4",
        "--mlp_layers=1",
        "--train_every=1",
        "--checkpoint_every=1",
        f"--root_dir={tmp_path}",
        "--run_name=minedojo",
        "--cnn_keys", "rgb",
        "--mlp_keys",
        "inventory", "equipment", "life_stats",
        "mask_action_type", "mask_equip/place", "mask_destroy",
        "mask_craft_smelt",
    ])
    ckpt_dir = tmp_path / "minedojo" / "checkpoints"
    assert any(e.startswith("ckpt_") for e in os.listdir(ckpt_dir))
