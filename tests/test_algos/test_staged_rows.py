"""The gradient loops of the six Dreamer-family mains take their rows from
`stage_batch` (one compiled program a sampled block) and cut nothing
themselves: between two train steps no program is enqueued, and once every
block shape has been met no iteration compiles anything."""

import importlib
import inspect
import re

import pytest

from sheeprl_tpu.telemetry.compile_tracker import CompileTracker

MAINS = [
    "sheeprl_tpu.algos.dreamer_v1.dreamer_v1",
    "sheeprl_tpu.algos.dreamer_v2.dreamer_v2",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3_decoupled",
    "sheeprl_tpu.algos.p2e_dv1.p2e_dv1",
    "sheeprl_tpu.algos.p2e_dv2.p2e_dv2",
]

SMALL = [
    "--num_devices=1",
    "--num_envs=1",
    "--sync_env",
    "--env_id=discrete_dummy",
    "--cnn_keys", "rgb",
    "--per_rank_batch_size=2",
    "--per_rank_sequence_length=2",
    "--buffer_size=64",
    # the dummy environment's episodes are 5 steps: the first episode end
    # under the policy (step 5) re-compiles the player's step for the reset
    # state, before the third training (step 6) stages its block
    "--learning_starts=4",
    "--total_steps=16",
    "--pretrain_steps=1",
    "--gradient_steps=2",
    "--train_every=1",
    "--horizon=3",
    "--dense_units=8",
    "--cnn_channels_multiplier=2",
    "--recurrent_state_size=8",
    "--hidden_size=8",
    "--stochastic_size=4",
    "--discrete_size=4",
    "--mlp_layers=1",
    "--checkpoint_every=1000",
    "--run_name=test",
]


@pytest.mark.parametrize("module", MAINS, ids=[m.rsplit(".", 1)[1] for m in MAINS])
def test_no_main_cuts_the_staged_block_itself(module):
    src = inspect.getsource(importlib.import_module(module).main)
    assert src.count("stage_batch(") == 1
    assert "sample = staged[i]" in src
    # nothing else touches the rows (the eager cut, `v[i]` over
    # `staged.items()`, was a `slice` and a `squeeze` per key every train
    # step); the decoupled main moves them to the trainers' mesh first
    uses = re.findall(r"\bstaged(?! = stage_batch\()((?:\[|\.|,|\)| =).{0,9})", src)
    allowed = ("[i]", " = meshes.", ", axis=1)")
    assert all(u.startswith(allowed) for u in uses), uses


@pytest.mark.timeout(300)
@pytest.mark.parametrize(
    "module, settled",
    # DreamerV2's policy step compiles a second time at its second call, in
    # the iteration after the third training: its observation arrives with
    # another sharding than the first one had (the main's own, not staging's)
    [(MAINS[2], 2), (MAINS[1], 3)],
    ids=["dreamer_v3", "dreamer_v2"],
)
def test_no_iteration_compiles_after_both_block_shapes(tmp_path, monkeypatch, module, settled):
    """`--pretrain_steps 1 --gradient_steps 2`: the first training meets a
    `[1, T, B]` block and every later one a `[2, T, B]` block. From the third
    training on, whole iterations (policy step, environment, add, sample,
    stage, train steps, log) pass without a trace, a lowering or a compile."""
    mod = importlib.import_module(module)
    tracker = CompileTracker().attach()
    seen_at_stage: list[tuple[int, float]] = []
    blocks: list[int] = []
    stage = mod.stage_batch

    def counting_stage(local_data, **kw):
        totals = tracker.flush()
        seen_at_stage.append((totals["total_compiles"], totals["total_compile_seconds"]))
        rows = stage(local_data, **kw)
        blocks.append(len(rows))
        return rows

    monkeypatch.setattr(mod, "stage_batch", counting_stage)
    try:
        mod.main(SMALL + [f"--root_dir={tmp_path}"])
    finally:
        tracker.detach()
    assert blocks[:3] == [1, 2, 2] and len(blocks) >= 6, blocks
    assert seen_at_stage[0][0] > 0  # the tracker hears this program compile
    # from that training's stage to the last one's: no compile, and not a
    # second of tracing or lowering either
    assert seen_at_stage[settled:] == [seen_at_stage[settled]] * (len(blocks) - settled), seen_at_stage
