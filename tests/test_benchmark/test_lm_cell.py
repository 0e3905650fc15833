"""The cells this PR adds: `ouro_2p6b_pp12.rl_ar32` (its entries in the
manifest, its configuration against the catalog's published widths, its
traffic and schedule, its readers, the analytic counts, and a rehearsal of
its driver end to end on the CPU at a tiny preset with the control and the
three planted faults) and `dv3_s_bf16.ratio64_pipeline` (its traffic is
`ratio64_16env`'s with `--pipeline on`)."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_ouro, run as bench_run
from benchmark.envs.token_episodes import Traffic

from . import keyed
from .conftest import DATA, ROOT, load

CELL = "ouro_2p6b_pp12.rl_ar32"
PIPELINE = "dv3_s_bf16.ratio64_pipeline"
NEW_METRICS = ("lm_policy_step_ms", "lm_policy_roofline", "lm_train_step_ms", "lm_train_mfu", "lm_update_share")
PUBLISHED = dict(hidden_size=2048, num_attention_heads=16, num_key_value_heads=16, head_dim=128, intermediate_size=5632, vocab_size=49152,
                 total_ut_steps=4, early_exit_threshold=1, rope_theta=1000000, rms_norm_eps=1e-6, max_position_embeddings=65536,
                 tie_word_embeddings=False, model_type="ouro", hidden_act="silu")


def test_the_manifests_new_entries_resolve(any_manifest):
    manifest = any_manifest
    cell = bench_run.load_cell(manifest, CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["name"] == "ouro_2p6b_pp12" and traffic["name"] == "rl_ar_32env"
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED  # no width is cut
    assert config["reduced"] == ["num_hidden_layers"] and config["num_hidden_layers"] == 4 and config["published"] == {"num_hidden_layers": 48}
    assert len(config["layer_types"]) == 48 and config["deployment"].startswith("12 chips in a pipeline")
    assert traffic["num_envs"] == 32 and traffic["args"] == {"group_size": 4, "temperature": 1.0, "update_sequences": 16, "per_rank_batch_size": 8,
                                                             "clip_coef": 0.2, "lr": 1e-06}
    assert traffic["env"]["prompt_len"] == {"median": 192, "sigma": 0.6, "clip": [64, 512]}
    assert traffic["env"]["response_len"] == {"median": 256, "sigma": 0.6, "clip": [64, 512]} and traffic["env"]["zipf_exponent"] == 1.0
    mine = {m["name"]: m for m in bench_run.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_METRICS) <= set(mine)
    assert all(mine[name]["moves"] == "env_steps_per_s" and mine[name]["source"] in ("device_trace", "host_clock") for name in NEW_METRICS)
    keyed.check_spans(manifest, CELL)  # which other readers list the cell: keyed by its main, not by a list
    assert {m["name"] for m in bench_run.metrics_for(manifest, "end_to_end", CELL)} == {"env_steps_per_s", "setup_s"}
    assert all(w["chips"] == 1 for w in manifest["workloads"] if w["name"] in (CELL, PIPELINE))


def test_the_pipeline_cell_is_ratio64_with_the_pipeline_on(any_manifest):
    manifest = any_manifest
    cell = bench_run.load_cell(manifest, PIPELINE)
    base = load(os.path.join(ROOT, "benchmark", "traffic", "ratio64_16env.json"))
    assert cell["config"]["name"] == "dv3_s_pixel_bf16" and cell["chips"] == 1
    assert cell["traffic"]["argv"] == base["argv"] + ["--pipeline", "on"]
    assert {k: v for k, v in cell["traffic"].items() if k not in ("name", "argv")} == {k: v for k, v in base.items() if k not in ("name", "argv")}
    keyed.check_spans(manifest, PIPELINE)  # its readers: keyed by the main it runs (its rehearsal: test_run_cpu_spans.py)


def updates_of(traffic: dict, seed: int, steps: int = 20000) -> list[int]:
    """The steps at which an update starts: every group advances one token a step, ends together and re-poses at once."""
    load = Traffic({**traffic["env"], "vocab_size": 49152, "group_size": traffic["args"]["group_size"]}, traffic["num_envs"], 1, seed)
    groups = traffic["num_envs"] // traffic["args"]["group_size"]
    episode, left, ready, out = [0] * groups, [load.pose(g, 0)[1] for g in range(groups)], 0, []
    for step in range(1, steps + 1):
        for g in range(groups):
            left[g] -= 1
            if left[g] == 0:
                ready, episode[g] = ready + traffic["args"]["group_size"], episode[g] + 1
                left[g] = load.pose(g, episode[g])[1]
        if ready >= traffic["args"]["update_sequences"]:
            ready -= traffic["args"]["update_sequences"]
            out.append(step)
    return out


def test_the_schedule_is_the_same_for_every_seed_and_a_traced_stretch_holds_an_update(manifest):
    traffic = bench_run.load_cell(manifest, CELL)["traffic"]
    schedules = [updates_of(traffic, seed) for seed in (7, 2**31 + 12345)]
    assert schedules[0] == schedules[1]  # the seed permutes which group runs which lengths, not when updates come
    gaps = np.diff(schedules[0])
    assert gaps.max() < traffic["trace_iterations"]  # the window closes after an update: the next one falls inside the trace
    assert 130 <= gaps.mean() <= 150 and schedules[0][traffic["warmup_updates"] - 1] > traffic["warmup_iterations"]
    a, b = Traffic({**traffic["env"], "vocab_size": 49152, "group_size": 4}, 32, 1, 7), Traffic({**traffic["env"], "vocab_size": 49152, "group_size": 4}, 32, 1, 8)
    assert not np.array_equal(a.pose(0, 0)[0][:16], b.pose(0, 0)[0][:16])  # the ids are the seed's


def test_the_readers_return_none_on_a_run_without_their_programs():
    bare = {"events": [], "t_open": 0.0, "window_s": 1.0, "iterations": 3, "iteration_seconds": [0.3, 0.3, 0.4], "chips": 1,
            "peaks": {"flops_per_s": 1.0, "bytes_per_s": 1.0}, "memory_peak_bytes": 0}
    other_program = {**bare, "trace": {"modules": {"jit_bd_train_step": [0.5], "jit_bd_policy_step": [0.005]}, "ops": []}}
    for run in (bare, other_program):
        for name in NEW_METRICS:
            assert bench_run.read_metric(name, dict(run)) is None, name


def test_the_analytic_counts_at_the_published_widths(manifest):
    from benchmark.drivers.lm_main import model_config

    cell = bench_run.load_cell(manifest, CELL)
    c = model_config(cell["config"], cell["traffic"])
    assert flops_ouro.layer_params(c) == 51_380_224  # attention 16.78 M + SwiGLU 34.60 M
    # 4 layers, embedding and head, norm scales and the gate: 406.9 M parameters, 7.32 GB at 18 bytes
    params = 4 * (flops_ouro.layer_params(c) + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    assert round(params / 1e6, 1) == 406.9
    ops, moved = flops_ouro.policy_step(c, 32, 370.0)
    assert 3.3e9 < moved < 3.5e9 and ops / 197e12 < moved / 819e9  # ~4.1 ms over the bus at 819 GB/s: bound by bytes
    full = flops_ouro.train_step(c, [[512, 512]] * 8)
    assert 40e12 < full < 50e12  # 44.5 TFLOP for a full batch, every loop counted; the issue's ~60 counted the recomputed forward and every score pair


@pytest.fixture(scope="module")
def rehearsal(manifest):
    """-> (the result line, the cell, the run as the readers saw it): traced, so the per-layer readers run."""
    cell = {"name": "tiny_lm", "chips": 1, "config": load(f"{DATA}/ouro_tiny.json"), "traffic": load(f"{DATA}/ouro_tiny_traffic.json")}
    result, run = keyed.rehearse(manifest, cell, "lm_main", 2**31 + 301, True, control=True)
    return result, cell, run


def test_the_rehearsal_is_correct_and_holds_whole_cycles(rehearsal):
    result, cell, _ = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"  # no CPU number under a device metric's name
    held = {k for k, v in cell["config"]["limits"].items() if v is not None}
    assert held == {"logits_policy", "exit_mismatch", "logprob_old", "grad_med_policy", "delta_policy", "rows_mismatch"}
    assert all(result["compared"][k]["value"] <= result["compared"][k]["limit"] for k in held)
    assert 0 < result["cpu_rehearsal"]["lm_update_share"]["value"] < 100  # the harness's own clock; the other four read the device
    assert result["cpu_rehearsal"]["host_wait_ms"]["value"] > 0 and result["cpu_rehearsal"]["log_scalars_per_iter"]["value"] > 0
    assert result["detail"]["exit_loops"] == [0, 0, 0, result["detail"]["logits_rows"]]  # threshold 1: the last of 4 loops, every token
    assert result["attempted"] % cell["traffic"]["num_envs"] == 0
    json.dumps(result)


def test_every_metric_of_the_drivers_cells_reads_on_its_rehearsal(rehearsal, any_manifest):
    """Keyed by the driver (keyed.py): whatever lists a cell of `lm_main` reads this rehearsal."""
    cells = keyed.cells_of(any_manifest, "lm_main")
    assert CELL in cells
    for cell in cells:
        keyed.check_spans(any_manifest, cell)
        keyed.check_rehearsal(any_manifest, cell, rehearsal[2])


@pytest.mark.parametrize("stand_in", ["control", "drop_loop", "one_cache", "shifted_mask"])
def test_the_control_and_the_planted_faults_fail_a_limit(rehearsal, stand_in):
    verdict = rehearsal[0]["detail"][stand_in + "_verdict"]
    assert verdict["correct"] is False and verdict["failed_by"]
    if stand_in == "drop_loop":
        assert "exit_mismatch" in verdict["failed_by"]  # three loops: every token leaves after the third


def test_the_new_files_are_where_the_harness_looks():
    for path in ("benchmark/drivers/lm_main.py", "benchmark/drivers/ouro_names.py", "benchmark/reference/ouro_loop.py", "benchmark/flops_ouro.py",
                 "benchmark/configs/ouro_2p6b_pp12.json", "benchmark/traffic/rl_ar_32env.json", "benchmark/traffic/ratio64_16env_pipeline.json",
                 *(f"benchmark/metrics/{m}.py" for m in NEW_METRICS)):
        assert os.path.exists(os.path.join(ROOT, path)), path
