"""The cell `sdar_30b_a3b_ep8.rl_bd4`: its entries in the manifest, its traffic,
its readers, and a rehearsal of its driver end to end on the CPU at a tiny
preset (the real `ppo_bd` main, the benchmark's token environments, the
window, the reference, the control and the planted faults)."""

import collections
import importlib
import json
import os

import numpy as np
import pytest

from benchmark import flops_sdar, run as bench_run
from benchmark.envs.token_episodes import Traffic, length_pool, response_of
from benchmark.reduce import by_module

from .conftest import DATA, ROOT, load

CELL = "sdar_30b_a3b_ep8.rl_bd4"
NEW_METRICS = (
    "bd_train_step_ms", "bd_train_mfu", "bd_policy_step_ms", "bd_policy_roofline", "moe_experts_ms", "moe_experts_roofline",
    "bd_tokens_per_env_step", "bd_update_share",
)
# readers the benchmark had whose inputs this cell's driver supplies too: the cell is appended to their lists
SHARED_METRICS = ("compile_s", "env_host_share", "iter_ms_p50", "iter_ms_p95", "device_idle", "peak_hbm_gib")


def test_the_manifests_new_entries_resolve(manifest):
    cell = bench_run.load_cell(manifest, CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and config["name"] == "sdar_30b_a3b_ep8" and traffic["name"] == "rl_bd4_64env"
    assert config["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert (config["num_hidden_layers"], config["experts_held"], config["vocab_size"]) == (6, 16, 18992)
    assert config["published"] == {"num_hidden_layers": 48, "experts_held": 128, "vocab_size": 151936}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] and config["experts_held"] * 8 == config["num_experts"]
    # no width is cut: the catalog's numbers under their own keys
    widths = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128, moe_intermediate_size=768,
                  num_experts=128, num_experts_per_tok=8, rope_theta=1000000, rms_norm_eps=1e-6, intermediate_size=6144, max_position_embeddings=32768)
    assert {k: config[k] for k in widths} == widths
    importlib.import_module(f"benchmark.drivers.{config['driver']}")
    importlib.import_module(f"benchmark.reference.{config['reference']}")
    # the traffic of the issue, letter for letter
    assert traffic["num_envs"] == 64
    assert traffic["args"] == {"group_size": 4, "block_length": 4, "denoise_steps": 2, "temperature": 1.0, "update_sequences": 16,
                               "per_rank_batch_size": 8, "clip_coef": 0.2, "lr": 1e-06}
    for spec in (traffic["env"]["prompt_len"], traffic["env"]["response_len"]):
        assert spec == {"median": 192, "sigma": 0.6, "clip": [64, 512]}
    assert traffic["env"]["zipf_exponent"] == 1.0
    mine = [m for m in manifest["per_layer"] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in mine] == [*SHARED_METRICS, *NEW_METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "env_steps_per_s" for m in mine if m["name"] in NEW_METRICS)
    assert all(m["workloads"][-1] == CELL and len(m["workloads"]) == 3 for m in mine if m["name"] in SHARED_METRICS)
    assert {m["name"] for m in bench_run.metrics_for(manifest, "end_to_end", CELL)} == {"env_steps_per_s", "setup_s"}


def test_the_traffics_lengths_are_the_same_for_every_seed_permuted(manifest):
    cell = bench_run.load_cell(manifest, CELL)
    params = {**cell["traffic"]["env"], "vocab_size": cell["config"]["vocab_size"], "group_size": 4}
    pool = length_pool(params, 4)
    assert len(pool) == 64 and all(64 <= p <= 512 and 64 <= r <= 512 and p % 4 == 0 and r % 4 == 0 for p, r in pool)
    assert 180 <= np.median([p for p, _ in pool]) <= 204 and 180 <= np.median([r for _, r in pool]) <= 204
    schedules = []
    for seed in (7, 2**31 + 12345):
        traffic = Traffic(params, 64, 4, seed)
        assert traffic.pool == pool  # no seed moves a length
        groups = [[tuple(len(x) if i == 0 else x for i, x in enumerate(traffic.pose(g, e))) for e in range(6)] for g in range(16)]
        members = [traffic.pose(3, 2)[0] for _ in range(4)]
        assert all((m == members[0]).all() for m in members)  # a group's members share the prompt
        assert all(int(traffic.pose(g, 0)[0].max()) < params["vocab_size"] - 1 for g in range(16))  # never the mask id
        schedules.append(groups)
    assert schedules[0] != schedules[1]  # which group runs which part: the seed's
    assert collections.Counter(map(tuple, schedules[0])) == collections.Counter(map(tuple, schedules[1]))  # the same multiset
    a, b = Traffic(params, 64, 4, 7), Traffic(params, 64, 4, 8)
    assert not np.array_equal(a.pose(0, 0)[0][:16], b.pose(a.offsets.index(a.offsets[0]), 0)[0][:16]) or a.offsets != b.offsets


def test_an_episode_is_done_with_its_response_and_rewards_the_classes():
    params = {"pool": 4, "prompt_len": {"median": 8, "sigma": 0.5, "clip": [4, 12]}, "response_len": {"median": 8, "sigma": 0.5, "clip": [4, 8]},
              "zipf_exponent": 1.0, "classes": 2, "vocab_size": 32, "group_size": 2}
    traffic = Traffic(params, 2, 4, 5)
    env = traffic.make_env()
    obs, _ = env.reset()
    ep = env.log[-1]
    want = traffic.targets(ep.prompt, ep.response_len)
    assert (obs["prompt"][: obs["prompt_len"][0]] == ep.prompt).all() and obs["response_len"][0] == ep.response_len
    done, steps = False, 0
    while not done:
        block = steps // 2
        picks = [0, 2] if steps % 2 == 0 else [1, 3]
        action = np.full(4, -1)
        for j in picks:
            action[j] = 2 + want[4 * block + j]  # an even id for class 0, an odd one for class 1
        obs, reward, done, _, _ = env.step(action)
        steps += 1
    assert steps == ep.response_len // 2 and reward == 1.0 and ep.reward == 1.0
    ids, step = response_of(ep, 4)
    assert (ids % 2 == want).all() and set(step) == {1, 2}
    assert traffic.context_tokens == sum(len(ep.prompt) + 4 * (s // 2) for s in range(steps))


def test_the_readers_return_none_on_a_run_without_their_spans():
    bare = {"events": [], "t_open": 0.0, "window_s": 1.0, "iterations": 3, "iteration_seconds": [0.3, 0.3, 0.4], "chips": 1,
            "peaks": {"flops_per_s": 1.0, "bytes_per_s": 1.0}, "memory_peak_bytes": 0}
    other_program = {**bare, "trace": {"modules": {"jit_train_step": [0.02]}, "ops": [{"name": "fusion.1", "shape": "", "seconds": 0.01}]}}
    for run in (bare, other_program):
        for name in NEW_METRICS:
            assert bench_run.read_metric(name, dict(run)) is None, name


@pytest.mark.parametrize("what", ["sound", "flipped_picks", "one_expert_wrong", "no_expert_judged"])
def test_the_worst_held_experts_rows_see_one_expert_wrong_and_pass_over_flipped_picks(what):
    from benchmark.drivers.token_main import LEAST_ROWS, _logits_numbers

    rng = np.random.default_rng(3)
    rows, held, wide = 400, 4, 32
    reference = rng.normal(size=(rows, wide))
    picks = rng.random((rows, held)) < [0.5, 0.3, 0.06, 0.01]  # the last expert: fewer rows than are judged
    assert picks[:, 3].sum() < LEAST_ROWS <= picks[:, 2].sum()
    value = reference + 0.005 * rng.normal(size=reference.shape)  # the floor every row has
    if what == "flipped_picks":  # single rows far off, a few of them the third expert's
        far = np.concatenate([rng.choice(rows, 20, replace=False), np.flatnonzero(picks[:, 2])[:3]])
        value[far] += 0.06 * rng.normal(size=(len(far), wide))
    if what == "one_expert_wrong":
        value[picks[:, 2]] += 0.04 * rng.normal(size=(int(picks[:, 2].sum()), wide))
    if what == "no_expert_judged":
        picks = picks[:, 3:]
    numbers, seen = _logits_numbers(value, reference, picks)
    assert seen["rows"] == rows and seen["experts_judged"] == (0 if what == "no_expert_judged" else 3)
    if what == "one_expert_wrong":  # 6 % of the rows: the whole's RMS moves by a few parts, the expert's own rows say it
        assert numbers["logits_policy"] < 0.015 and numbers["logits_expert_policy"] > 0.03 and seen["worst_expert"] == 2
    elif what == "flipped_picks":
        assert numbers["logits_policy"] > 0.012 and numbers["logits_expert_policy"] < 0.007
    else:
        assert 0.004 < numbers["logits_policy"] < 0.006 and numbers["logits_expert_policy"] == (0.0 if what == "no_expert_judged" else pytest.approx(0.005, rel=0.2))
    assert _logits_numbers(np.full_like(reference, np.nan), reference, picks)[0]["logits_policy"] == 1e30  # not a number fails any limit


def test_operations_inside_a_programs_executions_are_told_apart_by_their_start():
    ops = [["ragged-dot-none.1", 105, 10, ""], ["fusion.2", 120, 5, ""], ["ragged-dot-metadata", 130, 2, ""], ["ragged-dot-none.3", 300, 7, ""], ["ragged-dot-none.1", 520, 11, ""]]
    modules = [["jit_bd_train_step(1)", 100, 100, ""], ["jit_bd_policy_step(2)", 290, 30, ""], ["jit_bd_train_step(1)", 500, 100, ""]]
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": modules}]}]
    got = by_module.seconds_inside(planes, "bd_train_step", "ragged-dot")
    assert [(g["ops"], round(g["op_seconds"] * 1e9)) for g in got] == [(2, 12), (1, 11)]
    assert by_module.seconds_inside([{"name": "/host:CPU", "lines": []}], "bd_train_step", "ragged-dot") == []


def test_the_analytic_counts_at_the_published_widths(manifest):
    from benchmark.drivers.token_main import model_config

    cell = bench_run.load_cell(manifest, CELL)
    c = model_config(cell["config"], cell["traffic"])
    assert round(flops_sdar.position(c) / 1e6, 1) == 38.3 and round(flops_sdar.assignment(c) / 1e6, 1) == 9.4  # a layer's projections and router; one routed expert
    assert flops_sdar.layout_pairs(8, 8, c) == 16 * 4 * 5 / 2 + 2 * (4 * (8 + 4) + 4 * (12 + 4))
    full = flops_sdar.train_step(c, [[512, 512]] * 8, 16384 * 6)
    assert 17e12 < full < 19e12  # 18.1 TFLOP: the issue sized ~28 with every score pair counted; the mask lets a third of them through
    ops, moved = flops_sdar.policy_step(c, 64, 300.0)
    assert 1.2e9 < moved < 1.6e9 and ops / 197e12 < moved / 819e9  # the bf16 weights once and the cache: bound by bytes


@pytest.fixture(scope="module")
def rehearsal(manifest):
    cell = {"name": "tiny_tokens", "chips": 1, "config": load(f"{DATA}/sdar_tiny.json"), "traffic": load(f"{DATA}/sdar_tiny_traffic.json")}
    m = json.loads(json.dumps(manifest))
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric and (CELL in metric["workloads"]):
            metric["workloads"].append("tiny_tokens")
    return bench_run.run_cell(m, cell, 2**31 + 301, 1.0, True, control=True, require_chip=False), cell  # traced: the per-layer readers run


def test_the_rehearsal_is_correct_and_holds_whole_cycles(rehearsal):
    result, cell = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"  # no CPU number under a device metric's name
    held = {k for k, v in cell["config"]["limits"].items() if v is not None}
    assert held == {"logits_policy", "logits_expert_policy", "logprob_old", "grad_med_policy", "delta_policy", "rows_mismatch"}
    assert all(result["compared"][k]["value"] <= result["compared"][k]["limit"] for k in held)
    got = result["cpu_rehearsal"]
    assert got["bd_tokens_per_env_step"]["value"] == 2.0  # what an environment step means
    assert 0 < got["bd_update_share"]["value"] < 100
    # the shared readers find their inputs in this driver's run (the two that read the device find none on a CPU)
    assert got["iter_ms_p50"]["value"] > 0 and got["compile_s"]["value"] > 0 and 0 < got["env_host_share"]["value"] < 100
    assert result["attempted"] % cell["traffic"]["num_envs"] == 0
    json.dumps(result)


@pytest.mark.parametrize("stand_in", ["control", "drop_expert", "causal"])
def test_the_control_and_the_planted_faults_fail_a_limit(rehearsal, stand_in):
    verdict = rehearsal[0]["detail"][stand_in + "_verdict"]
    assert verdict["correct"] is False and verdict["failed_by"]


def test_the_new_files_are_where_the_harness_looks():
    for path in ("benchmark/drivers/token_main.py", "benchmark/envs/token_episodes.py", "benchmark/reference/sdar_moe.py", "benchmark/flops_sdar.py",
                 "benchmark/configs/sdar_30b_a3b_ep8.json", "benchmark/traffic/rl_bd4_64env.json", *(f"benchmark/metrics/{m}.py" for m in NEW_METRICS)):
        assert os.path.exists(os.path.join(ROOT, path)), path
