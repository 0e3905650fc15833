"""The traffic generator is a pure function of the seed, and the seed changes
the order of the work, not its amount."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.envs.pixel_episodes import Traffic

from . import keyed
from .conftest import ROOT, load

PARAMS = {"frame_pool": 8, "actions": 18, "episode_steps": [5, 11], "reward_prob": 0.3}


def play(seed, steps=40, num_envs=4):
    load = Traffic(PARAMS, num_envs, seed)
    envs = [load.make_env() for _ in range(num_envs)]
    frames = [[e.reset(seed=seed + i)[0]] for i, e in enumerate(envs)]
    rng = np.random.default_rng(7)
    for _ in range(steps):
        for i, e in enumerate(envs):
            obs, reward, term, trunc, _ = e.step(int(rng.integers(18)))
            frames[i].append(obs)
            if term or trunc:
                frames[i].append(e.reset()[0])
    return load, np.stack([np.stack(f[:steps]) for f in frames], axis=1)


def test_same_seed_same_traffic_other_seed_other_traffic():
    a, fa = play(2**31 + 12345)
    b, fb = play(2**31 + 12345)
    c, fc = play(77)
    assert (fa == fb).all() and not (fa == fc).all()
    assert [e.log.reward for e in a.envs] == [e.log.reward for e in b.envs]
    assert [e.log.done for e in a.envs] == [e.log.done for e in b.envs]


@pytest.mark.parametrize("num_envs", [4, 16])
def test_the_seed_permutes_the_episodes_and_keeps_the_amount_of_work(num_envs):
    plans = [sorted(Traffic(PARAMS, num_envs, seed).plan) for seed in (1, 2, 3_000_000_000)]
    assert plans[0] == plans[1] == plans[2]
    assert Traffic(PARAMS, num_envs, 1).plan != Traffic(PARAMS, num_envs, 2).plan or num_envs == 1
    resets = [play(seed, steps=60, num_envs=num_envs)[0].resets for seed in (1, 2, 3)]
    assert len(set(resets)) == 1 and resets[0] > 0


def test_every_row_is_stamped_differently_and_rebuilt_from_the_log():
    load, frames = play(5)
    flat = frames.reshape(-1, 64 * 64 * 3)
    assert len({bytes(f[:6]) for f in flat}) == len(flat)
    rows, valid = load.expected_rows(frames)
    assert valid.all() and (rows["rgb"] == frames).all()
    assert rows["actions"].sum(-1).max() == 1.0
    # a terminal observation is answered by no action; the one after it is a first
    done = rows["dones"][..., 0] == 1
    assert done.any() and (rows["actions"][done] == 0).all()
    assert (rows["is_first"][1:][done[:-1]] == 1).all()
    tampered = frames.copy()
    tampered[3, 1, 0, 0, 0] ^= 1
    assert not load.expected_rows(tampered)[1][3, 1]


def test_traffic_files_are_parameters_the_generator_reads(manifest):
    """Each file goes to the generator of the driver its cells run (the driver's
    own `make_traffic`), and every file is some cell's."""
    folder = os.path.join(ROOT, "benchmark", "traffic")
    used = {w["traffic"] for w in manifest["workloads"]}
    assert {name[: -len(".json")] for name in os.listdir(folder)} == used
    for w in manifest["workloads"]:
        t = load(os.path.join(folder, w["traffic"] + ".json"))
        config = keyed.config_of(manifest, w["name"])
        driver = importlib.import_module(f"benchmark.drivers.{config['driver']}")
        generator = driver.make_traffic(config, t, 1)  # a token file's episode_steps is checked as it is built
        if isinstance(generator, Traffic):  # the pixel episodes
            lengths = [length for length, _, _ in generator.plan]
            assert min(lengths) == t["env"]["episode_steps"][0] and max(lengths) == t["env"]["episode_steps"][1]
            assert t["learning_starts"] // t["num_envs"] >= 64  # a T=64 window per environment


@pytest.mark.parametrize("cell,warmup_iterations", [("sdar_30b_a3b_ep8.rl_bd4", 4096 // 64), ("ouro_2p6b_pp12.rl_ar32", 2048 // 32)])
def test_the_token_drivers_window_gets_the_numbers_it_had(manifest, cell, warmup_iterations):
    """`warmup_iterations` is the floor the token drivers read as
    `learning_starts // num_envs` before the key had its own name."""
    found = bench_run.load_cell(manifest, cell)
    driver = importlib.import_module(f"benchmark.drivers.{found['config']['driver']}")
    w = driver.make_window(found["traffic"], 35.0, lambda: 0)
    assert (w.seconds, w.warmup_updates, w.warmup_iterations, w.steps_per_update, w.trace) == (35.0, 2, warmup_iterations, 2, None)


def first_bunch(traffic, seed, steps=3000):
    """The first step, all environments in lockstep, at which two episodes end together."""
    load = Traffic(traffic["env"], traffic["num_envs"], seed)
    envs = [load.make_env() for _ in range(traffic["num_envs"])]
    for e in envs:
        e.reset()
    for step in range(1, steps + 1):
        done = [e for e in envs if e.step(0)[2]]
        if len(done) > 1:
            return step
        for e in done:
            e.reset()
    return None


@pytest.mark.parametrize("name,expected", [("ratio64_16env.json", 1286), ("ratio1024_4env.json", None)])
def test_where_two_episodes_first_end_on_one_step_whatever_the_seed(name, expected):
    """Vector environments do end episodes together, and the program adds a
    step's ends in one call shaped by their number: the first pair is a
    compile (PERF.md 7.1). With 16 environments it falls on step 1,286, window
    index 1,192: 46 to 49 s into a window at 38 ms an iteration, past the
    manifest's 35 s until an iteration takes under 29 ms."""
    with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
        traffic = json.load(f)
    assert [first_bunch(traffic, seed) for seed in (1, 3_000_000_000)] == [expected, expected]
    if expected:
        open_at = traffic["learning_starts"] // traffic["num_envs"] + 2 + traffic["warmup_iterations"]  # drivers/train_main.py
        assert expected - open_at == 1192
