"""`replay_programs_ms`: the ring's add and sample by module name, whatever shape the ring is stored in."""

import json
import os

import pytest

from benchmark.metrics import replay_programs_ms
from benchmark.reduce import trace as T

from .conftest import DATA


def run_with(modules):
    return {"trace": {"modules": modules}}


def test_one_add_plus_one_sample_by_their_means():
    run = run_with({
        "jit__store_add_packed": [0.075, 0.077], "jit__store_sample": [0.052, 0.052, 0.055],
        "jit_train_step": [0.0543], "jit__threefry_split": [3e-6],
    })
    assert replay_programs_ms.read(run) == pytest.approx(76.0 + 53.0)
    assert run["notes"] == [
        "replay_programs_ms: _store_add_packed x2, mean 76.0000 ms",
        "replay_programs_ms: _store_sample x3, mean 53.0000 ms",
    ]


@pytest.mark.parametrize("modules", [
    {"jit__store_sample": [0.052]}, {"jit__store_add_packed": [0.075]}, {"jit_train_step": [0.0543]}, {},
])
def test_half_the_sum_is_no_number(modules):
    assert replay_programs_ms.read(run_with(modules)) is None


def test_without_a_trace_there_is_nothing_to_read():
    assert replay_programs_ms.read({"trace": None}) is None and replay_programs_ms.read({}) is None


def test_the_recorded_cut_holds_the_sample_alone():
    with open(os.path.join(DATA, "trace_cut.json")) as f:
        recorded = T.reduce(json.load(f)["planes"], chips=1)
    assert "jit__store_sample" in recorded["modules"] and replay_programs_ms.read({"trace": recorded}) is None
    recorded["modules"]["jit__store_add_packed(7)"] = [0.0747]
    assert replay_programs_ms.read({"trace": recorded}) == pytest.approx(74.7 + 52.514226)


def test_the_names_are_the_programs_own():
    """A jitted function's module is `jit_<its name>`: the reader's two names are the ring's two jits."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.data import AsyncReplayBuffer

    store = {"rgb": jnp.zeros((8, 2, 128), jnp.uint8)}
    row = {"rgb": jnp.zeros((1, 2, 128), jnp.uint8), "__idx__": jnp.zeros((4,), jnp.int32)}
    add = AsyncReplayBuffer._store_add_packed.lower(store, row, {}, (), 1).as_text()
    sample = AsyncReplayBuffer._store_sample.lower(
        store, jax.random.PRNGKey(0), jnp.zeros((4 + 6,), jnp.int32),
        n_samples=1, seq_len=2, sequential=True, sample_next_obs=False, obs_keys=(),
    ).as_text()
    for program, text in zip(replay_programs_ms.PROGRAMS, (add, sample)):
        assert f"module @jit_{program} " in text
