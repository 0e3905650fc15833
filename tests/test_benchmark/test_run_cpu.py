"""`run_cell` end to end at tiny widths on the CPU: the real `dreamer_v3`
main, the benchmark's environments, the window, the reference.

A sound run is correct and prints the contract's keys; off a TPU it reports
no metric at all. The control (the reference in the next precision down)
and each fault the cell can have, planted under the timed step, come out as
not correct. One process, four runs of the main: ~1 min each on the CPU.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import compare, run as bench_run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny_manifest(manifest):
    m = json.loads(json.dumps(manifest))
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append("tiny")
    return m


def go(manifest, cell, **kw):
    return bench_run.run_cell(tiny_manifest(manifest), cell, 2**31 + 17, 1.0, False, require_chip=False, **kw)


@pytest.fixture(scope="module")
def sound(manifest):
    from .conftest import DATA, load

    cell = {"name": "tiny", "chips": 1, "config": load(f"{DATA}/dv3_tiny.json"), "traffic": load(f"{DATA}/tiny_traffic.json")}
    return go(manifest, cell, control=True), cell


def test_a_sound_run_is_correct_and_has_the_contracts_keys(sound):
    result, cell = sound
    assert CONTRACT_KEYS <= set(result) and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    held = {k for k, v in cell["config"]["limits"].items() if v is not None}
    assert held <= set(result["compared"])
    assert all(row["value"] <= row["limit"] for name, row in result["compared"].items() if name in held)
    json.dumps(result)


def test_off_a_tpu_no_number_goes_under_a_device_metrics_name(sound):
    result, _ = sound
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "busy_s" not in result["device"]
    assert "env_steps_per_s" in result["cpu_rehearsal"]  # kept apart, under its own label


def test_with_a_chip_required_there_is_no_result(manifest, tiny_cell):
    with pytest.raises(SystemExit) as exit_:
        bench_run.run_cell(manifest, tiny_cell, 1, 1.0, False)
    assert exit_.value.code not in (0, None)


@pytest.mark.parametrize("stand_in", ["control", "half_batch"])
def test_in_the_programs_place_the_control_and_the_half_batch_are_not_correct(sound, stand_in):
    """`--control 1` judges each by the cell's own limits, as the chip runs print it."""
    result, cell = sound
    verdict = result["detail"][stand_in + "_verdict"]
    assert verdict["correct"] is False and verdict["failed_by"]
    limits = {k: v for k, v in cell["config"]["limits"].items() if k != "rows_mismatch"}
    ok, table = compare.judge(result["detail"][stand_in], limits)
    assert not ok and verdict["failed_by"] == [k for k, row in table.items() if row["limit"] is not None and row["value"] > row["limit"]]


def unchanged_state(step, state, sample, key, tau):
    kept = jax.tree_util.tree_map(jnp.copy, state)
    _, metrics = step(state, sample, key, tau)
    return kept, metrics


def half_batch(step, state, sample, key, tau):
    half = {k: jnp.concatenate([v[:, : v.shape[1] // 2]] * 2, axis=1) for k, v in sample.items()}
    return step(state, half, key, tau)


@pytest.mark.parametrize("fault,caught_by", [
    (unchanged_state, "delta_wm"),
    (half_batch, "grad_wm"),
])
def test_a_fault_under_the_timed_step_is_not_correct(manifest, tiny_cell, fault, caught_by):
    result = go(manifest, tiny_cell, fault=fault)
    assert result["correct"] is False
    row = result["compared"][caught_by]
    assert row["value"] > row["limit"]
