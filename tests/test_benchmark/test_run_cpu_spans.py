"""The program's spans through the whole harness, at tiny widths on the CPU:
one traced run of `run_cell` (the real `dreamer_v3` main, stopped by SIGTERM,
its `telemetry.jsonl` read back), kept for the module.

What a CPU run can show: the spans reach the readers after the preempted
exit, their count of iterations is the harness's, every span metric reads a
number (under `cpu_rehearsal`, never under a device metric's name), and each
iteration is made up of its children and its self time. No time is judged.
"""

import pytest

from benchmark.reduce import spans

from . import keyed
from .conftest import DATA, load


@pytest.fixture(scope="module")
def traced(manifest):
    """-> (the result line, the run as the readers saw it): the tiny cell read by
    every metric that lists a cell of its driver."""
    cell = {"name": "tiny", "chips": 1, "config": load(f"{DATA}/dv3_tiny.json"), "traffic": load(f"{DATA}/tiny_traffic.json")}
    return keyed.rehearse(manifest, cell, "train_main", 2**31 + 23, True)


def test_the_spans_survive_the_preempted_exit_and_count_the_harnesss_iterations(traced):
    result, run = traced
    assert result["correct"] is True
    w = spans.window(run)
    assert w is not None and w.ok
    assert len(w.iterations) == run["iterations"] > 0
    steps = [it["step"] for it in w.iterations]
    assert steps == list(range(steps[0], steps[0] + len(steps)))  # consecutive loop bodies, none dropped
    assert any(line.startswith(f"spans: {run['iterations']} iteration spans start in the window") for line in run["notes"])


def test_every_span_metric_reads_a_number_kept_apart_from_the_device_metrics(traced, any_manifest):
    """Every metric that lists a cell of this driver, keyed by the main's source
    and this rehearsal (keyed.py); no count of them is held."""
    result, run = traced
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    cells = keyed.cells_of(any_manifest, "train_main")
    assert cells
    for cell in cells:
        keyed.check_spans(any_manifest, cell)
        keyed.check_rehearsal(any_manifest, cell, run)
    # off a TPU there is no device trace: a kernel reader has nothing to read
    assert not {"gru_kernel_ms", "two_hot_kernel_ms"} & set(result["cpu_rehearsal"])
    assert result["cpu_rehearsal"]["log_scalars_per_iter"]["value"] > 20


def test_an_iteration_is_its_children_and_its_self_time(traced):
    _, run = traced
    w = spans.window(run)
    expected = {"rollout/pack", "rollout/policy_dispatch", "rollout/add_dispatch", "rollout/action_wait",
                "rollout/env_step", "log/pull", "log/write"}
    trained = 0
    for it in w.iterations:
        children = sorted(w.children[it["span"]], key=lambda c: c["p0"])
        names = [c["name"] for c in children]
        assert expected <= set(names) and "rollout" not in names  # the lump is gone on the blob path
        once = [n for n in names if not n.startswith("train/")]
        assert len(once) == len(set(once))
        # a pair of spans per train step
        assert names.count("train/slice") == names.count("train/dispatch")
        trained += {"buffer/sample", "buffer/stage", "train/slice", "train/dispatch"} <= set(names)
        end = it["p0"]
        for c in children:  # linear marks: inside the parent, never overlapping
            assert c["p0"] >= end - 1e-6 and c["step"] == it["step"]
            end = c["p0"] + c["dur_ms"] / 1e3
        assert end <= it["p0"] + it["dur_ms"] / 1e3 + 1e-6
        assert it["dur_ms"] - sum(c["dur_ms"] for c in children) >= -1e-3  # self time
    assert trained == len(w.iterations)  # --train_every 2 over 2 environments: every iteration trains
