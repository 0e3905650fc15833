"""The reduction from a trace to numbers: on hand-made planes whose answers
are known, and on a small trace recorded on the v5e (data/trace_cut.json)."""

import json
import os

import pytest

from benchmark.reduce import trace as T

from .conftest import DATA

MS = 1_000_000


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v} for k, v in lines.items()]}


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.total([(0, 3), (5, 8)]) == 6
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


def test_result_shape_is_read_from_the_operations_text():
    assert T.result_shape("%copy.6 = u8[21504,16,64,64,3]{3,2,4,1,0:T(8,128)(4,1)} copy(%p)") == "u8[21504,16,64,64,3]"
    assert T.result_shape("%fusion.1 = (f32[4,8], f32[4]) fusion(%a)") == "f32[4,8]"
    assert T.result_shape("") == ""


def synthetic():
    ring = "%copy.1 = u8[100,4,64,64,3]{3,2,4,1,0} copy(%ring)"
    ops0 = [
        ["copy.1", 0 * MS, 4 * MS, ring],
        ["fusion.2", 4 * MS, 2 * MS, "%fusion.2 = f32[16,512] fusion(%x)"],
        ["all-reduce.3", 8 * MS, 2 * MS, "%all-reduce.3 = f32[512] all-reduce(%g)"],
        ["fusion.4", 9 * MS, 3 * MS, "%fusion.4 = f32[16,512] fusion(%y)"],
        ["copy.1", 16 * MS, 4 * MS, ring],
    ]
    ops1 = [["fusion.2", 0, 10 * MS, ""], ["all-reduce.3", 10 * MS, 2 * MS, ""]]
    modules = [["jit_train_step(123)", 4 * MS, 8 * MS, ""], ["jit__blob_step(7)", 16 * MS, 4 * MS, ""]]
    host = [["env.step", 6 * MS, 1 * MS, ""], ["main", 0, 20 * MS, ""]]
    return [
        plane("/device:TPU:0", XLA_Ops=ops0, XLA_Modules=modules),
        plane("/device:TPU:1", XLA_Ops=ops1),
        plane("/host:CPU", python=host),
    ]


def test_busy_idle_programs_and_ring_on_known_planes():
    one = T.reduce(synthetic(), chips=1)
    assert one["window_s"] == pytest.approx(0.020)
    assert one["busy_s"] == pytest.approx(0.014)  # [0,6] [8,12] [16,20] ms
    assert one["modules"] == {"jit_train_step": [0.008], "jit__blob_step": [0.004]}
    ring = sum(op["seconds"] for op in one["ops"] if op["shape"] == "u8[100,4,64,64,3]")
    assert ring == pytest.approx(0.008)
    assert one["breakdown"]["device_ops"][0] == ["copy.1 u8[100,4,64,64,3]", pytest.approx(0.008)]
    gaps = dict((k, v) for k, v in one["breakdown"]["idle_gaps"])
    assert gaps["all gaps during: env.step"] == pytest.approx(0.002)  # the gap that opens at 6 ms
    assert gaps["all gaps during: main"] == pytest.approx(0.004)
    four = T.reduce(synthetic(), chips=2)  # the mean over the devices used
    assert four["busy_s"] == pytest.approx((0.014 + 0.012) / 2)
    assert four["busy_s_by_device"] == [pytest.approx(0.014), pytest.approx(0.012)]


def ev(name, a_ms, b_ms):
    return [name, int(a_ms * MS), int((b_ms - a_ms) * MS), ""]


def looped(main, other=(), ops=((0, 1), (9, 10))):
    """One device busy over `ops` (ms), idle between; the loop's thread `main`
    and another thread `other`, as (name, start ms, end ms)."""
    return [
        plane("/device:TPU:0", XLA_Ops=[ev(f"fusion.{i}", a, b) for i, (a, b) in enumerate(ops)]),
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [ev(*e) for e in main]},
                                        {"name": "runtime", "events": [ev(*e) for e in other]}]},
    ]


IT = ("sheeprl/iteration", 0, 10)
SPLITS = {
    # the innermost span wins: update [2, 8] inside the iteration, train/dispatch [4, 6] inside update
    "nested": ([IT, ("sheeprl/update", 2, 8), ("sheeprl/train/dispatch", 4, 6)], (),
               {"iteration": 2, "update": 4, "train/dispatch": 2}, 0, "sheeprl/iteration"),
    # a gap that crosses from the action pull into the pack is split between them
    "crossing": ([IT, ("sheeprl/rollout/action_wait", 1, 5), ("np.asarray(jax.Array)", 1, 5), ("sheeprl/rollout/pack", 5, 9)], (),
                 {"rollout/action_wait": 4, "rollout/pack": 4}, 0, "sheeprl/rollout/action_wait > np.asarray(jax.Array)"),
    # before the first iteration the traced stretch is outside the loop
    "before_the_first_iteration": ([("sheeprl/iteration", 5, 10), ("sheeprl/rollout/env_step", 6, 9)], (),
                                   {"iteration": 1, "rollout/env_step": 3}, 4, "no host span"),
    # another thread's events are never the span, but its shortest one names the gap after it
    "another_thread": ([IT, ("sheeprl/log/pull", 1, 9)], [("ReadSyncFlag", 0.5, 2), ("sheeprl/rollout/pack", 0.5, 9.5)],
                       {"log/pull": 8}, 0, "sheeprl/log/pull > ReadSyncFlag"),
    # the benchmark's own annotation is no span of the program's
    "benchmark_annotation": ([IT, ("sheeprl/rollout/env_step", 1, 9), ("env.step", 1, 8)], (),
                             {"rollout/env_step": 8}, 0, "sheeprl/rollout/env_step > env.step"),
}


@pytest.mark.parametrize("case", list(SPLITS))
def test_each_idle_gap_goes_to_the_programs_innermost_span(case):
    main, other, by_span, outside, named = SPLITS[case]
    got = T.reduce(looped(main, other), chips=1)
    assert got["idle_by_span_s"] == {k: pytest.approx(v / 1e3) for k, v in by_span.items()}
    assert got["idle_outside_s"] == pytest.approx(outside / 1e3)
    assert got["iterations_traced"] == 1
    assert got["breakdown"]["idle_gaps"][0] == [f"all gaps during: {named}", pytest.approx(0.008)]
    assert got["breakdown"]["idle_gaps"][1] == [f"one gap at 1.0 ms during: {named}", pytest.approx(0.008)]


@pytest.mark.parametrize("chips", [1, 2])
def test_device_idle_is_in_pull_plus_in_host_plus_outside_the_loop(chips):
    from benchmark.metrics import device_idle, device_idle_in_host, device_idle_in_pull

    main = [("sheeprl/iteration", 3, 30), ("sheeprl/rollout/action_wait", 4, 9), ("sheeprl/rollout/env_step", 9, 12),
            ("sheeprl/log/pull", 14, 22), ("sheeprl/log/write", 22, 23), ("sheeprl/iteration", 30, 38)]
    planes = looped(main, ops=((0, 1), (6, 7), (12, 15), (24, 31), (37, 40)))
    planes.insert(1, plane("/device:TPU:1", XLA_Ops=[ev("fusion.9", 0, 20), ev("fusion.10", 39, 40)]))
    run = {"trace": T.reduce(planes, chips=chips)}
    pull, host = device_idle_in_pull.read(run), device_idle_in_host.read(run)
    outside = 100 * run["trace"]["idle_outside_s"] / run["trace"]["window_s"]
    assert device_idle.read(run) == pytest.approx(pull + host + outside, abs=1e-9)
    if chips == 1:  # idle [1, 6] [7, 12] [15, 24] [31, 37] of a 40 ms window
        assert pull == pytest.approx(100 * (2 + 2 + 7) / 40)  # action_wait [4, 6] [7, 9], log/pull [15, 22]
        assert host == pytest.approx(100 * (1 + 3 + 1 + 1 + 6) / 40)  # self [3, 4] [23, 24] [31, 37], env_step [9, 12], log/write [22, 23]
        assert outside == pytest.approx(100 * 2 / 40)  # [1, 3]
    assert any(line.startswith("device_idle = in_pull + in_host + outside the loop: ") for line in run["notes"])
    assert run["trace"]["iterations_traced"] == 2


def test_without_the_programs_iterations_the_split_reads_nothing():
    from benchmark.metrics import device_idle_in_host, device_idle_in_pull

    run = {"trace": T.reduce(synthetic(), chips=1)}  # host events, none of them the program's loop
    assert run["trace"]["iterations_traced"] == 0 and run["trace"]["idle_by_span_s"] == {}
    assert device_idle_in_pull.read(run) is None and device_idle_in_host.read(run) is None
    assert device_idle_in_pull.read({"trace": None}) is None


def test_a_trace_with_nothing_on_the_device_is_an_error():
    with pytest.raises(ValueError):
        T.reduce([plane("/host:CPU", python=[["x", 0, 1, ""]])], chips=1)
    with pytest.raises(ValueError):
        T.reduce([plane("/device:TPU:0", XLA_Ops=[])], chips=1)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_cut.json")) as f:
        return T.reduce(json.load(f)["planes"], chips=1)


def test_recorded_trace_busy_programs_and_ring_copy(recorded):
    assert recorded["window_s"] == pytest.approx(0.121760915)
    assert recorded["busy_s"] == pytest.approx(0.054654757)  # the cut keeps three stretches: the rest reads idle
    assert recorded["modules"]["jit_train_step"] == [pytest.approx(0.07330609)]
    assert recorded["modules"]["jit__store_sample"] == [pytest.approx(0.052514226)]
    top = recorded["breakdown"]["device_ops"][0]
    assert top[0] == "copy.9 u8[86016,4,64,64,3]" and top[1] == pytest.approx(0.051880143)
    assert len(recorded["breakdown"]["device_ops"]) == 10 and len(recorded["breakdown"]["idle_gaps"]) <= 10


def test_recorded_trace_through_the_metric_readers(recorded):
    from benchmark.metrics import device_idle, gru_roofline, gru_scan_roofline, train_mfu, train_step_ms

    from .conftest import ROOT, load

    args = load(f"{ROOT}/benchmark/configs/dv3_s_pixel_bf16.json")["args"]
    run = {
        "trace": recorded, "chips": 1,
        "peaks": load(f"{ROOT}/benchmark/peaks.json")["TPU v5 lite"],
        "model_config": {**args, "actions": 18, "image_channels": 3},
    }
    found = gru_roofline.calls(run)
    assert {k: len(v) for k, v in found.items()} == {(16, 512, 512, "f32"): 21, (1024, 512, 512, "f32"): 8}
    # 2*rows*1024*1536 operations at 197 TFLOP/s: 0.2555 us at 16 rows, where the calls took 1.183 us and
    # every operand sits on the chip; 16.35 us at 1024 rows, where they took 25.46 us and 6.3 MB went out
    assert all(b == 0.0 for _, b in found[(16, 512, 512, "f32")])
    assert all(b == 4 * (1024 * 1536 + 1024) for _, b in found[(1024, 512, 512, "f32")])
    assert gru_scan_roofline.read(run) == pytest.approx(100 * 0.25549e-6 / 1.183143e-6, rel=1e-3)
    assert gru_roofline.read(run) == pytest.approx(100 * (21 * 0.25549 + 8 * 16.3516) / (21 * 1.183143 + 8 * 25.46425), rel=1e-3)
    assert len(run["notes"]) == 2 and all("bound by operations" in n for n in run["notes"])
    assert train_step_ms.read(run) == pytest.approx(73.30609)
    assert train_mfu.read(run) == pytest.approx(100 * 0.900458348544e12 / 0.07330609 / 197e12)
    assert device_idle.read(run) == pytest.approx(100 * (1 - 0.054654757 / 0.121760915))
    empty = {"trace": None, "peaks": run["peaks"]}
    assert all(m.read(empty) is None for m in (device_idle, gru_roofline, gru_scan_roofline, train_mfu, train_step_ms))
