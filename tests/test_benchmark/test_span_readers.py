"""The readers of the program's own spans and kernel names, on hand-made runs
whose answers are known: spans inside and outside the window, nothing to
read, kernel events by name over two train-step executions."""

import importlib

import pytest

from benchmark.reduce import spans
from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
from sheeprl_tpu.ops import pallas_kernels
from sheeprl_tpu.telemetry.phase import ITERATION

SPAN_METRICS = ["host_wait_ms", "host_work_ms", "host_refill_ms", "sample_dispatch_ms",
                "log_write_ms_p50", "log_write_ms_max", "log_scalars_per_iter", "log_backlog_max"]
KERNEL_METRICS = ["gru_kernel_ms", "two_hot_kernel_ms"]

# one iteration of the loop, as (name, start ms, length ms) from the top of its
# body; what no child covers (2 ms between the last child and the end) is self time
PHASES = [
    ("rollout/pack", 0, 1), ("rollout/policy_dispatch", 1, 2), ("rollout/add_dispatch", 3, 1),
    ("rollout/action_wait", 4, 6), ("rollout/env_step", 10, 5),
    ("buffer/sample", 20, 2), ("buffer/stage", 22, 1), ("train/slice", 23, 3), ("train/dispatch", 26, 4),
    ("log/pull", 30, 50), ("log/write", 80, 8),
]
PERIOD_MS = 90


def read(metric, run):
    return importlib.import_module(f"benchmark.metrics.{metric}").read(run)


def loop(n_iterations, t_first, slow_write=None):
    """`span` events of n iterations that follow each other, the first at t_first (s)."""
    events, t = [], t_first
    for step in range(n_iterations):
        period = PERIOD_MS + (slow_write[1] if slow_write and slow_write[0] == step else 0)
        parent = f"it{step}"
        events.append({"event": "span", "name": ITERATION, "span": parent, "parent": None,
                       "p0": t, "dur_ms": period, "step": step})
        for name, at, ms in PHASES:
            if name == "log/write" and period != PERIOD_MS:
                ms += period - PERIOD_MS
            events.append({"event": "span", "name": name, "span": f"{parent}.{name}", "parent": parent,
                           "p0": t + at / 1e3, "dur_ms": ms, "step": step,
                           **({"scalars": 80 + step} if name == "log/write" else {})})
        t += period / 1e3
    return events


def run_of(events, first_boundary_iteration, iterations, t_first=100.0, extra_ms=()):
    """The harness's side: its boundaries lie inside `rollout/env_step`, 12 ms
    into each iteration; the window opens at one and spans `iterations`."""
    seconds = [(PERIOD_MS + dict(extra_ms).get(first_boundary_iteration + i, 0)) / 1e3 for i in range(iterations)]
    starts = [e["p0"] for e in events if e["name"] == ITERATION]
    return {
        "events": [{"event": "start"}, {"event": "span", "name": "publish", "span": "x", "parent": None, "t0": 1.0},
                   *events, {"event": "end"}],
        "t_open": starts[first_boundary_iteration] + 0.012,
        "window_s": sum(seconds),
        "iterations": iterations,
        "iteration_seconds": seconds,
    }


def test_each_reader_on_a_loop_whose_numbers_are_known():
    run = run_of(loop(10, 100.0), first_boundary_iteration=2, iterations=5)
    assert read("host_wait_ms", run) == 6 + 50  # one train step an iteration: its slice is the fastest, nothing held
    assert read("host_work_ms", run) == PERIOD_MS - 56
    # log/pull ends 80 ms into an iteration, the next policy dispatch 3 ms into the next
    assert read("host_refill_ms", run) == pytest.approx(PERIOD_MS - 80 + 3)
    assert read("sample_dispatch_ms", run) == 3
    assert read("log_write_ms_p50", run) == 8 and read("log_write_ms_max", run) == 8
    # the window's log/write spans are those of iterations 2..6: the ones that start in it
    assert read("log_scalars_per_iter", run) == 80 + 4
    w = spans.window(run)
    assert [it["step"] for it in w.iterations] == [3, 4, 5, 6, 7]  # start in the window: one boundary later
    assert any(line.startswith("spans: 5 iteration spans start in the window; the harness counted 5") for line in run["notes"])
    assert any("children + self = iteration" in line and "self 7.000" in line for line in run["notes"])


def test_what_a_later_train_steps_slice_takes_beyond_the_fastest_is_a_wait():
    """Four train steps an iteration: the runtime holds the host back in the
    third and fourth slice, which do the same work as the first."""
    events = loop(10, 100.0)
    for e in [e for e in events if e["name"] == "log/pull"]:
        e["dur_ms"] = 10  # the device is further on when the host gets there
        for k, ms in enumerate((3.5, 43, 30)):
            events.append({**e, "name": "train/slice", "span": f"{e['span']}.slice{k}", "dur_ms": ms})
    run = run_of(events, first_boundary_iteration=2, iterations=5)
    held = (3 + 3.5 + 43 + 30) - 4 * 3
    assert read("host_wait_ms", run) == 6 + 10 + held
    assert read("host_work_ms", run) == PERIOD_MS - (16 + held)
    assert any(line.startswith("host_wait_ms: of it held inside train/slice, median 67.500 ms an iteration (slices an iteration: 4, the fastest 3.000 ms)")
               for line in run["notes"])


def test_the_largest_write_is_named_by_the_harnesss_own_iteration_index():
    events = loop(10, 100.0, slow_write=(4, 55))
    run = run_of(events, first_boundary_iteration=2, iterations=5, extra_ms=[(4, 55)])
    assert read("log_write_ms_max", run) == 8 + 55
    assert read("log_write_ms_p50", run) == 8
    # loop iteration 4's write lies in the harness's interval 2 (boundaries in iterations 2, 3, 4, ...)
    assert any(line.startswith("log_write_ms_max: 63.000 ms in iteration 2 of the window (step 4, 84 scalars)") for line in run["notes"])


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_program_without_spans_reads_none(metric):
    """The parent of the PR that brought the spans: the readers raise nothing."""
    run = run_of(loop(10, 100.0), 2, 5)
    run["events"] = [e for e in run["events"] if "p0" not in e]  # its telemetry has other events, and Tracer's spans
    assert read(metric, run) is None
    assert read(metric, {**run, "events": []}) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_a_count_of_iterations_that_differs_reads_none_and_says_so(metric):
    run = run_of(loop(10, 100.0), 2, 5)
    run["iterations"] = 6
    assert read(metric, run) is None
    assert any("5 iteration spans" in line and "counted 6" in line for line in run["notes"])


def test_spans_outside_the_window_are_left_out():
    events = loop(10, 100.0)
    for e in events:  # the warm-up is slower: it must not reach the medians
        if e["step"] < 3 and e["name"] == "rollout/action_wait":
            e["dur_ms"] = 500
    run = run_of(events, 2, 5)
    assert read("host_wait_ms", run) == 56


# ------------------------------------------------------------------ kernels
def kernel_op(name, seconds):
    return {"name": name, "shape": "bf16[16,512]", "seconds": seconds, "detail": f"%{name} = bf16[16,512] custom-call(...)"}


def traced_run(ops, train_steps=2, events=()):
    return {"trace": {"ops": ops, "modules": {"jit_train_step": [0.05] * train_steps, "jit__blob_step": [0.001] * 3}},
            "events": list(events)}


def test_kernel_time_is_the_train_steps_calls_per_executed_train_step():
    ops = [kernel_op("gru_fwd_res.7", 2e-6)] * 100 + [kernel_op("gru_fwd_res", 3e-6)] + [
        kernel_op("gru_fwd.3", 1e-6),  # the policy step's call, once an iteration: named, not summed
        {"name": "fusion.12", "shape": "f32[4]", "seconds": 9.0},  # not a kernel: no operands kept
        {"name": "gru_fwd_res.8", "shape": "f32[4]", "seconds": 9.0},  # a name alone is not a custom-call
    ]
    run = traced_run(ops)
    assert read("gru_kernel_ms", run) == pytest.approx(1e3 * (100 * 2e-6 + 3e-6) / 2)
    assert any(line.startswith("gru kernels over 2 traced train steps: gru_fwd 1 calls, mean 1.00 us (policy step: not in the sum); "
                               "gru_fwd_res 101 calls") for line in run["notes"])
    # the figure does not move with the traffic's train ratio: four policy steps a train step change nothing
    more_policy_steps = traced_run(ops + [kernel_op("gru_fwd.3", 1e-6)] * 7)
    assert read("gru_kernel_ms", more_policy_steps) == read("gru_kernel_ms", run)


def test_a_family_that_did_not_run_reads_none_with_the_programs_reason():
    refused = {"event": "kernel.select", "family": "two_hot", "selected": False, "reason": "partitioned"}
    run = traced_run([kernel_op("gru_fwd_res.7", 2e-6)], events=[refused, {"event": "kernel.select", "family": "gru", "selected": True, "reason": "ok"}])
    assert read("two_hot_kernel_ms", run) is None
    assert any("two_hot kernels: no event named two_hot_fwd" in line and "partitioned" in line for line in run["notes"])
    assert read("gru_kernel_ms", run) is not None


@pytest.mark.parametrize("metric", KERNEL_METRICS)
def test_without_a_trace_or_a_train_step_there_is_nothing_to_read(metric):
    assert read(metric, {"events": []}) is None
    assert read(metric, traced_run([kernel_op("gru_fwd_res.1", 1e-6)], train_steps=0)) is None
    assert read(metric, traced_run([kernel_op("gru_fwd.1", 1e-6)])) is None  # policy steps alone


@pytest.mark.parametrize("metric", KERNEL_METRICS)
def test_the_readers_names_are_the_programs_table(metric):
    module = importlib.import_module(f"benchmark.metrics.{metric}")
    assert not set(module.TRAIN_STEP) & set(module.POLICY_STEP)
    assert sorted(module.TRAIN_STEP + module.POLICY_STEP) == sorted(pallas_kernels.KERNEL_NAMES[module.FAMILY])


def test_the_span_names_the_readers_hold_are_the_ones_the_main_opens(manifest):
    """Every name a reader looks for is a literal `telem.mark("<name>"...)` of the main."""
    import inspect

    source = inspect.getsource(program.main)
    from benchmark.metrics import host_wait_ms, log_write_ms_p50

    held = {*host_wait_ms.WAITS, host_wait_ms.HELD, log_write_ms_p50.SPAN, "buffer/sample", "buffer/stage", "rollout/policy_dispatch", "log/pull"}
    for name in held | {name for name, _, _ in PHASES}:
        assert f'telem.mark("{name}"' in source, name
    assert "telem.iteration(global_step)" in source
    new = [m for m in manifest["per_layer"] if m["name"] in SPAN_METRICS + KERNEL_METRICS]
    assert len(new) == 10 and all(m["workloads"] == ["dv3_s_bf16.ratio1024", "dv3_s_bf16.ratio64"] for m in new)
