"""The readers of a blocking read's two parts (`action_ready_ms`,
`action_copy_ms`, `metric_ready_ms`, `metric_copy_ms`) on hand-made runs
whose answers are known: two `log/pull` spans in one iteration, as the token
mains' update opens, an iteration whose read has no parts, and a program
that does not split its reads."""

import importlib
import statistics

import pytest

from benchmark.reduce import spans
from sheeprl_tpu.telemetry.phase import ITERATION

READERS = {"action_ready_ms": ("rollout/action_wait", "ready"), "action_copy_ms": ("rollout/action_wait", "copy"),
           "metric_ready_ms": ("log/pull", "ready"), "metric_copy_ms": ("log/pull", "copy")}
PERIOD_MS = 100
# one iteration, as (read, start ms, length ms, ready ms, copy ms, bytes, arrays) from the top of its body
READS = [("rollout/action_wait", 10, 6, 4.5, 1.0, 384, 1), ("log/pull", 40, 20, 15.0, 0.5, 52, 1),
         ("log/pull", 80, 3, 0.5, 2.0, 1024, 2)]


def read(metric, run):
    return importlib.import_module(f"benchmark.metrics.{metric}").read(run)


def loop(n_iterations, bare=(), split=True):
    """`span` events of n iterations from t = 100 s; the reads of the steps in
    `bare` have no parts, and with `split` off no read has."""
    events, t = [], 100.0
    for step in range(n_iterations):
        it = f"it{step}"
        events.append({"event": "span", "name": ITERATION, "span": it, "parent": None, "p0": t, "dur_ms": PERIOD_MS, "step": step})
        for k, (name, at, ms, ready, copy, nbytes, arrays) in enumerate(READS):
            span = f"{it}.{k}"
            parts = split and step not in bare
            events.append({"event": "span", "name": name, "span": span, "parent": it, "p0": t + at / 1e3, "dur_ms": ms, "step": step,
                           **({"bytes": nbytes, "arrays": arrays} if parts else {})})
            if parts:
                events.append({"event": "span", "name": f"{name}/ready", "span": f"{span}.r", "parent": span,
                               "p0": t + (at + 0.25) / 1e3, "dur_ms": ready, "step": step})
                events.append({"event": "span", "name": f"{name}/copy", "span": f"{span}.c", "parent": span,
                               "p0": t + (at + 0.25 + ready) / 1e3, "dur_ms": copy, "step": step})
        t += PERIOD_MS / 1e3
    return events


def run_of(events, first=2, iterations=5):
    starts = [e["p0"] for e in events if e["name"] == ITERATION]
    return {"events": [{"event": "start"}, *events, {"event": "end"}], "t_open": starts[first] + 0.005,
            "window_s": iterations * PERIOD_MS / 1e3, "iterations": iterations, "iteration_seconds": [PERIOD_MS / 1e3] * iterations}


def test_each_reader_sums_its_part_over_the_iterations_reads():
    run = run_of(loop(10))
    assert read("action_ready_ms", run) == 4.5 and read("action_copy_ms", run) == 1.0
    # the two log/pull spans of one iteration add up
    assert read("metric_ready_ms", run) == 15.5 and read("metric_copy_ms", run) == 2.5
    notes = "\n".join(run["notes"])
    assert "rollout/action_wait: ready 4.5000 + copy 1.0000 + rest 0.5000 = read 6.0000 ms, median of 5 iterations; largest rest 0.5000 ms" in notes
    assert "log/pull: ready 15.5000 + copy 2.5000 + rest 5.0000 = read 23.0000 ms" in notes
    assert "rollout/action_wait: 5 reads, median 384 bytes in 1 arrays a read; 0.384 MB/s of copy time" in notes
    assert "log/pull: 10 reads, median 538.0 bytes in 1.5 arrays a read" in notes


def test_an_iteration_whose_read_has_no_parts_reads_zero():
    bare = (3, 4, 5)  # three of the window's five iterations (3..7)
    run = run_of(loop(10, bare=bare))
    for metric, (name, part) in READERS.items():
        whole = dict(ready={"rollout/action_wait": 4.5, "log/pull": 15.5}, copy={"rollout/action_wait": 1.0, "log/pull": 2.5})[part][name]
        assert read(metric, run) == statistics.median([0.0, 0.0, 0.0, whole, whole])
    # what the parts leave of a read without parts is the whole read
    assert any(line.startswith("log/pull:") and "largest rest 23.0000 ms" in line for line in run["notes"])


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_program_that_does_not_split_its_reads_reads_none(metric):
    """The parent of the PR that brought the parts: the readers raise nothing."""
    assert read(metric, run_of(loop(10, split=False))) is None
    run = run_of(loop(10))
    run["events"] = [e for e in run["events"] if "p0" not in e]
    assert read(metric, run) is None
    assert spans.window(run) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_read_outside_the_window_is_left_out(metric):
    events = loop(10)
    for e in events:  # the warm-up is slower: it must not reach the medians
        if e["step"] < 3 and e["name"].endswith(("/ready", "/copy")):
            e["dur_ms"] = 500.0
    assert read(metric, run_of(events)) == read(metric, run_of(loop(10)))
