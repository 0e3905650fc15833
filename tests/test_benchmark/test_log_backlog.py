"""`log_backlog_max`: the `backlog` counter of the program's `log/write` spans
(events the logger's writer thread still held when the span closed), on
recorded spans whose answers are known."""

import inspect

import pytest

from benchmark.metrics import log_backlog_max, log_scalars_per_iter, log_write_ms_max, log_write_ms_p50

from .test_span_readers import loop, run_of


def with_backlog(counts, first_boundary_iteration=2, iterations=5):
    """The loop of `test_span_readers`, its `log/write` of iteration i carrying `backlog=counts[i]` (None: no counter)."""
    events = loop(len(counts), 100.0)
    for e in events:
        if e["name"] == log_write_ms_p50.SPAN and counts[e["step"]] is not None:
            e["backlog"] = counts[e["step"]]
    return run_of(events, first_boundary_iteration, iterations)


@pytest.mark.parametrize("counts, largest, halves", [
    ([9, 9, 1, 1, 1, 1, 1, 9, 9, 9], 1, "first half max 1, second half max 1"),  # a writer that keeps up; outside the window is left out
    ([0, 0, 1, 2, 1, 0, 1, 0, 0, 0], 2, "first half max 2, second half max 1"),
    ([0, 0, 1, 3, 5, 7, 9, 11, 0, 0], 9, "first half max 3, second half max 9"),  # a writer that falls behind: it grows
])
def test_the_largest_backlog_of_the_windows_writes_and_its_two_halves(counts, largest, halves):
    run = with_backlog(counts)
    assert log_backlog_max.read(run) == largest
    assert [line for line in run["notes"] if line.startswith("log_backlog_max")] == [
        f"log_backlog_max: {largest} events over 5 writes; {halves}"]


def test_the_other_readers_of_the_span_read_as_before():
    run, plain = with_backlog([1] * 10), run_of(loop(10, 100.0), 2, 5)
    for reader in (log_write_ms_p50, log_write_ms_max, log_scalars_per_iter):
        assert reader.read(run) == reader.read(plain)


@pytest.mark.parametrize("run", [
    with_backlog([None] * 10),  # the parent of the PR that brought the counter: spans, no `backlog`
    {**with_backlog([1] * 10), "events": []},  # no spans at all
    {**with_backlog([1] * 10), "iterations": 6},  # a count of iterations that is not the harness's
], ids=["no_counter", "no_spans", "count_differs"])
def test_nothing_to_read_is_none_and_raises_nothing(run):
    assert log_backlog_max.read(run) is None


def test_the_counter_is_the_one_the_main_records_beside_scalars():
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program
    from sheeprl_tpu.utils.logger import TensorBoardLogger

    assert "telem.count(scalars=scalars, backlog=logger.backlog)" in inspect.getsource(program.main)
    assert isinstance(TensorBoardLogger.backlog, property)
