"""The TensorBoard logger off the iteration's critical path: one event a
`log_dict`, a writer thread the main thread never waits on, and an event
file that is whole after every way out of a main.

The reference for "the same output" is the path this logger replaced: one
`SummaryWriter.add_scalar` a scalar. Both files are read back with a small
tfrecord reader and compared as `(tag, step, value)` sequences (`simple_value`
is a float32 on both sides)."""

import glob
import os
import struct
import sys
import threading
import time

import numpy as np
import pytest

from sheeprl_tpu import resilience
from sheeprl_tpu.resilience.guard import RC_PREEMPTED, Preempted, RunGuard
from sheeprl_tpu.utils import logger as logger_module
from sheeprl_tpu.utils.logger import TensorBoardLogger

JOIN_S = 30.0  # no wait of a test is open-ended
OLD_QUEUE = 10  # tensorboardX's default `max_queue`, which the per-scalar path filled


def records(log_dir):
    """Every event of the directory's one event file, in file order."""
    from tensorboardX.proto.event_pb2 import Event

    (path,) = glob.glob(os.path.join(log_dir, "events.out.tfevents.*"))
    with open(path, "rb") as f:
        data = f.read()
    at = 0
    while at < len(data):
        (length,) = struct.unpack("Q", data[at:at + 8])
        event = Event()
        event.ParseFromString(data[at + 12:at + 12 + length])
        at += 12 + length + 4
        yield event
    assert at == len(data)  # no torn record


def scalars(log_dir):
    return [(v.tag, e.step, v.simple_value) for e in records(log_dir) for v in e.summary.value if v.HasField("simple_value")]


def texts(log_dir):
    return [(v.tag, e.step, v.tensor.string_val[0]) for e in records(log_dir) for v in e.summary.value if v.HasField("tensor")]


def per_scalar(log_dir, calls, hyperparams=None):
    """What the parent of PR 29 wrote: `add_text`, then one `add_scalar` a scalar."""
    from tensorboardX import SummaryWriter

    writer = SummaryWriter(log_dir)
    if hyperparams is not None:
        rows = "\n".join(f"| {k} | {str(v).replace('|', chr(92) + '|')} |" for k, v in sorted(hyperparams.items()))
        writer.add_text("hyperparams", "| key | value |\n| --- | --- |\n" + rows)
    for metrics, step in calls:
        for k, v in metrics.items():
            writer.add_scalar(k, float(v), step)
    writer.flush()
    writer.close()


def iteration(step, n_tags=41):
    """A dict like an iteration's: losses, times, counters; values that float32 rounds."""
    return {f"Loss/metric_{i}": 0.1 * step + i / 3.0 for i in range(n_tags - 3)} | {
        "Time/step_per_second": 181.649 + step, "XLA/recompiles": step % 3, "Params/exploration_amount": np.float32(0.3)}


CALLS = {
    "one_iteration": [(iteration(7), 7)],
    "two_hundred_iterations": [(iteration(s), 16 * s) for s in range(200)],
    "steps_that_go_back_and_repeat": [(iteration(s, 5), s) for s in (5, 3, 3, 9, 0)],
    "tags_the_writer_cleans": [({"Loss/world model (kl)": 1.5, "/leading/slash": 2, "ok-tag_1.x": np.int64(3)}, 2)],
    "one_scalar_a_call": [({"Test/cumulative_reward": float(s)}, 0) for s in range(12)],
}


@pytest.mark.parametrize("name", CALLS)
def test_the_event_file_reads_back_as_the_per_scalar_paths(tmp_path, name):
    calls, hyperparams = CALLS[name], {"env_id": "a|b", "seed": 5}
    per_scalar(str(tmp_path / "old"), calls, hyperparams)
    new = TensorBoardLogger(str(tmp_path / "new"))
    new.log_hyperparams(hyperparams)
    for metrics, step in calls:
        if len(metrics) == 1:
            ((k, v),) = metrics.items()
            new.log(k, v, step)
        else:
            new.log_dict(metrics, step)
    new.close()
    assert scalars(str(tmp_path / "new")) == scalars(str(tmp_path / "old"))
    assert len(scalars(str(tmp_path / "new"))) == sum(len(m) for m, _ in calls)
    assert texts(str(tmp_path / "new")) == texts(str(tmp_path / "old")) and len(texts(str(tmp_path / "new"))) == 1
    # one event a call (plus the file's header and the hyperparameters), not one a scalar
    assert len(list(records(str(tmp_path / "new")))) == 2 + len(calls)
    walls = [e.wall_time for e in records(str(tmp_path / "new"))]
    assert walls == sorted(walls) and time.time() - 3600 < walls[-1] <= time.time()


def test_a_logger_that_is_not_rank_zero_writes_nothing(tmp_path):
    off = TensorBoardLogger(str(tmp_path / "off"), enabled=False)
    off.log_hyperparams({"a": 1})
    off.log_dict(iteration(1), 1)
    off.log("x", 1.0, 1)
    assert off.backlog == 0
    off.close()
    assert not os.path.exists(tmp_path / "off") and off not in logger_module.live_loggers()


def held_writer(logger):
    """Hold the logger's record writer inside a write until `release` is set."""
    entered, release = threading.Event(), threading.Event()
    write = logger._file.write_event

    def held(event):
        entered.set()
        assert release.wait(JOIN_S)
        write(event)

    logger._file.write_event = held
    return entered, release


def test_log_dict_does_not_wait_for_a_writer_held_in_a_write(tmp_path):
    """The per-scalar path blocked in `queue.put` once ten events waited; here
    five times as many dicts are handed over while the writer cannot move."""
    log = TensorBoardLogger(str(tmp_path))
    entered, release = held_writer(log)
    n = 5 * OLD_QUEUE
    took = []

    def main_thread():
        for step in range(n):
            t0 = time.perf_counter()
            log.log_dict(iteration(step), step)
            took.append(time.perf_counter() - t0)

    caller = threading.Thread(target=main_thread)
    caller.start()
    caller.join(JOIN_S)
    assert not caller.is_alive() and len(took) == n  # every call came back with the writer still held
    assert entered.wait(JOIN_S) and not release.is_set()
    assert log.backlog == n  # handed over, none written: what `telem.count(backlog=...)` records
    assert max(took) < 1.0  # bounded: a call builds a list and puts it (a fraction of a ms; the bound is for a loaded box)
    release.set()
    log.close()
    assert log.backlog == 0
    assert scalars(str(tmp_path)) == [(k, step, np.float32(v)) for step in range(n) for k, v in iteration(step).items()]


def test_two_thousand_calls_under_a_short_switch_interval_arrive_whole_and_in_order(tmp_path):
    """The queue and two counters are what the two threads share: no event is lost or reordered."""
    log = TensorBoardLogger(str(tmp_path))
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        deadline = time.monotonic() + JOIN_S
        for step in range(2000):
            log.log_dict({"a": step, "b": -step}, step)
            assert 0 <= log.backlog <= step + 1 and time.monotonic() < deadline
    finally:
        sys.setswitchinterval(before)
    log.close()
    assert log.backlog == 0 and log._handed == log._written == 2000
    assert scalars(str(tmp_path)) == [(tag, step, sign * step) for step in range(2000) for tag, sign in (("a", 1), ("b", -1))]


def test_close_is_idempotent_and_leaves_every_event_on_disk(tmp_path):
    log = TensorBoardLogger(str(tmp_path))
    assert log in logger_module.live_loggers()
    for step in range(30):
        log.log_dict(iteration(step), step)
    log.close()
    size = os.path.getsize(glob.glob(str(tmp_path / "events.*"))[0])
    assert log not in logger_module.live_loggers() and not log._thread.is_alive()
    log.close()
    log.log_dict(iteration(31), 31)  # after the end: dropped, as on a disabled logger; no second file, no error
    log.close()
    assert os.path.getsize(glob.glob(str(tmp_path / "events.*"))[0]) == size
    assert len(scalars(str(tmp_path))) == 30 * 41


def way_out(name, step):
    if name == "preempted":
        raise Preempted(step, "SIGTERM")
    if name == "crash":
        raise ValueError("boom")
    if name == "system_exit":
        raise SystemExit(3)


@pytest.mark.parametrize("road", ["returns_without_closing", "preempted", "crash", "system_exit", "closes_itself"])
def test_every_way_out_of_a_crashsafe_main_leaves_the_event_file_whole(tmp_path, road):
    logged = []

    @resilience.crashsafe
    def main():
        log = TensorBoardLogger(str(tmp_path))
        entered, release = held_writer(log)  # the writer is behind when the main ends
        for step in range(3 * OLD_QUEUE):
            metrics = iteration(step, 6)
            log.log_dict(metrics, step)
            logged.extend((k, step, np.float32(v)) for k, v in metrics.items())
        assert entered.wait(JOIN_S) and log.backlog == 3 * OLD_QUEUE
        release.set()
        way_out(road, step)
        if road == "closes_itself":
            log.close()

    try:
        expected = {"preempted": SystemExit, "crash": ValueError, "system_exit": SystemExit}.get(road)
        if expected is None:
            main()
        else:
            with pytest.raises(expected) as raised:
                main()
            if road == "preempted":
                assert raised.value.code == RC_PREEMPTED
            if road == "system_exit":
                assert raised.value.code == 3
    finally:
        RunGuard.uninstall()
    assert logger_module.live_loggers() == []
    assert scalars(str(tmp_path)) == logged and len(logged) == 3 * OLD_QUEUE * 6


class DiskFull(OSError):
    pass


def failing_writer(logger, fail_at):
    """The record writer raises on its `fail_at`-th event and works again afterwards."""
    write, seen = logger._file.write_event, []

    def failing(event):
        seen.append(event)
        if len(seen) == fail_at:
            raise DiskFull("no space left on device")
        write(event)

    logger._file.write_event = failing


@pytest.mark.parametrize("where", ["close", "crashsafe_after_a_crash", "crashsafe_after_a_return"])
def test_an_error_in_the_writer_thread_surfaces_when_the_logger_is_closed(tmp_path, capsys, where):
    def body():
        log = TensorBoardLogger(str(tmp_path))
        failing_writer(log, fail_at=2)
        for step in range(4):
            log.log_dict({"a": step}, step)
        return log

    if where == "close":
        log = body()
        with pytest.raises(RuntimeError, match="a write failed") as raised:
            log.close()
        assert isinstance(raised.value.__cause__, DiskFull)
        log.close()  # said once
    else:
        @resilience.crashsafe
        def main():
            body()
            if where == "crashsafe_after_a_crash":
                raise ValueError("the crash itself")

        try:
            if where == "crashsafe_after_a_crash":
                with pytest.raises(ValueError, match="the crash itself"):  # not replaced by the logger's error
                    main()
            else:
                main()
        finally:
            RunGuard.uninstall()
        err = capsys.readouterr().err
        assert "a write failed" in err and "DiskFull" in err
    assert logger_module.live_loggers() == []
    # the thread went on after the failed write: the other three events are there
    assert scalars(str(tmp_path)) == [("a", 0, 0.0), ("a", 2, 2.0), ("a", 3, 3.0)]
