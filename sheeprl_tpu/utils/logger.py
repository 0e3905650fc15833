"""TensorBoard logging (capability parity with
/root/reference/sheeprl/utils/logger.py): run-dir layout
`{root_dir}/{run_name}` with `root_dir` defaulting to
`logs/{algo}/{env_id}` and `run_name` to a timestamp; resuming from a
checkpoint reuses the checkpoint's run directory (logger.py:36-39).

In SPMD JAX one process drives all local devices, so the reference's
"broadcast log_dir to other ranks" collective is only needed multi-host:
process 0 creates the dir, other processes log nothing (rank-0-only logging,
logger.py:21-34)."""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any


# every logger that still has a writer thread: `resilience.crashsafe` closes
# them on the ways out of a main that never reach its own `logger.close()`
_live: list["TensorBoardLogger"] = []


def live_loggers() -> list["TensorBoardLogger"]:
    return list(_live)


class TensorBoardLogger:
    """Event-file writer; a no-op on non-zero processes.

    `log_dict` hands ONE event (all its values share the step) to a writer
    thread and returns: the main thread never waits for the disk. The queue
    has no bound: an iteration of a main's loop fills one entry of ~40
    (tag, float) pairs, so an hour behind a dead disk is tens of MB and
    `backlog` says so. `close()` drains it; a hard kill loses what it holds."""

    def __init__(self, log_dir: str, enabled: bool = True):
        self.log_dir = log_dir
        self._queue: queue.SimpleQueue | None = None
        if enabled:
            # tensorboardX, NOT torch.utils.tensorboard: with tensorflow
            # present, torch's writer makes the `tensorboard` package load
            # libtensorflow_framework, whose GL deps segfault dm_control's
            # EGL context creation afterwards (r4 pixel-receipt debugging:
            # create_logger-then-DMC-render crashed in MjrContext / TF
            # framework; tensorboardX writes identical event files with no
            # TF import)
            from tensorboardX.event_file_writer import EventsWriter

            os.makedirs(log_dir, exist_ok=True)
            self._file = EventsWriter(os.path.join(log_dir, "events"))
            self._queue = queue.SimpleQueue()
            self._handed = self._written = 0  # one writer each: no lock
            self._error: BaseException | None = None
            self._thread = threading.Thread(
                target=self._write_loop, args=(self._queue,), name="tb-writer", daemon=True
            )
            self._thread.start()
            _live.append(self)

    @property
    def backlog(self) -> int:
        """Events handed over and not yet in the file's buffer."""
        return 0 if self._queue is None else self._handed - self._written

    def _write_loop(self, events: queue.SimpleQueue) -> None:
        from tensorboardX.proto.event_pb2 import Event
        from tensorboardX.summary import Summary

        while (item := events.get()) is not None:
            summary, step, wall_time = item
            try:
                if not isinstance(summary, Summary):  # a log_dict's (tag, value) pairs
                    summary = Summary(value=[Summary.Value(tag=k, simple_value=v) for k, v in summary])
                self._file.write_event(Event(summary=summary, step=step, wall_time=wall_time))
                if events.empty():
                    self._file.flush()  # to the OS, once the thread has caught up
            except Exception as exc:  # kept for close(): a daemon thread's error reaches no one
                self._error = self._error or exc
            self._written += 1

    def _put(self, summary, step: int) -> None:
        self._handed += 1
        self._queue.put((summary, int(step), time.time()))

    def log(self, name: str, value: Any, step: int) -> None:
        self.log_dict({name: value}, step)

    def log_dict(self, metrics: dict[str, Any], step: int) -> None:
        if self._queue is not None and metrics:
            from tensorboardX.summary import _clean_tag

            self._put([(_clean_tag(k), float(v)) for k, v in metrics.items()], step)

    def log_hyperparams(self, params: dict[str, Any]) -> None:
        # TensorBoard's text plugin renders markdown: a proper two-column
        # table instead of one run-on text blob (pipes in values would break
        # the row structure, so they are escaped)
        if self._queue is not None:
            from tensorboardX.summary import text

            escaped = [
                (k, str(v).replace("|", "\\|")) for k, v in sorted(params.items())
            ]
            rows = "\n".join(f"| {k} | {v} |" for k, v in escaped)
            table = "| key | value |\n| --- | --- |\n" + rows
            self._put(text("hyperparams", table), 0)

    def close(self) -> None:
        """Drain the queue, flush and close the file; idempotent. Raises the
        first error the writer thread met."""
        if self._queue is None:
            return
        queue_, self._queue = self._queue, None
        _live.remove(self)
        queue_.put(None)
        self._thread.join()
        try:
            self._file.close()
        except OSError as exc:  # the last flush
            self._error = self._error or exc
        if self._error is not None:
            raise RuntimeError(f"event file {self.log_dir}: a write failed, events are missing") from self._error


def _broadcast_run_name(run_name: str) -> str:
    """Agree on one run directory across hosts — the JAX-collective analog of
    the reference's rank-0 log_dir broadcast (reference logger.py:21-52).
    Timestamp-derived names otherwise desync when hosts cross a second
    boundary."""
    import jax

    if jax.process_count() == 1:
        return run_name
    import numpy as np
    from jax.experimental import multihost_utils

    buf = np.zeros(256, dtype=np.uint8)
    raw = run_name.encode()[:256]
    buf[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    out = multihost_utils.broadcast_one_to_all(buf)
    return bytes(np.asarray(out)).rstrip(b"\x00").decode()


def create_logger(args: Any, algo_name: str, process_index: int = 0):
    """Build (logger, log_dir, run_name); sets `args.log_dir` (which dumps
    args.json as a side effect on process 0, algos/args.py contract)."""
    if (
        args.checkpoint_path
        and os.path.exists(args.checkpoint_path)
        # --eval_only with an explicit --root_dir logs into the requested
        # directory; otherwise (training resume, or eval without a
        # destination) reuse the checkpoint's run directory
        and not (getattr(args, "eval_only", False) and args.root_dir)
    ):
        # resume into the checkpoint's run directory
        log_dir = os.path.dirname(os.path.dirname(os.path.abspath(args.checkpoint_path)))
        root_dir = os.path.dirname(log_dir)
        run_name = os.path.basename(log_dir)
    else:
        root_dir = args.root_dir or os.path.join("logs", algo_name, args.env_id)
        run_name = _broadcast_run_name(args.run_name or time.strftime("%Y-%m-%d_%H-%M-%S"))
        log_dir = os.path.join(root_dir, run_name)
    logger = TensorBoardLogger(log_dir, enabled=process_index == 0)
    args.root_dir = root_dir
    args.run_name = run_name
    if process_index == 0:
        args.log_dir = log_dir  # side effect: mkdir + args.json dump
    else:
        object.__setattr__(args, "log_dir", log_dir)
    return logger, log_dir, run_name
