"""The largest `log/write` span that starts in the window: the stall in the
logger's queue, where a run has one. Which of the harness's iterations holds
it (the index `slowest iterations` prints) goes on an earlier line."""

from ..reduce import spans
from .log_write_ms_p50 import SPAN


def read(run: dict):
    w = spans.window(run)
    writes = w.named(SPAN) if w else []
    if not writes:
        return None
    worst = max(writes, key=lambda s: s["dur_ms"])
    spans.note(run, f"log_write_ms_max: {worst['dur_ms']:.3f} ms in iteration {w.harness_index(worst)} of the window "
                    f"(step {worst['step']}, {worst.get('scalars')} scalars)")
    return worst["dur_ms"]
