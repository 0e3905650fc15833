"""Median of the program's `log/write` span: `telem.interval`, the logger's
scalars, `Time/step_per_second`, once an iteration, while the device waits."""

import statistics

from ..reduce import spans

SPAN = "log/write"


def read(run: dict):
    w = spans.window(run)
    writes = w.named(SPAN) if w else []
    return statistics.median(s["dur_ms"] for s in writes) if writes else None
