"""Scalars handed to the logger in an iteration: the counter the program
records on its `log/write` span, mean over the window."""

from ..reduce import spans
from .log_write_ms_p50 import SPAN


def read(run: dict):
    w = spans.window(run)
    counts = [s["scalars"] for s in w.named(SPAN) if "scalars" in s] if w else []
    return sum(counts) / len(counts) if counts else None
