"""Events the logger's writer thread still held when a `log/write` span
closed: the program's `backlog` counter on that span, its largest value over
the window. A writer that keeps up reads 0 or 1 (the event just handed over);
one that falls behind reads a number that grows from the window's first half
to its second, which the note line shows."""

from ..reduce import spans
from .log_write_ms_p50 import SPAN


def read(run: dict):
    w = spans.window(run)
    counts = [s["backlog"] for s in w.named(SPAN) if "backlog" in s] if w else []
    if not counts:
        return None
    half = len(counts) // 2
    spans.note(run, f"log_backlog_max: {max(counts)} events over {len(counts)} writes; "
                    f"first half max {max(counts[:half], default=0)}, second half max {max(counts[half:])}")
    return max(counts)
