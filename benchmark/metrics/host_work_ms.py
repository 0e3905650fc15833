"""The iteration the host alone would allow: per iteration, the program's
`iteration` span less the time the main thread is blocked on the device
(host_wait_ms). Median over the window's iterations."""

import statistics

from ..reduce import spans
from . import host_wait_ms

SPANS = host_wait_ms.SPANS


def read(run: dict):
    w = spans.window(run)
    if not w:
        return None
    return statistics.median(
        it["dur_ms"] - wait for it, wait in zip(w.iterations, host_wait_ms.per_iteration(w))
    )
