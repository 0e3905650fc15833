"""The share of the traced window in which the device is idle while the loop's
thread is inside one of its blocking reads (`host_wait_ms.WAITS`: the action
pull and the metric pull), on the window `device_idle` reads (first device
operation to last; on several chips the mean over the devices). The split is
`reduce/trace.py`'s: each idle stretch goes to the innermost `sheeprl/` span
of that thread which covers it. A note line gives the identity
device_idle = in_pull + in_host + outside the loop."""

from ..reduce import spans
from .host_wait_ms import WAITS


def shares(run: dict):
    """-> (in_pull, in_host, outside the loop) in % of the traced window; None
    where the trace holds no iteration of the program's."""
    trace = run.get("trace")
    if not trace or not trace.get("iterations_traced"):
        return None
    by_span, window = trace["idle_by_span_s"], trace["window_s"]
    pull = sum(s for name, s in by_span.items() if name in WAITS)
    host = sum(s for name, s in by_span.items() if name not in WAITS)
    out = tuple(100.0 * s / window for s in (pull, host, trace["idle_outside_s"]))
    idle = 100.0 * (1.0 - trace["busy_s"] / window)
    top = ", ".join(f"{name} {1e3 * s:.3f}" for name, s in list(by_span.items())[:6])
    spans.note(run, f"device_idle = in_pull + in_host + outside the loop: {idle:.4f} = {out[0]:.4f} + {out[1]:.4f} + {out[2]:.4f} % "
                    f"over {trace['iterations_traced']} traced iterations; idle ms by span: {top}")
    return out


def read(run: dict):
    found = shares(run)
    return found[0] if found else None
