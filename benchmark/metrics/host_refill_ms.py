"""The stretch in which the device has nothing queued: from the end of
`log/pull` (the host has seen the last train step's results, so the device
has run dry) to the end of the next iteration's `rollout/policy_dispatch` (work
is queued again): logging, the checkpoint test, the blob pack, the dispatch.
Median over the window's consecutive iterations."""

import statistics

from ..reduce import spans


def read(run: dict):
    w = spans.window(run)
    if not w:
        return None
    dispatched = {s["step"]: s["p0"] + s["dur_ms"] / 1e3 for s in w.named("rollout/policy_dispatch")}
    steps = sorted(dispatched)
    following = dict(zip(steps, steps[1:]))  # the step that comes after each
    gaps = [
        1e3 * (dispatched[following[s["step"]]] - (s["p0"] + s["dur_ms"] / 1e3))
        for s in w.named("log/pull") if s["step"] in following
    ]
    return statistics.median(gaps) if gaps else None
