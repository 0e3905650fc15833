"""The main thread blocked on the device, per iteration: the program's spans
`rollout/action_wait` (the pull of the action indices, which waits for the
policy step) and `log/pull` (the pull of the train metrics, which with
`--pipeline off` waits for the iteration's last train step), and what the
runtime holds the host back inside `train/slice`. That span, once a train
step, picks the step's row of the staged block and enqueues nothing on one
device (on several it holds the row's re-sharding); a later slice of an
iteration that takes longer than its fastest was held in the runtime. So the
held time is what an iteration's slices take beyond as many times its
fastest: 0 where the main has no `train/slice`, as the token mains have not.
Median over the window's iterations."""

import statistics

from ..reduce import spans

WAITS = ("rollout/action_wait", "log/pull")
HELD = "train/slice"
SPANS = WAITS  # the spans the main has to open for this reader; HELD may be absent


def per_iteration(w: spans.Window) -> list[float]:
    held = [sum(ms) - len(ms) * min(ms) if ms else 0.0 for ms in w.each(HELD)]
    return [wait + h for wait, h in zip(w.per_iteration(*WAITS), held)]


def read(run: dict):
    w = spans.window(run)
    if not w:
        return None
    slices = [ms for ms in w.each(HELD) if ms]
    if slices:
        spans.note(run, f"host_wait_ms: of it held inside {HELD}, median {statistics.median(sum(ms) - len(ms) * min(ms) for ms in slices):.3f} ms "
                        f"an iteration (slices an iteration: {statistics.median_low(len(ms) for ms in slices)}, the fastest {statistics.median(min(ms) for ms in slices):.3f} ms)")
    return statistics.median(per_iteration(w))
