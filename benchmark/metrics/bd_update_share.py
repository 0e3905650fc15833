"""The window's time in the iterations that held an update: by the harness's
own iteration stamps and its count of the train steps dispatched between them
(an update's iteration holds its two train steps, the build of their batches,
the wait for the last, and one collection step). The program's `update` spans
say from inside how much of those iterations the update itself is (a note line)."""


def read(run: dict):
    held = run.get("update_iteration_seconds")
    return 100.0 * sum(held) / run["window_s"] if held else None
