"""The metric pull's copy of the ready arrays, per iteration: the program's
`log/pull/copy` spans, the part of the read that turns the train steps'
defined metrics into host values. Median over the window's iterations
(`action_ready_ms.split`)."""

from .action_ready_ms import split
from .metric_ready_ms import READ, SPANS  # noqa: F401


def read(run: dict):
    return split(run, READ, "copy")
