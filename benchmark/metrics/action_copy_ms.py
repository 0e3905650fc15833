"""The action pull's copy of the ready arrays, per iteration: the program's
`rollout/action_wait/copy` spans, the part of the read that turns the
policy step's defined output into host values. Median over the window's
iterations (`action_ready_ms.split`)."""

from .action_ready_ms import READ, SPANS, split  # noqa: F401


def read(run: dict):
    return split(run, READ, "copy")
