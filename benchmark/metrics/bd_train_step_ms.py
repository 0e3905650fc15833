"""Device time of one execution of the update's program (`jit_bd_train_step`), from the trace: the mean over the traced executions."""

from ..reduce import by_module


def read(run: dict):
    runs = by_module.executions(run, "bd_train_step")
    return 1e3 * sum(runs) / len(runs) if runs else None
