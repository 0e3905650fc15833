"""Seconds in trace, lowering and backend compilation (or cache load) before
the window, from the benchmark's own `jax.monitoring` listener."""

SPANS = ()  # no span of the program's


def read(run: dict):
    return run["compile_seconds_before_window"]
