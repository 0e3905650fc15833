"""Seconds in trace, lowering and backend compilation (or cache load) before
the window, from the benchmark's own `jax.monitoring` listener."""


def read(run: dict):
    return run["compile_seconds_before_window"]
