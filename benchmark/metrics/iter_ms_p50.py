"""Median of the window's iteration times."""


import statistics


def read(run: dict):
    return 1e3 * statistics.median(run["iteration_seconds"])
