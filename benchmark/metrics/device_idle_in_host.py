"""The share of the traced window in which the device is idle while the loop's
thread is in any other `sheeprl/` span of an iteration than its blocking
reads, or in the iteration's self time (see device_idle_in_pull.py)."""

from .device_idle_in_pull import shares


def read(run: dict):
    found = shares(run)
    return found[1] if found else None
