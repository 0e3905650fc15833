"""Device time in the two-hot log-probability kernel per executed train step:
the reward head's and the critic's losses (see reduce/kernels.py)."""

from ..reduce import kernels
from . import train_step_ms

FAMILY = "two_hot"
TRAIN_STEP = ("two_hot_fwd",)
POLICY_STEP = ()  # the forward outside differentiation: on the note line only


def read(run: dict):
    return kernels.family_ms(run, FAMILY, TRAIN_STEP, POLICY_STEP, len(train_step_ms.executions(run)))
