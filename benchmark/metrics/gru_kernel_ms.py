"""Device time in the LayerNorm-GRU kernel per executed train step: the
scan's and the imagination's calls (see reduce/kernels.py)."""

from ..reduce import kernels
from . import train_step_ms

FAMILY = "gru"
TRAIN_STEP = ("gru_fwd_res",)
POLICY_STEP = ("gru_fwd",)  # the forward outside differentiation: on the note line only


def read(run: dict):
    return kernels.family_ms(run, FAMILY, TRAIN_STEP, POLICY_STEP, len(train_step_ms.executions(run)))
