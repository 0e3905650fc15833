"""Device time in the encoder's and decoder's fused conv + LayerNorm + SiLU
kernels per executed train step (see reduce/kernels.py)."""

from ..reduce import kernels
from . import train_step_ms

FAMILY = "cnn"
TRAIN_STEP = ("cnn_enc_fwd_res", "cnn_dec_fwd_res")
POLICY_STEP = ("cnn_enc_fwd", "cnn_dec_fwd")  # the forward outside differentiation: on the note line only


def read(run: dict):
    return kernels.family_ms(run, FAMILY, TRAIN_STEP, POLICY_STEP, len(train_step_ms.executions(run)))
