"""The whole train step's share of the chips' peak: the analytic forward and
backward operations of one update (benchmark/flops.py, from shapes, no
recomputation) over its device time and chips x the bf16 peak. The v5e has no
other MXU rate (f32 products run as bf16 passes), so both precisions are held
to the same peak."""

from .. import flops
from . import train_step_ms


def read(run: dict):
    ms = train_step_ms.read(run)
    if ms is None or not run.get("peaks"):
        return None
    need = flops.dreamer_v3_train_step(run["model_config"])
    return 100.0 * need / (ms / 1e3) / (run["chips"] * run["peaks"]["flops_per_s"])
