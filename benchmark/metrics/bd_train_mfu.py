"""The whole train step's share of the chip's peak: the analytic forward and
backward operations of one train step (benchmark/flops_sdar.py: from the
trained sequences' lengths, the routed products by the assignments the
program's counter reports; no padding, no recomputation), mean over the train
steps from the window's opening on, over the traced executions' mean device
time and the bf16 peak. The program computes every batch at one padded shape,
so its time does not follow a batch's content; the mean of many batches does."""

from .. import flops_sdar
from ..reduce import updates
from . import bd_train_step_ms


def read(run: dict):
    ms = bd_train_step_ms.read(run)
    steps = updates.train_steps(updates.from_window_on(run))
    if ms is None or not steps or not run.get("peaks"):
        return None
    need = sum(flops_sdar.train_step(run["model_config"], s["lengths"], s["assignments"]) for s in steps) / len(steps)
    return 100.0 * need / (ms / 1e3) / (run["chips"] * run["peaks"]["flops_per_s"])
