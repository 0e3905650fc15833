"""The grouped expert products' share of their roofline in a train step: the
least time for the forward and backward products of the assignments the
program's counter reports (mean over the train steps from the window's
opening on; benchmark/flops_sdar.py), the larger of operations over the peak
rate and bytes over the peak bandwidth, over `moe_experts_ms`, which holds the
recomputed forward too."""

from .. import flops_sdar
from ..reduce import updates
from ..reduce.spans import note
from . import moe_experts_ms


def read(run: dict):
    ms = moe_experts_ms.read(run)
    steps = updates.train_steps(updates.from_window_on(run))
    if ms is None or not steps or not run.get("peaks"):
        return None
    ops, moved = flops_sdar.expert_products(run["model_config"], sum(s["assignments"] for s in steps) / len(steps))
    by_ops, by_bytes = ops / run["peaks"]["flops_per_s"], moved / run["peaks"]["bytes_per_s"]
    note(run, f"expert products: {ms:.3f} ms a train step; bound by {'operations' if by_ops >= by_bytes else 'bytes'} "
              f"({1e3 * by_ops:.3f} ms against {1e3 * by_bytes:.3f} ms over the bus)")
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
