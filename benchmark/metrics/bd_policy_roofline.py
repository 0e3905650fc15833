"""A denoising step's share of its roofline: the least time the chip could
take for it, the larger of its operations over the peak rate and the bytes it
has to move (the bf16 weights touched, the valid part of the cache) over the
peak bandwidth (benchmark/flops_sdar.py; the context behind a block is the
traced stretch's mean, from the benchmark's own environments), over its mean
device time. Which bound applies goes on a note line."""

from .. import flops_sdar
from ..reduce.spans import note
from . import bd_policy_step_ms


def read(run: dict):
    ms = bd_policy_step_ms.read(run)
    if ms is None or not run.get("peaks") or "traced_context_tokens" not in run:
        return None
    ops, moved = flops_sdar.policy_step(run["model_config"], run["num_envs"], run["traced_context_tokens"])
    by_ops, by_bytes = ops / run["peaks"]["flops_per_s"], moved / run["peaks"]["bytes_per_s"]
    note(run, f"policy step: {ms:.3f} ms; bound by {'operations' if by_ops >= by_bytes else 'bytes'} "
              f"({1e3 * by_ops:.3f} ms against {1e3 * by_bytes:.3f} ms over the bus; context {run['traced_context_tokens']:.1f} tokens an environment)")
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
