"""The LayerNorm-GRU kernel's share of its roofline in the T-step RSSM scan
alone: its [B, .] calls, one per time step (see gru_roofline for the
arithmetic). There the loop carries the weight in on-chip memory, so the
calls are bound by their operations, and B rows fill little of the matrix unit."""

from . import gru_roofline


def read(run: dict):
    if not run.get("trace"):
        return None
    return gru_roofline.share(run, only_rows=run["model_config"]["per_rank_batch_size"])
