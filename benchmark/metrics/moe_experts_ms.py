"""Device time of the grouped expert products in one train step: the
operations named `ragged-dot*` (`jax.lax.ragged_dot` as the TPU compiler
lowers it, the products and their metadata) that began inside an execution of
`jit_bd_train_step`, forward, recomputation and backward, mean over the traced
executions (benchmark/reduce/by_module.py)."""


def read(run: dict):
    steps = [s for s in run.get("moe_in_train_steps", ()) if s["ops"]]
    return 1e3 * sum(s["op_seconds"] for s in steps) / len(steps) if steps else None
