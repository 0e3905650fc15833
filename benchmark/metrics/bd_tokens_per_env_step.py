"""Ids the benchmark's environments were handed per environment step, over
the window: counted on the benchmark's own side, in `step()`. 2.0 by this
traffic (4 positions a block, 2 denoising steps): it guards what an
environment step means. The program's own `tokens_committed` counter (on its
`rollout/pack` spans) says the same from inside."""


def read(run: dict):
    return run["tokens_committed"] / run["env_steps"] if run.get("tokens_committed") is not None and run.get("env_steps") else None
