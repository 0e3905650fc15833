"""Wall time inside the benchmark environments' `step()` and `reset()` over the
window: shows that the generator is not the bottleneck."""


def read(run: dict):
    return 100.0 * run["env_host_seconds"] / run["window_s"]
