"""Device time of one execution of the train-step program, from the trace: the
mean over the executions in the traced window."""


def executions(run: dict) -> list[float]:
    trace = run.get("trace")
    if not trace:
        return []
    return [s for name, runs in trace["modules"].items() if "train_step" in name for s in runs]


def read(run: dict):
    runs = executions(run)
    return 1e3 * sum(runs) / len(runs) if runs else None
