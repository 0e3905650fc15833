"""All environment steps of the window over all the measured time of the window:
whole iterations between two fenced iteration boundaries, never a count over
the nominal `--seconds`."""


def read(run: dict):
    return run["env_steps"] / run["window_s"]
