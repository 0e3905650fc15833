"""Process start to the opening of the window: imports, backend, model and ring
allocation, prefill, compilation or cache load, warm-up."""


def read(run: dict):
    return run["setup_s"]
