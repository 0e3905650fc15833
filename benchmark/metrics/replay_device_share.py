"""Device time of the operations whose result has the replay ring's own shape
(`u8[rows,envs,64,64,3]`): whole-ring copies and scatters, over the traced window."""


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    ring = sum(op["seconds"] for op in trace["ops"] if op["shape"] == run["ring_shape"])
    return 100.0 * ring / trace["window_s"]
