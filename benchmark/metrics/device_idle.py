"""1 - the union of the intervals in which an operation ran on the device, over
the traced window; on several chips the mean over the devices."""


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
