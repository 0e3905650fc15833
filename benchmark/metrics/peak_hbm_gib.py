"""`memory_stats()["peak_bytes_in_use"]` of the fullest device after the window."""

SPANS = ()  # no span of the program's


def read(run: dict):
    return run["memory_peak_bytes"] / 2**30 if run["memory_peak_bytes"] else None
