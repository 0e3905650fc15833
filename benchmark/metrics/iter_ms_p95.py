"""95th percentile of the window's iteration times; the sample count goes on an
earlier output line. None under 20 iterations: no tail to read."""


import statistics


def read(run: dict):
    times = run["iteration_seconds"]
    if len(times) < 20:
        return None
    run.setdefault("notes", []).append(f"iter_ms_p95 over {len(times)} iterations")
    return 1e3 * statistics.quantiles(times, n=20)[18]
