"""From the program's own `span` events to the numbers the metrics read.

The main keeps its loop's spans in memory and writes them to
`telemetry.jsonl` as it ends (sheeprl_tpu/telemetry/phase.py): one `iteration`
per loop body and, as its children, one span per phase, each with its start on
the `perf_counter` clock (`p0`), its length (`dur_ms`), its parent's id and
the counters the program attached. The harness hands the file's events to
every reader as `run["events"]`, and its window as two readings of the same
clock (`run["t_open"]`, `run["window_s"]`).

A span belongs to the window if it starts inside it. The harness's iteration
boundaries lie inside `rollout/env_step` (its clock is environment 0's
`step()`), the program's at the top of the loop body: the same count of
iterations, shifted by a part of one.

A program without these spans (the parent of the PR that brought them) gives
`None` everywhere, and so does a run whose count of `iteration` spans is not
the harness's count of iterations.
"""

from __future__ import annotations

import bisect
import itertools
import statistics

ITERATION = "iteration"


def note(run: dict, line: str) -> None:
    if line not in run.setdefault("notes", []):
        run["notes"].append(line)


class Window:
    """The loop's spans that start inside the measured window."""

    def __init__(self, run: dict):
        spans = [e for e in run.get("events", ()) if e.get("event") == "span" and "p0" in e]
        t0, t1 = run["t_open"], run["t_open"] + run["window_s"]
        self.started = [s for s in spans if t0 <= s["p0"] < t1]
        self.iterations = [s for s in self.started if s["name"] == ITERATION]
        # the loop bodies that start after the window: a traced run's profiler session covers them
        self.iterations_after = [s for s in spans if s["name"] == ITERATION and s["p0"] >= t1]
        self.children: dict[str, list[dict]] = {}
        for s in spans:
            if s.get("parent") is not None:
                self.children.setdefault(s["parent"], []).append(s)
        # the harness's own boundaries, to name one of its iterations by a span
        self.boundaries = list(itertools.accumulate(run["iteration_seconds"], initial=t0))
        self.ok = bool(self.iterations) and len(self.iterations) == run["iterations"]

    def named(self, name: str) -> list[dict]:
        return [s for s in self.started if s["name"] == name]

    def per_iteration(self, *names: str) -> list[float]:
        """ms in the children of those names, for each iteration of the window."""
        return [
            sum(c["dur_ms"] for c in self.children.get(it["span"], ()) if c["name"] in names)
            for it in self.iterations
        ]

    def each(self, name: str) -> list[list[float]]:
        """ms of every child of that name (a span a train step opens has several), for each iteration of the window."""
        return [[c["dur_ms"] for c in self.children.get(it["span"], ()) if c["name"] == name] for it in self.iterations]

    def harness_index(self, span: dict) -> int:
        """Which of the harness's iterations (`slowest iterations` counts the same way) holds the span's start."""
        return bisect.bisect_right(self.boundaries, span["p0"]) - 1


def window(run: dict) -> Window | None:
    """The window's spans, read once per run; None where there is nothing sound
    to read. The identities that make the numbers trustworthy go on note lines."""
    if "span_window" not in run:
        run["span_window"] = _checked(run)
    return run["span_window"]


def _checked(run: dict) -> Window | None:
    w = Window(run)
    if not w.iterations:
        return None
    note(run, f"spans: {len(w.iterations)} iteration spans start in the window; the harness counted {run['iterations']} iterations")
    if not w.ok:
        return None
    whole = [it["dur_ms"] for it in w.iterations]
    median, outside = statistics.median(whole), 1e3 * statistics.median(run["iteration_seconds"])
    note(run, f"spans: iteration span median {median:.3f} ms against the harness's {outside:.3f} ms "
              f"({100.0 * (median / outside - 1.0):+.2f} %)")
    names = sorted({c["name"] for it in w.iterations for c in w.children.get(it["span"], ())})
    parts = {n: statistics.median(w.per_iteration(n)) for n in names}
    covered = w.per_iteration(*names)
    self_ms = [a - b for a, b in zip(whole, covered)]
    note(run, "spans: median iteration = " + " + ".join(f"{n} {v:.3f}" for n, v in parts.items())
              + f" + self {statistics.median(self_ms):.3f} ms; children + self = iteration in every one, least self time {min(self_ms):.4f} ms")
    after = [it["dur_ms"] for it in w.iterations_after[:-1]]  # the last one holds the way out
    if run.get("trace_dir") and after:
        # what the annotations cost with a profiler session open: the same loop, traced right after the window
        note(run, f"spans: {len(after)} iterations after the window, under the profiler: median {statistics.median(after):.3f} ms "
                  f"against the window's {median:.3f} ms")
    return w


def median_ms(run: dict, *names: str):
    """Median over the window's iterations of the time in those children."""
    w = window(run)
    return statistics.median(w.per_iteration(*names)) if w else None
