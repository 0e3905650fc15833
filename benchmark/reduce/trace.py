"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

`load` turns the trace into plain lists (plane -> line -> events of
[name, start_ns, duration_ns, detail]); `reduce` works on those alone, so a
small recorded trace kept as JSON tests it without a chip.

What is read from a TPU's trace: each chip is a plane `/device:TPU:<n>`;
its line `XLA Ops` holds one event per executed HLO operation, named by the
operation's whole text (`%copy.7 = u8[86016,4,64,64,3]{...} copy(...)`: kept
as `detail`, the name cut to `copy.7`), its line `XLA Modules` one per
executed program (`jit_train_step(<fingerprint>)`). The host is the plane `/host:CPU`, one line per
thread, with the `TraceAnnotation` spans the benchmark wrote (`env.step`) and those
the program wrote, `sheeprl/<span>` (sheeprl_tpu/telemetry/phase.py): its loop's
thread is the line that holds the `sheeprl/iteration` step annotations.

Every idle stretch of a device is put down to the innermost `sheeprl/` span of
that thread which covers it, piece by piece where a gap crosses from one span
into the next; what no `sheeprl/iteration` covers is outside the loop.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
OP_NAME = re.compile(r"^%?([\w.\-]+)")
MAX_DETAIL = 700
TOP = 10
SPAN_PREFIX = "sheeprl/"
ITERATION = SPAN_PREFIX + "iteration"


# ------------------------------------------------------------------ loading
def load(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                events.append(event(e.name, int(e.start_ns), int(e.duration_ns)))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def event(text: str, start_ns: int, duration_ns: int) -> list:
    """[short name, start, duration, the text the trace gave]."""
    m = OP_NAME.match(text) if " = " in text else None
    return [m.group(1) if m else text, start_ns, duration_ns, text[:MAX_DETAIL] if m else ""]


def newest_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce(load(newest_trace(trace_dir)), chips)


# ---------------------------------------------------------------- intervals
def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: list[tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The parts of the merged intervals `a` that no interval of merged `b` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# ---------------------------------------------------------------- reduction
def _line(plane: dict, name: str) -> list:
    return next((l["events"] for l in plane["lines"] if l["name"] == name), [])


def result_shape(detail: str) -> str:
    """`u8[21504,16,64,64,3]` out of an operation's text; '' where it has none."""
    m = re.search(r"=\s*\(?([a-z]+[0-9]*\[[0-9,]*\])", detail) or re.search(r"([a-z]+[0-9]*\[[0-9,]*\])", detail)
    return m.group(1) if m else ""


def program_spans(host_lines: list[dict]) -> list[tuple[int, int, str]]:
    """(start, end, name) of the program's `sheeprl/` spans on the thread
    whose line holds its `sheeprl/iteration` annotations; [] where none does."""
    def iterations(line):
        return sum(e[0] == ITERATION for e in line["events"])

    main = max(host_lines, key=iterations, default=None)
    if main is None or not iterations(main):
        return []
    return [(e[1], e[1] + e[2], e[0]) for e in main["events"] if e[0].startswith(SPAN_PREFIX) and e[2] > 0]


def innermost(spans: list[tuple[int, int, str]]) -> list[tuple[int, int, str, bool]]:
    """The stretches between the spans' ends, each with the innermost span that
    covers it (the latest to open; of two, the shorter) and whether an
    iteration covers it. Stretches no span covers are left out."""
    points = sorted({t for s in spans for t in s[:2]})
    opening = sorted(spans)
    out, active, nxt = [], [], 0
    for a, b in zip(points, points[1:]):
        while nxt < len(opening) and opening[nxt][0] <= a:
            active.append(opening[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        if active:
            inner = max(active, key=lambda s: (s[0], s[0] - s[1]))
            out.append((a, b, inner[2], any(s[2] == ITERATION for s in active)))
    return out


def split_idle(idle: list[tuple[int, int]], pieces: list[tuple[int, int, str, bool]]) -> tuple[dict[str, int], int]:
    """Idle nanoseconds inside an iteration by the innermost span (its name
    without the prefix; `iteration` is the iteration's self time), and those
    outside any iteration. `idle` and `pieces` are sorted and disjoint."""
    by_span: dict[str, int] = {}
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi, name, in_loop = pieces[k]
            part = min(b, hi) - max(a, lo)
            if in_loop and part > 0:
                key = name[len(SPAN_PREFIX):]
                by_span[key] = by_span.get(key, 0) + part
            k += 1
    return by_span, total(idle) - sum(by_span.values())


def name_gaps(idle_gaps: list[tuple[int, int]], host_events: list, t0: int, spans=()) -> list[list]:
    """Each idle gap by what the host was doing when it opened: the program's
    innermost span there (of `spans`, from `program_spans`), then the shortest
    other host event of any thread that covers that instant:
    `sheeprl/rollout/action_wait > np.asarray(jax.Array)`. -> the time lost
    by name, then the longest single gaps, at most TOP rows."""
    own, pieces = set(spans), innermost(list(spans))
    events = sorted(s for s in ((e[1], e[1] + e[2], e[0]) for e in host_events if e[2] > 0) if s not in own)
    named: dict[str, float] = {}
    longest, active, nxt, j = [], [], 0, 0
    for a, b in idle_gaps:
        while nxt < len(events) and events[nxt][0] <= a:
            active.append(events[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        span = pieces[j][2] if j < len(pieces) and pieces[j][0] <= a else None
        event = min(active, key=lambda s: s[1] - s[0])[2] if active else None
        what = " > ".join(x for x in (span, event) if x) or "no host span"
        named[what] = named.get(what, 0.0) + (b - a) / 1e9
        longest.append(((b - a) / 1e9, what, (a - t0) / 1e6))
    rows = [[f"all gaps during: {k}", v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[: TOP // 2]]
    rows += [[f"one gap at {at:.1f} ms during: {what}", s] for s, what, at in sorted(longest, reverse=True)[: TOP - len(rows)]]
    return rows


def reduce(planes: list[dict], chips: int) -> dict:
    devices = sorted(
        (int(DEVICE_PLANE.match(p["name"]).group(1)), p) for p in planes if DEVICE_PLANE.match(p["name"])
    )[:chips]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    ops_by_device = [_line(p, OPS_LINE) for _, p in devices]
    every = [e for ops in ops_by_device for e in ops]
    if not every:
        raise ValueError("no operation ran on the device in the traced window")
    t0 = min(e[1] for e in every)
    t1 = max(e[1] + e[2] for e in every)

    host = next((p for p in planes if p["name"] == HOST_PLANE), {"lines": []})
    program = program_spans(host["lines"])
    pieces = innermost(program)

    busy_ns, idle_gaps = [], []
    idle_by_span: dict[str, int] = {}
    idle_outside = 0
    op_seconds: dict[str, float] = {}
    ops: list[dict] = []
    for d, events in enumerate(ops_by_device):
        busy = union([(e[1], e[1] + e[2]) for e in events])
        busy_ns.append(total(busy))
        idle = subtract([(t0, t1)], busy)
        by_span, outside = split_idle(idle, pieces)
        for name, ns in by_span.items():
            idle_by_span[name] = idle_by_span.get(name, 0) + ns
        idle_outside += outside
        if d == 0:
            idle_gaps = idle
            for name, _, dur, detail in events:
                shape = result_shape(detail)
                key = f"{name} {shape}".strip()
                op_seconds[key] = op_seconds.get(key, 0.0) + dur / 1e9
                op = {"name": name, "shape": shape, "seconds": dur / 1e9}
                if "custom-call(" in detail:  # a kernel: its reader wants the operands
                    op["detail"] = detail
                ops.append(op)

    modules: dict[str, list[float]] = {}
    for name, _, dur, _ in _line(devices[0][1], MODULES_LINE):
        modules.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(dur / 1e9)

    gaps = name_gaps(idle_gaps, [e for l in host["lines"] for e in l["events"]], t0, program)

    n = len(devices)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy_ns],
        "ops": ops,
        "modules": modules,
        # the idle time by the program's span, the mean over the devices: these and
        # the outside part add up to window_s - busy_s
        "idle_by_span_s": {k: v / n / 1e9 for k, v in sorted(idle_by_span.items(), key=lambda kv: -kv[1])},
        "idle_outside_s": idle_outside / n / 1e9,
        "iterations_traced": sum(s[2] == ITERATION for s in program),
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": gaps,
        },
    }
