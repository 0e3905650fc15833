"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

`load` turns the trace into plain lists (plane -> line -> events of
[name, start_ns, duration_ns, detail]); `reduce` works on those alone, so a
small recorded trace kept as JSON tests it without a chip.

What is read from a TPU's trace: each chip is a plane `/device:TPU:<n>`;
its line `XLA Ops` holds one event per executed HLO operation, named by the
operation's whole text (`%copy.7 = u8[86016,4,64,64,3]{...} copy(...)`: kept
as `detail`, the name cut to `copy.7`), its line `XLA Modules` one per
executed program (`jit_train_step(<fingerprint>)`). The host is the plane `/host:CPU`, one line per
thread, with the `TraceAnnotation` spans the benchmark wrote.
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


def name_gaps(idle_gaps: list[tuple[int, int]], host_events: list, t0: int) -> list[list]:
    """Each idle gap by what the host was doing when it opened: the shortest
    host span that covers that instant. -> the time lost by name, then the
    longest single gaps, at most TOP rows."""
    spans = sorted((e[1], e[1] + e[2], e[0]) for e in host_events if e[2] > 0)
    named: dict[str, float] = {}
    longest, active, nxt = [], [], 0
    for a, b in idle_gaps:
        while nxt < len(spans) and spans[nxt][0] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        what = min(active, key=lambda s: s[1] - s[0])[2] if active else "no host span"
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

    busy_ns, idle_gaps = [], []
    op_seconds: dict[str, float] = {}
    ops: list[dict] = []
    for d, events in enumerate(ops_by_device):
        spans = union([(e[1], e[1] + e[2]) for e in events])
        busy_ns.append(total(spans))
        if d == 0:
            idle_gaps = subtract([(t0, t1)], spans)
        if d == 0:
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

    host = next((p for p in planes if p["name"] == HOST_PLANE), {"lines": []})
    gaps = name_gaps(idle_gaps, [e for l in host["lines"] for e in l["events"]], t0)

    n = len(devices)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "busy_s_by_device": [b / 1e9 for b in busy_ns],
        "ops": ops,
        "modules": modules,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": gaps,
        },
    }
