"""Device time of some operations inside the executions of one program.

`trace.reduce` keeps every operation's time and every program's executions,
not which operation ran inside which program. Two programs that run the same
kind of operation (the grouped expert product runs in a train step, a policy
step and a prefill alike) are told apart here: by the start of each event on
the device's own clock. Works on `trace.load`'s plain lists alone.
"""

from __future__ import annotations

import bisect

from .trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, _line


def executions(run: dict, module_part: str) -> list[float]:
    """Device seconds of each traced execution of the programs whose name holds `module_part`."""
    trace = run.get("trace")
    return [s for name, runs in trace["modules"].items() if module_part in name for s in runs] if trace else []


def seconds_inside(planes: list[dict], module_part: str, op_prefix: str) -> list[dict]:
    """For each execution, on the first chip, of a program whose name holds
    `module_part`: its device seconds, and the seconds and count of the
    operations whose name starts with `op_prefix` that began inside it."""
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p) for p in planes if DEVICE_PLANE.match(p["name"]))
    if not devices:
        return []
    plane = devices[0][1]
    ops = sorted((e[1], e[2]) for e in _line(plane, OPS_LINE) if e[0].startswith(op_prefix))
    starts = [s for s, _ in ops]
    out = []
    for name, start, dur, _ in _line(plane, MODULES_LINE):
        if module_part not in name:
            continue
        lo, hi = bisect.bisect_left(starts, start), bisect.bisect_left(starts, start + dur)
        out.append({"seconds": dur / 1e9, "op_seconds": sum(d for _, d in ops[lo:hi]) / 1e9, "ops": hi - lo})
    return out
