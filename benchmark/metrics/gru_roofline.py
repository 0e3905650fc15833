"""The LayerNorm-GRU kernel's share of its roofline over every forward call
of it in the traced train steps: the least time the chip could take for the
calls — for each the larger of its operations over the peak rate and the
bytes it moves over the main-memory bus over the peak bandwidth
(benchmark/flops.py; the bytes as the trace shows the operands placed) — over
the device time the calls took. The calls by their rows, and which bound
applies to each, go on an earlier line. Nothing to read where the kernel did
not run (several chips: every family takes its XLA twin)."""

import re

from .. import flops

# (dt[rows,H], dt[rows,3H], ...) custom-call(dt[rows,n_in] x, dt[rows,H] h, dt[n_in+H,3H] w, ...
CALL = re.compile(r"= \(\w+\[(\d+),(\d+)\]\S*, \w+\[\d+,(\d+)\]\S*,.*? custom-call\((.*)")
OPERAND = re.compile(r"(\w+)\[(\d+),(\d+)\]")


def calls(run: dict) -> dict[tuple, list[tuple[float, float]]]:
    """(rows, n_in, hidden, dtype) -> (device seconds, bytes over the bus) of each forward call."""
    found: dict[tuple, list[tuple[float, float]]] = {}
    for op in (run.get("trace") or {}).get("ops", []):
        m = CALL.search(op.get("detail", ""))
        if not m:
            continue
        rows, hidden, gates = int(m.group(1)), int(m.group(2)), int(m.group(3))
        weight = next(
            ((d, int(k)) for d, k, n in OPERAND.findall(m.group(4)) if int(n) == gates and int(k) > hidden), None
        )
        if gates != 3 * hidden or weight is None:
            continue
        key = (rows, weight[1] - hidden, hidden, weight[0])
        found.setdefault(key, []).append((op["seconds"], flops.hbm_bytes(op["detail"])))
    return found


def share(run: dict, only_rows: int | None = None):
    """100 x least time / device time over the calls (those of `only_rows` rows)."""
    found = {k: v for k, v in calls(run).items() if only_rows is None or k[0] == only_rows}
    if not found or not run.get("peaks"):
        return None
    least = taken = 0.0
    for (rows, n_in, hidden, dtype), each in sorted(found.items()):
        by_ops = flops.layernorm_gru_cell(rows, n_in, hidden) / run["peaks"]["flops_per_s"]
        by_bytes = sum(b for _, b in each) / len(each) / run["peaks"]["bytes_per_s"]
        mean = sum(s for s, _ in each) / len(each)
        least += len(each) * max(by_ops, by_bytes)
        taken += len(each) * mean
        note = (
            f"gru kernel: {len(each)} calls of [{rows},{n_in}+{hidden}]x[{n_in + hidden},{3 * hidden}] {dtype}, "
            f"mean {1e6 * mean:.2f} us; bound by {'operations' if by_ops >= by_bytes else 'bytes'} "
            f"({1e6 * by_ops:.2f} us against {1e6 * by_bytes:.2f} us over the bus)"
        )
        if note not in run.setdefault("notes", []):
            run["notes"].append(note)
    return 100.0 * least / taken


def read(run: dict):
    return share(run)
