"""The action pull's wait for the device, per iteration: the program's
`rollout/action_wait/ready` spans, the part of the read (`rollout/action_wait`)
in which the host waits for the policy step's output to be defined, before
`rollout/action_wait/copy` turns it into host values. Median over the
window's iterations; an iteration whose read has no such part reads 0.

`split` serves the four readers of a read's parts (`action_*`, `metric_*`):
it sums the named part of every read span of an iteration (the token
mains' update opens a second `log/pull`), and notes, once a read, what the
parts leave of it (`rest`, the bookkeeping around them) and what it moved."""

import statistics

from ..reduce import spans

READ = "rollout/action_wait"
SPANS = (READ,)


def split(run: dict, read: str, part: str):
    w = spans.window(run)
    if not w:
        return None
    reads = [[r for r in w.children.get(it["span"], ()) if r["name"] == read] for it in w.iterations]
    parts = {name: [[c for r in rs for c in w.children.get(r["span"], ()) if c["name"] == f"{read}/{name}"] for rs in reads]
             for name in ("ready", "copy")}
    if not any(parts["ready"]) and not any(parts["copy"]):
        return None  # a program that does not split its reads
    ms = {name: [sum(c["dur_ms"] for c in cs) for cs in per_it] for name, per_it in parts.items()}
    whole = [sum(r["dur_ms"] for r in rs) for rs in reads]
    rest = [a - b - c for a, b, c in zip(whole, ms["ready"], ms["copy"])]
    counted = [r for rs in reads for r in rs if "bytes" in r]
    copy_ms = sum(ms["copy"])
    spans.note(run, f"{read}: ready {statistics.median(ms['ready']):.4f} + copy {statistics.median(ms['copy']):.4f} + rest "
                    f"{statistics.median(rest):.4f} = read {statistics.median(whole):.4f} ms, median of {len(whole)} iterations; "
                    f"largest rest {max(rest):.4f} ms")
    if counted:
        moved = sum(r["bytes"] for r in counted)
        spans.note(run, f"{read}: {len(counted)} reads, median {statistics.median(r['bytes'] for r in counted)} bytes in "
                        f"{statistics.median(r['arrays'] for r in counted)} arrays a read; "
                        f"{moved / (1e3 * copy_ms) if copy_ms > 0 else 0.0:.3f} MB/s of copy time")
    return statistics.median(ms[part])


def read(run: dict):
    return split(run, READ, "ready")
