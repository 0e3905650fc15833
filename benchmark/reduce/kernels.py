"""Device time of one family of the program's Pallas kernels, per executed
train step.

The program names its kernels (`pallas_call(name=)`, one table:
`sheeprl_tpu.ops.pallas_kernels.KERNEL_NAMES`); the instruction, and so the
trace's event, is `<name>.<n>`. Only the names the train step calls are summed
and divided by its executions (`<x>_fwd_res`, the forward under
differentiation; two-hot has one name and only the train step calls it). The
forward outside differentiation (`<x>_fwd`) is the policy step's, once an
iteration whatever the traffic's train ratio: it goes on the note line with
the calls by name and stays out of the sum. `None` where no kernel of the
family ran in a train step (on several chips every family takes its XLA
twin), with the program's own `kernel.select` record of why.
"""

from __future__ import annotations

from .spans import note


def family_ms(run: dict, family: str, train_step: tuple[str, ...], policy_step: tuple[str, ...], steps: int):
    """ms in the events named `train_step` per each of `steps` executions."""
    trace = run.get("trace")
    if not trace:
        return None
    found: dict[str, list[float]] = {}
    for op in trace["ops"]:
        name = op["name"].rsplit(".", 1)[0]
        if "detail" in op and name in train_step + policy_step:
            found.setdefault(name, []).append(op["seconds"])
    if not steps or not any(name in found for name in train_step):
        refused = sorted({
            e.get("reason", "?") for e in run.get("events", ())
            if e.get("event") == "kernel.select" and e.get("family") == family and not e.get("selected")
        })
        note(run, f"{family} kernels: no event named {' / '.join(train_step)} in a traced train step"
                  + (f"; kernel.select refused the family: {', '.join(refused)}" if refused else ""))
        return None
    note(run, f"{family} kernels over {steps} traced train steps: " + "; ".join(
        f"{name} {len(s)} calls, mean {1e6 * sum(s) / len(s):.2f} us" + (" (policy step: not in the sum)" if name in policy_step else "")
        for name, s in sorted(found.items())
    ))
    return 1e3 * sum(sum(found.get(name, ())) for name in train_step) / steps
