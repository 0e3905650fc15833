"""The metric pull's wait for the device, per iteration: the program's
`log/pull/ready` spans, the part of the read (`log/pull`) in which the host
waits for the train steps whose metrics it pulls to finish (with
`--pipeline on`, those of the snapshot an interval old). Median over the
window's iterations (`action_ready_ms.split`)."""

from .action_ready_ms import split

READ = "log/pull"
SPANS = (READ,)


def read(run: dict):
    return split(run, READ, "ready")
