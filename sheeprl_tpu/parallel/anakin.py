"""Anakin runtime helpers: env-batch sharding + collection telemetry.

The Podracer Anakin arrangement replicates the policy over the mesh and
shards the *environment batch* across it — each device steps its slice of
the envs and runs its slice of the policy, with zero cross-device traffic
inside the rollout scan (the gradient all-reduce in the update step is the
only collective). `shard_env_batch` places a collector carry (or any
pytree of `[N, ...]` leaves) accordingly; leaves whose leading dim does not
divide the mesh (PRNG keys, scalars) are replicated.

`AnakinStats` is the `Anakin/*` gauge source every wired main registers
with its Telemetry: collection rate, scan span, env batch and device
count."""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AnakinStats", "shard_env_batch"]


def shard_env_batch(tree: Any, mesh: Mesh, axis_name: str = "data") -> Any:
    """Shard every `[N, ...]` leaf of `tree` over the mesh's `axis_name`
    (leading axis = env batch); anything that doesn't divide is replicated.
    A no-op commit on 1-device meshes — the arrays still become committed,
    so `CompilePlan` shape capture records the layout the live calls use."""
    n_dev = mesh.shape[axis_name]

    def one(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % n_dev == 0:
            spec = P(axis_name)
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(one, tree)


class AnakinStats:
    """Collection-side counters for the fully-jitted rollout path.

    Usage in a main:

        anakin = AnakinStats(scan_span=T, env_batch=N, devices=n_dev)
        telem.add_gauges(anakin.gauges)
        ...
        t0 = time.perf_counter()
        carry, traj, ep = collect(...)   # jitted rollout
        jax.block_until_ready(traj)      # honest rate: scan fully retired
        anakin.note(T * N, time.perf_counter() - t0)
    """

    def __init__(self, scan_span: int, env_batch: int, devices: int):
        self.scan_span = int(scan_span)
        self.env_batch = int(env_batch)
        self.devices = int(devices)
        self.rollouts = 0
        self.env_steps_total = 0
        self.collect_seconds_total = 0.0
        self._last_sps = 0.0

    def note(self, env_steps: int, seconds: float) -> None:
        self.rollouts += 1
        self.env_steps_total += int(env_steps)
        self.collect_seconds_total += float(seconds)
        if seconds > 0:
            self._last_sps = env_steps / seconds

    @property
    def env_steps_per_second(self) -> float:
        return self._last_sps

    def gauges(self) -> dict[str, float]:
        """`Anakin/*` gauge source for `Telemetry.add_gauges`."""
        out = {
            "Anakin/env_steps_per_second": self._last_sps,
            "Anakin/scan_span": float(self.scan_span),
            "Anakin/env_batch": float(self.env_batch),
            "Anakin/devices": float(self.devices),
            "Anakin/rollouts": float(self.rollouts),
            "Anakin/env_steps_total": float(self.env_steps_total),
            "Anakin/collect_seconds_total": self.collect_seconds_total,
        }
        if self.collect_seconds_total > 0:
            out["Anakin/env_steps_per_second_avg"] = (
                self.env_steps_total / self.collect_seconds_total
            )
        return out
