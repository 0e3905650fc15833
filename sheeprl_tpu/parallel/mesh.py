"""Device mesh + sharding utilities — the framework's distributed runtime.

Replaces the reference's Lightning-Fabric/torch.distributed layer (DDP wrap,
process groups, NCCL/Gloo collectives — SURVEY.md §2.7) with the JAX SPMD
model: one process per host drives all its local devices; parallelism is a
`jax.sharding.Mesh` with named axes; gradient all-reduce, data sharding and
cross-device statistics are XLA collectives inserted by the compiler from
sharding annotations, riding ICI within a slice and DCN across slices.

Axes:
  - "data": batch/env-parallelism (the reference's DDP world) — params
    replicated, batch sharded, grad psum implicit in the sharded jit.
  - "seq": optional sequence/context parallelism — the TIME axis of
    `[T, B]` sequence batches sharded across devices for the per-timestep
    stages (conv encoder/decoder, reward/continue heads), with sharding
    constraints resharding to batch-only around the sequential RSSM scan.
    GSPMD inserts the all-gather/all-to-all collectives over ICI. Lets the
    world-model losses scale to long sequences / small batches where pure
    data parallelism runs out of batch to shard.
  - decoupled player/trainer topologies use *sub-meshes* of the same device
    set (see sheeprl_tpu/parallel/decoupled.py) instead of torch process
    groups.

Multi-host: call `distributed_setup()` (jax.distributed.initialize) once per
host before building the mesh; `jax.devices()` then spans the pod and the
same annotations scale out with zero code change.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "distributed_setup",
    "make_mesh",
    "data_sharding",
    "replicated_sharding",
    "shard_batch",
    "replicate",
    "local_mesh_devices",
    "process_index",
    "assert_divisible",
    "constrain_scan_inputs",
    "constrain_time_batch",
    "make_constrain",
    "scan_batch_spec",
    "seq_axis_size",
    "shard_time_batch",
    "time_batch_sharding",
]


def assert_divisible(total: int, n_dev: int, what: str) -> None:
    """Refuse silently-degraded sharding: a batch dimension that does not
    divide the mesh would either need padding or fall back to replicated
    compute, so a bad size/device combination is a configuration error."""
    if n_dev > 1 and total % n_dev != 0:
        raise ValueError(
            f"{what}={total} is not divisible by the {n_dev}-device mesh; "
            f"pick a size that is a multiple of the device count"
        )


def distributed_setup(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize multi-host JAX (one call per host process). No-ops when
    single-host or when the TPU pod runtime auto-configures itself."""
    if num_processes is not None and num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()


def process_index() -> int:
    return jax.process_index()


def local_mesh_devices(num_devices: int = -1, platform: Optional[str] = None):
    devices = jax.devices(platform) if platform else jax.devices()
    if num_devices > 0:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    return devices


def make_mesh(
    num_devices: int = -1,
    platform: Optional[str] = None,
    axis_name: str = "data",
    devices: Any = None,
    seq_devices: int = 1,
) -> Mesh:
    """Data mesh over (a prefix of) the visible devices. With
    `seq_devices > 1` the mesh is 2-D `(axis_name, "seq")` of shape
    `(n // seq_devices, seq_devices)` — the context-parallel layout where
    "seq" shards the time axis of sequence batches."""
    if devices is None:
        devices = local_mesh_devices(num_devices, platform)
    devices = np.asarray(devices)
    if seq_devices > 1:
        if devices.size % seq_devices != 0:
            raise ValueError(
                f"seq_devices={seq_devices} must divide the device count "
                f"({devices.size})"
            )
        return Mesh(
            devices.reshape(devices.size // seq_devices, seq_devices),
            (axis_name, "seq"),
        )
    return Mesh(devices, (axis_name,))


def seq_axis_size(mesh: Mesh) -> int:
    """Size of the sequence/context-parallel axis (1 when absent)."""
    return mesh.shape.get("seq", 1)


def make_constrain(mesh: Optional[Mesh]):
    """Return `constrain(x, *spec)` applying a `with_sharding_constraint`
    when `mesh` has an active "seq" axis, else the identity — the helper the
    context-parallel train steps use at their phase boundaries."""
    if mesh is not None and seq_axis_size(mesh) > 1:

        def constrain(x, *spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec))
            )

    else:

        def constrain(x, *spec):
            return x

    return constrain


_FULL_SCAN_SPEC = (None, ("data", "seq"))


def constrain_time_batch(constrain, *arrays, from_spec=None):
    """Apply the time-sharded `("seq", "data")` boundary spec to each of the
    `[T, B, ...]` RSSM scan outputs (the shared reshard point of every
    Dreamer-family train step).

    When the outputs come from the fully-sharded scan layout
    (`from_spec == (None, ("data", "seq"))`), reshard via the batch-on-"data"
    intermediate — see `constrain_scan_inputs` for why."""
    if from_spec == _FULL_SCAN_SPEC:
        arrays = tuple(constrain(a, None, "data") for a in arrays)
    return tuple(constrain(a, "seq", "data") for a in arrays)


def constrain_scan_inputs(constrain, scan_spec, *arrays):
    """Reshard time-sharded `[T, B, ...]` arrays into the RSSM scan layout.

    The direct reshard `("seq", "data") <-> (None, ("data", "seq"))` moves a
    mesh sub-axis between tensor axes in one step; GSPMD handles the forward
    but meets its TRANSPOSE in the backward pass with an involuntary full
    rematerialization (replicate-then-repartition, the partitioner's own
    words for it, in the dp x sp DV3 backward). Stepping through the
    batch-on-"data" intermediate splits both directions into a single-axis
    all-gather plus a local slice, which GSPMD partitions efficiently both
    ways."""
    if scan_spec == _FULL_SCAN_SPEC:
        arrays = tuple(constrain(a, None, "data") for a in arrays)
    out = tuple(constrain(a, *scan_spec) for a in arrays)
    return out if len(out) > 1 else out[0]


def scan_batch_spec(mesh: Optional[Mesh], batch_size: int) -> tuple:
    """Partition spec for the `[T, B, ...]` inputs of the sequential RSSM
    scan under context parallelism: batch over "data", replicated over
    "seq". The scan needs full T per shard, so its batch is the only
    shardable axis; the seq groups compute replicated scans (seq-times the
    scan FLOPs — a small, latency-bound slice of the step), and both phase
    boundaries are then single-axis reshards: a "seq" all-gather into the
    scan, a local time-slice out of it, in both differentiation directions.

    The alternative — sharding the scan batch over the WHOLE grid,
    `(None, ("data", "seq"))`, when B divides it — does zero redundant
    FLOPs but its boundary reshard moves a mesh sub-axis between tensor
    axes, which GSPMD's transpose meets with an involuntary full
    rematerialization (replicate + repartition) in EVERY backward pass
    (still present through a two-step reshard). The replicated-scan layout
    avoids that copy of the whole scan input on every device; which of the
    two is faster end to end is not measured on the chip.
    `constrain_scan_inputs` keeps the two-step path for when a
    fully-sharded spec returns."""
    return (None, "data")


def data_sharding(mesh: Mesh, axis: int = 0, axis_name: str = "data") -> NamedSharding:
    """Shard the given array axis across the mesh's data axis."""
    spec = [None] * (axis + 1)
    spec[axis] = axis_name
    return NamedSharding(mesh, P(*spec))


def time_batch_sharding(
    mesh: Mesh, time_axis: int = 0, batch_axis: int = 1
) -> NamedSharding:
    """Sharding for `[..., T, ..., B, ...]` sequence batches: batch over
    "data" and — when the mesh has a "seq" axis — time over "seq" (the
    context-parallel input layout)."""
    spec = [None] * (max(time_axis, batch_axis) + 1)
    spec[batch_axis] = "data"
    if seq_axis_size(mesh) > 1:
        spec[time_axis] = "seq"
    return NamedSharding(mesh, P(*spec))


def shard_time_batch(
    tree: Any, mesh: Mesh, time_axis: int = 0, batch_axis: int = 1
) -> Any:
    """`shard_batch` for `[T, B, ...]` sequence data: batch always shards
    over "data"; time additionally shards over "seq" when present.

    Multi-host: each process contributes full-T, local-B data, so every seq
    group (a fixed data index, all seq indices) must live on ONE process —
    a seq axis spanning hosts would stitch unrelated per-host samples along
    time. `make_mesh` lays devices out process-major, so this holds whenever
    seq_devices divides the local device count; guard against the rest."""
    if jax.process_count() > 1 and seq_axis_size(mesh) > 1:
        for row in mesh.devices:  # fixed data index, varying seq
            if len({d.process_index for d in row}) != 1:
                raise ValueError(
                    "the seq mesh axis spans processes; pick seq_devices "
                    f"dividing the local device count ({jax.local_device_count()})"
                )
    return _put_sharded(tree, time_batch_sharding(mesh, time_axis, batch_axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _put_sharded(tree: Any, sharding: NamedSharding) -> Any:
    """One transfer per leaf, landing already distributed. Multi-host: each
    process passes its *local* shard and the result is a global array
    spanning the pod (the JAX-native replacement for the reference's
    DistributedSampler sharding, SURVEY.md §2.7)."""
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
            tree,
        )
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def shard_batch(tree: Any, mesh: Mesh, axis: int = 0, axis_name: str = "data") -> Any:
    """device_put a host batch with its `axis` sharded over the mesh."""
    return _put_sharded(tree, data_sharding(mesh, axis, axis_name))


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Replicate params across the mesh (the DDP 'same weights everywhere'
    invariant, enforced by sharding instead of broadcast)."""
    sharding = replicated_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)
