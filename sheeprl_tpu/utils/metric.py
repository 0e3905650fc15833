"""Metric aggregation (capability parity with
/root/reference/sheeprl/utils/metric.py): a named dict of running means
updated every step and computed/reset once per logging interval, plus a
windowed moving-average metric. Values may be jax scalars — they are pulled
to host lazily at compute() time, so updating inside the hot loop never
forces a device sync; compute() first issues ONE overlapping async
device->host copy per pending device array, so the N pulls of a compute
over N train metrics overlap instead of running one after another.

A compiled step may hand its scalars over as ONE vector (`PackedScalars`,
built inside the jit by `packed_metrics`): its names are `_Lane`s that
share the vector's one copy and one conversion, so an interval's pull is
one array a train step, not one per metric."""

from __future__ import annotations

import functools
from collections import deque
from collections.abc import Mapping
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MetricAggregator",
    "MovingAverageMetric",
    "PackedScalars",
    "PendingMetrics",
    "packed_metrics",
]


@jax.tree_util.register_pytree_node_class
class PackedScalars(Mapping):
    """The named scalar metrics of one compiled step as ONE float32 vector
    (`values[i]` is `names[i]`, names sorted as a jit's dict output sorts
    them), beside the arrays that stay their own (`loose`: resilience's skip
    flag, which `update_skipped` pops and reads one update lagged). A pytree
    whose leaves are the vector and the loose arrays and whose names are
    static, so a jit returns it; on the host `self[name]` is a `_Lane`."""

    __slots__ = ("names", "values", "loose", "_host")

    def __init__(self, names: tuple[str, ...], values, loose: dict | None = None) -> None:
        self.names = names
        self.values = values
        self.loose = dict(loose or {})
        self._host: np.ndarray | None = None

    @classmethod
    def pack(cls, metrics: Mapping[str, Any], loose: Iterable[str] = ()) -> "PackedScalars":
        """Inside the jit: every scalar of `metrics` but the `loose` keys
        into one float32 vector."""
        kept = {k: metrics[k] for k in loose if k in metrics}
        names = tuple(sorted(k for k in metrics if k not in kept))
        for k in names:
            if jnp.ndim(metrics[k]) != 0:
                raise ValueError(f"metric {k!r} is not a scalar: shape {jnp.shape(metrics[k])}")
        values = jnp.stack([jnp.asarray(metrics[k], jnp.float32) for k in names])
        return cls(names, values, kept)

    def tree_flatten(self):
        return (self.values, self.loose), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(names, *children)

    def copy_to_host_async(self) -> None:
        if self._host is None:
            self.values.copy_to_host_async()

    def host(self) -> np.ndarray:
        """The vector on the host: converted once, on first use."""
        if self._host is None:
            self._host = np.asarray(self.values)
        return self._host

    def __getitem__(self, name: str):
        if name in self.loose:
            return self.loose[name]
        try:
            return _Lane(self, self.names.index(name))
        except ValueError:
            raise KeyError(name) from None

    def __iter__(self):
        yield from self.names
        yield from self.loose

    def __len__(self) -> int:
        return len(self.names) + len(self.loose)

    def pop(self, name: str, *default):
        """Take a loose array out (`resilience.update_skipped`); the packed
        names stay in their vector."""
        return self.loose.pop(name, *default)


class _Lane:
    """One named scalar of a `PackedScalars` on the host: `float()` and
    `np.asarray()` read it off the vector's one conversion."""

    __slots__ = ("packed", "index")

    def __init__(self, packed: PackedScalars, index: int) -> None:
        self.packed = packed
        self.index = index

    def __float__(self) -> float:
        return float(self.packed.host()[self.index])

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        return np.asarray(self.packed.host()[self.index], dtype=dtype)


def packed_metrics(body: Callable[..., tuple], loose: Iterable[str] = ()) -> Callable[..., tuple]:
    """Wrap an unjitted step `(state, *args) -> (state, metrics)` so that
    its metrics leave the compiled program as one `PackedScalars` (the
    `loose` keys apart). Keeps the body's name: the jit's module name is
    what the trace readers match."""
    loose = tuple(loose)

    @functools.wraps(body)
    def packed(*args):
        state, metrics = body(*args)
        return state, PackedScalars.pack(metrics, loose)

    return packed


def _prefetch(values) -> int:
    """Start one async device->host copy per device array among `values`
    (the lanes of one vector share its copy) so that the conversions after
    find the transfers in flight. Returns the number of arrays."""
    arrays = {}
    for v in values:
        src = v.packed if isinstance(v, _Lane) else v
        if hasattr(src, "copy_to_host_async"):
            arrays[id(src)] = src
    for a in arrays.values():
        try:
            a.copy_to_host_async()
        # sheeplint: disable=SL012 — prefetch-only path; compute()'s
        # blocking pull is the correctness path and raises for real
        except Exception:
            pass  # fall back to the blocking pull in compute
    return len(arrays)


class _Snapshot:
    """A metric's pending values frozen at snapshot time, with the metric's
    own resolve function bound to them — the deferred half of the pipeline
    MetricDrain (parallel/pipeline.py). `resolve()` produces exactly what
    `compute()` would have at snapshot time."""

    __slots__ = ("values", "_resolve")

    def __init__(self, values: list[Any], resolve) -> None:
        self.values = values
        self._resolve = resolve

    def resolve(self):
        return self._resolve(self.values)


class MeanMetric:
    def __init__(self) -> None:
        self._values: list[Any] = []

    def pending(self) -> list[Any]:
        return self._values

    def update(self, value: Any) -> None:
        self._values.append(value)

    @staticmethod
    def _resolve(values: list[Any]) -> float | None:
        if not values:
            return None
        return float(np.mean([float(v) for v in values]))

    def compute(self) -> float | None:
        return self._resolve(self._values)

    def snapshot(self) -> _Snapshot:
        return _Snapshot(list(self._values), self._resolve)

    def reset(self) -> None:
        self._values.clear()


class MovingAverageMetric:
    """Windowed statistics over the last `window` values
    (reference MovingAverageMetric, metric.py:70-137). Values are kept raw
    (possibly device scalars) and pulled at compute() time.

    `reset_on_compute=False` (the default): the window SURVIVES the
    aggregator's per-logging-interval reset — a windowed moving average that
    is wiped every interval degenerates into an interval mean, which is
    exactly the bug the flag exists to prevent. An explicit `.reset()` call
    still clears."""

    reset_on_compute = False

    def __init__(self, window: int = 100, reset_on_compute: bool = False) -> None:
        self._window = deque(maxlen=window)
        self.reset_on_compute = reset_on_compute

    def pending(self) -> list[Any]:
        return list(self._window)

    def update(self, value: Any) -> None:
        self._window.append(value)

    @staticmethod
    def _resolve(values: list[Any]) -> dict[str, float] | None:
        if not values:
            return None
        arr = np.asarray([float(v) for v in values])
        return {
            "mean": float(arr.mean()),
            "std": float(arr.std()),
            "min": float(arr.min()),
            "max": float(arr.max()),
        }

    def compute(self) -> dict[str, float] | None:
        return self._resolve(list(self._window))

    def snapshot(self) -> _Snapshot:
        return _Snapshot(list(self._window), self._resolve)

    def reset(self) -> None:
        self._window.clear()


class MetricAggregator:
    def __init__(self, metrics: dict[str, Any] | None = None) -> None:
        self.metrics: dict[str, Any] = metrics if metrics is not None else {}
        # device arrays the last compute() / snapshot() pulled (the mains'
        # `log/pull` counter): one a train step for a packed step's metrics
        self.arrays = 0

    def add(self, name: str, metric: Any | None = None) -> None:
        if name in self.metrics:
            raise ValueError(f"metric {name!r} already exists")
        self.metrics[name] = metric if metric is not None else MeanMetric()

    def update(self, name: str, value: Any) -> None:
        if name not in self.metrics:
            self.add(name)
        self.metrics[name].update(value)

    def pop(self, name: str) -> None:
        self.metrics.pop(name, None)

    @staticmethod
    def _flatten(name: str, val, out: dict) -> None:
        if val is None:
            return
        if isinstance(val, dict):
            for k, v in val.items():
                out[f"{name}/{k}"] = v
        else:
            out[name] = val

    def compute(self) -> dict[str, float]:
        # overlap all pending device pulls before the blocking conversions
        self.arrays = _prefetch(
            v
            for metric in self.metrics.values()
            for v in getattr(metric, "pending", list)()
        )
        out: dict = {}
        for name, metric in self.metrics.items():
            self._flatten(name, metric.compute(), out)
        return out

    def snapshot(self) -> "PendingMetrics":
        """Freeze every metric's pending values and issue their async
        device->host copies NOW; the returned handle's `resolve()` produces
        the exact dict `compute()` would have, but the blocking conversions
        run later — after the copies have landed (the pipeline MetricDrain's
        deferred-drain contract, parallel/pipeline.py). Metric types without
        a `snapshot()` resolve eagerly here."""
        snaps: dict[str, _Snapshot] = {}
        eager: dict = {}
        for name, metric in self.metrics.items():
            snap_fn = getattr(metric, "snapshot", None)
            if snap_fn is not None:
                snaps[name] = snap_fn()
            else:
                self._flatten(name, metric.compute(), eager)
        self.arrays = _prefetch(v for s in snaps.values() for v in s.values)
        return PendingMetrics(snaps, eager)

    def reset(self, force: bool = False) -> None:
        """Per-logging-interval reset. Metrics that declare
        `reset_on_compute = False` (windowed moving averages) keep their
        state across intervals; `force=True` clears everything (end-of-run
        teardown)."""
        for metric in self.metrics.values():
            if force or getattr(metric, "reset_on_compute", True):
                metric.reset()


class PendingMetrics:
    """An interval's metric values captured by `MetricAggregator.snapshot()`
    with their d2h copies in flight; `resolve()` performs the (by then
    cheap) blocking conversions and returns the flattened metric dict."""

    __slots__ = ("_snaps", "_eager")

    def __init__(self, snaps: dict[str, _Snapshot], eager: dict) -> None:
        self._snaps = snaps
        self._eager = eager

    def resolve(self) -> dict:
        out = dict(self._eager)
        for name, snap in self._snaps.items():
            MetricAggregator._flatten(name, snap.resolve(), out)
        return out
