"""Metric aggregation (capability parity with
/root/reference/sheeprl/utils/metric.py): a named dict of running means
updated every step and computed/reset once per logging interval, plus a
windowed moving-average metric. Values may be jax scalars — they are pulled
to host lazily at compute() time, so updating inside the hot loop never
forces a device sync; compute() first issues ONE overlapping async
device->host copy per pending device value, so the N pulls of a compute
over N train metrics overlap instead of running one after another."""

from __future__ import annotations

from collections import deque
from typing import Any

import numpy as np

__all__ = ["MetricAggregator", "MovingAverageMetric", "PendingMetrics"]


def _prefetch(values) -> None:
    """Start async device->host copies for any jax arrays so the subsequent
    float() conversions find the transfer already in flight: issuing all
    copies first overlaps the blocking pulls."""
    for v in values:
        copy_async = getattr(v, "copy_to_host_async", None)
        if copy_async is not None:
            try:
                copy_async()
            # sheeplint: disable=SL012 — prefetch-only path; compute()'s
            # blocking pull is the correctness path and raises for real
            except Exception:
                pass  # fall back to the blocking pull in compute


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
        _prefetch(
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
        _prefetch(v for s in snaps.values() for v in s.values)
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
