"""MetricAggregator / MovingAverageMetric (reference metric.py:12-137):
running means, windowed stats, and the lazy device-scalar pull — updating
with jax scalars in the hot loop must not force a sync, and compute() must
batch-prefetch then convert correctly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.utils.metric import (
    MeanMetric,
    MetricAggregator,
    MovingAverageMetric,
    PackedScalars,
    packed_metrics,
)


def test_mean_metric_update_compute_reset():
    agg = MetricAggregator()
    agg.update("loss", 1.0)
    agg.update("loss", 3.0)
    out = agg.compute()
    assert out == {"loss": 2.0}
    agg.reset()
    assert agg.compute() == {}  # empty metrics are skipped


def test_device_scalars_pull_at_compute_time():
    agg = MetricAggregator()
    # jax scalars (what train_step metrics are) — update must accept them
    # raw; compute prefetches then converts
    agg.update("a", jnp.float32(1.5))
    agg.update("a", jnp.float32(2.5))
    agg.update("b", jnp.float32(-1.0))
    out = agg.compute()
    assert out["a"] == pytest.approx(2.0)
    assert out["b"] == pytest.approx(-1.0)


def test_moving_average_window_and_dict_flattening():
    agg = MetricAggregator({"rew": MovingAverageMetric(window=3)})
    for v in (1.0, 2.0, jnp.float32(3.0), 4.0):  # first value evicted
        agg.update("rew", v)
    out = agg.compute()
    assert out["rew/mean"] == pytest.approx(3.0)
    assert out["rew/min"] == pytest.approx(2.0)
    assert out["rew/max"] == pytest.approx(4.0)
    assert out["rew/std"] == pytest.approx(np.std([2.0, 3.0, 4.0]))
    # the per-interval reset must NOT wipe the moving-average window — a
    # windowed metric wiped every logging interval degenerates into an
    # interval mean (ISSUE 2 satellite)
    agg.reset()
    out = agg.compute()
    assert out["rew/mean"] == pytest.approx(3.0)
    agg.reset(force=True)
    assert agg.compute() == {}


def test_reset_on_compute_opt_in_and_mean_metric_default():
    agg = MetricAggregator(
        {
            "windowed": MovingAverageMetric(window=4),
            "interval": MovingAverageMetric(window=4, reset_on_compute=True),
        }
    )
    agg.update("windowed", 1.0)
    agg.update("interval", 1.0)
    agg.update("plain", 5.0)  # auto-added MeanMetric: resets every interval
    agg.reset()
    out = agg.compute()
    assert "windowed/mean" in out  # survived
    assert "interval/mean" not in out  # opted into interval resets
    assert "plain" not in out


def test_add_duplicate_raises_and_pop():
    agg = MetricAggregator()
    agg.add("x")
    with pytest.raises(ValueError):
        agg.add("x")
    agg.pop("x")
    agg.add("x")  # fine after pop


# ---- packed metrics: one device vector a train step -------------------------

NAMES = ("Loss/a", "Loss/b", "Grads/c", "State/d")


def _body(state, x):
    """A stand-in train step: its metrics are scalars of several dtypes."""
    metrics = {
        "Loss/a": jnp.sum(x),
        "Loss/b": jnp.mean(x * x),
        "Grads/c": jnp.max(x).astype(jnp.bfloat16),
        "State/d": jnp.min(x) - state,
    }
    return state + 1.0, metrics


def _steps(n_steps):
    return [jnp.asarray(np.random.default_rng(i).standard_normal(5), jnp.float32) for i in range(n_steps)]


def _drained(per_step, metric_cls, deferred):
    """What an interval's drain hands the logger after `per_step` train
    steps' metrics went into the aggregator as the mains feed it."""
    from sheeprl_tpu.parallel.pipeline import MetricDrain

    agg = MetricAggregator({n: metric_cls() for n in NAMES})
    for metrics in per_step:
        for name, val in metrics.items():
            agg.update(name, val)
    drain = MetricDrain(enabled=deferred)
    out = drain.drain(agg, 7)
    if deferred:
        assert out == []  # the interval's copies are in flight: resolved by the next drain
        out = drain.flush()
    ((resolved, step),) = out
    assert step == 7
    return resolved, agg.arrays


@pytest.mark.parametrize("n_steps", [1, 4])
@pytest.mark.parametrize("metric_cls", [MeanMetric, lambda: MovingAverageMetric(window=3)], ids=["mean", "moving_average"])
@pytest.mark.parametrize("deferred", [False, True], ids=["compute", "snapshot_resolve"])
def test_a_packed_step_resolves_to_the_dict_its_scalars_give(n_steps, metric_cls, deferred):
    """The same train steps with their metrics as one vector and as one
    device scalar each: the logged dict is the same to the last bit, the
    pull one array a step instead of one a metric."""
    scalar_step = jax.jit(_body)
    packed_step = jax.jit(packed_metrics(_body))
    xs = _steps(n_steps)
    scalar = [scalar_step(jnp.float32(0.5), x)[1] for x in xs]
    packed = [packed_step(jnp.float32(0.5), x)[1] for x in xs]
    assert all(isinstance(p, PackedScalars) and p.names == tuple(sorted(NAMES)) for p in packed)
    want, scalar_arrays = _drained(scalar, metric_cls, deferred)
    got, packed_arrays = _drained(packed, metric_cls, deferred)
    assert got == want and list(got) == list(want)
    held = n_steps if metric_cls is MeanMetric else min(n_steps, 3)  # the window keeps 3
    assert (scalar_arrays, packed_arrays) == (len(NAMES) * held, held)
    # and as eager compute() and snapshot()/resolve() agree with each other
    assert got == _drained(packed, metric_cls, not deferred)[0]


class _CountingVector:
    """A device vector stand-in that counts its copies and conversions."""

    def __init__(self, values):
        self._values = np.asarray(values, np.float32)
        self.copies = self.conversions = 0

    def copy_to_host_async(self):
        self.copies += 1

    def __array__(self, dtype=None, copy=None):
        self.conversions += 1
        return np.asarray(self._values, dtype=dtype)


@pytest.mark.parametrize("deferred", [False, True], ids=["compute", "snapshot_resolve"])
def test_a_packed_vector_is_copied_and_converted_once(deferred):
    names = tuple(sorted(NAMES))
    vectors = [_CountingVector(np.arange(len(names)) + 10.0 * i) for i in range(4)]
    resolved, arrays = _drained([PackedScalars(names, v) for v in vectors], MeanMetric, deferred)
    assert arrays == 4
    assert [(v.copies, v.conversions) for v in vectors] == [(1, 1)] * 4
    assert resolved == {n: 15.0 + i for i, n in enumerate(names)}


def test_packed_scalars_keep_the_skip_flag_apart():
    """`guard_nonfinite`'s flag is a loose output: `update_skipped` pops it
    and reads it one update lagged, and only the packed names reach the
    aggregator."""
    from sheeprl_tpu import resilience

    step = jax.jit(packed_metrics(resilience.guard_nonfinite(_body, "skip"), loose=(resilience.SKIP_FLAG,)))
    _, metrics = step(jnp.float32(0.5), _steps(1)[0])
    assert resilience.SKIP_FLAG in metrics and len(metrics) == len(NAMES) + 1
    assert metrics.values.shape == (len(NAMES),)
    assert resilience.update_skipped(metrics, "skip") is False
    assert set(metrics) == set(NAMES) and resilience.SKIP_FLAG not in metrics
    assert "Loss/none" not in metrics and metrics.get("Loss/none") is None
    x = _steps(1)[0]
    assert float(metrics["Loss/a"]) == float(np.asarray(jnp.sum(x)))
    assert np.asarray(metrics["Loss/b"]).dtype == np.float32


def test_packing_refuses_a_metric_that_is_not_a_scalar():
    with pytest.raises(ValueError, match="not a scalar"):
        jax.jit(packed_metrics(lambda s, x: (s, {"v": x})))(jnp.float32(0), jnp.zeros(3))
