"""Equivalence receipts for the critical-path latency-hiding primitives
(ISSUE 4): ActionPipeline ordering, the SamplePrefetcher epoch-consistency
guard under concurrent adds, MetricDrain value equality vs eager compute,
and a DreamerV3 e2e dry run whose ring contents and train math match the
synchronous path bit-exactly with `--pipeline on`."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data import AsyncReplayBuffer, ReplayBuffer
from sheeprl_tpu.parallel.pipeline import (
    ActionPipeline,
    MetricDrain,
    Pipeline,
    PipelineStats,
    SamplePrefetcher,
    pull,
)
from sheeprl_tpu.telemetry import PhaseTimers, Telemetry
from sheeprl_tpu.utils.metric import MetricAggregator, MovingAverageMetric


# ---------------------------------------------------------------------------
# ActionPipeline
# ---------------------------------------------------------------------------


def test_action_pipeline_fetch_matches_sync_pull():
    pipe = ActionPipeline(enabled=True, lag=0)
    dev = jnp.arange(6, dtype=jnp.int32)
    out = pipe.fetch(dev)
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(out, np.arange(6, dtype=np.int32))
    # pytrees and host leaves pass through unchanged
    tree = {"a": jnp.ones((2, 3)), "b": np.full(4, 7.0)}
    host = pipe.fetch(tree)
    np.testing.assert_array_equal(host["a"], np.ones((2, 3)))
    np.testing.assert_array_equal(host["b"], np.full(4, 7.0))


def _read_and_parts(spans, name):
    """The read spans of that name and, for each, its children in order."""
    reads = [s for s in spans if s["name"] == name]
    return [(r, [c for c in spans if c["parent"] == r["span"]]) for r in reads]


def test_action_pipeline_disabled_is_sync():
    """Disabled mode is the synchronous pull, split as the enabled one is:
    inside the read's span it waits for the array (`ready`), then copies it
    (`copy`), and counts its one array and its bytes on the read."""
    t = PhaseTimers()
    pipe = ActionPipeline(enabled=False, lag=0, telem=t)
    dev = jnp.arange(3)
    t.iteration(0)
    t.mark("rollout/action_wait")
    out = pipe.fetch(dev)
    t.mark(None)
    np.testing.assert_array_equal(out, np.arange(3))
    ((read, parts),) = _read_and_parts(list(t.drain()), "rollout/action_wait")
    assert [p["name"] for p in parts] == ["rollout/action_wait/ready", "rollout/action_wait/copy"]
    assert (read["arrays"], read["bytes"]) == (1, dev.nbytes)
    assert set(t.flush()) == {"rollout/action_wait"}  # the parts have no sum of their own


def test_action_pipeline_ordering_dispatch_then_read():
    """Action t is consumed (read) before obs t+1 would be dispatched: the
    handle returned for step t resolves to step t's values regardless of
    how many later dispatches were issued in between. Each read is split
    inside its own span, its parts tiling one stretch of it."""
    t = PhaseTimers()
    pipe = ActionPipeline(enabled=True, telem=t)
    t.iteration(0)
    handles = [pipe.dispatch(jnp.full((2,), s, jnp.int32)) for s in range(5)]
    for s, h in enumerate(handles):
        t.mark("rollout/action_wait")
        np.testing.assert_array_equal(h.get(), np.full((2,), s, np.int32))
    t.mark(None)
    reads = _read_and_parts(list(t.drain()), "rollout/action_wait")
    assert len(reads) == 5
    for read, (ready, copy) in reads:
        assert (ready["name"], copy["name"]) == ("rollout/action_wait/ready", "rollout/action_wait/copy")
        assert read["p0"] <= ready["p0"] and ready["t1"] == copy["t0"] and copy["t1"] <= read["t1"]
        assert (read["arrays"], read["bytes"]) == (1, 8)


def test_action_pipeline_one_step_lag_fifo():
    """lag=1: the first fetch primes the FIFO (returns None), and fetch t
    then returns the value dispatched at t-1 — the one-step-lagged overlap
    contract (howto/pipelining.md)."""
    pipe = ActionPipeline(enabled=True, lag=1)
    assert pipe.fetch(jnp.int32(0)) is None
    for t in range(1, 5):
        got = pipe.fetch(jnp.int32(t))
        assert int(got) == t - 1
    leftover = pipe.flush()
    assert [int(v) for v in leftover] == [4]
    assert pipe.flush() == []


# ---------------------------------------------------------------------------
# SamplePrefetcher
# ---------------------------------------------------------------------------


def _row(rng, n_envs):
    return {
        "obs": rng.normal(size=(1, n_envs, 3)).astype(np.float32),
        "rewards": rng.normal(size=(1, n_envs, 1)).astype(np.float32),
    }


def _fill(rb, rng, n_rows, n_envs):
    for _ in range(n_rows):
        rb.add(_row(rng, n_envs))


def _assert_batches_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_sample_prefetcher_hits_on_quiet_buffer():
    """With no writes between samples (a pretrain/catch-up burst), the
    prefetched batch is served and is identical to what the synchronous
    path would have drawn."""
    rng = np.random.default_rng(0)
    rb = AsyncReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=3)
    rb_sync = AsyncReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=3)
    rows = [_row(rng, 2) for _ in range(16)]
    for r in rows:
        rb.add(r)
        rb_sync.add(r)
    stats = PipelineStats()
    pre = SamplePrefetcher(rb, enabled=True, stats=stats)
    for _ in range(6):
        _assert_batches_equal(pre.sample(4), rb_sync.sample(4))
    assert stats.sample_hits >= 4  # first serve is fresh, the rest hit
    assert stats.sample_misses == 0


def test_sample_prefetcher_epoch_guard_under_concurrent_adds():
    """Writes between samples invalidate the prefetch: the guard discards
    it, rewinds the sampler PRNG, and the fresh resample matches the
    synchronous path bit-exactly (same keys, same rows)."""
    rng = np.random.default_rng(1)
    rb = AsyncReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=5)
    rb_sync = AsyncReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=5)
    rows = [_row(rng, 2) for _ in range(40)]
    for r in rows[:16]:
        rb.add(r)
        rb_sync.add(r)
    pre = SamplePrefetcher(rb, enabled=True)
    for r in rows[16:]:
        _assert_batches_equal(pre.sample(4), rb_sync.sample(4))
        rb.add(r)  # concurrent add: advances the epoch past any prefetch
        rb_sync.add(r)
    # and the final state agrees too: one more quiet pair
    _assert_batches_equal(pre.sample(4), rb_sync.sample(4))


def test_sample_prefetcher_epoch_guard_replay_buffer():
    """Same receipt on the base ReplayBuffer (SAC-family rings), including
    a call-signature change (which must also rewind)."""
    rng = np.random.default_rng(2)
    rb = ReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=9)
    rb_sync = ReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=9)
    rows = [_row(rng, 2) for _ in range(24)]
    for r in rows[:8]:
        rb.add(r)
        rb_sync.add(r)
    pre = SamplePrefetcher(rb, enabled=True)
    sizes = [4, 4, 6, 4]  # the 6 forces a signature-mismatch discard
    for r, bs in zip(rows[8:], sizes):
        _assert_batches_equal(pre.sample(bs), rb_sync.sample(bs))
        rb.add(r)
        rb_sync.add(r)


def test_sample_prefetcher_staleness_opt_in():
    """max_staleness > 0 serves the prefetched (one-epoch-stale) batch — a
    consistent snapshot of the ring at prefetch time."""
    rng = np.random.default_rng(3)
    rb = AsyncReplayBuffer(64, 2, storage="device", obs_keys=("obs",), seed=11)
    _fill(rb, rng, 16, 2)
    stats = PipelineStats()
    pre = SamplePrefetcher(rb, enabled=True, max_staleness=4, stats=stats)
    pre.sample(4)  # fresh + prefetch
    rb.add(_row(rng, 2))
    pre.sample(4)  # stale by 1 epoch <= 4: served
    assert stats.sample_hits == 1


def test_sample_prefetcher_host_buffer_passthrough():
    """Host-storage rings gather synchronously on host — the wrapper stays
    a passthrough (no prefetch, identical sampling)."""
    rng = np.random.default_rng(4)
    rb = ReplayBuffer(32, 2, storage="host", obs_keys=("obs",), seed=13)
    rb_sync = ReplayBuffer(32, 2, storage="host", obs_keys=("obs",), seed=13)
    rows = [_row(rng, 2) for _ in range(8)]
    for r in rows:
        rb.add(r)
        rb_sync.add(r)
    stats = PipelineStats()
    pre = SamplePrefetcher(rb, enabled=True, stats=stats)
    assert not pre.enabled
    for _ in range(3):
        _assert_batches_equal(pre.sample(4), rb_sync.sample(4))
    assert stats.sample_prefetches == 0


# ---------------------------------------------------------------------------
# MetricDrain
# ---------------------------------------------------------------------------


def _feed(agg):
    agg.update("Loss/a", jnp.float32(1.5))
    agg.update("Loss/a", jnp.float32(2.5))
    agg.update("Loss/b", 3.0)


def test_metric_drain_value_equality_vs_eager():
    """The deferred drain logs exactly the values eager compute would
    have, tagged with the interval they were measured in (one interval
    later in wall-clock)."""
    eager, deferred = MetricAggregator(), MetricAggregator()
    eager.add("win", MovingAverageMetric(window=4))
    deferred.add("win", MovingAverageMetric(window=4))
    drain = MetricDrain(enabled=True)
    logged: list = []
    for step in range(1, 4):
        for agg in (eager, deferred):
            _feed(agg)
            agg.update("win", float(step))
        expected = (eager.compute(), step)
        eager.reset()
        logged.extend(drain.drain(deferred, step))
        # drained output lags one interval; compare when it lands
        if step > 1:
            assert logged[-1][1] == step - 1
        globals().setdefault("_expect", []).append(expected)
    logged.extend(drain.flush())
    expected_all = globals().pop("_expect")
    assert len(logged) == len(expected_all)
    for (got, gstep), (want, wstep) in zip(logged, expected_all):
        assert gstep == wstep
        assert got == want  # exact float equality: same ops on same values


def test_metric_drain_disabled_is_eager():
    agg = MetricAggregator()
    _feed(agg)
    drain = MetricDrain(enabled=False)
    out = drain.drain(agg, 7)
    assert out == [({"Loss/a": 2.0, "Loss/b": 3.0}, 7)]
    assert agg.compute() == {}  # reset happened
    assert drain.flush() == []


def test_pipeline_facade_gauges_and_modes(tmp_path):
    """`from_args` gives both modes the run's telemetry: their reads are
    split in the read's span, and the on mode's gauges are the prefetcher's
    alone; the reads are timed by the spans, not again as gauges."""

    class _Args:
        pipeline = "on"

    class _Off:
        pipeline = "off"

    telem = Telemetry(str(tmp_path), rank=0, algo="unit")
    try:
        pipe = Pipeline.from_args(_Args(), telem)
        assert pipe.enabled
        off = Pipeline.from_args(_Off(), telem)
        assert not off.enabled
        telem.iteration(0)
        for p in (pipe, off):
            telem.mark("rollout/action_wait", phase="rollout")
            p.action.fetch(jnp.arange(2))
        telem.mark(None)
        assert pipe.gauges() == {"Pipeline/sample_prefetches": 0.0}
        merged = telem.interval({}, step=0)
        assert not [k for k in merged if k.startswith("Pipeline/") and k != "Pipeline/sample_prefetches"]
        assert [k for k in merged if k.startswith("Time/")] == ["Time/rollout_seconds"]
        reads = _read_and_parts(list(telem.timers.drain()), "rollout/action_wait")
        assert [[p["name"].rsplit("/", 1)[1] for p in parts] for _, parts in reads] == [["ready", "copy"]] * 2
    finally:
        telem.close()


@pytest.mark.parametrize("mode", ["off", "on"])
def test_pull_splits_every_read_and_changes_no_value(mode):
    """Both reads of a loop, in both modes: the host values are those of a
    plain `np.asarray`, each read's span holds `ready` then `copy`, and its
    `bytes` are the device arrays' `nbytes`."""
    t = PhaseTimers()
    pipe = Pipeline(enabled=mode == "on", telem=t)
    agg = MetricAggregator()
    tree = {"idx": jnp.arange(6, dtype=jnp.int32).reshape(2, 3), "logp": jnp.linspace(-1.0, 0.0, 4, dtype=jnp.bfloat16)}
    want = {k: np.asarray(v) for k, v in tree.items()}
    logged = []
    for step in range(3):
        t.iteration(step)
        t.mark("rollout/action_wait")
        got = pipe.action.fetch(tree)
        for k in tree:
            assert got[k].dtype == want[k].dtype
            np.testing.assert_array_equal(got[k], want[k])
        _feed(agg)
        t.mark("log/pull")
        logged += pipe.drain_metrics(agg, step)
        t.mark(None)
    t.iteration(None)
    logged += pipe.flush_metrics()
    assert logged == [({"Loss/a": 2.0, "Loss/b": 3.0}, s) for s in range(3)]
    spans = list(t.drain())
    actions = _read_and_parts(spans, "rollout/action_wait")
    assert [(r["arrays"], r["bytes"]) for r, _ in actions] == [(2, 6 * 4 + 4 * 2)] * 3
    pulls = _read_and_parts(spans, "log/pull")
    # the deferred drain reads the snapshot an interval old: nothing in the first interval
    split = pulls if mode == "off" else pulls[1:]
    assert mode == "off" or (pulls[0][1] == [] and "bytes" not in pulls[0][0])
    assert [(r["arrays"], r["bytes"]) for r, _ in split] == [(2, 8)] * len(split)  # two float32 scalars an interval
    for read, parts in actions + split:
        assert [p["name"] for p in parts] == [read["name"] + "/ready", read["name"] + "/copy"]


class _Recorder:
    """A device array stand-in that records what a read does to it."""

    nbytes = 4

    def __init__(self, log):
        self.log = log

    def copy_to_host_async(self):
        self.log.append("copy issued")

    def block_until_ready(self):
        self.log.append("waited")
        return self

    def __array__(self, dtype=None, copy=None):
        self.log.append("converted")
        return np.zeros(1, np.float32)


@pytest.mark.parametrize("spans", [True, False], ids=["spans", "no_spans"])
@pytest.mark.parametrize("enabled", [False, True], ids=["off", "on"])
def test_a_reads_copy_is_in_flight_before_it_waits(enabled, spans):
    """The split must not delay the transfer: a plain conversion issues the
    copy as the read starts, so `pull` issues it before waiting, once
    (`--pipeline on` issued it at dispatch), in both modes."""
    log = []
    t = PhaseTimers(spans=spans)
    t.iteration(0)
    t.mark("rollout/action_wait")
    ActionPipeline(enabled=enabled, telem=t).fetch(_Recorder(log))
    assert log == (["copy issued", "waited", "converted"] if spans else ["copy issued", "converted"])


@pytest.mark.parametrize("switch", ["no_telemetry", "SHEEPRL_TPU_TRACE", "SHEEPRL_TPU_TELEMETRY"])
def test_pull_with_the_spans_off_is_the_single_conversion(tmp_path, monkeypatch, switch):
    """The kill switch leaves a read as it was: one conversion, no wait
    for the device before it, nothing counted."""
    telem = None
    if switch != "no_telemetry":
        monkeypatch.setenv(switch, "0")
        telem = Telemetry.from_args(object(), str(tmp_path), algo="unit")

    def refused(*a, **k):
        raise AssertionError("block_until_ready with the spans off")

    monkeypatch.setattr(jax, "block_until_ready", refused)
    dev = jnp.arange(4, dtype=jnp.float32)
    calls = []

    def convert():
        calls.append(1)
        return np.asarray(dev)

    if telem is not None:
        telem.iteration(0)
        telem.mark("rollout/action_wait")
    np.testing.assert_array_equal(pull(dev, telem, convert), np.arange(4, dtype=np.float32))
    np.testing.assert_array_equal(Pipeline(enabled=True, telem=telem).action.fetch(dev), np.arange(4, dtype=np.float32))
    assert calls == [1]
    if telem is not None:
        telem.iteration(None)
        assert not telem.timers.ring
        telem.close()


def test_pipeline_sampler_is_cached_per_buffer():
    pipe = Pipeline(enabled=True)
    rb = AsyncReplayBuffer(16, 1, storage="device", obs_keys=("obs",), seed=0)
    assert pipe.sampler(rb) is pipe.sampler(rb)


# ---------------------------------------------------------------------------
# DreamerV3 end-to-end equivalence: --pipeline on == off, bit for bit
# ---------------------------------------------------------------------------

TINY = [
    "--dry_run",
    "--num_devices=1",
    "--num_envs=1",
    "--sync_env",
    "--per_rank_batch_size=1",
    "--per_rank_sequence_length=1",
    "--buffer_size=4",
    "--learning_starts=0",
    "--gradient_steps=1",
    "--horizon=4",
    "--dense_units=8",
    "--cnn_channels_multiplier=2",
    "--recurrent_state_size=8",
    "--hidden_size=8",
    "--stochastic_size=4",
    "--discrete_size=4",
    "--mlp_layers=1",
    "--train_every=1",
    "--checkpoint_every=1",
    "--checkpoint_buffer",
    "--env_id=discrete_dummy",
    "--cnn_keys", "rgb",
    "--seed=7",
]


def _loss_events(log_dir):
    """step -> {Loss/*: value} from the run's telemetry.jsonl."""
    out = {}
    with open(os.path.join(log_dir, "telemetry.jsonl")) as fh:
        for line in fh:
            ev = json.loads(line)
            if ev.get("event") != "log":
                continue
            losses = {
                k: v for k, v in ev.get("metrics", {}).items()
                if k.startswith("Loss/")
            }
            if losses:
                out.setdefault(ev["step"], {}).update(losses)
    return out


@pytest.mark.timeout(600)
def test_dv3_e2e_pipeline_on_matches_sync_bit_exact(tmp_path):
    """The flagship equivalence receipt: one DreamerV3 dry-run cycle with
    `--pipeline on` produces the same replay ring bits and the same logged
    train losses as `--pipeline off` (same seed) — the pipeline hides
    latency without changing a single value."""
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import main

    for mode in ("off", "on"):
        main(TINY + [f"--root_dir={tmp_path}", f"--run_name={mode}", f"--pipeline={mode}"])

    def ring(mode):
        paths = glob.glob(str(tmp_path / mode / "checkpoints" / "ckpt_*_buffer.npz"))
        assert paths, f"no buffer checkpoint for {mode}"
        return dict(np.load(paths[0]))

    off, on = ring("off"), ring("on")
    assert set(off) == set(on)
    for k in off:
        if k == "sampler_state":
            # the checkpointed sampler PRNG (ISSUE 12) is legitimately one
            # draw ahead under the prefetcher at save time; the ring bits
            # and the logged losses below are the equivalence contract
            continue
        np.testing.assert_array_equal(off[k], on[k], err_msg=f"ring key {k}")

    losses_off = _loss_events(str(tmp_path / "off"))
    losses_on = _loss_events(str(tmp_path / "on"))
    assert losses_off and losses_off.keys() == losses_on.keys()
    for step, vals in losses_off.items():
        assert vals == losses_on[step], f"train metrics diverge at step {step}"
