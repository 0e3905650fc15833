"""Critical-path latency hiding for the interaction hot loop (ISSUE 4).

Round-3's phase attribution priced the remaining duty-vs-e2e gap in
synchronous host round-trips on the hot-loop critical path: the per-step
action device->host pull, the per-update replay-sample pull, and the
per-interval metric drain. This module removes them from the critical path
with three primitives — the single-chip analogue of the acting/learning/
data-movement overlap Podracer (arXiv:2104.06272) and MSRL
(arXiv:2210.00882) build with decoupled topologies:

  - :class:`ActionPipeline` — the policy-step jit's action indices start a
    `copy_to_host_async` the moment the jit returns (`dispatch`), and the
    blocking read happens only when `env.step` actually consumes them
    (`Handle.get`), so the d2h RTT overlaps JAX async dispatch and the
    host-side replay/bookkeeping work in between. Optionally one-step
    lagged (`lag=1`): the loop consumes action t-1 while step t's copy is
    still in flight, hiding the FULL RTT behind env compute — off-policy
    safe only (the executed action was computed from a one-step-stale
    observation; see howto/pipelining.md).
  - :class:`SamplePrefetcher` — double-buffers the replay sampler: when
    sample N is served, sample N+1's packed index put + device gather are
    dispatched immediately, so they execute while train step N runs. The
    epoch-consistency guard makes this bit-exact: a prefetched batch is
    served only if the ring has NOT been written since the prefetch
    (`buffer.epoch` unchanged, up to `max_staleness`); otherwise it is
    discarded and the sampler's PRNG state is REWOUND to what the prefetch
    consumed, so the fresh resample draws exactly the key the synchronous
    path would have — prefetched indices can never precede the rows they
    reference, and the on/off A/B trains on identical batches.
  - :class:`MetricDrain` — defers `MetricAggregator.compute()`'s blocking
    host pulls by one logging interval: at interval T the aggregator's
    pending device values are snapshotted and their async d2h copies
    issued; the blocking resolve happens at interval T+1, by which time
    the copies have long landed — logging costs zero synchronous round
    trips. Values are identical to eager compute (same floats, same step
    tags), they just reach the logger one interval later.

Every primitive has an `enabled=False` mode that IS the synchronous path
(same calls, same ordering), so call sites are identical under
`--pipeline on|off` and the equivalence receipts in
tests/test_parallel/test_pipeline.py compare the two modes directly.

Every blocking read of the hot loop goes through :func:`pull`, in both
modes: inside the read's own span (`rollout/action_wait`, `log/pull`) it
times waiting for the device (`ready`) apart from copying the ready arrays
(`copy`) and counts the read's `arrays` and `bytes` (howto/pipelining.md).
Construct via :meth:`Pipeline.from_args`; the prefetcher's gauges
(`Pipeline/sample_hit_rate`, `Pipeline/sample_prefetches`) ride the
existing interval merge.
"""

from __future__ import annotations

import functools
import os
from collections import deque

import numpy as np

__all__ = [
    "ActionPipeline",
    "MetricDrain",
    "Pipeline",
    "PipelineStats",
    "SamplePrefetcher",
    "pull",
    "start_copies",
]


def start_copies(tree) -> list:
    """Issue a non-blocking device->host copy for every device array among
    `tree`'s leaves (host values pass untouched) and return those arrays.
    Issuing a copy that is in flight or done again is a no-op."""
    import jax

    arrays = [a for a in jax.tree_util.tree_leaves(tree) if hasattr(a, "copy_to_host_async")]
    for a in arrays:
        try:
            a.copy_to_host_async()
        # sheeplint: disable=SL012 — prefetch is a pure optimization; the
        # blocking read after it is the correctness path and surfaces real errors
        except Exception:
            pass  # the blocking read still works
    return arrays


def _tree_map(fn, tree):
    import jax

    return jax.tree_util.tree_map(fn, tree)


def pull(tree, telem=None, convert=None, started: bool = False):
    """The hot loop's one blocking device->host read: `convert()` (by
    default every leaf of `tree` as a numpy array) once the device arrays
    among `tree`'s leaves are defined. Every array's copy is in flight
    before anything waits, as a plain conversion issues it: `pull` issues
    them first, all of them together, unless the caller has (`started`).

    With the run's spans on, the read's span (the innermost open one) gets
    two children: `ready`, issuing those copies and waiting for the
    arrays' definition, and `copy`, turning the ready arrays into host
    values; and the counters `arrays` and `bytes` (the arrays' `nbytes`).
    With them off (no `telem`, or a kill switch) it is the conversion
    alone, after the copies."""
    import jax

    if convert is None:
        convert = functools.partial(_tree_map, np.asarray, tree)
    if telem is None or not telem.spans:
        if not started:
            start_copies(tree)
        return convert()
    arrays = [a for a in jax.tree_util.tree_leaves(tree) if hasattr(a, "copy_to_host_async")]
    telem.count(arrays=len(arrays), bytes=sum(a.nbytes for a in arrays))
    telem.child("ready")
    try:
        if not started:
            start_copies(arrays)
        jax.block_until_ready(arrays)
        telem.child("copy")
        return convert()
    finally:
        telem.child(None)


class PipelineStats:
    """Shared counters behind the prefetcher's `Pipeline/*` telemetry
    gauges. `flush()` returns the per-interval gauge dict and zeroes the
    window."""

    def __init__(self) -> None:
        self.sample_hits = 0
        self.sample_misses = 0
        self.sample_prefetches = 0

    def flush(self) -> dict[str, float]:
        out: dict[str, float] = {}
        served = self.sample_hits + self.sample_misses
        if served:
            out["Pipeline/sample_hit_rate"] = self.sample_hits / served
        out["Pipeline/sample_prefetches"] = float(self.sample_prefetches)
        self.__init__()
        return out


class _Handle:
    """One dispatched d2h copy; `get()` is the blocking read."""

    __slots__ = ("_tree", "_telem", "_started")

    def __init__(self, tree, telem, started: bool):
        self._tree = tree
        self._telem = telem
        self._started = started

    def get(self):
        return pull(self._tree, self._telem, started=self._started)


class ActionPipeline:
    """Split the policy-step d2h pull into dispatch (async copy starts) and
    read (blocking), so the RTT overlaps whatever host work runs in
    between. Disabled mode performs the same blocking conversion the
    synchronous loops always did — call sites are mode-agnostic."""

    def __init__(self, enabled: bool = True, lag: int = 0, telem=None):
        if lag < 0:
            raise ValueError(f"lag must be >= 0, got {lag}")
        self.enabled = enabled
        self.lag = lag
        self._telem = telem
        self._fifo: deque[_Handle] = deque()

    def dispatch(self, tree) -> _Handle:
        """Start the async device->host copies for every jax leaf and
        return a handle whose `get()` blocks on the (by then usually
        landed) transfer."""
        if self.enabled:
            start_copies(tree)
        return _Handle(tree, self._telem, started=self.enabled)

    def fetch(self, tree):
        """dispatch + read in one call, honoring `lag`: with `lag=0` the
        returned host values are this step's (bit-exact vs the synchronous
        pull); with `lag=k`, the value dispatched k calls ago is returned
        and the first k calls return None (the caller primes with k extra
        policy steps, or falls back to a random action)."""
        if not self.enabled:
            return pull(tree, self._telem)
        handle = self.dispatch(tree)
        if self.lag == 0:
            return handle.get()
        self._fifo.append(handle)
        if len(self._fifo) <= self.lag:
            return None
        return self._fifo.popleft().get()

    def flush(self) -> list:
        """Drain any in-flight lagged entries (end of run)."""
        out = [h.get() for h in self._fifo]
        self._fifo.clear()
        return out


class SamplePrefetcher:
    """K=1 double-buffered replay sampler (see module docstring for the
    epoch-consistency guard). Wraps any buffer exposing `sample`; the
    guard and PRNG rewind engage when the buffer also exposes `epoch` and
    `get_sample_state`/`set_sample_state` (data/buffers.py) — without
    them every serve falls back to a fresh synchronous sample.

    `max_staleness` (buffer epochs) > 0 opts into serving prefetched
    batches across ring writes: the batch is a consistent snapshot of the
    ring at prefetch time (device gathers capture the store at dispatch),
    but the newest `<= max_staleness` writes are not sampleable — an
    off-policy-only relaxation (howto/pipelining.md)."""

    def __init__(
        self,
        rb,
        enabled: bool = True,
        max_staleness: int = 0,
        stats: PipelineStats | None = None,
    ):
        self._rb = rb
        # host/memmap rings gather synchronously on host — prefetching
        # would do the same blocking work one call early for no overlap
        self.enabled = enabled and getattr(rb, "is_device_backed", False)
        self.max_staleness = max_staleness
        self._stats = stats if stats is not None else PipelineStats()
        self._pre: tuple | None = None  # (sig, epoch, prng_state, batch)
        self._last_epoch: int | None = None  # epoch at the previous serve

    def __getattr__(self, name):  # delegate everything else to the buffer
        return getattr(self._rb, name)

    def sample(self, *args, **kwargs):
        rb = self._rb
        if not self.enabled:
            return rb.sample(*args, **kwargs)
        sig = (args, tuple(sorted(kwargs.items())))
        batch = None
        if self._pre is not None:
            p_sig, p_epoch, p_state, p_batch = self._pre
            self._pre = None
            epoch = getattr(rb, "epoch", None)
            fresh = (
                p_sig == sig
                and p_epoch is not None
                and epoch is not None
                and epoch - p_epoch <= self.max_staleness
            )
            if fresh:
                self._stats.sample_hits += 1
                batch = p_batch
            else:
                # epoch-consistency guard: the ring advanced (or the call
                # signature changed) since the prefetch — discard it and
                # REWIND the sampler's PRNG to the state the prefetch
                # consumed, so the fresh resample draws the same key the
                # synchronous path would have (bit-exact on/off A/B) and
                # samples against the rows that now exist
                self._stats.sample_misses += 1
                if p_state is not None:
                    try:
                        rb.set_sample_state(p_state)
                    # sheeplint: disable=SL012 — best-effort PRNG rewind after a
                    # discarded prefetch; the miss is already counted in
                    # sample_misses and the fresh resample is correct either way
                    except Exception:
                        pass
        if batch is None:
            batch = rb.sample(*args, **kwargs)
        # dispatch the NEXT sample now — its packed index put + device
        # gather execute while the caller's train step runs — but only when
        # it can plausibly hit: a discarded prefetch still paid its put +
        # gather, so in write-every-gap loops (epoch advanced between the
        # last two serves, strict staleness) prefetching is paused until a
        # quiet gap re-arms it (e.g. the multi-sample pretrain/catch-up
        # bursts, where it then hits every call)
        epoch_now = getattr(rb, "epoch", None)
        predict_quiet = (
            self.max_staleness > 0
            or self._last_epoch is None
            or epoch_now is None
            or epoch_now == self._last_epoch
        )
        self._last_epoch = epoch_now
        if predict_quiet:
            try:
                state = (
                    rb.get_sample_state() if hasattr(rb, "get_sample_state") else None
                )
                pre_batch = rb.sample(*args, **kwargs)
                self._pre = (sig, epoch_now, state, pre_batch)
                self._stats.sample_prefetches += 1
            except Exception:
                self._pre = None
        return batch


class MetricDrain:
    """Deferred metric resolution: `drain(agg, step)` returns the PREVIOUS
    interval's `(metrics, step)` pairs (whose d2h copies were issued one
    interval ago and have landed) and snapshots + resets the current one.
    Disabled mode computes eagerly — identical to the pre-pipeline loops.
    Call `flush()` after the training loop to resolve the final snapshot.
    Either way the blocking read is `pull`'s, split under `telem`."""

    def __init__(self, enabled: bool = True, telem=None):
        self.enabled = enabled
        self._telem = telem
        self._pending: tuple | None = None  # (PendingMetrics, step)

    def drain(self, aggregator, step: int) -> list[tuple[dict, int]]:
        if not self.enabled:
            out = [(aggregator.compute(self._telem), step)]
            aggregator.reset()
            return out
        out = []
        if self._pending is not None:
            snap, s = self._pending
            out.append((snap.resolve(self._telem), s))
        self._pending = (aggregator.snapshot(), step)
        aggregator.reset()
        return out

    def flush(self) -> list[tuple[dict, int]]:
        if self._pending is None:
            return []
        snap, s = self._pending
        self._pending = None
        return [(snap.resolve(self._telem), s)]


class Pipeline:
    """Facade the algorithm mains construct once: `.action` (the d2h
    pipeline), `.sampler(rb)` (the prefetching wrapper), and
    `.drain_metrics` / `.flush_metrics` (the deferred drain). With
    `--pipeline off` every member runs the exact synchronous path, so the
    mains carry ONE code path for both modes."""

    def __init__(
        self, enabled: bool = False, lag: int = 0, max_staleness: int = 0, telem=None
    ):
        self.enabled = enabled
        self.max_staleness = max_staleness
        self.stats = PipelineStats()
        self.action = ActionPipeline(enabled, lag=lag, telem=telem)
        self._drain = MetricDrain(enabled, telem=telem)
        self._samplers: dict[int, SamplePrefetcher] = {}

    @classmethod
    def from_args(cls, args, telem=None) -> "Pipeline":
        """The mains' shared construction helper: `--pipeline on` enables
        all three primitives (bit-exact defaults: lag=0, strict epoch
        guard); SHEEPRL_TPU_PIPELINE_STALENESS opts into the off-policy
        staleness relaxation. The reads split their spans under `telem` in
        both modes; the prefetcher's `Pipeline/*` gauges are registered on
        it when enabled."""
        enabled = str(getattr(args, "pipeline", "off")) == "on"
        staleness = int(os.environ.get("SHEEPRL_TPU_PIPELINE_STALENESS", "0"))
        pipe = cls(enabled=enabled, max_staleness=staleness, telem=telem)
        if telem is not None and enabled:
            telem.add_gauges(pipe.gauges)
        return pipe

    def sampler(self, rb) -> SamplePrefetcher:
        """The prefetching wrapper for `rb`, cached per buffer instance so
        call sites may use `pipe.sampler(rb).sample(...)` inline — the
        double-buffer state persists across calls."""
        wrapper = self._samplers.get(id(rb))
        if wrapper is None or wrapper._rb is not rb:
            wrapper = SamplePrefetcher(
                rb, enabled=self.enabled, max_staleness=self.max_staleness,
                stats=self.stats,
            )
            self._samplers[id(rb)] = wrapper
        return wrapper

    def drain_metrics(self, aggregator, step: int) -> list[tuple[dict, int]]:
        return self._drain.drain(aggregator, step)

    def flush_metrics(self) -> list[tuple[dict, int]]:
        return self._drain.flush()

    def gauges(self) -> dict[str, float]:
        return self.stats.flush()
