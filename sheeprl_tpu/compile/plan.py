"""CompilePlan: AOT shape-capture + background warm-start compilation.

The ISSUE 5 tentpole. XLA compile latency is the dominant startup cost of
every algorithm task (the full-scale DreamerV3 step is ~30-40 s per config
on TPU and ~30 s even at debug widths on XLA:CPU — graph complexity, not
width, drives it), and the off-policy tasks all spend their
`learning_starts` window collecting random actions — dead time in which
the update executables could already be compiling. Podracer
(arXiv:2104.06272) keeps the chip busy through exactly these
startup/handoff windows; MSRL (arXiv:2210.00882) treats the training
program as schedulable fragments. This module does the minimal JAX-native
version of both:

  1. **shape capture** — each algo main registers its hot jits (train step,
     player policy, GAE, recon, imagination) together with a zero-cost
     *example thunk* producing their exact call arguments (live pytrees
     and/or `jax.ShapeDtypeStruct` specs);
  2. **AOT compile** — `jit.lower(*avals).compile()` builds the executable
     without executing anything;
  3. **background warm start** — worker threads run the AOT compiles
     concurrently with env collection (`--warm_compile on`); the returned
     wrapper is the **barrier**: its first call blocks until that entry's
     compile finishes, then dispatches the AOT executable directly. XLA
     compilation releases the GIL, so collection and compilation genuinely
     overlap on one process — fully on multi-core hosts, and inside the
     env-latency windows (real-time envs) even on a single core.

`SHEEPRL_TPU_WARM_MODE=warmup` swaps step 2-3 for a background warmup
call on synthesized dummy zeros: the executable lands in the jit's own
dispatch cache (it IS the cold-path executable, and this dodges a measured
~1.7x AOT-vs-dispatch compile penalty on XLA:CPU) at the price of
executing one dummy update — use where execution is cheap vs compile.

Equivalence guarantee: the AOT path lowers the SAME jitted callable at the
SAME input avals the live call would, so the compiled program is identical
to the cold-path one and results are bit-exact vs `--warm_compile off`
(tests/test_compile/test_plan.py). Any aval mismatch at call time (shape
drift, weak-type flip, resharded input) falls back to the original jitted
callable — warm start can only lose its head start, never change results.

Observability: per-executable compile seconds and persistent-cache hit/miss
counts surface as `Compile/*` gauges (registered with the run's Telemetry)
plus `compile` events in telemetry.jsonl, and the plan stamps
`Compile/time_to_first_update_seconds` when the first `role="update"` call
returns.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Any, Callable

from .cache import CacheStats

__all__ = [
    "CaptureComplete",
    "CompilePlan",
    "DataEdge",
    "WarmJit",
    "avals_of",
    "sds",
]


class CaptureComplete(BaseException):
    """Raised by `CompilePlan.start()` in capture mode
    (`SHEEPRL_TPU_PLAN_MODE=capture`): unwinds the algo main at the exact
    point where the training loop would begin — every hot jit is registered
    with its example thunk, nothing has executed — carrying the plan to the
    caller (tools/sheepcheck.py). BaseException on purpose: a stray
    `except Exception` in a main must not swallow the unwind."""

    def __init__(self, plan: "CompilePlan"):
        super().__init__("compile plan captured (SHEEPRL_TPU_PLAN_MODE=capture)")
        self.plan = plan


def sds(shape, dtype, sharding=None):
    """Shorthand for `jax.ShapeDtypeStruct` (the shape-capture spec leaf)."""
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def avals_of(tree: Any) -> Any:
    """Map a pytree of arrays to ShapeDtypeStructs. COMMITTED jax.Arrays
    (device_put with an explicit sharding/device — replicated train states,
    trainer-mesh batches, player-device obs) keep their sharding so the AOT
    executable is built for the layout the live call uses; uncommitted
    arrays (fresh `jnp.asarray` puts, PRNG keys) stay sharding-free —
    capturing their incidental device-0 placement would make the lowering
    reject mixed-device calls the live jit resolves fine. Non-array leaves
    (python scalars, None, specs) pass through untouched — `lower()` treats
    them exactly as a live call would, weak types included."""
    import jax
    import numpy as np

    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, jax.Array):
            sharding = x.sharding if getattr(x, "_committed", False) else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(one, tree)


class DataEdge:
    """A declared producer->consumer contract between two registered jits:
    "(some of) `src`'s outputs become `dst`'s inputs". The sheepshard
    analyzer (analysis/shard_check.py) resolves both ends to their
    compiled SPMD shardings and checks the contract:

      - `expect="match"`: the data flows device-to-device with no host
        reshuffle in between (the Anakin rollout->gae path), so the
        producer's output sharding and the consumer's input sharding must
        agree — a disagreement forces an implicit reshard (all-gather +
        re-slice) on EVERY handoff (rule SC008);
      - `expect="reshard"`: the main reshuffles the data on purpose between
        the two jits (host reshape + shard_batch, a replay ring, a
        decoupled to_trainers put), so a sharding change across the edge is
        the documented contract; the resolved pair is still recorded in
        the comms ledger so drift stays visible.

    `pairs` optionally names exact (src_output_index, dst_input_index)
    flat positions; when None the analyzer matches outputs to inputs by
    (shape, dtype) groups. This is the first concrete slice of the
    ROADMAP-4 fragment graph: the data edges of the fragment dataflow,
    declared once per main, machine-checkable."""

    __slots__ = ("src", "dst", "pairs", "expect", "note")

    def __init__(
        self,
        src: str,
        dst: str,
        pairs: list[tuple[int, int]] | None = None,
        expect: str = "match",
        note: str | None = None,
    ):
        if expect not in ("match", "reshard"):
            raise ValueError(f"expect must be 'match' or 'reshard', got {expect!r}")
        self.src = src
        self.dst = dst
        self.pairs = pairs
        self.expect = expect
        self.note = note

    @property
    def key(self) -> str:
        return f"{self.src}->{self.dst}"


class _Entry:
    __slots__ = (
        "name", "fn", "example", "role", "executable", "compile_seconds",
        "cache_hits", "cache_misses", "error", "done", "aot_calls",
        "fallbacks", "barrier_wait_s", "warmed", "memory",
    )

    def __init__(self, name: str, fn: Callable, example: Callable | None, role: str | None):
        self.name = name
        self.fn = fn
        self.example = example
        self.role = role
        self.executable: Any = None
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.error: str | None = None
        self.done = threading.Event()
        self.aot_calls = 0
        self.fallbacks = 0
        self.barrier_wait_s = 0.0
        self.warmed = False
        self.memory: dict | None = None  # memory_analysis of the AOT exe


def _materialize(specs: Any) -> Any:
    """Dummy call arguments for warmup mode: zeros for every captured aval
    (device_put to the captured sharding when committed); non-spec leaves
    (python scalars) pass through."""
    import jax
    import jax.numpy as jnp

    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            z = jnp.zeros(x.shape, x.dtype)
            if x.sharding is not None:
                z = jax.device_put(z, x.sharding)
            return z
        return x

    return jax.tree_util.tree_map(one, specs)


class WarmJit:
    """The callable a main uses in place of its raw jit. Dispatch policy:

    - warm start running and this entry not compiled yet -> BLOCK (the
      barrier before the first update);
    - AOT executable available -> call it directly (no retrace, no
      dispatch-cache miss);
    - no executable (warm off, unsupported fn, compile error, or a prior
      aval mismatch) -> call the original jitted fn.

    Also the `time_to_first_update_seconds` probe: the first completed call
    of a `role="update"` entry stamps the plan, warm or cold alike.
    """

    __slots__ = ("_entry", "_plan")

    def __init__(self, entry: _Entry, plan: "CompilePlan"):
        self._entry = entry
        self._plan = plan

    @property
    def fn(self) -> Callable:
        """The underlying jitted callable (escape hatch for introspection)."""
        return self._entry.fn

    def __call__(self, *args, **kwargs):
        e = self._entry
        plan = self._plan
        if plan._started and not e.done.is_set():
            t0 = time.perf_counter()
            e.done.wait()
            e.barrier_wait_s += time.perf_counter() - t0
        exe = e.executable
        if exe is not None and not kwargs:
            try:
                out = exe(*args)
                e.aot_calls += 1
            except Exception as err:  # aval/sharding drift: fall back for good
                e.executable = None
                e.fallbacks += 1
                plan._event(
                    "compile",
                    jit=e.name,
                    mode="aot_fallback",
                    error=f"{type(err).__name__}: {err}"[:300],
                )
                out = e.fn(*args, **kwargs)
        else:
            out = e.fn(*args, **kwargs)
        if e.role == "update" and plan._first_update_s is None:
            plan._note_first_update()
        return out


class CompilePlan:
    """Registry of a run's hot jits + the background warm-start engine.

    Wiring (every algo main):

        plan = CompilePlan.from_args(args, telem)
        telem.add_gauges(plan.gauges)
        ...
        train_step = plan.register("train_step", train_step,
                                   example=lambda: (state, data_spec, key, flag),
                                   role="update")
        policy_step = plan.register("policy_step", policy_step,
                                    example=lambda: (actor, obs_spec, key))
        plan.start()          # overlaps with the learning_starts collection
        ... training loop unchanged (first update blocks on the barrier) ...
        plan.close()

    With `--warm_compile off` the wrappers are pass-throughs (plus the
    first-update stamp) and `start()` is a no-op — the cold path is the
    exact seed behavior.
    """

    def __init__(
        self,
        enabled: bool = False,
        telem: Any = None,
        threads: int | None = None,
        capture_only: bool = False,
    ):
        self.enabled = enabled
        # capture mode (sheepcheck): record EVERY register() with its example
        # thunk regardless of --warm_compile, compile nothing, and raise
        # CaptureComplete from start() so the main never runs a step
        self.capture_only = capture_only
        self._telem = telem
        self._threads = threads
        self._entries: list[_Entry] = []
        self._edges: list[DataEdge] = []
        self._lock = threading.Lock()
        self._started = False
        self._closed = False
        self._t0 = time.perf_counter()
        self._first_update_s: float | None = None
        self._workers: list[threading.Thread] = []
        self._queue: list[_Entry] = []
        self._cache_stats = CacheStats()

    @classmethod
    def from_args(cls, args: Any, telem: Any = None) -> "CompilePlan":
        capture_only = os.environ.get("SHEEPRL_TPU_PLAN_MODE") == "capture"
        enabled = getattr(args, "warm_compile", "off") == "on" and not capture_only
        threads = int(os.environ.get("SHEEPRL_TPU_WARM_THREADS", "0")) or None
        return cls(
            enabled=enabled, telem=telem, threads=threads, capture_only=capture_only
        )

    # ---- registration ------------------------------------------------------
    def register(
        self,
        name: str,
        fn: Callable,
        example: Callable[[], tuple] | None = None,
        role: str | None = None,
    ) -> Callable:
        """Register a jitted callable with a thunk producing its exact call
        arguments (live pytrees / ShapeDtypeStructs; evaluated lazily in the
        compile worker). Returns the callable the main should use in place
        of `fn`. A fn without `.lower` (e.g. a checkify wrapper) or without
        an example is tracked for first-update timing only."""
        if self.capture_only:
            # shape capture: keep the raw entry (fn + example thunk) for
            # sheepcheck's abstract eval; the main keeps its plain callable
            # (it never runs — start() raises CaptureComplete)
            entry = _Entry(name, fn, example, role)
            entry.done.set()
            with self._lock:
                self._entries.append(entry)
            return fn
        if not self.enabled and role is None:
            return fn
        entry = _Entry(name, fn, example, role)
        if not self.enabled or example is None or not hasattr(fn, "lower"):
            if self.enabled and example is not None:
                entry.error = "not AOT-lowerable"
            entry.done.set()
        with self._lock:
            self._entries.append(entry)
        return WarmJit(entry, self)

    def declare_edge(
        self,
        src: str,
        dst: str,
        pairs: list[tuple[int, int]] | None = None,
        expect: str = "match",
        note: str | None = None,
    ) -> None:
        """Declare that (some of) `src`'s outputs feed `dst`'s inputs — the
        cross-jit dataflow contract sheepshard's SC008 checks against the
        compiled SPMD shardings (see DataEdge). Zero-cost at runtime:
        edges are metadata, recorded in every plan mode."""
        with self._lock:
            self._edges.append(DataEdge(src, dst, pairs=pairs, expect=expect, note=note))

    @property
    def edges(self) -> list[DataEdge]:
        return list(self._edges)

    # ---- background compilation -------------------------------------------
    def start(self) -> None:
        """Kick off the AOT compiles. Call after the last register() and
        before the collection loop; idempotent; warm-off plans only re-anchor
        the first-update clock.

        `time_to_first_update_seconds` anchors HERE (not at construction):
        the metric prices the collect-then-compile critical path the warm
        start attacks, so it starts when collection starts — process setup
        (env build, buffer alloc, init-time mini-compiles) is identical in
        both arms and outside the subsystem's control."""
        if self._started:
            return
        if self.capture_only:
            self._started = True
            raise CaptureComplete(self)
        self._t0 = time.perf_counter()
        if not self.enabled:
            self._started = True
            return
        self._cache_stats.attach()
        # a run that dies (or returns) without plan.close() must still join
        # the compile workers: a daemon thread mid-XLA-compile at interpreter
        # teardown aborts the process (`terminate called without an active
        # exception`) — the registered-but-never-called-jit exit abort
        atexit.register(self.close)
        with self._lock:
            self._queue = [e for e in self._entries if not e.done.is_set()]
            # interaction jits (player/policy/gae) are needed from the FIRST
            # collection step; the update jits only at the training barrier.
            # Compile the cheap interaction entries first so the rollout
            # never queues behind a long train-step compile.
            self._queue.sort(key=lambda e: e.role == "update")
            n = min(
                self._threads or 1,
                max(len(self._queue), 1),
            )
        self._started = True
        for i in range(n):
            t = threading.Thread(
                target=self._worker, name=f"warm-compile-{i}", daemon=True
            )
            t.start()
            self._workers.append(t)

    def _worker(self) -> None:
        while True:
            with self._lock:
                if not self._queue or self._closed:
                    return
                entry = self._queue.pop(0)
            self._compile_entry(entry)

    def _compile_entry(self, e: _Entry) -> None:
        import jax  # noqa: F401  (worker threads need jax initialized)

        # SHEEPRL_TPU_WARM_MODE=warmup switches the engine from AOT
        # (`lower().compile()`, executes nothing, returns a Compiled the
        # wrapper dispatches directly) to a background WARMUP CALL: dummy
        # zeros are synthesized from the captured avals (respecting any
        # committed shardings) and `fn` is called once, outputs discarded —
        # the executable lands in the jit's own dispatch cache, so the main
        # thread's first real call is a pure cache hit. Warmup is the
        # stronger equivalence (the cached executable IS the cold-path one,
        # and it dodges the measured ~1.7x AOT compile penalty on XLA:CPU)
        # but it EXECUTES one dummy update — only worth it where execution
        # is cheap relative to compile. Donation is safe either way: the
        # donated buffers are the synthesized dummies.
        warmup = os.environ.get("SHEEPRL_TPU_WARM_MODE") == "warmup"
        before = self._cache_stats.snapshot()
        t0 = time.perf_counter()
        try:
            args = e.example()
            specs = avals_of(args)
            if warmup:
                dummies = _materialize(specs)
                jax.block_until_ready(e.fn(*dummies))
                e.warmed = True
            else:
                e.executable = e.fn.lower(*specs).compile()
                # the ISSUE-10 memory-capture hook: every AOT executable
                # reports its static footprint (the runtime half of the
                # sheepmem ledger — telemetry_report compares the two)
                from .partition import compiled_memory_stats

                e.memory = compiled_memory_stats(e.executable)
        except Exception as err:
            e.error = f"{type(err).__name__}: {err}"[:300]
        e.compile_seconds = time.perf_counter() - t0
        after = self._cache_stats.snapshot()
        # with the default single worker these deltas attribute exactly;
        # with SHEEPRL_TPU_WARM_THREADS>1 concurrent compiles share them
        e.cache_hits = after["hits"] - before["hits"]
        e.cache_misses = after["misses"] - before["misses"]
        e.done.set()
        self._event(
            "compile",
            jit=e.name,
            mode="warmup" if warmup else "warm",
            seconds=round(e.compile_seconds, 3),
            cache_hits=e.cache_hits,
            cache_misses=e.cache_misses,
            error=e.error,
        )

    def wait(self, timeout: float | None = None) -> bool:
        """Explicit barrier over every registered entry (the per-call
        barrier in WarmJit usually makes this unnecessary)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for e in list(self._entries):
            left = None if deadline is None else max(deadline - time.monotonic(), 0.0)
            if not e.done.wait(left):
                return False
        return True

    # ---- observability -----------------------------------------------------
    def _event(self, name: str, **data: Any) -> None:
        if self._telem is not None:
            try:
                self._telem.event(name, **data)
            # sheeplint: disable=SL012 — same contract as the sanitizer: the
            # event sink itself is the thing that failed
            except Exception:
                pass  # telemetry must never kill the compile path

    def _note_first_update(self) -> None:
        with self._lock:
            if self._first_update_s is not None:
                return
            self._first_update_s = time.perf_counter() - self._t0
        self._event(
            "first_update",
            seconds=round(self._first_update_s, 3),
            warm_compile="on" if self.enabled else "off",
        )

    @property
    def time_to_first_update_seconds(self) -> float | None:
        return self._first_update_s

    def stats(self) -> dict[str, Any]:
        entries = list(self._entries)
        return {
            "enabled": self.enabled,
            "entries": {
                e.name: {
                    "compiled": e.executable is not None or e.warmed,
                    "warmed": e.warmed,
                    "compile_seconds": e.compile_seconds,
                    "cache_hits": e.cache_hits,
                    "cache_misses": e.cache_misses,
                    "aot_calls": e.aot_calls,
                    "fallbacks": e.fallbacks,
                    "error": e.error,
                    "memory": e.memory,
                }
                for e in entries
            },
            "time_to_first_update_seconds": self._first_update_s,
        }

    def gauges(self) -> dict[str, float]:
        """`Compile/*` gauge source for Telemetry.add_gauges."""
        entries = list(self._entries)
        out = {
            "Compile/warm_enabled": float(self.enabled),
            "Compile/plan_entries": float(len(entries)),
            "Compile/plan_compiled": float(
                sum(1 for e in entries if e.executable is not None or e.warmed)
            ),
            "Compile/warm_compile_seconds": sum(e.compile_seconds for e in entries),
            "Compile/cache_hits": float(sum(e.cache_hits for e in entries)),
            "Compile/cache_misses": float(sum(e.cache_misses for e in entries)),
            "Compile/aot_calls": float(sum(e.aot_calls for e in entries)),
            "Compile/aot_fallbacks": float(sum(e.fallbacks for e in entries)),
            "Compile/barrier_wait_seconds": sum(e.barrier_wait_s for e in entries),
        }
        for e in entries:
            if e.compile_seconds:
                out[f"Compile/exe/{e.name}_seconds"] = e.compile_seconds
            if e.memory is not None:
                out[f"Compile/exe/{e.name}_peak_bytes"] = float(
                    e.memory["peak_bytes"]
                )
        peaks = [e.memory["peak_bytes"] for e in entries if e.memory is not None]
        if peaks:
            out["Compile/plan_peak_bytes_max"] = float(max(peaks))
        if self._first_update_s is not None:
            out["Compile/time_to_first_update_seconds"] = self._first_update_s
        return out

    # ---- lifecycle ---------------------------------------------------------
    def close(self, join_timeout: float | None = None) -> None:
        """End-of-run teardown: cancel queued compiles, join the workers
        (bounded), emit the summary event, detach listeners.

        The join is the exit-abort fix: a WarmJit whose jit is never called
        never waits on its entry, so a run could reach interpreter teardown
        with a worker daemon thread still inside an XLA compile — which
        aborts the process with `terminate called without an active
        exception`. Cancelling the queue bounds the wait to the ONE compile
        already in flight; the join waits for it up to
        `SHEEPRL_TPU_WARM_JOIN_S` (default 120 s — every measured XLA:CPU
        compile in this repo is well under that). `start()` wires this to
        `atexit` so even an exception path gets the join."""
        if self._closed:
            return
        self._closed = True
        try:
            atexit.unregister(self.close)
        # sheeplint: disable=SL012 — unregister of an already-drained atexit
        # hook during interpreter teardown; nothing to record, nowhere to
        # record it
        except Exception:
            pass
        # cancel entries the workers have not picked up yet; their barrier
        # waiters (if any raced close) fall back to the cold jitted fn
        with self._lock:
            cancelled, self._queue = self._queue, []
        for e in cancelled:
            if not e.done.is_set():
                e.error = e.error or "cancelled: plan closed before compile started"
                e.done.set()
        if join_timeout is None:
            try:
                join_timeout = float(os.environ.get("SHEEPRL_TPU_WARM_JOIN_S", "120"))
            except ValueError:
                join_timeout = 120.0
        deadline = time.monotonic() + max(join_timeout, 0.0)
        for t in self._workers:
            t.join(max(deadline - time.monotonic(), 0.0))
        self._cache_stats.detach()
        if self.enabled or self._first_update_s is not None:
            self._event("compile.summary", **_jsonable(self.stats()))


def _jsonable(d: dict) -> dict:
    import json

    try:
        json.dumps(d)
        return d
    except (TypeError, ValueError):
        return {"repr": repr(d)[:1000]}
