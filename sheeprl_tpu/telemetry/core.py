"""The run-wide telemetry orchestrator every algorithm main constructs.

Always-on, low-overhead observability (ISSUE 2 tentpole): hierarchical phase
timers, an XLA recompile tracker, device-memory gauges, a NaN/inf watchdog
over the logged metrics, and a rank-0 JSONL event log with a periodic
one-line console heartbeat. A main wires it in ~3 calls:

    telem = Telemetry.from_args(args, log_dir, rank, algo="ppo")
    ...
    telem.iteration(global_step)     # the loop body: the spans' parent
    telem.mark("rollout")            # or: with telem.phase("rollout"): ...
    ...
    telem.mark("rollout/env_step", phase="rollout")  # a finer span, same sum
    ...
    telem.mark("log/write", phase="log")
    logger.log_dict(telem.interval(aggregator.compute(), global_step, sps), step)
    telem.count(scalars=n)           # a counter on the open span, for a reader that is there
    ...
    telem.close()

`mark` / `phase` are the only two ways a main opens a phase; each is at once
a `Time/<phase>_seconds` sum, a `sheeprl/<phase>` annotation in any open
profiler session, and a span kept in memory (phase.py) that `close()` and
`abort()` write out as `span` events. `child` opens a part of the innermost
open span that is a span alone, with no sum and no annotation: the one
blocking read (`parallel/pipeline.py`'s `pull`) splits itself with it.

`interval()` merges everything the subsystem measured since the last call
into the metric dict (so the phase/compile/memory series ride the existing
TensorBoard pipeline with no extra logger calls), appends the merged dict to
`<log_dir>/telemetry.jsonl`, runs the non-finite watchdog, and prints the
heartbeat when due. Everything is host-side bookkeeping — no device syncs,
no jit retraces — so the instrumented hot loop stays within noise of the
uninstrumented one (tests/test_utils/test_telemetry.py holds the overhead on
the CPU; PERF.md has the chip's reading).

Kill switches: SHEEPRL_TPU_TELEMETRY=0 disables the subsystem (interval()
passes metrics through untouched, no phase opens); SHEEPRL_TPU_TRACE=0 keeps
the `Time/*` sums and drops the annotations and the spans. Non-rank-0
processes keep the timers (the merged dict goes to their no-op logger
anyway) but never write JSONL or heartbeat lines.
"""

from __future__ import annotations

import atexit
import math
import os
import sys
import time
import traceback
from contextlib import nullcontext
from typing import Any, Callable, ContextManager

from ..compile.cache import CacheStats
from .compile_tracker import CompileTracker
from .events import JsonlEventLog
from .phase import PhaseTimers
from .trace import trace_enabled

__all__ = [
    "Telemetry", "emit", "active_telemetry", "device_memory_gauges", "device_report",
]

# ---------------------------------------------------------------------------
# Global emit: shared helpers that should not depend on a Telemetry handle
# (save_checkpoint, StepProfiler) publish lifecycle events through here; they
# reach every live instance (normally exactly one per process).
# ---------------------------------------------------------------------------

_active: list["Telemetry"] = []


def active_telemetry() -> list["Telemetry"]:
    return list(_active)


def emit(event: str, **data: Any) -> None:
    """Publish a lifecycle event to every active Telemetry instance; no-op
    when none is live (tools, tests, bare library use)."""
    for t in list(_active):
        t.event(event, **data)


# last uncaught exception, captured so the atexit crash event can name it
_last_exc: list[str] = []
_excepthook_installed = False


def _install_excepthook() -> None:
    global _excepthook_installed
    if _excepthook_installed:
        return
    prev = sys.excepthook

    def hook(exc_type, exc, tb):
        _last_exc[:] = ["".join(traceback.format_exception_only(exc_type, exc)).strip()]
        prev(exc_type, exc, tb)

    sys.excepthook = hook
    _excepthook_installed = True


def device_report() -> dict[str, Any]:
    """What this process runs on, as jax reports it — opens the backend, and
    raises if jax cannot (a run whose device is unknown must not start):
    platform, device kind, local/global device count, the jax / jaxlib /
    libtpu versions and the armed compile-cache directory."""
    import importlib.metadata

    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }


def device_memory_gauges() -> dict[str, float]:
    """Per-local-device HBM gauges from `device.memory_stats()`:
    bytes_in_use + peak_bytes_in_use (CPU devices report none — empty dict)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return {}
    out: dict[str, float] = {}
    for i, d in enumerate(devices):
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for src, dst in (
            ("bytes_in_use", f"Memory/d{i}_bytes_in_use"),
            ("peak_bytes_in_use", f"Memory/d{i}_peak_bytes_in_use"),
        ):
            if src in stats:
                out[dst] = float(stats[src])
    return out


class Telemetry:
    FILENAME = "telemetry.jsonl"

    def __init__(
        self,
        log_dir: str | None,
        rank: int = 0,
        algo: str = "",
        enabled: bool = True,
        heartbeat_s: float = 30.0,
        role: str = "",
        run_id: str | None = None,
    ):
        self.enabled = enabled
        self.rank = rank
        self.algo = algo
        # sheepscope role shard (ISSUE 17): non-learner roles write
        # telemetry.<role>.jsonl next to the learner's telemetry.jsonl so
        # tools/sheeptrace.py can merge all of a run's shards by run id
        self.role = role or "learner"
        self.run_id = run_id
        self.log_dir = log_dir
        self.heartbeat_s = heartbeat_s
        self.timers = PhaseTimers(spans=enabled and trace_enabled())
        self._gauge_sources: list[Callable[[], dict[str, float]]] = []
        self._last_step: int | None = None
        self._last_heartbeat = time.monotonic()
        self._last_jsonl_log = 0.0
        self._last_nan_warn = 0.0
        self._closed = not enabled
        self._compiles = CompileTracker()
        self._cache = CacheStats()  # persistent compile-cache hits/misses
        self._tracer = None
        write_jsonl = enabled and rank == 0 and log_dir is not None
        filename = (
            self.FILENAME
            if self.role == "learner"
            else f"telemetry.{self.role}.jsonl"
        )
        self._log = JsonlEventLog(
            os.path.join(log_dir, filename) if write_jsonl else None
        )
        if enabled:
            self._compiles.attach()
            self._cache.attach()
            _install_excepthook()
            atexit.register(self._atexit)
            _active.append(self)

    @property
    def tracer(self):
        """This shard's span emitter (lazy — trace.py is pure stdlib but
        there is no reason to build a Tracer nobody asks for)."""
        if self._tracer is None:
            from .trace import Tracer

            self._tracer = Tracer(self)
        return self._tracer

    # ---- construction policy ---------------------------------------------
    @classmethod
    def from_args(
        cls, args: Any, log_dir: str, rank: int = 0, algo: str = "", role: str = ""
    ) -> "Telemetry":
        """The mains' shared construction helper: always-on unless
        SHEEPRL_TPU_TELEMETRY=0, JSONL/heartbeat on process 0 only, and a
        `start` lifecycle event carrying the run identity and the device
        report (`device_report()`: it opens the backend). Checkpoint and
        profile-window lifecycle events arrive via the module-level `emit`
        (save_checkpoint / StepProfiler publish them directly). `role`
        selects the sheepscope shard filename (actor{N}/serve) and stamps
        the shared run id into the `start` event."""
        from .trace import ensure_run_id

        # sheepsync (ISSUE 18): the runtime thread sanitizer is installed
        # as early as possible so locks allocated by this process are
        # instrumented; its Sync/* gauges ride every telemetry interval
        from ..analysis import thread_sanitizer

        if getattr(args, "sanitize_threads", False):
            thread_sanitizer.install()
        else:
            thread_sanitizer.maybe_install_from_env()

        enabled = os.environ.get("SHEEPRL_TPU_TELEMETRY", "1") != "0"
        telem = cls(
            log_dir, rank=rank, algo=algo, enabled=enabled,
            role=role, run_id=ensure_run_id() if enabled else None,
        )
        if enabled:
            telem.event(
                "start",
                algo=algo,
                env_id=getattr(args, "env_id", None),
                seed=getattr(args, "seed", None),
                num_envs=getattr(args, "num_envs", None),
                precision=getattr(args, "precision", None),
                **device_report(),
                rank=rank,
                log_dir=log_dir,
                role=telem.role,
                run=telem.run_id,
                compile_tracking=telem._compiles.supported,
            )
        san = thread_sanitizer.installed()
        if san is not None:
            telem.add_gauges(thread_sanitizer.gauges)
            # install() ran before this instance existed, so its start
            # marker found no sink — re-emit through the live instance
            telem.event(
                "sync.sanitizer_start",
                committed_edges=len(san.committed),
                lock_sites=len(san.sites),
            )
        return telem

    # ---- phase timing -----------------------------------------------------
    def phase(self, name: str) -> ContextManager[None]:
        return self.timers.phase(name) if self.enabled else nullcontext()

    def mark(self, name: str | None, phase: str | None = None) -> None:
        if self.enabled:
            self.timers.mark(name, phase)

    def iteration(self, step: int | None) -> None:
        if self.enabled:
            self.timers.iteration(step)

    def child(self, name: str | None) -> None:
        self.timers.child(name)  # no spans where telemetry is off: one flag test

    def count(self, **counters: Any) -> None:
        if self.enabled:
            self.timers.count(**counters)

    @property
    def spans(self) -> bool:
        """Whether the loop's spans are kept (off under either kill switch)."""
        return self.timers.spans

    # ---- gauges / events --------------------------------------------------
    def add_gauges(self, source: Callable[[], dict[str, float]]) -> None:
        """Register a callable polled at every interval (e.g. the decoupled
        topology's queue-depth/staleness gauges)."""
        self._gauge_sources.append(source)

    def event(self, name: str, /, **data: Any) -> None:
        # positional-only: span events carry their own `name` payload key
        self._log.emit(name, **data)

    # ---- the per-logging-interval merge ----------------------------------
    def interval(
        self, metrics: dict[str, Any], step: int, sps: float | None = None
    ) -> dict[str, Any]:
        """Merge this interval's telemetry into `metrics` (returned as a new
        dict), append the JSONL `log` event, run the NaN watchdog, and print
        the heartbeat when due. Call once per logging interval, BEFORE
        `logger.log_dict`."""
        if not self.enabled:
            return metrics
        out = dict(metrics)
        dstep = None if self._last_step is None else step - self._last_step
        for name, secs in self.timers.flush().items():
            out[f"Time/{name}_seconds"] = secs
            if dstep and secs > 0.0:
                out[f"Time/{name}_sps"] = dstep / secs
        if self._compiles.supported:
            comp = self._compiles.flush()
            out["XLA/recompiles"] = comp["compiles"]
            out["XLA/compile_seconds"] = comp["compile_seconds"]
            out["XLA/total_compiles"] = comp["total_compiles"]
            out["XLA/total_compile_seconds"] = comp["total_compile_seconds"]
        cache = self._cache.snapshot()
        out["XLA/cache_hits"] = cache["hits"]
        out["XLA/cache_misses"] = cache["misses"]
        out.update(device_memory_gauges())
        gauge_errors = 0
        for source in self._gauge_sources:
            try:
                out.update(source())
            except Exception:
                # a gauge source must never kill the loop — but a silently
                # dead source is an observability hole (SL012), so the
                # failure count rides the metrics it failed to produce
                gauge_errors += 1
        if gauge_errors:
            out["Health/gauge_source_errors"] = float(gauge_errors)
        self._nan_watchdog(out, step)
        self._last_step = step
        now = time.monotonic()
        # JSONL: every interval that carries real metrics, throttled to the
        # heartbeat cadence for metric-less intervals (the dreamer family
        # calls interval() every env step; most carry only phase time)
        if metrics or (now - self._last_jsonl_log) >= self.heartbeat_s:
            payload = dict(out)
            if sps is not None:
                payload["Time/step_per_second"] = sps
            self.event("log", step=step, metrics=payload)
            self._last_jsonl_log = now
        if self.rank == 0 and (now - self._last_heartbeat) >= self.heartbeat_s:
            self._heartbeat(out, step, sps)
            self._last_heartbeat = now
        return out

    # ---- internals --------------------------------------------------------
    def _nan_watchdog(self, merged: dict[str, Any], step: int) -> None:
        bad = {}
        for k, v in merged.items():
            if isinstance(v, float) and not math.isfinite(v):
                bad[k] = repr(v)
        if not bad:
            return
        merged["Health/nonfinite_metrics"] = float(len(bad))
        self.event("health.nan", step=step, keys=sorted(bad), values=bad)
        now = time.monotonic()
        if self.rank == 0 and now - self._last_nan_warn >= self.heartbeat_s:
            print(
                f"[telemetry {self.algo}] WARNING: non-finite metrics at "
                f"step {step}: {sorted(bad)}",
                file=sys.stderr,
            )
            self._last_nan_warn = now

    def _heartbeat(self, merged: dict[str, Any], step: int, sps: float | None) -> None:
        phases = {
            k[len("Time/"):-len("_seconds")]: v
            for k, v in merged.items()
            if k.startswith("Time/") and k.endswith("_seconds")
        }
        total = sum(phases.values())
        if total > 0:
            top = sorted(phases.items(), key=lambda kv: -kv[1])[:4]
            breakdown = " ".join(f"{n} {100 * s / total:.0f}%" for n, s in top)
        else:
            breakdown = "-"
        bits = [f"[telemetry {self.algo}] step={step}"]
        if sps is not None:
            bits.append(f"sps={sps:.1f}")
        bits.append(f"| {breakdown}")
        if "XLA/total_compiles" in merged:
            bits.append(
                f"| compiles={merged['XLA/total_compiles']:.0f} "
                f"({merged['XLA/total_compile_seconds']:.1f}s)"
            )
        mem = [v for k, v in merged.items() if k.endswith("_bytes_in_use")]
        if mem:
            bits.append(f"| mem={sum(mem) / 2**30:.2f}GiB")
        print(" ".join(bits), file=sys.stderr)

    # ---- lifecycle --------------------------------------------------------
    def _write_spans(self) -> None:
        """The loop's spans, kept in memory until now (phase.py): every way
        out of a run writes them, the preempted and the crashed one too."""
        if self._log.enabled:
            for record in self.timers.drain():
                self.event("span", **record)

    def _atexit(self) -> None:
        if not self._closed:
            self._write_spans()
            self.event(
                "crash",
                error=_last_exc[0] if _last_exc else "process exited without close()",
            )
            self._teardown()

    def abort(self, error: str | None = None) -> None:
        """Crash-path teardown (the `@resilience.crashsafe` scope): emit a
        `crash` record when given one, then close the JSONL WITHOUT the
        clean-exit `end` event — a post-mortem can tell an aborted run from
        a completed one by the missing `end`."""
        if self._closed:
            return
        self._write_spans()
        if error is not None:
            self.event("crash", error=error, handled=True)
        try:
            atexit.unregister(self._atexit)
        # sheeplint: disable=SL012 — unregister during interpreter teardown;
        # the event log this would be reported to is being closed right here
        except Exception:
            pass
        self._teardown()

    def close(self) -> None:
        """Normal end-of-run teardown: flush open phases, emit `end`."""
        if self._closed:
            return
        self._write_spans()
        cache = self._cache.snapshot()
        self.event(
            "end", phases=self.timers.flush(),
            cache_hits=cache["hits"], cache_misses=cache["misses"],
        )
        try:
            atexit.unregister(self._atexit)
        # sheeplint: disable=SL012 — unregister during interpreter teardown;
        # the event log this would be reported to is being closed right here
        except Exception:
            pass
        self._teardown()

    def _teardown(self) -> None:
        self._closed = True
        self._compiles.detach()
        self._cache.detach()
        self._log.close()
        if self in _active:
            _active.remove(self)
