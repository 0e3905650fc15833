"""The ONE persistent-compilation-cache arming path.

:func:`arm_compile_cache` (called once, at package import) points jax's
persistent compilation cache at :func:`cache_dir` with the single
compile-time floor :data:`MIN_COMPILE_SECS`. Nothing else in the repo sets
`jax_compilation_cache_dir`.

Where the cache lives:

  1. ``JAX_COMPILATION_CACHE_DIR`` when set — the location is a deployment
     setting, placed from outside;
  2. otherwise ``<checkout>/logs/jax_compile_cache``, an absolute path
     derived from this file — the same from every cwd and every process of
     the checkout, so a second run finds what the first one wrote (never a
     tmpdir, uid, pid or time).

The unified decision store (``decisions.json``, compile/decisions.py) lives
in the same directory. ``SHEEPRL_TPU_XLA_CACHE=0`` disables the cache
entirely (arm_compile_cache returns None and touches nothing).

Cache hit/miss observability rides jax.monitoring: jax records
``/jax/compilation_cache/cache_hits`` per executable loaded from the cache
and ``cache_misses`` per executable written to it, and :class:`CacheStats`
counts them with the same attach/detach-listener pattern as telemetry's
CompileTracker (jax's listener registry is append-only, so ONE module-level
listener forwards to attached instances).
"""

from __future__ import annotations

import os
import threading

__all__ = ["MIN_COMPILE_SECS", "arm_compile_cache", "cache_dir", "CacheStats"]

# The single compile-time floor below which executables are not persisted:
# sub-half-second compiles recompile faster than a cache round-trip and would
# bloat the cache.
MIN_COMPILE_SECS = 0.5

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory of the compile cache and the decision store."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, "logs", "jax_compile_cache"
    )


def arm_compile_cache(*, min_compile_secs: float | None = None) -> str | None:
    """Point jax's persistent compilation cache at :func:`cache_dir` with
    one threshold. Returns the armed path, or None when the cache is
    disabled (``SHEEPRL_TPU_XLA_CACHE=0``) or jax is not installed (the
    pure-AST lint lane imports the package on bare CPython). A jax that
    refuses the configuration raises. Idempotent.

    ``min_compile_secs`` overrides :data:`MIN_COMPILE_SECS` — tests use 0.0
    to cache tiny graphs; production callers should not pass it.
    """
    if os.environ.get("SHEEPRL_TPU_XLA_CACHE", "1") == "0":
        return None
    try:
        import jax
    except ImportError:
        return None
    path = cache_dir()
    floor = MIN_COMPILE_SECS if min_compile_secs is None else min_compile_secs
    jax.config.update("jax_compilation_cache_dir", path)
    # no size floor; the compile-time floor is the only gate
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return path


# ---------------------------------------------------------------------------
# Hit/miss counting (module-level listener, instances attach/detach)
# ---------------------------------------------------------------------------

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_stats: set["CacheStats"] = set()
_installed = False


def _on_event(name: str, **kw) -> None:
    if name == _HIT_EVENT:
        with _lock:
            for s in _stats:
                s._hits += 1
    elif name == _MISS_EVENT:
        with _lock:
            for s in _stats:
                s._misses += 1


def _install_listener() -> None:
    global _installed
    if not _installed:
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_event)
        _installed = True


class CacheStats:
    """Counts persistent-cache hits and misses seen while attached."""

    def __init__(self) -> None:
        _install_listener()
        self._hits = 0
        self._misses = 0
        self._attached = False

    def attach(self) -> "CacheStats":
        if not self._attached:
            with _lock:
                _stats.add(self)
            self._attached = True
        return self

    def detach(self) -> None:
        if self._attached:
            with _lock:
                _stats.discard(self)
            self._attached = False

    def snapshot(self) -> dict[str, int]:
        with _lock:
            return {"hits": self._hits, "misses": self._misses}
