"""Regression receipts for the `--warm_compile on` exit abort (ISSUE 7
satellite): a registered-but-never-called jit used to leave a warm-compile
daemon thread inside an XLA compile at interpreter teardown, which aborts
the process with `terminate called without an active exception` (racy rc
134). `CompilePlan.start()` now wires `close()` to atexit, and `close()`
cancels the untouched queue and joins in-flight workers (bounded by
SHEEPRL_TPU_WARM_JOIN_S)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.compile import CompilePlan, sds

_REPO = Path(__file__).resolve().parents[2]

_NEVER_CALLED_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    # a persistent-cache hit would make the compile instant and the race
    # moot — force a real in-flight XLA compile at exit
    os.environ["SHEEPRL_TPU_XLA_CACHE"] = "0"
    os.environ.pop("SHEEPRL_TPU_PLAN_MODE", None)
    import jax
    import jax.numpy as jnp
    from sheeprl_tpu.compile import CompilePlan, sds

    class _Args:
        warm_compile = "on"

    plan = CompilePlan.from_args(_Args())

    @jax.jit
    def step(x):  # non-trivial: the worker is still compiling when we exit
        def body(c, _):
            c = jnp.tanh(c @ c.T) @ c
            return c, c.sum()
        c, ys = jax.lax.scan(body, x, None, length=8)
        return c, ys

    warm = plan.register(
        "never_called", step, example=lambda: (sds((64, 64), jnp.float32),)
    )
    plan.start()
    # the bug: return from main without ever calling `warm` and without
    # plan.close() — pre-fix this tears down the interpreter under the
    # worker thread mid-compile and aborts
    sys.exit(0)
    """
)


@pytest.mark.timeout(300)
def test_register_but_never_call_exits_cleanly():
    p = subprocess.run(
        [sys.executable, "-c", _NEVER_CALLED_SCRIPT],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "terminate called" not in p.stderr, p.stderr[-2000:]
    assert p.returncode == 0, (p.returncode, p.stderr[-2000:])


@pytest.mark.timeout(300)
def test_close_cancels_queued_compiles():
    """close() must drain the queue: entries no worker picked up get a
    cancellation error and a set done-event (so any racing barrier waiter
    falls through to the cold fn instead of hanging)."""
    plan = CompilePlan(enabled=True, threads=1)

    def gate_example():
        return (sds((8, 8), jnp.float32),)

    fns = [jax.jit(lambda x, i=i: x + i) for i in range(4)]
    wrapped = [
        plan.register(f"jit_{i}", fn, example=gate_example)
        for i, fn in enumerate(fns)
    ]
    plan.start()
    plan.close(join_timeout=120.0)
    for entry in plan._entries:
        assert entry.done.is_set()
    cancelled = [e for e in plan._entries if e.error and "cancelled" in e.error]
    compiled = [e for e in plan._entries if e.executable is not None]
    assert len(cancelled) + len(compiled) == len(plan._entries)
    # post-close calls still work (cold path for cancelled entries)
    x = jnp.ones((8, 8), jnp.float32)
    for i, w in enumerate(wrapped):
        assert jnp.allclose(w(x), x + i)


@pytest.mark.timeout(300)
def test_close_idempotent_and_unregisters_atexit():
    plan = CompilePlan(enabled=True)
    plan.register("j", jax.jit(lambda x: x * 2), example=lambda: (sds((4,), jnp.float32),))
    plan.start()
    plan.close()
    plan.close()  # second close is a no-op, not a double-join
    assert plan._closed
