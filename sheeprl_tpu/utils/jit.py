"""Donation-aware jit: `donating_jit` is `jax.jit` whose `donate_argnums`
can be switched off process-wide with SHEEPRL_TPU_DONATE=0 (the debugging
escape hatch for a suspected aliasing bug — HBM reuse is the whole point on
TPU, so donation is on everywhere by default: tests and chip run the same
program).
"""

from __future__ import annotations

import os
from typing import Any, Callable

__all__ = ["donating_jit", "donation_safe"]


def donation_safe() -> bool:
    return os.environ.get("SHEEPRL_TPU_DONATE") != "0"


def donating_jit(fun: Callable | None = None, *, donate_argnums: Any = (), **kw):
    """Drop-in for `jax.jit(fun, donate_argnums=...)`; usable as a decorator
    via functools.partial like jax.jit itself."""
    import jax

    if fun is None:
        from functools import partial

        return partial(donating_jit, donate_argnums=donate_argnums, **kw)
    if donation_safe():
        kw["donate_argnums"] = donate_argnums
    return jax.jit(fun, **kw)
