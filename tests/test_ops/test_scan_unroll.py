"""SHEEPRL_TPU_SCAN_UNROLL changes scheduling, not numerics: the unrolled
RSSM dynamic + imagination scans must produce the SAME losses and updated
parameters as the plain while-loop on the same batch and seeds (the
autotuned choice relies on the configs being interchangeable,
ops/scan.py)."""

import jax
import numpy as np
import pytest

from sheeprl_tpu.ops.scan import scan_unroll
from tests.test_algos.test_precision import _run_one_step


def test_scan_unroll_env_parsing(monkeypatch):
    monkeypatch.delenv("SHEEPRL_TPU_SCAN_UNROLL", raising=False)
    assert scan_unroll() == 1
    monkeypatch.setenv("SHEEPRL_TPU_SCAN_UNROLL", "4")
    assert scan_unroll() == 4
    monkeypatch.setenv("SHEEPRL_TPU_SCAN_UNROLL", "0")
    assert scan_unroll() == 1  # floor
    monkeypatch.setenv("SHEEPRL_TPU_SCAN_UNROLL", "junk")
    assert scan_unroll() == 1  # unparseable -> plain loop


@pytest.mark.slow  # two full DV3 train-step compiles; runs per round
@pytest.mark.timeout(300)
def test_unrolled_step_matches_plain(monkeypatch):
    # unroll=2 against T=5, horizon=4: exercises both the non-divisible
    # remainder path (5 % 2) and the divisible one (4 % 2)
    monkeypatch.delenv("SHEEPRL_TPU_SCAN_UNROLL", raising=False)
    state_plain, m_plain = _run_one_step("float32")
    monkeypatch.setenv("SHEEPRL_TPU_SCAN_UNROLL", "2")
    state_unrolled, m_unrolled = _run_one_step("float32")

    for name in m_plain:
        np.testing.assert_allclose(
            m_unrolled[name], m_plain[name], rtol=1e-4, atol=1e-5, err_msg=name
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(state_plain.world_model),
        jax.tree_util.tree_leaves(state_unrolled.world_model),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# =============================================================================
# Measured unroll ladder (ISSUE 9 tentpole c)
# =============================================================================


def test_unroll_override_and_mode(monkeypatch):
    from sheeprl_tpu.ops import scan as scan_mod

    monkeypatch.delenv("SHEEPRL_TPU_SCAN_UNROLL", raising=False)
    assert scan_mod.unroll_mode() == "off"
    monkeypatch.setenv("SHEEPRL_TPU_SCAN_UNROLL", "auto")
    assert scan_mod.unroll_mode() == "auto"
    # "auto" is not an integer: the static read stays at 1 until a winner
    # is installed
    assert scan_unroll() == 1
    scan_mod.set_unroll(8)
    try:
        assert scan_unroll() == 8
        with scan_mod.unroll(2):
            assert scan_unroll() == 2
        assert scan_unroll() == 8
    finally:
        scan_mod.set_unroll(None)
    assert scan_unroll() == 1


def test_autotune_ladder_bit_exact_and_persisted(tmp_path, monkeypatch):
    """The measured ladder: every rung's outputs are bit-identical to rung
    1 (the per-rung receipt), the winner is one of the rungs, the decision
    persists next to the compile cache, and a same-key re-run is a cache
    hit that skips measurement."""
    import jax.numpy as jnp

    from sheeprl_tpu.ops import scan as scan_mod

    def fn(xs, c0):
        def step(c, x):
            c = jnp.tanh(c * 1.01 + x)
            return c, c

        _, ys = jax.lax.scan(step, c0, xs, unroll=scan_unroll())
        return ys

    xs = jnp.linspace(-1.0, 1.0, 12 * 3).reshape(12, 3)
    c0 = jnp.zeros((3,))
    store = str(tmp_path / "scan_unroll.json")
    try:
        decision = scan_mod.autotune_unroll(
            "test.scan", fn, (xs, c0), rungs=(1, 4, 8), repeats=2,
            store_path=store, apply=True,
        )
        assert decision.source == "measured"
        assert set(decision.bit_exact) == {1, 4, 8}
        assert all(decision.bit_exact.values())
        assert decision.winner in (1, 4, 8)
        assert scan_unroll() == decision.winner  # installed
        import json as _json

        with open(store) as fh:
            stored = _json.load(fh)
        assert decision.key in stored

        again = scan_mod.autotune_unroll(
            "test.scan", fn, (xs, c0), rungs=(1, 4, 8), repeats=2,
            store_path=store, apply=False,
        )
        assert again.source == "cache"
        assert again.winner == decision.winner
    finally:
        scan_mod.set_unroll(None)


def test_autotune_disqualifies_non_bit_exact_rung(tmp_path):
    """A rung whose outputs differ from rung 1 must never win — receipts
    gate the ladder, not just annotate it. (Forced via a function that
    READS the unroll knob into its numerics — a misuse the receipt is
    exactly there to catch.)"""
    import jax.numpy as jnp

    from sheeprl_tpu.ops import scan as scan_mod

    def fn(xs):
        # numerics depend on the knob: every rung != 1 is disqualified
        return xs * float(scan_unroll())

    xs = jnp.arange(8.0)
    try:
        decision = scan_mod.autotune_unroll(
            "test.tainted", fn, (xs,), rungs=(1, 4), repeats=1,
            store_path=str(tmp_path / "s.json"), apply=False,
        )
        assert decision.bit_exact[4] is False
        assert decision.winner == 1
    finally:
        scan_mod.set_unroll(None)
