"""donating_jit: donates by default on every backend (the CPU tier-1
configuration included) and honors the SHEEPRL_TPU_DONATE=0 kill switch."""

import jax.numpy as jnp

from sheeprl_tpu.utils.jit import donating_jit, donation_safe


def test_donates_by_default_and_kill_switch(monkeypatch):
    monkeypatch.delenv("SHEEPRL_TPU_DONATE", raising=False)
    assert donation_safe() is True
    f = donating_jit(lambda a: a + 1, donate_argnums=(0,))
    x = jnp.ones((3,))
    f(x)
    assert x.is_deleted()  # donation actually happened

    monkeypatch.setenv("SHEEPRL_TPU_DONATE", "0")
    assert donation_safe() is False
    g = donating_jit(lambda a: a + 1, donate_argnums=(0,))
    z = jnp.ones((3,))
    g(z)
    assert not z.is_deleted()


def test_decorator_form_matches_jax_jit():
    from functools import partial

    @partial(donating_jit, donate_argnums=(0,))
    def step(s, d):
        return s + d

    assert float(step(jnp.float32(1.0), jnp.float32(2.0))) == 3.0
