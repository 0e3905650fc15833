"""CompilePlan receipts (ISSUE 5 tentpole): AOT-vs-direct bit-exactness,
warm-start barrier ordering, cache hit/miss counting, and the fallback
safety net."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.compile import CompilePlan, avals_of, sds


class _Args:
    warm_compile = "on"


class _Off:
    warm_compile = "off"


def _sac_step():
    """A real (small) registered train step: SAC's scan-over-gradient-steps
    update — representative math (grads, optimizers, EMA gate)."""
    from sheeprl_tpu.algos.sac.agent import SACAgent
    from sheeprl_tpu.algos.sac.args import SACArgs
    from sheeprl_tpu.algos.sac.sac import TrainState, make_optimizers, make_train_step

    args = SACArgs(actor_hidden_size=16, critic_hidden_size=16)
    key = jax.random.PRNGKey(0)
    agent = SACAgent.init(
        key, 3, 1, num_critics=args.num_critics,
        actor_hidden_size=16, critic_hidden_size=16,
        action_low=np.array([-1.0]), action_high=np.array([1.0]),
        alpha=args.alpha, tau=args.tau,
    )
    qf_optim, actor_optim, alpha_optim = make_optimizers(args)
    state = TrainState(
        agent=agent,
        qf_opt=qf_optim.init(agent.critics),
        actor_opt=actor_optim.init(agent.actor),
        alpha_opt=alpha_optim.init(agent.log_alpha),
    )
    train_step = make_train_step(args, qf_optim, actor_optim, alpha_optim)
    g, b = 2, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    data = {
        "observations": jax.random.normal(ks[0], (g, b, 3), jnp.float32),
        "next_observations": jax.random.normal(ks[1], (g, b, 3), jnp.float32),
        "actions": jax.random.uniform(ks[2], (g, b, 1), jnp.float32, -1, 1),
        "rewards": jax.random.normal(ks[3], (g, b, 1), jnp.float32),
        "dones": jnp.zeros((g, b, 1), jnp.float32),
    }
    return train_step, state, data, jax.random.PRNGKey(2)


@pytest.mark.timeout(300)
def test_aot_vs_direct_bit_exact():
    """The equivalence guarantee: the AOT executable built from captured
    avals produces bitwise-identical outputs to the cold jit path."""
    train_step, state, data, key = _sac_step()
    flag = jnp.asarray(True)
    # cold/direct path first (its own jit cache entry); the step donates its
    # state, so it gets a copy and `state` stays alive for the AOT call
    s_direct, m_direct = train_step(
        jax.tree_util.tree_map(jnp.copy, state), data, key, flag
    )

    plan = CompilePlan.from_args(_Args())
    wrapped = plan.register(
        "train_step", train_step,
        example=lambda: (state, data, key, flag), role="update",
    )
    plan.start()
    assert plan.wait(timeout=240), "warm compile did not finish"
    s_aot, m_aot = wrapped(state, data, key, flag)

    st = plan.stats()["entries"]["train_step"]
    assert st["compiled"] and st["error"] is None
    assert st["aot_calls"] == 1 and st["fallbacks"] == 0
    for a, b in zip(
        jax.tree_util.tree_leaves(s_direct), jax.tree_util.tree_leaves(s_aot)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in m_direct:
        np.testing.assert_array_equal(
            np.asarray(m_direct[k]), np.asarray(m_aot[k])
        )
    assert plan.time_to_first_update_seconds is not None
    plan.close()


@pytest.mark.timeout(120)
def test_barrier_blocks_update_until_compile_done():
    """Ordering: a call into a registered jit must not execute before its
    background compile completes — the wrapper IS the barrier."""
    order = []

    def slow_fn(x):
        # runs at TRACE time, i.e. inside the background compile worker
        time.sleep(0.8)
        order.append("compiled")
        return x + 1

    fn = jax.jit(slow_fn)
    plan = CompilePlan(enabled=True)
    wrapped = plan.register("slow", fn, example=lambda: (sds((2,), jnp.float32),))
    plan.start()
    t0 = time.perf_counter()
    out = wrapped(jnp.zeros(2, jnp.float32))
    waited = time.perf_counter() - t0
    order.append("executed")
    np.testing.assert_array_equal(np.asarray(out), np.ones(2, np.float32))
    assert order == ["compiled", "executed"]
    e = plan._entries[0]
    assert e.done.is_set() and e.barrier_wait_s > 0.0
    assert waited >= 0.3  # genuinely blocked on the in-flight compile
    plan.close()


@pytest.mark.timeout(120)
def test_aval_mismatch_falls_back_to_cold_path():
    """A registered spec that drifts from the live call must never change
    results — the wrapper falls back to the original jit for good."""
    fn = jax.jit(lambda x: x * 2)
    plan = CompilePlan(enabled=True)
    wrapped = plan.register(
        "wrong", fn, example=lambda: (sds((3,), jnp.float32),)
    )
    plan.start()
    assert plan.wait(timeout=60)
    # live call uses a DIFFERENT shape than the captured spec
    out = wrapped(jnp.ones(5, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones(5, np.float32))
    e = plan._entries[0]
    assert e.fallbacks == 1 and e.executable is None
    # subsequent calls stay on the cold path without re-raising
    wrapped(jnp.ones(5, jnp.float32))
    assert e.fallbacks == 1
    plan.close()


@pytest.mark.timeout(120)
def test_disabled_plan_is_passthrough():
    fn = jax.jit(lambda x: x + 1)
    plan = CompilePlan.from_args(_Off())
    assert plan.register("f", fn, example=lambda: (sds((2,), jnp.float32),)) is fn
    wrapped = plan.register("g", fn, example=None, role="update")
    out = wrapped(jnp.zeros(2, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.ones(2, np.float32))
    # the role wrapper still stamps time_to_first_update on the cold path
    assert plan.time_to_first_update_seconds is not None
    plan.close()


@pytest.mark.timeout(120)
def test_unlowerable_fn_degrades_gracefully():
    """A fn without .lower (e.g. a checkify wrapper or python loop) is
    tracked for timing only; start() must not hang on it."""

    def plain(x):
        return x - 1

    plan = CompilePlan(enabled=True)
    wrapped = plan.register("plain", plain, example=lambda: (jnp.zeros(2),))
    plan.start()
    assert plan.wait(timeout=10)
    out = wrapped(jnp.ones(2, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(2, np.float32))
    assert plan.stats()["entries"]["plain"]["error"] == "not AOT-lowerable"
    plan.close()


def test_avals_of_commitment_rules():
    """Committed arrays keep sharding; uncommitted arrays and non-arrays
    pass through sharding-free (the decoupled-mesh lowering fix)."""
    dev = jax.devices()[0]
    committed = jax.device_put(jnp.zeros((2, 2)), dev)
    uncommitted = jnp.zeros((3,))
    spec, passthrough = avals_of((committed, 0.5))[0], avals_of((committed, 0.5))[1]
    assert spec.sharding is not None
    assert passthrough == 0.5
    u = avals_of((uncommitted,))[0]
    assert u.sharding is None and u.shape == (3,)


@pytest.mark.timeout(120)
def test_gauges_shape():
    fn = jax.jit(lambda x: x + 1)
    plan = CompilePlan(enabled=True)
    wrapped = plan.register("f", fn, example=lambda: (sds((2,), jnp.float32),))
    plan.start()
    assert plan.wait(timeout=60)
    wrapped(jnp.zeros(2, jnp.float32))
    g = plan.gauges()
    assert g["Compile/warm_enabled"] == 1.0
    assert g["Compile/plan_compiled"] == 1.0
    assert g["Compile/aot_calls"] == 1.0
    assert "Compile/exe/f_seconds" in g
    plan.close()


@pytest.mark.timeout(120)
def test_warmup_mode_populates_dispatch_cache(monkeypatch):
    """SHEEPRL_TPU_WARM_MODE=warmup: the worker calls the jit once on
    synthesized dummies; the executable lands in the jit's own dispatch
    cache and results stay bit-exact (it IS the cold-path executable)."""
    monkeypatch.setenv("SHEEPRL_TPU_WARM_MODE", "warmup")
    calls = []

    def f(x):
        calls.append(x.shape)  # trace-time: once for warmup, never again
        return x * 3

    fn = jax.jit(f)
    plan = CompilePlan(enabled=True)
    wrapped = plan.register("f", fn, example=lambda: (sds((4,), jnp.float32),))
    plan.start()
    assert plan.wait(timeout=60)
    st = plan.stats()["entries"]["f"]
    assert st["warmed"] and st["compiled"] and st["error"] is None
    out = wrapped(jnp.ones(4, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 3 * np.ones(4, np.float32))
    # the real call hit the dispatch cache: no second trace
    assert calls == [(4,)]
    plan.close()


@pytest.mark.timeout(60)
def test_wait_timeout_returns_false():
    plan = CompilePlan(enabled=True)
    e_fn = jax.jit(lambda x: x)
    plan.register("never", e_fn, example=lambda: (sds((2,), jnp.float32),))
    # start() NOT called: entries pending forever
    t = threading.Thread(target=lambda: None, name="test-noop", daemon=True)
    t.start(); t.join()
    assert plan.wait(timeout=0.1) is False
    plan.close()
