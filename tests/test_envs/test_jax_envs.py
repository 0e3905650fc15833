"""Receipts for the Anakin path (ISSUE 6): pure-JAX env dynamics parity vs
Gymnasium, vmap/auto-reset invariants, rollout->`add_direct` ring contents
bit-exact vs a step-by-step reference, transfer-guard purity of the jitted
collector, and mesh-sharded collection equivalence."""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data import AsyncReplayBuffer
from sheeprl_tpu.envs.jax import (
    DreamerCollectorCarry,
    JaxCartPole,
    JaxEnvGymWrapper,
    JaxPendulum,
    JaxPixelToy,
    PPOCollectorCarry,
    VecJaxEnv,
    make_dreamer_collector,
    make_jax_env,
    make_ppo_collector,
)
from sheeprl_tpu.envs.jax.cartpole import CartPoleState
from sheeprl_tpu.envs.jax.pendulum import PendulumState
from sheeprl_tpu.parallel import make_mesh, shard_env_batch


def _tiny_agent(env, seed=1):
    from sheeprl_tpu.algos.ppo.agent import PPOAgent

    space = env.observation_space
    cnn_keys = [k for k, s in space.spaces.items() if len(s.shape) == 3]
    mlp_keys = [k for k, s in space.spaces.items() if len(s.shape) == 1]
    act = env.action_space
    if isinstance(act, gym.spaces.Discrete):
        actions_dim, cont = [int(act.n)], False
    else:
        actions_dim, cont = [int(np.prod(act.shape))], True
    agent = PPOAgent.init(
        jax.random.PRNGKey(seed), actions_dim, space.spaces, cnn_keys, mlp_keys,
        dense_units=8, mlp_layers=1, mlp_features_dim=8, cnn_features_dim=16,
        is_continuous=cont,
    )
    return agent, actions_dim, cont


# ---------------------------------------------------------------------------
# dynamics parity vs Gymnasium (teacher-forced: both backends step from the
# SAME state each step over a seeded 200-step action trajectory, so a single
# step's numerics are compared without chaotic drift compounding)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_cartpole_parity_vs_gymnasium():
    genv = gym.make("CartPole-v1")
    genv.reset(seed=3)
    jenv = JaxCartPole()
    jstep = jax.jit(jenv.step)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    for t in range(200):
        host_state = np.asarray(genv.unwrapped.state, np.float64)
        action = int(rng.integers(0, 2))
        s = CartPoleState(
            state=jnp.asarray(host_state, jnp.float32), t=jnp.zeros((), jnp.int32)
        )
        _, jobs, jr, jterm, _ = jstep(s, jnp.int32(action), key)
        gobs, gr, gterm, _, _ = genv.step(action)
        np.testing.assert_allclose(
            np.asarray(jobs["state"]), gobs, atol=1e-5, err_msg=f"step {t}"
        )
        assert float(jr) == gr
        assert bool(jterm) == gterm, f"step {t}"
        if gterm:
            genv.reset()
    genv.close()


@pytest.mark.timeout(120)
def test_pendulum_parity_vs_gymnasium():
    genv = gym.make("Pendulum-v1")
    genv.reset(seed=5)
    jenv = JaxPendulum()
    jstep = jax.jit(jenv.step)
    rng = np.random.default_rng(1)
    key = jax.random.PRNGKey(0)
    for t in range(200):
        host_state = np.asarray(genv.unwrapped.state, np.float64)
        action = rng.uniform(-2.0, 2.0, size=(1,)).astype(np.float32)
        s = PendulumState(
            state=jnp.asarray(host_state, jnp.float32), t=jnp.zeros((), jnp.int32)
        )
        _, jobs, jr, _, _ = jstep(s, jnp.asarray(action), key)
        gobs, gr, gterm, _, _ = genv.step(action)
        assert not gterm  # pendulum never terminates
        np.testing.assert_allclose(
            np.asarray(jobs["state"]), gobs, atol=1e-4, err_msg=f"step {t}"
        )
        np.testing.assert_allclose(float(jr), gr, atol=1e-4)
    genv.close()


# ---------------------------------------------------------------------------
# vmap / auto-reset shape invariants
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
@pytest.mark.parametrize(
    "env_id,obs_key,shape,dtype",
    [
        ("CartPole-v1", "state", (4,), jnp.float32),
        ("Pendulum-v1", "state", (3,), jnp.float32),
        ("pixeltoy", "rgb", (64, 64, 3), jnp.uint8),
    ],
)
def test_vmap_shapes_and_dtypes(env_id, obs_key, shape, dtype):
    n = 5
    venv = VecJaxEnv(env=make_jax_env(env_id), num_envs=n)
    state, obs = jax.jit(venv.reset)(jax.random.PRNGKey(0))
    assert obs[obs_key].shape == (n,) + shape
    assert obs[obs_key].dtype == dtype
    space = venv.single_action_space
    if isinstance(space, gym.spaces.Discrete):
        actions = jnp.zeros((n,), jnp.int32)
    else:
        actions = jnp.zeros((n,) + space.shape, jnp.float32)
    state2, obs2, reward, done, info = jax.jit(venv.step)(
        state, actions, jax.random.PRNGKey(1)
    )
    assert obs2[obs_key].shape == (n,) + shape and obs2[obs_key].dtype == dtype
    assert reward.shape == (n,) and reward.dtype == jnp.float32
    assert done.shape == (n,) and done.dtype == jnp.bool_
    assert info["final_obs"][obs_key].shape == (n,) + shape
    assert state2.ep_length.shape == (n,)
    # observation values match the space the host agent was built for
    assert venv.single_observation_space[obs_key].shape == shape


@pytest.mark.timeout(120)
def test_autoreset_resets_state_and_stats():
    """Drive CartPole to termination with a constant action: the done env's
    state/step-counter/episode stats reset in the same step, and the final
    pre-reset observation is surfaced in info (same-step auto-reset, matching
    envs/vector.py)."""
    n = 4
    venv = VecJaxEnv(env=JaxCartPole(), num_envs=n)
    step = jax.jit(venv.step)
    state, obs = venv.reset(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    saw_done = False
    for t in range(60):
        key, k = jax.random.split(key)
        state, obs, reward, done, info = step(
            state, jnp.ones((n,), jnp.int32), k
        )
        done_np = np.asarray(done)
        if done_np.any():
            saw_done = True
            i = int(np.argmax(done_np))
            # episode stats were zeroed for the finished env...
            assert float(state.ep_return[i]) == 0.0
            assert int(state.ep_length[i]) == 0
            # ...its step counter restarted...
            assert int(state.env_state.t[i]) == 0
            # ...the completed-episode stats are in info...
            assert float(info["ep_return"][i]) == t + 1  # +1 reward per step
            assert int(info["ep_length"][i]) == t + 1
            # ...and the returned obs is the RESET obs (within the reset
            # distribution), while final_obs is the out-of-bounds terminal one
            assert np.all(np.abs(np.asarray(obs["state"])[i]) <= 0.05)
            final = np.asarray(info["final_obs"]["state"])[i]
            assert np.abs(final[2]) > 12 * 2 * np.pi / 360 or np.abs(final[0]) > 2.4
            break
    assert saw_done, "constant-action cartpole never terminated in 60 steps"


@pytest.mark.timeout(120)
def test_truncation_at_max_episode_steps():
    venv = VecJaxEnv(env=JaxPendulum(max_episode_steps=7), num_envs=2)
    step = jax.jit(venv.step)
    state, _ = venv.reset(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    for t in range(1, 8):
        key, k = jax.random.split(key)
        state, _, _, done, info = step(
            state, jnp.zeros((2, 1), jnp.float32), k
        )
        if t < 7:
            assert not np.asarray(done).any()
    assert np.asarray(done).all()
    assert np.asarray(info["truncated"]).all()
    assert not np.asarray(info["terminated"]).any()
    assert np.asarray(state.env_state.t == 0).all()  # auto-reset


@pytest.mark.timeout(120)
def test_pixeltoy_reaches_goal_with_scripted_actions():
    env = JaxPixelToy(size=16, grid=4, max_episode_steps=50)
    key = jax.random.PRNGKey(2)
    state, obs = env.reset(key)
    assert obs["rgb"].dtype == jnp.uint8 and obs["rgb"].shape == (16, 16, 3)
    step = jax.jit(env.step)
    # walk the manhattan path: rows first (actions 1=up/2=down), then cols
    for _ in range(12):
        dr = int(state.goal[0] - state.agent[0])
        dc = int(state.goal[1] - state.agent[1])
        if dr != 0:
            a = 2 if dr > 0 else 1
        elif dc != 0:
            a = 4 if dc > 0 else 3
        else:
            break
        state, obs, reward, term, trunc = step(state, jnp.int32(a), key)
        if bool(term):
            assert float(reward) == 1.0
            return
    pytest.fail("scripted manhattan walk never reached the goal")


# ---------------------------------------------------------------------------
# rollout -> add_direct ring contents, bit-exact vs a step-by-step reference
# ---------------------------------------------------------------------------


def _ring_arrays(rb):
    return {k: np.asarray(v) for k, v in rb._store.items()}


@pytest.mark.timeout(300)
def test_dreamer_rollout_ring_bit_exact_vs_step_by_step():
    """One T-length jitted scan writing via reserve()/add_direct() produces
    the SAME device ring as T single-step collects: same scatter layout,
    same PRNG stream (the scan body's split discipline is replayed by
    chaining `split(key, 3)[0]`), bitwise-identical contents."""
    T, n = 6, 3
    venv = VecJaxEnv(env=JaxCartPole(), num_envs=n)
    obs_keys = ("state",)
    kwargs = dict(
        actions_dim=(2,), is_continuous=False,
        dev_preprocess=lambda o: o, random_actions=True,
    )
    collect_T = jax.jit(make_dreamer_collector(venv, T, **kwargs))
    collect_1 = jax.jit(make_dreamer_collector(venv, 1, **kwargs))

    def fresh(seed):
        state, obs = jax.jit(venv.reset)(jax.random.PRNGKey(seed))
        carry = DreamerCollectorCarry(
            vec=state, obs=obs,
            prev_reward=jnp.zeros((n, 1), jnp.float32),
            prev_done=jnp.zeros((n, 1), jnp.float32),
            is_first=jnp.ones((n, 1), jnp.float32),
        )
        rb = AsyncReplayBuffer(
            16, n, storage="device", sequential=True, obs_keys=obs_keys, seed=7
        )
        return carry, rb

    key = jax.random.PRNGKey(11)
    expl = jnp.float32(0.0)

    carry, rb_scan = fresh(0)
    idx = rb_scan.reserve(T)
    _, carry, traj, ep = collect_T(None, None, carry, key, expl)
    rb_scan.add_direct(traj, jnp.asarray(idx), data_len=T)

    carry, rb_ref = fresh(0)
    k = key
    for _ in range(T):
        idx = rb_ref.reserve(1)
        _, carry, row, _ = collect_1(None, None, carry, k, expl)
        rb_ref.add_direct(row, jnp.asarray(idx), data_len=1)
        k = jax.random.split(k, 3)[0]  # the scan body's carried key

    scan_store, ref_store = _ring_arrays(rb_scan), _ring_arrays(rb_ref)
    assert set(scan_store) == set(ref_store)
    for k_ in scan_store:
        np.testing.assert_array_equal(scan_store[k_], ref_store[k_], err_msg=k_)
    np.testing.assert_array_equal(rb_scan._upos, rb_ref._upos)
    np.testing.assert_array_equal(rb_scan._ufull, rb_ref._ufull)
    # row semantics: every row's is_first/dones/rewards are host-shifted
    assert scan_store["is_first"].shape == (16, n, 1)
    assert float(np.asarray(ep["episodes"])) >= 0


@pytest.mark.timeout(300)
def test_ppo_collector_bit_exact_vs_step_by_step():
    venv = VecJaxEnv(env=JaxCartPole(), num_envs=4)
    agent, actions_dim, cont = _tiny_agent(venv.env)
    T = 5
    collect_T = jax.jit(make_ppo_collector(venv, T, actions_dim, cont))
    collect_1 = jax.jit(make_ppo_collector(venv, 1, actions_dim, cont))

    def fresh():
        state, obs = jax.jit(venv.reset)(jax.random.PRNGKey(3))
        return PPOCollectorCarry(
            vec=state, obs=obs, prev_done=jnp.zeros((4, 1), jnp.float32)
        )

    key = jax.random.PRNGKey(9)
    carry_a, traj, ep = collect_T(agent, fresh(), key)

    carry_b = fresh()
    k = key
    rows = []
    for _ in range(T):
        carry_b, row, _ = collect_1(agent, carry_b, k)
        rows.append(row)
        k = jax.random.split(k, 3)[0]
    ref = {
        k_: np.stack([np.asarray(r[k_])[0] for r in rows]) for k_ in rows[0]
    }
    for k_ in ref:
        np.testing.assert_array_equal(np.asarray(traj[k_]), ref[k_], err_msg=k_)
    np.testing.assert_array_equal(
        np.asarray(carry_a.prev_done), np.asarray(carry_b.prev_done)
    )
    np.testing.assert_array_equal(
        np.asarray(carry_a.obs["state"]), np.asarray(carry_b.obs["state"])
    )


# ---------------------------------------------------------------------------
# purity: zero host syncs / transfers inside the compiled collector
# ---------------------------------------------------------------------------


@pytest.mark.timeout(300)
def test_rollout_transfer_guard_purity():
    """The runtime half of the zero-host-transfers guarantee: a compiled
    collector dispatches and retires under `transfer_guard("disallow")` —
    any implicit h2d/d2h inside the scan would raise."""
    venv = VecJaxEnv(env=JaxCartPole(), num_envs=8)
    agent, actions_dim, cont = _tiny_agent(venv.env)
    collect = jax.jit(make_ppo_collector(venv, 16, actions_dim, cont))
    state, obs = jax.jit(venv.reset)(jax.random.PRNGKey(0))
    carry = PPOCollectorCarry(
        vec=state, obs=obs, prev_done=jnp.zeros((8, 1), jnp.float32)
    )
    # compile (and land closure constants + keys on device) outside the guard
    key2 = jax.block_until_ready(jax.random.PRNGKey(2))
    carry, traj, ep = collect(agent, carry, jax.random.PRNGKey(1))
    jax.block_until_ready(traj["dones"])
    with jax.transfer_guard("disallow"):
        carry, traj, ep = collect(agent, carry, key2)
        jax.block_until_ready((traj, ep))


# ---------------------------------------------------------------------------
# mesh sharding: env batch sharded over the virtual 8-device mesh
# ---------------------------------------------------------------------------


@pytest.mark.timeout(300)
def test_sharded_rollout_matches_unsharded():
    mesh = make_mesh()  # all 8 virtual CPU devices
    n_dev = mesh.devices.size
    assert n_dev == 8
    n = 2 * n_dev
    venv = VecJaxEnv(env=JaxCartPole(), num_envs=n)
    agent, actions_dim, cont = _tiny_agent(venv.env)
    collect = jax.jit(make_ppo_collector(venv, 8, actions_dim, cont))
    state, obs = jax.jit(venv.reset)(jax.random.PRNGKey(0))
    carry = PPOCollectorCarry(
        vec=state, obs=obs, prev_done=jnp.zeros((n, 1), jnp.float32)
    )
    key = jax.random.PRNGKey(4)
    _, traj_plain, ep_plain = collect(agent, carry, key)
    sharded = shard_env_batch(carry, mesh)
    # every [N, ...] leaf landed sharded over the data axis
    assert len(sharded.obs["state"].sharding.device_set) == n_dev
    _, traj_shard, ep_shard = collect(agent, sharded, key)
    # what the policy network computes in floating point is held to a
    # tolerance: XLA:CPU orders a partitioned reduction differently from the
    # unpartitioned one (`logprobs`: 12 of 128 off by 1.19e-07, one unit in
    # the last place). Observations, actions, rewards and dones stay exact.
    policy_floats = {"logprobs", "values"}
    for k in traj_plain:
        plain, shard = np.asarray(traj_plain[k]), np.asarray(traj_shard[k])
        if k in policy_floats:
            np.testing.assert_allclose(plain, shard, rtol=1e-6, err_msg=k)
        else:
            np.testing.assert_array_equal(plain, shard, err_msg=k)
    np.testing.assert_allclose(
        float(ep_plain["return_sum"]), float(ep_shard["return_sum"]), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# host twin (gym_compat)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_gym_wrapper_pixeltoy():
    env = JaxEnvGymWrapper(make_jax_env("pixeltoy"), seed=0)
    obs, _ = env.reset(seed=0)
    assert obs["rgb"].shape == (64, 64, 3) and obs["rgb"].dtype == np.uint8
    obs, reward, term, trunc, _ = env.step(1)
    assert isinstance(reward, float) and isinstance(term, bool)
    assert obs["rgb"].shape == (64, 64, 3)
    frame = env.render()
    assert frame is not None and frame.shape == (64, 64, 3)
