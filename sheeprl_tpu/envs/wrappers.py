"""Environment wrappers.

Capability parity with /root/reference/sheeprl/envs/wrappers.py, re-designed
for the TPU pipeline's channel-LAST convention: images are `[H, W, C]`
everywhere (the NHWC layout TPU convs consume natively), and `FrameStack`
concatenates along the channel axis -> `[H, W, C * num_stack]`, so stacked
pixels feed `Conv2d` with zero reshapes on device.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Sequence

import gymnasium as gym
import numpy as np

__all__ = [
    "MaskVelocityWrapper",
    "ActionRepeat",
    "RestartOnException",
    "FrameStack",
    "DictObservation",
]


class StepLatencyWrapper(gym.Wrapper):
    """Model a real-time environment: every `step()` pays a fixed wall-clock
    latency without consuming host CPU (`time.sleep` releases the GIL and
    the core). Robots, remote/throttled simulators and rate-limited web
    envs all look like this to the learner — the env-step window is IDLE
    host time that background work (warm-start compilation, prefetchers)
    can genuinely hide, even on a single-core host.

    Enabled repo-wide by `SHEEPRL_TPU_ENV_LATENCY_MS` (see utils/env.py)."""

    def __init__(self, env: gym.Env, latency_ms: float):
        super().__init__(env)
        self._latency_s = float(latency_ms) / 1000.0

    def step(self, action):
        import time

        time.sleep(self._latency_s)
        return self.env.step(action)


def maybe_step_latency(env: gym.Env) -> gym.Env:
    """Apply StepLatencyWrapper when SHEEPRL_TPU_ENV_LATENCY_MS is set (>0)."""
    import os

    ms = os.environ.get("SHEEPRL_TPU_ENV_LATENCY_MS")
    try:
        ms_f = float(ms) if ms else 0.0
    except ValueError:
        ms_f = 0.0
    return StepLatencyWrapper(env, ms_f) if ms_f > 0 else env


class MaskVelocityWrapper(gym.ObservationWrapper):
    """Zero out velocity entries to make classic-control tasks partially
    observable (/root/reference/sheeprl/envs/wrappers.py:11-43)."""

    velocity_indices: dict[str, np.ndarray] = {
        "CartPole-v0": np.array([1, 3]),
        "CartPole-v1": np.array([1, 3]),
        "MountainCar-v0": np.array([1]),
        "MountainCarContinuous-v0": np.array([1]),
        "Pendulum-v1": np.array([2]),
        "LunarLander-v2": np.array([2, 3, 5]),
        "LunarLanderContinuous-v2": np.array([2, 3, 5]),
    }

    def __init__(self, env: gym.Env):
        super().__init__(env)
        assert env.unwrapped.spec is not None
        env_id = env.unwrapped.spec.id
        self.mask = np.ones_like(env.observation_space.sample())
        try:
            self.mask[self.velocity_indices[env_id]] = 0.0
        except KeyError as e:
            raise NotImplementedError(f"velocity masking not implemented for {env_id}") from e

    def observation(self, observation: np.ndarray) -> np.ndarray:
        return observation * self.mask


class ActionRepeat(gym.Wrapper):
    """Repeat the action `amount` times, accumulating reward and stopping at
    episode end (/root/reference/sheeprl/envs/wrappers.py:46-70)."""

    def __init__(self, env: gym.Env, amount: int = 1):
        super().__init__(env)
        if amount <= 0:
            raise ValueError("`amount` must be a positive integer")
        self._amount = amount

    @property
    def action_repeat(self) -> int:
        return self._amount

    def step(self, action):
        total_reward, terminated, truncated = 0.0, False, False
        obs, info = None, {}
        for _ in range(self._amount):
            obs, reward, terminated, truncated, info = self.env.step(action)
            total_reward += float(reward)
            if terminated or truncated:
                break
        return obs, total_reward, terminated, truncated, info


class RestartOnException(gym.Wrapper):
    """Recreate a crashed env (flaky Minecraft-style backends), capped at
    `maxfails` per `window` seconds; flags `info["restart_on_exception"]` so
    the training loop can patch its buffer
    (/root/reference/sheeprl/envs/wrappers.py:73-122).

    ISSUE 12: shares the generic `resilience.envwrap` machinery's
    observability — restarts count into `Fault/env_restarts`, emit
    `fault.env_error`/`fault.recovered` telemetry events, and the
    deterministic `env.step@n` injection site fires inside the retry scope
    here too (the dreamer mains wrap this OUTSIDE the per-thunk
    `RestartingEnv`, so whichever wrapper sees the fault first recovers it).
    Semantics differ from `RestartingEnv` on purpose: this wrapper returns a
    NON-terminal transition plus the info flag, and the dreamer loops patch
    the replay ring themselves (dreamer_v3.py buffer surgery)."""

    def __init__(
        self,
        env_fn: Callable[[], gym.Env],
        exceptions: Sequence[type] = (Exception,),
        window: float = 300.0,
        maxfails: int = 2,
        wait: float = 20.0,
    ):
        if not isinstance(exceptions, (tuple, list)):
            exceptions = (exceptions,)
        self._env_fn = env_fn
        self._exceptions = tuple(exceptions)
        self._window = window
        self._maxfails = maxfails
        self._wait = wait
        self._last = time.time()
        self._fails = 0
        super().__init__(env_fn())

    def _record_failure(self, err: Exception, where: str) -> None:
        from ..resilience import inject

        now = time.time()
        if now > self._last + self._window:
            self._last = now
            self._fails = 1
        else:
            self._fails += 1
        inject.count("Fault/env_errors")
        from ..telemetry import emit

        emit(
            "fault.env_error",
            error=f"{type(err).__name__}: {err}"[:300],
            attempt=self._fails,
            limit=self._maxfails,
            where=where,
        )
        if self._fails > self._maxfails:
            raise RuntimeError(f"env crashed too many times: {self._fails}") from err
        gym.logger.warn(
            f"{where} - restarting env after crash with {type(err).__name__}: {err}"
        )
        time.sleep(self._wait)

    def step(self, action):
        from ..resilience import inject

        try:
            # inject only when no inner RestartingEnv already owns the site
            # (double-wrapped dreamer envs would advance the counter twice)
            if not getattr(self.env, "_sheeprl_resilient", False):
                spec = inject.get_plan().fire_next("env.step")
                if spec is not None:
                    raise inject.InjectedFault(
                        f"injected env.step fault: {spec.describe()}"
                    )
            return self.env.step(action)
        except self._exceptions as e:
            self._record_failure(e, "STEP")
            self.env = self._env_fn()
            obs, info = self.env.reset()
            inject.note_recovery("env.step", "env_restarts", attempt=self._fails)
            info["restart_on_exception"] = True
            return obs, 0.0, False, False, info

    def reset(self, *, seed=None, options=None):
        try:
            return self.env.reset(seed=seed, options=options)
        except self._exceptions as e:
            self._record_failure(e, "RESET")
            self.env = self._env_fn()
            obs, info = self.env.reset()
            info["restart_on_exception"] = True
            return obs, info


class FrameStack(gym.Wrapper):
    """Stack the last `num_stack` frames of each image key along the CHANNEL
    axis (`[H, W, C] -> [H, W, C * num_stack]`), optionally dilated.

    Same capability as the reference FrameStack
    (/root/reference/sheeprl/envs/wrappers.py:125-182) but channel-last and
    channel-concatenated: the output feeds NHWC convs directly instead of
    introducing a stack axis that must be folded on device.
    """

    def __init__(self, env: gym.Env, num_stack: int, cnn_keys: Sequence[str], dilation: int = 1):
        super().__init__(env)
        if num_stack <= 0:
            raise ValueError(f"num_stack must be > 0, got {num_stack}")
        if dilation <= 0:
            raise ValueError(f"dilation must be > 0, got {dilation}")
        if not isinstance(env.observation_space, gym.spaces.Dict):
            raise RuntimeError(
                f"expected a Dict observation space, got {type(env.observation_space)}"
            )
        self._num_stack = num_stack
        self._dilation = dilation
        self._cnn_keys = [
            k
            for k, v in env.observation_space.spaces.items()
            if k in cnn_keys and len(v.shape) == 3
        ]
        if not self._cnn_keys:
            raise RuntimeError("specify at least one valid cnn key to stack")
        spaces = dict(env.observation_space.spaces)
        for k in self._cnn_keys:
            sp = env.observation_space[k]
            h, w, c = sp.shape
            spaces[k] = gym.spaces.Box(
                np.concatenate([sp.low] * num_stack, axis=-1),
                np.concatenate([sp.high] * num_stack, axis=-1),
                (h, w, c * num_stack),
                sp.dtype,
            )
        self.observation_space = gym.spaces.Dict(spaces)
        self._frames = {k: deque(maxlen=num_stack * dilation) for k in self._cnn_keys}

    def _stacked(self, key: str) -> np.ndarray:
        frames = list(self._frames[key])[self._dilation - 1 :: self._dilation]
        assert len(frames) == self._num_stack
        return np.concatenate(frames, axis=-1)

    def step(self, action):
        obs, reward, terminated, truncated, info = self.env.step(action)
        for k in self._cnn_keys:
            self._frames[k].append(obs[k])
            obs[k] = self._stacked(k)
        return obs, reward, terminated, truncated, info

    def reset(self, *, seed=None, options=None):
        obs, info = self.env.reset(seed=seed, options=options)
        for k in self._cnn_keys:
            self._frames[k].clear()
            for _ in range(self._num_stack * self._dilation):
                self._frames[k].append(obs[k])
            obs[k] = self._stacked(k)
        return obs, info


class DictObservation(gym.ObservationWrapper):
    """Wrap a Box observation into a single-key dict observation (the
    reference does this inline with TransformObservation,
    /root/reference/sheeprl/utils/env.py:185-220)."""

    def __init__(self, env: gym.Env, key: str):
        super().__init__(env)
        self._key = key
        self.observation_space = gym.spaces.Dict({key: env.observation_space})

    def observation(self, observation):
        return {self._key: observation}
