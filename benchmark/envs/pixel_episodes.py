"""The benchmark's traffic: pixel episodes, every byte drawn from the seed.

One general generator, parameterised by a traffic file's `env` group:

    frame_pool      frames in the pool (64x64x3 uint8, seeded noise)
    actions         size of the Discrete action set
    episode_steps   [shortest, longest] episode, in agent steps
    reward_prob     chance per step of a reward of +1 or -1

Each observation is a pool frame taken by index (microseconds of host work,
the same every step) with a six-byte stamp in its first two pixels: which
environment emitted it, and its serial number there. So no two rows of the
replay ring are equal, and a sampled row says where it came from.

The seed decides the frames, the rewards, and which environment runs which
episode length at which phase and brightness. It does not decide how much
work a run is: the set of (length, phase, brightness) triples is the same for
every seed — lengths evenly spread over `episode_steps`, one per environment,
each environment's first episode cut by its phase so resets never bunch — and
only their order over the environments changes. The brightness (0.25 to 1,
geometric) makes the environments' rows differ in kind, not only in noise: a
batch with some environments' rows left out has another loss and gradient.
"""

from __future__ import annotations

import time

import gymnasium as gym
import numpy as np

STAMP_BYTES = 6


class EpisodeLog:
    """What one environment emitted, in order, and the action that answered
    each observation: the benchmark's own record, which the rows sampled
    from the program's replay ring are checked against."""

    def __init__(self) -> None:
        self.frame: list[int] = []
        self.reward: list[float] = []
        self.done: list[bool] = []
        self.is_first: list[bool] = []
        self.action: list[int] = []  # -1: none came (a terminal observation)

    def emit(self, frame: int, reward: float, done: bool, is_first: bool) -> int:
        self.frame.append(frame)
        self.reward.append(reward)
        self.done.append(done)
        self.is_first.append(is_first)
        self.action.append(-1)
        return len(self.frame) - 1


class Traffic:
    """All environments of one run, their frame pool, logs and clock hook."""

    def __init__(self, params: dict, num_envs: int, seed: int, on_step=None, annotate=None):
        self.params, self.num_envs, self.seed = params, num_envs, seed
        self.on_step = on_step  # called at the top of environment 0's step()
        self.annotate = annotate  # context-manager factory around every step()
        rng = np.random.default_rng([seed, 0])
        self.pool = rng.integers(0, 256, (params["frame_pool"], 64, 64, 3), dtype=np.uint8)
        lo, hi = params["episode_steps"]
        lengths = np.linspace(lo, hi, num_envs).round().astype(int)
        phases = (np.arange(num_envs) + 0.5) / num_envs
        brightness = np.geomspace(0.25, 1.0, num_envs)
        self.plan = [(int(lengths[j]), float(phases[j]), float(brightness[j])) for j in rng.permutation(num_envs)]
        self.envs: list[PixelEpisodes] = []
        self.host_seconds = 0.0  # wall time inside step() and reset()
        self.resets = 0

    def make_env(self, render_mode=None, **_):
        if len(self.envs) >= self.num_envs:
            raise RuntimeError(f"the traffic has {self.num_envs} environments; one more was asked for")
        env = PixelEpisodes(self, len(self.envs))
        self.envs.append(env)
        return env

    def stamp(self, frame: np.ndarray, env: int, serial: int) -> None:
        frame.reshape(-1)[:STAMP_BYTES] = (
            env, (serial >> 16) & 255, (serial >> 8) & 255, serial & 255, env ^ 255, serial % 251,
        )

    def expected_rows(self, rgb: np.ndarray) -> tuple[dict[str, np.ndarray], np.ndarray]:
        """The rows that `rgb` [..., 64, 64, 3] claims to be, rebuilt from the
        logs by their stamps -> (rows, valid [...]): `valid` is false where a
        stamp names no observation that was emitted."""
        lead = rgb.shape[:-3]
        s = rgb.reshape(*lead, -1)[..., :STAMP_BYTES].astype(np.int64)
        env = s[..., 0]
        serial = (s[..., 1] << 16) | (s[..., 2] << 8) | s[..., 3]
        valid = (env < self.num_envs) & (s[..., 4] == (env ^ 255)) & (s[..., 5] == serial % 251)
        rows = {
            "rgb": np.zeros(rgb.shape, np.uint8),
            "actions": np.zeros((*lead, self.params["actions"]), np.float32),
            "rewards": np.zeros((*lead, 1), np.float32),
            "dones": np.zeros((*lead, 1), np.float32),
            "is_first": np.zeros((*lead, 1), np.float32),
            "env": env,
            "serial": serial,
        }
        for i, e in enumerate(self.envs):
            log = e.log
            pick = valid & (env == i) & (serial < len(log.frame))
            valid &= (env != i) | pick
            n = serial[pick]
            frames = e.frames[np.asarray(log.frame, np.int64)[n]].copy()
            flat = frames.reshape(len(n), -1)
            flat[:, :STAMP_BYTES] = s[pick]
            rows["rgb"][pick] = frames
            action = np.asarray(log.action, np.int64)[n]
            onehot = np.zeros((len(n), self.params["actions"]), np.float32)
            onehot[np.arange(len(n))[action >= 0], action[action >= 0]] = 1.0
            rows["actions"][pick] = onehot
            rows["rewards"][pick] = np.asarray(log.reward, np.float32)[n, None]
            rows["dones"][pick] = np.asarray(log.done, np.float32)[n, None]
            rows["is_first"][pick] = np.asarray(log.is_first, np.float32)[n, None]
        return rows, valid


class PixelEpisodes(gym.Env):
    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, traffic: Traffic, index: int):
        self.traffic, self.index = traffic, index
        p = traffic.params
        self.observation_space = gym.spaces.Box(0, 255, (64, 64, 3), np.uint8)
        self.action_space = gym.spaces.Discrete(p["actions"])
        self.rng = np.random.default_rng([traffic.seed, 1 + index])
        self.length, phase, brightness = traffic.plan[index]
        self.frames = (traffic.pool * np.float32(brightness)).astype(np.uint8)
        self.left = max(2, round(self.length * phase))  # steps left in the episode
        self.log = EpisodeLog()
        self.started = False

    def _observe(self, reward: float, done: bool, is_first: bool) -> np.ndarray:
        idx = int(self.rng.integers(0, len(self.frames)))
        frame = self.frames[idx].copy()
        self.traffic.stamp(frame, self.index, self.log.emit(idx, reward, done, is_first))
        return frame

    def reset(self, *, seed=None, options=None):
        t0 = time.perf_counter()
        if self.started:
            self.left = self.length
            self.traffic.resets += 1
        self.started = True
        obs = self._observe(0.0, False, True)
        self.traffic.host_seconds += time.perf_counter() - t0
        return obs, {}

    def step(self, action):
        if self.index == 0 and self.traffic.on_step is not None:
            self.traffic.on_step()
        t0 = time.perf_counter()
        scope = self.traffic.annotate("env.step") if self.traffic.annotate else None
        if scope is not None:
            scope.__enter__()
        try:
            self.log.action[-1] = int(action)
            self.left -= 1
            done = self.left <= 0
            p = self.traffic.params["reward_prob"]
            u = self.rng.random()
            reward = 0.0 if u >= p else (1.0 if u < p / 2 else -1.0)
            obs = self._observe(reward, done, False)
        finally:
            if scope is not None:
                scope.__exit__(None, None, None)
        self.traffic.host_seconds += time.perf_counter() - t0
        return obs, reward, done, False, {}
