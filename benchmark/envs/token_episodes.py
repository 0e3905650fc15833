"""The benchmark's token traffic: prompts to answer, every id drawn from the seed.

One general generator, parameterised by a traffic file's `env` group:

    group_size      environments that share a prompt and a response length
    vocab_size      ids of the slice held, the mask token (never in a prompt) the last
    prompt_len      {median, sigma, clip: [lo, hi]}: log-normal, clipped, rounded up to a block
    response_len    the same for the response the task asks for
    pool            (prompt, response) length pairs in the schedule every group cycles through
    zipf_exponent   prompt ids by rank, p(k) ~ 1 / k^s over the slice without the mask id
    classes         the reward's classes: `id % classes == target[position]`
    episode_steps   [shortest, longest] episode in agent steps, which the lengths above
                    imply (checked): the one key every traffic file has, whatever its generator

An environment is `sheeprl_tpu/envs/token_task.py`'s contract, from the
benchmark's own side: the observation is the prompt (padded, with its length
and the response length asked for), an action one denoising step's committed
ids over the block the response has reached (-1 where nothing is committed),
done once the response is whole; the reward comes with the last step, the share
of positions whose id falls in the class a seeded function of the prompt names.

The seed decides the ids and which group runs which part of the schedule. It
does not decide how much work a run is: the pool of length pairs is the
quantiles of the two log-normals, paired and ordered by a generator that never
sees the seed; every group walks the pool in that order from its own offset
(the offsets evenly spread, so episode ends never pile up), and the seed only
permutes which group gets which offset. Every environment keeps its own record
of every prompt it posed and every id committed to it, step by step: what the
update trains on and what the policy saw are checked against that.
"""

from __future__ import annotations

import math
import time
import zlib
from statistics import NormalDist

import gymnasium as gym
import numpy as np


def length_pool(params: dict, block_length: int) -> list[tuple[int, int]]:
    """The schedule's (prompt, response) lengths: the same for every seed."""
    n = params["pool"]

    def quantiles(spec):
        lo, hi = spec["clip"]
        raw = [spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf((i + 0.5) / n)) for i in range(n)]
        return [int(min(max(math.ceil(x / block_length) * block_length, lo), hi)) for x in raw]

    fixed = np.random.default_rng(20250309)  # the pairing and the order: no seed of a run
    prompts, responses = quantiles(params["prompt_len"]), quantiles(params["response_len"])
    pairs = list(zip(fixed.permutation(prompts).tolist(), fixed.permutation(responses).tolist()))
    return [pairs[i] for i in fixed.permutation(n)]


class Traffic:
    """All environments of one run, their schedule, records and clock hook."""

    def __init__(self, params: dict, num_envs: int, block_length: int, seed: int, on_step=None, annotate=None):
        self.params, self.num_envs, self.block_length, self.seed = params, num_envs, block_length, seed
        self.on_step, self.annotate = on_step, annotate
        self.vocab_size = params["vocab_size"]
        self.pool = length_pool(params, block_length)
        self.p_max, self.r_max = params["prompt_len"]["clip"][1], params["response_len"]["clip"][1]
        if "episode_steps" in params:  # one number of ids a step takes the shortest and the longest response to these
            (lo, hi), (short, long) = params["response_len"]["clip"], params["episode_steps"]
            if lo * long != hi * short:
                raise ValueError(f"episode_steps {[short, long]} do not follow from response_len's clip {[lo, hi]}")
        groups = num_envs // params["group_size"]
        offsets = [(g * len(self.pool)) // groups for g in range(groups)]
        self.offsets = [offsets[j] for j in np.random.default_rng([seed, 0]).permutation(groups)]
        ranks = np.arange(1, self.vocab_size, dtype=np.float64) ** -params["zipf_exponent"]
        self.id_cdf = np.cumsum(ranks / ranks.sum())
        self.envs: list[TokenEpisodes] = []
        self.host_seconds = 0.0  # wall time inside step() and reset()
        self.resets = 0
        self.context_tokens = 0  # over every step of every environment: prompt and response tokens behind the block
        self.tokens_committed = 0  # ids the environments were handed, over every step

    def make_env(self, render_mode=None, **_):
        if len(self.envs) >= self.num_envs:
            raise RuntimeError(f"the traffic has {self.num_envs} environments; one more was asked for")
        env = TokenEpisodes(self, len(self.envs))
        self.envs.append(env)
        return env

    def pose(self, group: int, episode: int) -> tuple[np.ndarray, int]:
        """The prompt and response length of a group's episode: the same for every member."""
        p_len, r_len = self.pool[(self.offsets[group] + episode) % len(self.pool)]
        rng = np.random.default_rng([self.seed, 1 + group, episode])
        return np.searchsorted(self.id_cdf, rng.random(p_len)).astype(np.int32).clip(0, self.vocab_size - 2), r_len

    def targets(self, prompt: np.ndarray, response_len: int) -> np.ndarray:
        return np.random.default_rng(zlib.crc32(prompt.tobytes())).integers(0, self.params["classes"], response_len)


class Episode:
    """What one episode posed and what was committed to it, step by step."""

    def __init__(self, prompt: np.ndarray, response_len: int):
        self.prompt, self.response_len = prompt, response_len
        self.actions: list[np.ndarray] = []  # one [block_length] vector a denoising step
        self.reward = None


class TokenEpisodes(gym.Env):
    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, traffic: Traffic, index: int):
        self.traffic, self.index = traffic, index
        self.group = index // traffic.params["group_size"]
        t = traffic
        self.observation_space = gym.spaces.Dict({
            "prompt": gym.spaces.Box(0, t.vocab_size - 1, (t.p_max,), np.int32),
            "prompt_len": gym.spaces.Box(0, t.p_max, (1,), np.int32),
            "response_len": gym.spaces.Box(0, t.r_max, (1,), np.int32),
        })
        self.action_space = gym.spaces.Box(-1, t.vocab_size - 1, (t.block_length,), np.int32)
        self.log: list[Episode] = []
        self.written = 0

    def _observe(self) -> dict:
        ep = self.log[-1]
        padded = np.zeros(self.traffic.p_max, np.int32)
        padded[: len(ep.prompt)] = ep.prompt
        return {"prompt": padded, "prompt_len": np.array([len(ep.prompt)], np.int32), "response_len": np.array([ep.response_len], np.int32)}

    def reset(self, *, seed=None, options=None):
        t0 = time.perf_counter()
        if self.log:
            self.traffic.resets += 1
        self.log.append(Episode(*self.traffic.pose(self.group, len(self.log))))
        self.written = 0
        obs = self._observe()
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
            ep = self.log[-1]
            action = np.array(action, np.int32).reshape(-1)
            self.traffic.context_tokens += len(ep.prompt) + self.written // self.traffic.block_length * self.traffic.block_length
            ep.actions.append(action)
            handed = int((action >= 0).sum())
            self.written += handed
            self.traffic.tokens_committed += handed
            done = self.written >= ep.response_len
            reward = 0.0
            if done:
                ids = response_of(ep, self.traffic.block_length)[0]
                reward = float((ids % self.traffic.params["classes"] == self.traffic.targets(ep.prompt, ep.response_len)).sum()) / ep.response_len
                ep.reward = reward
            obs = self._observe()
        finally:
            if scope is not None:
                scope.__exit__(None, None, None)
        self.traffic.host_seconds += time.perf_counter() - t0
        return obs, reward, done, False, {}


def response_of(ep: Episode, block_length: int) -> tuple[np.ndarray, np.ndarray]:
    """-> (the response's ids, the denoising step from 1 that committed each; 0 and -1 where none has)."""
    ids, step = np.full(ep.response_len, -1, np.int64), np.zeros(ep.response_len, np.int64)
    written = in_block = 0
    for action in ep.actions:
        start = written // block_length * block_length
        in_block += 1
        for j in np.nonzero(action >= 0)[0]:
            if start + j < ep.response_len:
                ids[start + j], step[start + j] = action[j], in_block
        written += int((action >= 0).sum())
        if written % block_length == 0:
            in_block = 0
    return ids, step
