"""A token task with a checkable answer, for policies that write text.

An episode is one prompt and one response. The observation is the prompt
(`prompt`, padded to `max_prompt`, with `prompt_len` and the `response_len`
the task asks for); an action is one denoising step's committed ids, a vector
of `block_length` entries over the block the response has reached, -1 where
nothing is committed. The episode is done once `response_len` ids are
committed (or, where the task has an end-of-sequence id, at the end of the
block that holds one). The reward comes with the last step: the share of the
response's positions whose id falls in the class a seeded function of the
prompt names for that position, `id % classes == target[position]`: in [0, 1],
0.5 by chance at two classes, exact match at `classes = vocab_size`.

The last id of the vocabulary is the mask token; no prompt holds it.
Everything is drawn from the seed `reset(seed=)` first gets: two environments
given the same seed pose the same prompts in the same order, which is how a
group of a policy-gradient main shares its prompt.
"""

from __future__ import annotations

import zlib

import gymnasium as gym
import numpy as np

__all__ = ["TokenTask", "ENV_ID"]

ENV_ID = "TokenTask-v0"


class TokenTask(gym.Env):
    metadata = {"render_modes": ["rgb_array"]}

    def __init__(self, vocab_size: int = 64, max_prompt: int = 32, max_response: int = 16, block_length: int = 4,
                 classes: int = 2, min_prompt: int = 8, min_response: int = 8, eos_token_id: int | None = None, render_mode=None):
        if max_prompt % block_length or max_response % block_length or min_prompt % block_length or min_response % block_length:
            raise ValueError("prompt and response lengths are multiples of block_length")
        self.vocab_size, self.block_length, self.classes, self.eos_token_id = vocab_size, block_length, classes, eos_token_id
        self.prompt_range, self.response_range = (min_prompt, max_prompt), (min_response, max_response)
        self.observation_space = gym.spaces.Dict({
            "prompt": gym.spaces.Box(0, vocab_size - 1, (max_prompt,), np.int32),
            "prompt_len": gym.spaces.Box(0, max_prompt, (1,), np.int32),
            "response_len": gym.spaces.Box(0, max_response, (1,), np.int32),
        })
        self.action_space = gym.spaces.Box(-1, vocab_size - 1, (block_length,), np.int32)
        self.rng = np.random.default_rng(0)

    def _length(self, lo: int, hi: int) -> int:
        return int(self.rng.integers(lo // self.block_length, hi // self.block_length + 1)) * self.block_length

    def pose(self) -> tuple[np.ndarray, int]:
        """The next prompt and the response length it asks for."""
        prompt = self.rng.integers(0, self.vocab_size - 1, self._length(*self.prompt_range)).astype(np.int32)
        return prompt, self._length(*self.response_range)

    def targets(self, prompt: np.ndarray, response_len: int) -> np.ndarray:
        """The class each position of the response should fall in: a seeded function of the prompt."""
        return np.random.default_rng(zlib.crc32(prompt.tobytes())).integers(0, self.classes, response_len)

    def _observe(self) -> dict:
        padded = np.zeros(self.observation_space["prompt"].shape, np.int32)
        padded[: len(self.prompt)] = self.prompt
        return {"prompt": padded, "prompt_len": np.array([len(self.prompt)], np.int32), "response_len": np.array([self.response_len], np.int32)}

    def reset(self, *, seed=None, options=None):
        if seed is not None:
            self.rng = np.random.default_rng(seed)
        self.prompt, self.response_len = self.pose()
        self.response = np.full(self.response_len, -1, np.int64)
        return self._observe(), {}

    def step(self, action):
        action = np.asarray(action).reshape(-1)
        done_before = int((self.response >= 0).sum())
        start = done_before // self.block_length * self.block_length
        for j in np.nonzero(action >= 0)[0]:
            if start + j < self.response_len:
                self.response[start + j] = action[j]
        block = self.response[start : start + self.block_length]
        written = int((self.response >= 0).sum())
        ended = self.eos_token_id is not None and (block >= 0).all() and (block == self.eos_token_id).any()
        done = written >= self.response_len or ended
        reward = 0.0
        if done:
            upto = start + self.block_length if ended else self.response_len
            want = self.targets(self.prompt, self.response_len)[:upto]
            got = self.response[:upto]
            reward = float(((got >= 0) & (got % self.classes == want)).sum()) / self.response_len
        return self._observe(), reward, bool(done), False, {}


if ENV_ID not in gym.registry:
    gym.register(ENV_ID, entry_point=TokenTask)
