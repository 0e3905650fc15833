"""The rollout record of `ppo_bd` and the update's input layout, on the host.

A denoising step commits ids; the main pulls them (they are the action) with
their log-probabilities, and that pull is the whole record: which id went
where at which step says what the policy saw at every step. So the record is
numpy on the host, one row an environment of fixed width, and the update's
batch is built from it at one fixed shape:

    [prompt (P_max) ; response clean (R_max) ; copy 1 (R_max) ; ... ; copy K (R_max)]

Copy k holds every response block as the policy saw it at denoising step k:
the ids committed at earlier steps of the block, the mask id elsewhere. A
position of a copy carries the RoPE position of the response token it stands
for (prompt length + index). The loss reads, for each response index, the
one position in the copy of the step that committed it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Dims", "Record", "build_batch"]


@dataclasses.dataclass(frozen=True)
class Dims:
    p_max: int
    r_max: int
    block_length: int
    denoise_steps: int
    mask_id: int

    @property
    def s_max(self) -> int:  # the cache: prompt and response
        return self.p_max + self.r_max

    @property
    def layout(self) -> int:  # the update: clean sequence and one copy a denoising step
        return self.p_max + (1 + self.denoise_steps) * self.r_max


class Record:
    """The open sequence of every environment, and the finished ones by group."""

    def __init__(self, num_envs: int, group_size: int, dims: Dims):
        self.dims, self.group_size = dims, group_size
        self.prompt = np.zeros((num_envs, dims.p_max), np.int32)
        self.prompt_len = np.zeros(num_envs, np.int32)
        self.ids = np.zeros((num_envs, dims.r_max), np.int32)
        self.step = np.zeros((num_envs, dims.r_max), np.int32)  # the denoising step (from 1) that committed the id
        self.logprob = np.zeros((num_envs, dims.r_max), np.float32)
        self.written = np.zeros(num_envs, np.int32)
        self.block_step = np.zeros(num_envs, np.int32)
        self.episode = np.zeros(num_envs, np.int64)
        self.groups: dict[tuple[int, int], list[dict]] = {}
        self.ready: list[dict] = []  # finished sequences with their advantage, oldest first

    def start(self, env: int, prompt: np.ndarray, prompt_len: int) -> None:
        self.prompt[env], self.prompt_len[env] = prompt, prompt_len
        self.ids[env], self.step[env], self.logprob[env] = 0, 0, 0.0
        self.written[env] = self.block_step[env] = 0

    def commit(self, actions: np.ndarray, logprob: np.ndarray) -> np.ndarray:
        """One denoising step of every environment: actions [envs, block_length],
        -1 where nothing was committed. -> which environments' blocks are clean now."""
        bl = self.dims.block_length
        rows, cols = np.nonzero(actions >= 0)
        at = self.written[rows] // bl * bl + cols
        keep = at < self.dims.r_max
        rows, cols, at = rows[keep], cols[keep], at[keep]
        self.block_step += 1
        self.ids[rows, at] = actions[rows, cols]
        self.step[rows, at] = self.block_step[rows]
        self.logprob[rows, at] = logprob[rows, cols]
        self.written += np.bincount(rows, minlength=len(self.written)).astype(np.int32)
        clean = (self.written % bl == 0) & (actions >= 0).any(axis=1)
        self.block_step[clean] = 0
        return clean

    def finish(self, env: int, reward: float) -> None:
        """Close the environment's sequence; a whole group closed gives its advantages."""
        n = int(self.written[env])
        seq = {
            "prompt": self.prompt[env, : self.prompt_len[env]].copy(), "ids": self.ids[env, :n].copy(),
            "step": self.step[env, :n].copy(), "logprob": self.logprob[env, :n].copy(), "reward": float(reward),
        }
        key = (env // self.group_size, int(self.episode[env]))
        self.episode[env] += 1
        group = self.groups.setdefault(key, [])
        group.append(seq)
        if len(group) == self.group_size:
            rewards = np.array([s["reward"] for s in group], np.float32)
            adv = (rewards - rewards.mean()) / (rewards.std() + 1e-6)
            for s, a in zip(group, adv):
                s["advantage"] = float(a)
            self.ready.extend(self.groups.pop(key))


def build_batch(seqs: list[dict], batch: int, dims: Dims) -> dict[str, np.ndarray]:
    """Whole sequences at one fixed shape; rows past `len(seqs)` and positions
    past a sequence's lengths are padding (`copy` -1, `loss_mask` 0)."""
    S, R, K = dims.layout, dims.r_max, dims.denoise_steps
    out = {
        "ids": np.zeros((batch, S), np.int32), "positions": np.zeros((batch, S), np.int32),
        "copy": np.full((batch, S), -1, np.int32), "block": np.zeros((batch, S), np.int32),
        "loss_pos": np.zeros((batch, R), np.int32), "loss_mask": np.zeros((batch, R), np.float32),
        "targets": np.zeros((batch, R), np.int32), "logprob_old": np.zeros((batch, R), np.float32),
        "advantages": np.zeros((batch,), np.float32),
    }
    for b, s in enumerate(seqs):
        p, n = len(s["prompt"]), len(s["ids"])
        out["ids"][b, :p], out["positions"][b, :p], out["copy"][b, :p] = s["prompt"], np.arange(p), 0
        for k in range(K + 1):  # 0: the clean response; k: as the policy saw it at step k
            at = dims.p_max + k * R
            out["ids"][b, at : at + n] = s["ids"] if k == 0 else np.where(s["step"] < k, s["ids"], dims.mask_id)
            out["positions"][b, at : at + n], out["copy"][b, at : at + n] = p + np.arange(n), k
        out["loss_pos"][b, :n] = dims.p_max + s["step"] * R + np.arange(n)
        out["loss_mask"][b, :n], out["targets"][b, :n], out["logprob_old"][b, :n] = 1.0, s["ids"], s["logprob"]
        out["advantages"][b] = s["advantage"]
    out["block"] = out["positions"] // dims.block_length
    return out
