"""A routed expert layer that is told which experts it holds.

The layer routes over all `num_experts` (softmax in float32, the `top_k`
largest, their weights normalised over all the picks, held or not) and
returns the part of the result its own experts give:

    sum over e in picks, first_expert <= e < first_expert + held, of w_e * E_e(r)

with E_e(r) = W_down,e (silu(W_gate,e r) * W_up,e r). What the absent experts
would add is left out: on a chip that holds every expert this is the whole
layer, on one chip of an expert-parallel deployment it is that chip's term of
the sum the exchange would complete. No code stands in for the absent chips.

No token is dropped whatever the imbalance. The assignments to held experts
are sorted by expert and multiplied group by group (`jax.lax.ragged_dot`,
which the TPU compiler lowers to its own grouped-matmul kernel: only the rows
of real groups are computed). The sorted list is walked in chunks of fixed
size (twice the tokens: the first holds a balanced routing and what spills
over it) that together cover the worst case, every token picking only held
experts; a chunk past the end of the list costs a predicate.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .core import Module, static

__all__ = ["RoutedExperts"]

ONE_CHUNK_ROWS = 32768  # up to this many worst-case rows the list is one chunk


class RoutedExperts(Module):
    router: jax.Array  # [hidden, num_experts]
    w_gate: jax.Array  # [held, hidden, ff]
    w_up: jax.Array  # [held, hidden, ff]
    w_down: jax.Array  # [held, ff, hidden]
    num_experts: int = static(default=8)
    top_k: int = static(default=2)
    first_expert: int = static(default=0)
    norm_topk: bool = static(default=True)

    @classmethod
    def init(cls, key, hidden: int, ff: int, num_experts: int, top_k: int, *, first_expert: int = 0, held: int | None = None, norm_topk: bool = True):
        held = num_experts - first_expert if held is None else held
        ks = jax.random.split(key, 4)
        normal = lambda k, shape, fan_in: jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        return cls(
            router=normal(ks[0], (hidden, num_experts), hidden), w_gate=normal(ks[1], (held, hidden, ff), hidden),
            w_up=normal(ks[2], (held, hidden, ff), hidden), w_down=normal(ks[3], (held, ff, hidden), ff),
            num_experts=num_experts, top_k=top_k, first_expert=first_expert, norm_topk=norm_topk,
        )

    @property
    def held(self) -> int:
        return self.w_gate.shape[0]

    def route(self, r: jax.Array):
        """r [T, hidden] -> (weights float32 [T, top_k], experts int32 [T, top_k]) over all experts."""
        with jax.named_scope("bd/router"):
            logits = jnp.dot(r.astype(jnp.float32), self.router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            weights, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), self.top_k)
            if self.norm_topk:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, picks

    def __call__(self, r: jax.Array, valid: jax.Array | None = None):
        """r [T, hidden] -> (this share's sum [T, hidden] in r's dtype, tokens per held expert int32 [held]).
        A row that is not `valid` [T] (padding) is no token: it is routed nowhere and gets 0."""
        T, held = r.shape[0], self.held
        weights, picks = self.route(r)
        worst = T * min(self.top_k, held)
        chunks = 1 if worst <= ONE_CHUNK_ROWS else -(-min(self.top_k, held) // 2)
        rows = -(-worst // chunks)

        # the assignments to held experts, sorted by expert: a counting sort (a
        # few experts, many assignments; the chip's comparison sort takes 17 ms
        # for 131,072 keys, this a cumulative sum and a scatter)
        local = picks.reshape(-1) - self.first_expert
        ours = (local >= 0) & (local < held)
        if valid is not None:
            ours = ours & jnp.repeat(valid, self.top_k)
        rank = jnp.cumsum((ours[:, None] & (local[:, None] == jnp.arange(held)[None, :])).astype(jnp.int32), axis=0)  # [assignments, held]
        sizes = rank[-1]
        ends = jnp.cumsum(sizes)
        total = ends[-1]
        mine = jnp.clip(local, 0, held - 1)
        place = (ends - sizes)[mine] + jnp.take_along_axis(rank, mine[:, None], axis=1)[:, 0] - 1
        place = jnp.where(ours, place, chunks * rows)  # not ours: past the end, written nowhere
        tokens = jnp.zeros((chunks * rows,), jnp.int32).at[place].set(jnp.arange(local.shape[0], dtype=jnp.int32) // self.top_k, mode="drop")
        scale = jnp.zeros((chunks * rows,), jnp.float32).at[place].set(weights.reshape(-1), mode="drop")

        dt = r.dtype
        w_gate, w_up, w_down = self.w_gate.astype(dt), self.w_up.astype(dt), self.w_down.astype(dt)

        def product(lo):
            """What the rows lo .. lo + rows of the sorted list add to the result."""
            tok, live = tokens[lo : lo + rows], ((lo + jnp.arange(rows)) < total)[:, None]
            inside = jnp.clip(ends, lo, lo + rows) - jnp.clip(ends - sizes, lo, lo + rows)
            # a row of no group is never written by the grouped product: what it
            # holds is masked on the way in and on the way out, in both directions
            x = jnp.where(live, r[tok], 0)
            with jax.named_scope("moe/experts"):
                gate = jax.lax.ragged_dot(x, w_gate, inside).astype(jnp.float32)  # products accumulate in float32 and leave in r's dtype
                up = jax.lax.ragged_dot(x, w_up, inside).astype(jnp.float32)
                hidden = jnp.where(live, jax.nn.silu(gate) * up, 0.0).astype(dt)
                out = jax.lax.ragged_dot(hidden, w_down, inside)
            return jnp.zeros((T, r.shape[1]), jnp.float32).at[tok].add(jnp.where(live, out.astype(jnp.float32), 0.0) * scale[lo : lo + rows, None])

        # the chunks one after another, each recomputed in the backward pass: what
        # is kept for it is the layer's input alone, whatever the number of chunks
        part = jax.checkpoint(product, static_argnums=(0,))
        y = part(0)
        for lo in range(rows, chunks * rows, rows):
            y = jax.lax.cond(total > lo, lambda y, lo=lo: y + part(lo), lambda y: y, y)
        return y.astype(dt), sizes
