"""Pre-norm transformer attention: RMSNorm, rotate-half RoPE and grouped-query
attention with per-head RMSNorm on q and k (the `sdar_moe` / Qwen3 family's
block), under whatever mask the caller builds, and the same attention read
through a key/value cache.

Layers follow their input's dtype (`nn/layers.py`): float32 master weights
are cast where they are used, norms and the softmax run in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .core import Module, static

__all__ = ["RMSNorm", "Attention", "rope", "block_causal_mask"]

NEG = -1e30  # a masked score: finite, so a row that attends nothing stays a number


class RMSNorm(Module):
    scale: jax.Array
    eps: float = static(default=1e-6)

    @classmethod
    def init(cls, dim: int, *, eps: float = 1e-6) -> "RMSNorm":
        return cls(scale=jnp.ones((dim,), jnp.float32), eps=eps)

    def __call__(self, x: jax.Array) -> jax.Array:
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * self.scale.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE over the whole head: x [..., S, heads, D], positions [..., S]."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def block_causal_mask(positions: jax.Array, valid: jax.Array, block_length: int) -> jax.Array:
    """[..., S, S] (query, key): bidirectional inside a block, causal across
    blocks, and nothing to or from a position that is not `valid`."""
    block = positions // block_length
    return (block[..., None, :] <= block[..., :, None]) & valid[..., None, :] & valid[..., :, None]


class Attention(Module):
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    q_norm: RMSNorm
    k_norm: RMSNorm
    num_heads: int = static(default=1)
    num_kv_heads: int = static(default=1)
    head_dim: int = static(default=64)
    rope_theta: float = static(default=1e6)

    @classmethod
    def init(cls, key, hidden: int, num_heads: int, num_kv_heads: int, head_dim: int, *, rope_theta: float = 1e6, eps: float = 1e-6):
        ks = jax.random.split(key, 4)
        dense = lambda k, n_in, n_out: jax.random.normal(k, (n_in, n_out), jnp.float32) / math.sqrt(n_in)
        return cls(
            wq=dense(ks[0], hidden, num_heads * head_dim), wk=dense(ks[1], hidden, num_kv_heads * head_dim),
            wv=dense(ks[2], hidden, num_kv_heads * head_dim), wo=dense(ks[3], num_heads * head_dim, hidden),
            q_norm=RMSNorm.init(head_dim, eps=eps), k_norm=RMSNorm.init(head_dim, eps=eps),
            num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
        )

    def project(self, u: jax.Array):
        """u [..., S, hidden] -> raw q [..., S, heads, D], k and v [..., S, kv heads, D]."""
        lead = u.shape[:-1]
        q = (u @ self.wq.astype(u.dtype)).reshape(*lead, self.num_heads, self.head_dim)
        k = (u @ self.wk.astype(u.dtype)).reshape(*lead, self.num_kv_heads, self.head_dim)
        v = (u @ self.wv.astype(u.dtype)).reshape(*lead, self.num_kv_heads, self.head_dim)
        return q, k, v

    def turn_q(self, q: jax.Array, positions: jax.Array) -> jax.Array:
        """Per-head RMSNorm, then RoPE: q [..., S, heads, D]."""
        return rope(self.q_norm(q), positions, self.rope_theta)

    def turn_k(self, k: jax.Array, positions: jax.Array) -> jax.Array:
        return rope(self.k_norm(k), positions, self.rope_theta)

    def qkv(self, u: jax.Array, positions: jax.Array):
        """u [..., S, hidden] -> q [..., S, heads, D], k and v [..., S, kv heads, D]; q and k normed and rotated."""
        q, k, v = self.project(u)
        return self.turn_q(q, positions), self.turn_k(k, positions), v

    def scores(self, q: jax.Array, k: jax.Array) -> jax.Array:
        """q [..., Sq, heads, D], k [..., Sk, kv heads, D] -> float32 [..., kv heads, group, Sq, Sk];
        query head h reads key/value head h // group."""
        g = self.num_heads // self.num_kv_heads
        q = q.reshape(*q.shape[:-2], self.num_kv_heads, g, self.head_dim)
        s = jnp.einsum("...qkgd,...skd->...kgqs", q, k, preferred_element_type=jnp.float32)
        return s / math.sqrt(self.head_dim)

    def mix(self, weights: jax.Array, v: jax.Array) -> jax.Array:
        """weights [..., kv heads, group, Sq, Sk], v [..., Sk, kv heads, D] -> [..., Sq, heads * D]."""
        out = jnp.einsum("...kgqs,...skd->...qkgd", weights.astype(v.dtype), v)
        return out.reshape(*out.shape[:-3], self.num_heads * self.head_dim)

    def attend_group(self, q, positions, k, v, group, mask) -> jax.Array:
        """One sequence's queries of one key/value head: raw q [S, heads, D]
        (normed and rotated here, a group at a time), k (turned) and v
        [S, kv heads, D], mask [S, S] -> [S, group size, D]. The scores of one
        group are a quarter (at 4 key/value heads) of a sequence's."""
        g = self.num_heads // self.num_kv_heads
        qg = self.turn_q(jax.lax.dynamic_slice_in_dim(q, group * g, g, axis=1), positions)
        kg, vg = jax.lax.dynamic_index_in_dim(k, group, 1, keepdims=False), jax.lax.dynamic_index_in_dim(v, group, 1, keepdims=False)
        s = jnp.einsum("qgd,sd->gqs", qg, kg, preferred_element_type=jnp.float32) / math.sqrt(self.head_dim)
        weights = jax.nn.softmax(jnp.where(mask[None], s, NEG), axis=-1)
        return jnp.einsum("gqs,sd->qgd", weights.astype(vg.dtype), vg)

    def attend_cached(self, q, k, v, cache_k, cache_v, cached, own) -> jax.Array:
        """The block's queries over the cache's clean keys and the block's
        own: cache_* [..., S_max, kv heads, D], `cached` [..., S_max] which
        slots hold clean keys, `own` [..., Sq, Sq] the mask inside the block."""
        s_cache = jnp.where(cached[..., None, None, None, :], self.scores(q, cache_k), NEG)
        s_own = jnp.where(own[..., None, None, :, :], self.scores(q, k), NEG)
        weights = jax.nn.softmax(jnp.concatenate([s_cache, s_own], axis=-1), axis=-1)
        n = cache_k.shape[-3]
        return self.mix(weights[..., :n], cache_v) + self.mix(weights[..., n:], v)

    def out(self, a: jax.Array) -> jax.Array:
        return a @ self.wo.astype(a.dtype)
