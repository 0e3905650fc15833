"""The block-diffusion policy: a pre-norm transformer of grouped-query
attention and routed experts (`nn/attention.py`, `nn/moe.py`) over token ids,
read three ways.

  - `layout_hidden`: a whole layout (the update, under its own mask computed
    the short way, `layout_attend`; the prompt's prefill, under any mask,
    `mask_attend`), each layer and each sequence's attention recomputed in
    the backward pass;
  - `cached_block`: one block of `block_length` positions against the cache of
    clean keys and values (a denoising step, and the pass that writes a
    finished block's keys and values);
  - the player's state, one slot an environment: the cache, the current
    block's ids (the mask id where nothing is committed yet) and the position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...nn.attention import NEG, Attention, RMSNorm, block_causal_mask
from ...nn.moe import RoutedExperts

__all__ = ["Layer", "BDPolicy", "PlayerState", "build_policy", "player_copy", "layout_mask", "layout_attend", "mask_attend"]


class Layer(nn.Module):
    attn_norm: RMSNorm
    attn: Attention
    mlp_norm: RMSNorm
    experts: RoutedExperts

    @classmethod
    def init(cls, key, args) -> "Layer":
        k_attn, k_moe = jax.random.split(key)
        return cls(
            attn_norm=RMSNorm.init(args.hidden_size, eps=args.rms_norm_eps),
            attn=Attention.init(
                k_attn, args.hidden_size, args.num_attention_heads, args.num_key_value_heads, args.head_dim,
                rope_theta=args.rope_theta, eps=args.rms_norm_eps,
            ),
            mlp_norm=RMSNorm.init(args.hidden_size, eps=args.rms_norm_eps),
            experts=RoutedExperts.init(
                k_moe, args.hidden_size, args.moe_intermediate_size, args.num_experts, args.num_experts_per_tok,
                first_expert=args.first_expert, held=args.experts_held, norm_topk=args.norm_topk_prob,
            ),
        )

    def mlp(self, x: jax.Array, valid: jax.Array | None = None):
        """x [..., hidden] -> (x + this share's experts' sum, tokens per held expert); padding (`valid` false) is routed nowhere."""
        r = self.mlp_norm(x)
        y, counts = self.experts(r.reshape(-1, r.shape[-1]), None if valid is None else valid.reshape(-1))
        return x + y.reshape(x.shape), counts


def layout_mask(copy: jax.Array, block: jax.Array) -> jax.Array:
    """[S, S] (query, key) over `[prompt ; response clean ; copy 1 ; copy 2 ...]`:
    `copy` is 0 on clean positions, k on copy k's, -1 on padding; `block` the
    block of the token a position is or stands for. Clean i attends clean j of
    blocks <= its own; a copy's position attends the clean blocks before its
    own and its own copy's own block; padding neither attends nor is attended."""
    ci, cj, bi, bj = copy[:, None], copy[None, :], block[:, None], block[None, :]
    clean = (cj == 0) & ((bj < bi) | ((ci == 0) & (bj == bi)))
    own = (cj == ci) & (ci > 0) & (bj == bi)
    return (clean | own) & (ci >= 0) & (cj >= 0)


class PlayerState(nn.Module):
    cache_k: tuple  # a layer each: [envs, S_max, kv heads, D] clean keys, compute dtype
    cache_v: tuple
    block_ids: jax.Array  # [envs, block_length] the current block, mask id where uncommitted
    pos: jax.Array  # [envs] position of the current block's first token


def mask_attend(mask_of):
    """-> the attention of one sequence's one key/value head under `mask_of(b)` [S, S]: any layout."""
    def attend(attn, q, positions, k, v, group, b):
        return attn.attend_group(q, positions, k, v, group, mask_of(b))

    return attend


def layout_attend(copy, block, n_clean: int, block_length: int):
    """-> the same under `layout_mask(copy[b], block[b])`, computed as the
    update's layout allows: every key a query may attend is either one of the
    first `n_clean` positions (prompt and clean response) or, for a copy's
    position, in its own aligned block of `block_length`. So the scores are
    [S, n_clean + block_length] a head, not [S, S]: half, at two copies."""
    def attend(attn, q, positions, k, v, group, b):
        g = attn.num_heads // attn.num_kv_heads
        q = attn.turn_q(jax.lax.dynamic_slice_in_dim(q, group * g, g, axis=1), positions)  # [S, g, D]
        k, v = jax.lax.dynamic_index_in_dim(k, group, 1, keepdims=False), jax.lax.dynamic_index_in_dim(v, group, 1, keepdims=False)
        S, bl, scale = q.shape[0], block_length, attn.head_dim ** -0.5
        c, blk = copy[b], block[b]
        ci, bi, cj, bj = c[:, None], blk[:, None], c[None, :n_clean], blk[None, :n_clean]
        seen = (cj == 0) & ((bj < bi) | ((ci == 0) & (bj == bi))) & (ci >= 0)
        s_clean = jnp.where(seen[None], jnp.einsum("qgd,sd->gqs", q, k[:n_clean], preferred_element_type=jnp.float32) * scale, NEG)
        blocks = (S - n_clean) // bl
        q_own, k_own, v_own = q[n_clean:].reshape(blocks, bl, g, -1), k[n_clean:].reshape(blocks, bl, -1), v[n_clean:].reshape(blocks, bl, -1)
        live = (c[n_clean:] > 0).reshape(blocks, bl)
        s_own = jnp.einsum("nqgd,nsd->gnqs", q_own, k_own, preferred_element_type=jnp.float32) * scale
        s_own = jnp.where((live[:, :, None] & live[:, None, :])[None], s_own, NEG).reshape(g, blocks * bl, bl)
        s_own = jnp.concatenate([jnp.full((g, n_clean, bl), NEG, jnp.float32), s_own], axis=1)  # a clean position has no such block
        w = jax.nn.softmax(jnp.concatenate([s_clean, s_own], axis=-1), axis=-1).astype(v.dtype)
        out = jnp.einsum("gqs,sd->qgd", w[..., :n_clean], v[:n_clean])
        own = jnp.einsum("gnqs,nsd->nqgd", w[:, n_clean:, n_clean:].reshape(g, blocks, bl, bl), v_own)
        return out.at[n_clean:].add(own.reshape(blocks * bl, g, -1))

    return attend


class BDPolicy(nn.Module):
    embed: jax.Array  # [vocab, hidden]
    layers: tuple  # of Layer: a layer's weights are arrays of their own, which a program reads where they lie
    final_norm: RMSNorm
    lm_head: jax.Array  # [hidden, vocab]
    block_length: int = nn.static(default=4)
    mask_token_id: int = nn.static(default=0)

    # ---- a whole layout -------------------------------------------------------
    def layout_hidden(self, ids, positions, valid, attend, dtype, keep_kv: bool = False):
        """ids, positions, valid [B, S]; `attend` as `mask_attend` or `layout_attend` make it.
        -> (hidden [B, S, hidden], tokens per held expert [layers, held], a layer's keys and values each or None).
        Each layer, and in it each sequence's each key/value head, is recomputed in the backward pass."""
        x = self.embed.astype(dtype)[ids]

        def block(x, layer: Layer):
            with jax.named_scope("bd/attn"):
                q, k, v = layer.attn.project(layer.attn_norm(x))
                k = layer.attn.turn_k(k, positions)
                kv = layer.attn.num_kv_heads

                def one(i):  # one sequence's one key/value head at a time: the scores are the layer's largest array
                    b, g = i // kv, i % kv
                    return attend(layer.attn, q[b], positions[b], k[b], v[b], g, b)

                a = jax.lax.map(jax.checkpoint(one), jnp.arange(x.shape[0] * kv))  # [B * kv, S, group, D]
                a = a.reshape(x.shape[0], kv, x.shape[1], -1).transpose(0, 2, 1, 3).reshape(*x.shape[:2], -1)
                x = x + layer.attn.out(a)
            x, counts = layer.mlp(x, valid)
            return x, counts, ((k, v) if keep_kv else None)

        counts, kvs = [], []
        for layer in self.layers:
            x, c, kv = jax.checkpoint(block)(x, layer)
            counts.append(c)
            kvs.append(kv)
        return x, jnp.stack(counts), kvs

    def logits(self, hidden: jax.Array) -> jax.Array:
        """Float32 logits over the vocabulary held; the mask token is never a choice."""
        with jax.named_scope("bd/head"):
            out = (self.final_norm(hidden) @ self.lm_head.astype(hidden.dtype)).astype(jnp.float32)
            return out.at[..., self.mask_token_id].set(-jnp.inf)

    # ---- one block against the cache ---------------------------------------
    def cached_block(self, state: PlayerState, ids: jax.Array):
        """ids [envs, block_length] at positions pos .. pos + block_length.
        -> (hidden, a layer's keys and values of the block each: [envs, block_length, kv heads, D])."""
        dtype = state.cache_k[0].dtype
        x = self.embed.astype(dtype)[ids]
        positions = state.pos[:, None] + jnp.arange(ids.shape[1])
        cached = jnp.arange(state.cache_k[0].shape[1])[None, :] < state.pos[:, None]
        own = jnp.ones((ids.shape[0], ids.shape[1], ids.shape[1]), bool)  # bidirectional inside the block
        kvs = []
        for layer, cache_k, cache_v in zip(self.layers, state.cache_k, state.cache_v):
            with jax.named_scope("bd/attn"):
                q, k, v = layer.attn.qkv(layer.attn_norm(x), positions)
                x = x + layer.attn.out(layer.attn.attend_cached(q, k, v, cache_k, cache_v, cached, own))
            x, _ = layer.mlp(x)
            kvs.append((k, v))
        return x, kvs

    # ---- the player's programs ----------------------------------------------
    def init_states(self, num_envs: int, s_max: int, dtype) -> PlayerState:
        attn = self.layers[0].attn
        zeros = lambda: tuple(jnp.zeros((num_envs, s_max, attn.num_kv_heads, attn.head_dim), dtype) for _ in self.layers)
        return PlayerState(
            cache_k=zeros(), cache_v=zeros(),
            block_ids=jnp.full((num_envs, self.block_length), self.mask_token_id, jnp.int32),
            pos=jnp.zeros((num_envs,), jnp.int32),
        )

    def reset_states(self, state: PlayerState, reset_mask: jax.Array) -> PlayerState:
        """Empty the finished environments' slots: position 0 (nothing of the
        cache is read below the position) and an all-mask block."""
        done = reset_mask > 0
        return state.replace(
            block_ids=jnp.where(done[:, None], self.mask_token_id, state.block_ids), pos=jnp.where(done, 0, state.pos)
        )

    def _written(self, state: PlayerState, kvs, rows, cols, **rest) -> PlayerState:
        return PlayerState(
            cache_k=tuple(c.at[rows, cols].set(k, mode="drop") for c, (k, _) in zip(state.cache_k, kvs)),
            cache_v=tuple(c.at[rows, cols].set(v, mode="drop") for c, (_, v) in zip(state.cache_v, kvs)), **rest,
        )

    def prefill(self, state: PlayerState, prompts, lengths, env_idx) -> PlayerState:
        """Write the prompts' keys and values in one pass: prompts [G, P_max]
        padded, lengths [G], env_idx [G] the slots (an index past the last
        environment writes nothing)."""
        positions = jnp.broadcast_to(jnp.arange(prompts.shape[1]), prompts.shape)
        valid = positions < lengths[:, None]
        mask = block_causal_mask(positions, valid, self.block_length)
        _, _, kvs = self.layout_hidden(prompts, positions, valid, mask_attend(lambda b: mask[b]), state.cache_k[0].dtype, keep_kv=True)
        return self._written(
            state, kvs, env_idx[:, None], positions,
            block_ids=state.block_ids.at[env_idx].set(self.mask_token_id, mode="drop"), pos=state.pos.at[env_idx].set(lengths, mode="drop"),
        )

    def commit(self, state: PlayerState, done_mask: jax.Array) -> PlayerState:
        """One more pass over the blocks that are clean now: their keys and
        values go into the cache, the position moves on, the block is all mask again."""
        _, kvs = self.cached_block(state, state.block_ids)
        done = done_mask > 0
        n = state.pos.shape[0]
        rows = jnp.where(done, jnp.arange(n), n)[:, None]  # past the last slot: dropped
        return self._written(
            state, kvs, rows, state.pos[:, None] + jnp.arange(self.block_length),
            block_ids=jnp.where(done[:, None], self.mask_token_id, state.block_ids), pos=jnp.where(done, state.pos + self.block_length, state.pos),
        )


def build_policy(key, args, vocab_size: int) -> BDPolicy:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    h = args.hidden_size
    return BDPolicy(
        embed=jax.random.normal(k_embed, (vocab_size, h), jnp.float32),
        layers=tuple(Layer.init(k, args) for k in jax.random.split(k_layers, args.num_hidden_layers)),
        final_norm=RMSNorm.init(h, eps=args.rms_norm_eps),
        lm_head=jax.random.normal(k_head, (h, vocab_size), jnp.float32) / jnp.sqrt(h),
        block_length=args.block_length, mask_token_id=vocab_size - 1,
    )


def player_copy(model: BDPolicy, dtype) -> BDPolicy:
    """The copy the policy steps read: every matrix in the compute dtype; the
    router and the norm scales stay float32 (they are read in float32). Every
    leaf is a buffer of its own: the train step donates the master weights and
    the copy alike, and one buffer cannot be given away twice."""
    def cast(path, leaf):
        name = getattr(path[-1], "name", "")
        return jnp.copy(leaf) if name in ("router", "scale") or leaf.dtype == dtype else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, model)
