"""The looped policy: a causal language model whose whole layer stack runs
`total_ut_steps` (R) times a token with the same weights (the `ouro` family's
"LoopLM"), read two ways.

With h = E[x], for t = 1..R:

    h <- layers_1..N(h), each layer reading and writing loop t's own keys and values
    h <- RMSNorm_f(h);  lambda_t = sigmoid(w_g . h + b_g);  z_t = h W_head

and one layer, with u = RMSNorm_1(h), v = RMSNorm_3(h) after the first line:

    h <- h + RMSNorm_2(Attn(u))                                (no q/k norm)
    h <- h + RMSNorm_4(W_down(silu(W_gate v) * W_up v))

The exit rule: p_t = lambda_t prod_{j<t}(1 - lambda_j) for t < R, p_R = prod_{j<R}(1 - lambda_j);
the exit loop is the first t with sum_{j<=t} p_j >= `early_exit_threshold` (R where
rounding leaves the sum under it). The policy's logits are z at the exit loop,
picked per token. The pick is an index: the gate gets no gradient through it
(nor through anything else here: it only decides which loop's output is read).

  - `hidden`: whole sequences, causal (the update, and the prompt's prefill,
    which keeps every loop's keys and values), each (loop, layer) recomputed
    in the backward pass;
  - `decode`: one token an environment against the R x N caches (a policy step),
    writing that token's keys and values into every one of them, and reading
    each row's cache only up to its own position where the `decode_attn`
    kernel is selected (`ops/pallas_kernels.py`; the XLA twin reads every slot).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...nn.attention import NEG, Attention, RMSNorm
from ...ops import pallas_kernels as pk

__all__ = ["Layer", "LoopedLM", "LMState", "build_policy", "player_copy", "exit_loop", "decode_attend", "read_block"]


def _dense(key, n_in: int, n_out: int) -> jax.Array:
    return jax.random.normal(key, (n_in, n_out), jnp.float32) / jnp.sqrt(n_in)


class Layer(nn.Module):
    input_norm: RMSNorm  # RMSNorm_1: before attention
    attn: Attention
    attn_out_norm: RMSNorm  # RMSNorm_2: after it
    mlp_norm: RMSNorm  # RMSNorm_3: before the MLP
    w_gate: jax.Array  # [hidden, intermediate]
    w_up: jax.Array
    w_down: jax.Array  # [intermediate, hidden]
    mlp_out_norm: RMSNorm  # RMSNorm_4: after it

    @classmethod
    def init(cls, key, args) -> "Layer":
        ks = jax.random.split(key, 4)
        h, f, eps = args.hidden_size, args.intermediate_size, args.rms_norm_eps
        norm = lambda: RMSNorm.init(h, eps=eps)
        return cls(
            input_norm=norm(),
            attn=Attention.init(ks[0], h, args.num_attention_heads, args.num_key_value_heads, args.head_dim,
                                rope_theta=args.rope_theta, eps=eps, qk_norm=False),
            attn_out_norm=norm(), mlp_norm=norm(),
            w_gate=_dense(ks[1], h, f), w_up=_dense(ks[2], h, f), w_down=_dense(ks[3], f, h),
            mlp_out_norm=norm(),
        )

    def mlp(self, x: jax.Array) -> jax.Array:
        with jax.named_scope("lm/mlp"):
            v = self.mlp_norm(x)
            y = jax.nn.silu(v @ self.w_gate.astype(v.dtype)) * (v @ self.w_up.astype(v.dtype))
            return x + self.mlp_out_norm(y @ self.w_down.astype(v.dtype))


def causal_attend(attn: Attention, q, k, v) -> jax.Array:
    """One sequence, all heads: q [S, heads, D], k and v [S, kv heads, D], turned -> [S, heads * D]."""
    S = q.shape[0]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    weights = jax.nn.softmax(jnp.where(causal, attn.scores(q, k), NEG), axis=-1)
    return attn.mix(weights, v)


def decode_attend(attn: Attention, q, cache_k, cache_v, pos, kernel: bool) -> jax.Array:
    """One token a row over its own cache's slots 0..pos: q [envs, 1, heads, D]
    turned, cache_k and cache_v [envs, S_max, kv heads, D] -> [envs, 1, heads * D].
    The kernel reads only the blocks that hold those slots; the XLA twin reads
    every slot and masks the ones past `pos`."""
    if kernel:
        return pk.decode_attention(q[:, 0], cache_k, cache_v, pos)[:, None]
    seen = jnp.arange(cache_k.shape[1])[None, :] <= pos[:, None]  # [envs, S_max]
    s = jnp.where(seen[:, None, None, None, :], attn.scores(q, cache_k), NEG)
    return attn.mix(jax.nn.softmax(s, axis=-1), cache_v)


def _refusal(attn: Attention, s_max: int) -> str | None:
    return pk.decode_attention_refusal(attn.num_heads, attn.num_kv_heads, s_max, attn.head_dim)


def read_block(attn: Attention, s_max: int) -> int:
    """The slots a policy step reads a row's cache in: the kernel's block where
    the gate and the shape admit it, else all S_max (the twin reads every
    slot). Records nothing: the host's count of what `decode` traces, on one
    device (where no partitioned jit refuses the kernel)."""
    return pk.DECODE_BLOCK if pk.pallas_enabled("decode_attn") and _refusal(attn, s_max) is None else s_max


def exit_loop(lams: jax.Array, threshold: float) -> jax.Array:
    """lams [R, ...] float32 gate values -> [...] int32, the exit loop from 0:
    the first t with sum_{j<=t} p_j >= threshold, R - 1 where none is."""
    R = lams.shape[0]
    stay = jnp.cumprod(1.0 - lams, axis=0)  # prod_{j<=t}(1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(lams[:1]), stay[:-1]], axis=0)  # prod_{j<t}
    p = jnp.concatenate([lams[:-1] * before[:-1], before[-1:]], axis=0)
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), R - 1).astype(jnp.int32)


class LMState(nn.Module):
    cache_k: tuple  # R x N arrays, loop-major ([t * N + l]): [envs, S_max, kv heads, D]
    cache_v: tuple
    last: jax.Array  # [envs] the token a policy step feeds: the prompt's last, then each sampled one
    pos: jax.Array  # [envs] its position; cache slots below it hold the sequence's keys and values


class LoopedLM(nn.Module):
    embed: jax.Array  # [vocab, hidden]
    layers: tuple  # of Layer: a layer's weights are arrays of their own, which a program reads where they lie
    final_norm: RMSNorm
    gate_w: jax.Array  # [hidden, 1] the exit gate
    gate_b: jax.Array  # [1]
    lm_head: jax.Array  # [hidden, vocab]
    total_ut_steps: int = nn.static(default=4)
    early_exit_threshold: float = nn.static(default=1.0)

    def loop_end(self, h: jax.Array):
        """The end of a pass of the stack: -> (h normed, which the next loop reads and the head may, lambda float32)."""
        h = self.final_norm(h)
        with jax.named_scope("lm/exit_gate"):
            lam = jax.nn.sigmoid(h.astype(jnp.float32) @ self.gate_w.astype(jnp.float32) + self.gate_b.astype(jnp.float32))[..., 0]
        return h, lam

    def picked(self, hs: list, lams: list) -> tuple[jax.Array, jax.Array]:
        """-> (each token's hidden at its exit loop, the exit loop from 0)."""
        exits = exit_loop(jax.lax.stop_gradient(jnp.stack(lams)), self.early_exit_threshold)
        h = hs[-1]
        for t in range(len(hs) - 2, -1, -1):
            h = jnp.where((exits == t)[..., None], hs[t], h)
        return h, exits

    def logits(self, h: jax.Array) -> jax.Array:
        with jax.named_scope("lm/head"):
            return (h @ self.lm_head.astype(h.dtype)).astype(jnp.float32)

    # ---- whole sequences -------------------------------------------------------
    def hidden(self, ids: jax.Array, dtype, keep_kv: bool = False):
        """ids [B, S], causal, positions 0..S-1 -> (each token's hidden at its
        exit loop [B, S, hidden], the exit loop [B, S], every (loop, layer)'s keys
        and values loop-major or None). A (loop, layer) is recomputed in the
        backward pass, and in it one sequence's attention at a time."""
        positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        h = self.embed.astype(dtype)[ids]

        def block(h, layer: Layer):
            with jax.named_scope("lm/attn"):
                q, k, v = layer.attn.qkv(layer.input_norm(h), positions)
                a = jax.lax.map(jax.checkpoint(lambda qkv: causal_attend(layer.attn, *qkv)), (q, k, v))
                h = h + layer.attn_out_norm(layer.attn.out(a))
            return layer.mlp(h), ((k, v) if keep_kv else None)

        hs, lams, kvs = [], [], []
        for t in range(self.total_ut_steps):
            with jax.named_scope(f"lm/loop{t}"):
                for layer in self.layers:
                    h, kv = jax.checkpoint(block)(h, layer)
                    kvs.append(kv)
                h, lam = self.loop_end(h)
            hs.append(h)
            lams.append(lam)
        h, exits = self.picked(hs, lams)
        return h, exits, kvs

    # ---- the player's programs ----------------------------------------------
    def init_states(self, num_envs: int, s_max: int, dtype) -> LMState:
        attn = self.layers[0].attn
        n = self.total_ut_steps * len(self.layers)
        zeros = lambda: tuple(jnp.zeros((num_envs, s_max, attn.num_kv_heads, attn.head_dim), dtype) for _ in range(n))
        return LMState(cache_k=zeros(), cache_v=zeros(), last=jnp.zeros((num_envs,), jnp.int32), pos=jnp.zeros((num_envs,), jnp.int32))

    def decode(self, state: LMState):
        """Feed each environment's `last` token at `pos`: its keys and values go
        into every (loop, layer)'s cache at `pos`, and each (loop, layer) attends
        its own cache's slots up to `pos`. -> (the new state, position moved on,
        the exit loop's logits [envs, vocab] float32, the exit loop [envs])."""
        dtype = state.cache_k[0].dtype
        n = state.pos.shape[0]
        h = self.embed.astype(dtype)[state.last][:, None]  # [envs, 1, hidden]
        positions = state.pos[:, None]
        why = _refusal(self.layers[0].attn, state.cache_k[0].shape[1])
        kernel = pk.use_pallas("decode_attn", self, state) and pk.select("decode_attn", why is None, detail=why, block=pk.DECODE_BLOCK)
        rows = jnp.arange(n)
        cache_k, cache_v = list(state.cache_k), list(state.cache_v)
        hs, lams = [], []
        for t in range(self.total_ut_steps):
            with jax.named_scope(f"lm/loop{t}"):
                for i, layer in enumerate(self.layers):
                    c = t * len(self.layers) + i
                    with jax.named_scope("lm/attn"):
                        q, k, v = layer.attn.qkv(layer.input_norm(h), positions)
                        cache_k[c] = cache_k[c].at[rows, state.pos].set(k[:, 0], mode="drop")
                        cache_v[c] = cache_v[c].at[rows, state.pos].set(v[:, 0], mode="drop")
                        a = decode_attend(layer.attn, q, cache_k[c], cache_v[c], state.pos, kernel)
                        h = h + layer.attn_out_norm(layer.attn.out(a))
                    h = layer.mlp(h)
                h, lam = self.loop_end(h)
            hs.append(h)
            lams.append(lam)
        h, exits = self.picked(hs, lams)
        new = LMState(cache_k=tuple(cache_k), cache_v=tuple(cache_v), last=state.last, pos=state.pos + 1)
        return new, self.logits(h[:, 0]), exits[:, 0]

    def prefill(self, state: LMState, prompts, lengths, env_idx) -> LMState:
        """Write the prompts' keys and values in one pass: prompts [G, P_max]
        padded, lengths [G], env_idx [G] the slots (an index past the last
        environment writes nothing). Every loop's cache gets its own; the last
        prompt token is left for the first policy step to feed. Positions past
        a prompt write slots nothing reads before a policy step writes them."""
        _, _, kvs = self.hidden(prompts, state.cache_k[0].dtype, keep_kv=True)
        slots = jnp.arange(prompts.shape[1])
        rows = env_idx[:, None]
        last = jnp.take_along_axis(prompts, (lengths - 1)[:, None], axis=1)[:, 0]
        return LMState(
            cache_k=tuple(c.at[rows, slots].set(k, mode="drop") for c, (k, _) in zip(state.cache_k, kvs)),
            cache_v=tuple(c.at[rows, slots].set(v, mode="drop") for c, (_, v) in zip(state.cache_v, kvs)),
            last=state.last.at[env_idx].set(last, mode="drop"), pos=state.pos.at[env_idx].set(lengths - 1, mode="drop"),
        )


def build_policy(key, args, vocab_size: int) -> LoopedLM:
    k_embed, k_layers, k_gate, k_head = jax.random.split(key, 4)
    h = args.hidden_size
    return LoopedLM(
        embed=jax.random.normal(k_embed, (vocab_size, h), jnp.float32),
        layers=tuple(Layer.init(k, args) for k in jax.random.split(k_layers, args.num_hidden_layers)),
        final_norm=RMSNorm.init(h, eps=args.rms_norm_eps),
        gate_w=_dense(k_gate, h, 1), gate_b=jnp.zeros((1,), jnp.float32),
        lm_head=_dense(k_head, h, vocab_size),
        total_ut_steps=args.total_ut_steps, early_exit_threshold=float(args.early_exit_threshold),
    )


def player_copy(model: LoopedLM, dtype) -> LoopedLM:
    """The copy the policy steps read: every matrix in the compute dtype; the
    norm scales and the exit gate stay float32 (they are read in float32).
    Every leaf is a buffer of its own (the train step donates both trees)."""
    def cast(path, leaf):
        name = getattr(path[-1], "name", "")
        return jnp.copy(leaf) if name in ("scale", "gate_w", "gate_b") or leaf.dtype == dtype else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, model)
