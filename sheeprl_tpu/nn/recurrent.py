"""Recurrent cells: GRU (plain + LayerNorm/Hafner variant) and LSTM.

These are the sequence workhorses of the framework — the reference has no
attention anywhere; its sequence models are a LayerNorm-GRU (DreamerV1-3,
/root/reference/sheeprl/models/models.py:330-402) and an LSTM (recurrent PPO,
/root/reference/sheeprl/algos/ppo_recurrent/agent.py:41). Cells here are
single-step pure functions designed to be the body of `jax.lax.scan` over
time, with batch sharded across the device mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .core import Module, static
from .layers import LayerNorm, Linear

__all__ = ["GRUCell", "LayerNormGRUCell", "LSTMCell", "scan_cell"]


class GRUCell(Module):
    """Standard (textbook / torch.nn.GRUCell) GRU: the reset gate scales only
    the hidden-state contribution of the candidate,
    `n = tanh(W_in x + r * (W_hn h))`."""

    input_proj: Linear  # [in, 3*hidden]
    hidden_proj: Linear  # [hidden, 3*hidden]
    hidden_size: int = static()

    @classmethod
    def init(cls, key, input_size: int, hidden_size: int, *, use_bias: bool = True):
        k1, k2 = jax.random.split(key)
        input_proj = Linear.init(k1, input_size, 3 * hidden_size, use_bias=use_bias)
        hidden_proj = Linear.init(k2, hidden_size, 3 * hidden_size, use_bias=use_bias)
        return cls(input_proj=input_proj, hidden_proj=hidden_proj, hidden_size=hidden_size)

    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        xi_r, xi_z, xi_n = jnp.split(self.input_proj(x), 3, axis=-1)
        hh_r, hh_z, hh_n = jnp.split(self.hidden_proj(h), 3, axis=-1)
        r = jax.nn.sigmoid(xi_r + hh_r)
        z = jax.nn.sigmoid(xi_z + hh_z)
        n = jnp.tanh(xi_n + r * hh_n)
        return (1.0 - z) * n + z * h


class LayerNormGRUCell(Module):
    """GRU with LayerNorm on the fused projection and the `sigmoid(u - 1)`
    update-gate bias trick — the DreamerV2/V3 recurrence
    (/root/reference/sheeprl/models/models.py:330-402). The fused
    [x,h] @ W projection is a single MXU matmul; the gate math is elementwise
    and fuses into it under XLA."""

    proj: Linear
    norm: LayerNorm | None
    hidden_size: int = static()

    @classmethod
    def init(
        cls,
        key,
        input_size: int,
        hidden_size: int,
        *,
        layer_norm: bool = True,
        use_bias: bool = False,
    ):
        proj = Linear.init(key, input_size + hidden_size, 3 * hidden_size, use_bias=use_bias)
        norm = LayerNorm.init(3 * hidden_size) if layer_norm else None
        return cls(proj=proj, norm=norm, hidden_size=hidden_size)

    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        from ..ops.pallas_kernels import layernorm_gru_cell, select, use_pallas

        fits = (
            self.norm is not None
            and self.norm.scale is not None
            and self.proj.bias is None
            and x.ndim == 2
        )
        if use_pallas("gru", x, h, self.proj.weight) and select("gru", fits):
            return layernorm_gru_cell(
                x,
                h,
                # weights follow the input dtype (bf16 compute with f32
                # master params, like the plain-XLA Linear path); LN affine
                # params stay f32 — the kernel normalizes in f32 regardless
                self.proj.weight.astype(x.dtype),
                self.norm.scale,
                self.norm.offset,
                self.norm.eps,
            )
        parts = self.proj(jnp.concatenate([x, h], axis=-1))
        if self.norm is not None:
            parts = self.norm(parts)
        r, c, u = jnp.split(parts, 3, axis=-1)
        reset = jax.nn.sigmoid(r)
        cand = jnp.tanh(reset * c)
        update = jax.nn.sigmoid(u - 1.0)
        return update * cand + (1.0 - update) * h


class LSTMCell(Module):
    """Standard LSTM cell; state is an (h, c) tuple."""

    proj: Linear  # [in+hidden, 4*hidden]
    hidden_size: int = static()

    @classmethod
    def init(cls, key, input_size: int, hidden_size: int, *, use_bias: bool = True):
        proj = Linear.init(key, input_size + hidden_size, 4 * hidden_size, use_bias=use_bias)
        return cls(proj=proj, hidden_size=hidden_size)

    def __call__(self, x: jax.Array, state: tuple[jax.Array, jax.Array]):
        h, c = state
        parts = self.proj(jnp.concatenate([x, h], axis=-1))
        i, f, g, o = jnp.split(parts, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)

    def initial_state(self, batch_shape: tuple[int, ...]) -> tuple[jax.Array, jax.Array]:
        z = jnp.zeros(batch_shape + (self.hidden_size,))
        return z, z


def scan_cell(cell, xs: jax.Array, h0, *, reset_mask: jax.Array | None = None):
    """Run a cell over time with `lax.scan`.

    xs: [T, B, D] inputs; h0: initial state pytree; reset_mask: optional
    [T, B] bool/float — where True the state is zeroed *before* the step
    (the `is_first` semantics of the Dreamer RSSM,
    /root/reference/sheeprl/algos/dreamer_v3/agent.py:373-378).
    Returns (final_state, stacked_outputs [T, B, H]).
    """

    def step(h, inp):
        if reset_mask is None:
            x = inp
        else:
            x, m = inp
            # keep the reset arithmetic in each state leaf's dtype — a f32
            # mask would promote a bf16 carry and destabilize the scan
            m = m[..., None]
            h = jax.tree_util.tree_map(
                lambda s: s * (1.0 - m.astype(s.dtype)), h
            )
        out = cell(x, h)
        # GRU cells return the new state directly; LSTM returns (out, state)
        if isinstance(out, tuple):
            y, h_new = out
        else:
            y, h_new = out, out
        return h_new, y

    inputs = xs if reset_mask is None else (xs, reset_mask)
    return jax.lax.scan(step, h0, inputs)
