"""Composite blocks: MLP, CNN, DeCNN, NatureCNN, MultiEncoder/MultiDecoder.

Functional equivalents of the reference's miniblock machinery
(/root/reference/sheeprl/models/models.py:15-327, utils/model.py:24-222):
each block is a stack of (linear|conv) -> norm -> activation [-> dropout]
miniblocks. Dropout is pure (keys threaded explicitly).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from .core import Activation, Module, activation, static
from .layers import Conv2d, ConvTranspose2d, LayerNorm, Linear, dropout

__all__ = ["MLP", "CNN", "DeCNN", "NatureCNN", "MultiEncoder", "MultiDecoder"]


def _split(key, n):
    return jax.random.split(key, n) if n > 0 else []


def _fold_rows(x: jax.Array):
    """Fold leading dims of `[lead..., H, W, C]` into conv rows; returns the
    rows and the inverse. A `[T, B]` sequence batch folds BATCH-major: under
    context parallelism the input is `("seq", "data")`-sharded, and batch-
    major rows are contiguously sharded over the full mesh grid
    (`P(("data", "seq"))`) so the convs parallelize over every device — the
    time-major fold interleaves the shards, which GSPMD can only represent
    by replicating the convs over "data" (observed in the dp x sp DV3 step,
    round 3). The swap is sharding metadata plus a local relayout; numerics
    are unchanged (each (t, b) row maps through the same convolution)."""
    lead = x.shape[:-3]
    if len(lead) == 2:
        x = jnp.swapaxes(x, 0, 1)
    rows = x.reshape((-1,) + x.shape[-3:])

    def unfold(y: jax.Array) -> jax.Array:
        if len(lead) == 2:
            t, b = lead
            return jnp.swapaxes(y.reshape((b, t) + y.shape[1:]), 0, 1)
        return y.reshape(lead + y.shape[1:])

    return rows, unfold


class MLP(Module):
    """Linear stack with optional per-layer LayerNorm / dropout and output head.

    Mirrors the capability of the reference MLP
    (/root/reference/sheeprl/models/models.py:15-118): hidden miniblocks are
    Linear -> [dropout] -> [LayerNorm] -> act (the reference miniblock order,
    utils/model.py:70-87 — the DroQ-paper critic layout); the optional output
    head is a bare Linear.
    """

    layers: tuple[Linear, ...]
    norms: tuple[LayerNorm | None, ...]
    head: Linear | None
    act: Activation = static(default="tanh")
    dropout_rate: float = static(default=0.0)

    @classmethod
    def init(
        cls,
        key,
        input_dim: int,
        hidden_sizes: Sequence[int],
        output_dim: int | None = None,
        *,
        act: Activation = "tanh",
        layer_norm: bool = False,
        dropout_rate: float = 0.0,
        use_bias: bool = True,
        norm_eps: float = 1e-5,
    ):
        sizes = [input_dim, *hidden_sizes]
        keys = _split(key, len(hidden_sizes) + 1)
        layers = tuple(
            Linear.init(k, sizes[i], sizes[i + 1], use_bias=use_bias)
            for i, k in enumerate(keys[: len(hidden_sizes)])
        )
        norms = tuple(
            LayerNorm.init(s, eps=norm_eps) if layer_norm else None for s in sizes[1:]
        )
        head = None
        if output_dim is not None:
            head = Linear.init(keys[-1], sizes[-1], output_dim)
        return cls(
            layers=layers, norms=norms, head=head, act=act, dropout_rate=dropout_rate
        )

    def __call__(self, x: jax.Array, *, key=None, training: bool = False):
        act = activation(self.act)
        for i, layer in enumerate(self.layers):
            x = layer(x)
            if self.dropout_rate > 0.0 and training and key is not None:
                key, sub = jax.random.split(key)
                x = dropout(sub, x, self.dropout_rate)
            if self.norms[i] is not None:
                x = self.norms[i](x)
            x = act(x)
        if self.head is not None:
            x = self.head(x)
        return x

    @property
    def output_dim(self) -> int:
        if self.head is not None:
            return self.head.out_features
        return self.layers[-1].out_features


class CNN(Module):
    """Conv2d stack (NHWC): conv -> [LayerNorm over channels] -> act."""

    layers: tuple[Conv2d, ...]
    norms: tuple[LayerNorm | None, ...]
    act: Activation = static(default="relu")

    @classmethod
    def init(
        cls,
        key,
        in_channels: int,
        channels: Sequence[int],
        kernel_sizes: Sequence[int],
        strides: Sequence[int],
        *,
        paddings: Sequence[str | int] | None = None,
        act: Activation = "relu",
        layer_norm: bool = False,
        use_bias: bool = True,
        norm_eps: float = 1e-5,
    ):
        n = len(channels)
        if paddings is None:
            paddings = ["SAME"] * n
        chans = [in_channels, *channels]
        keys = _split(key, n)
        layers = tuple(
            Conv2d.init(
                keys[i],
                chans[i],
                chans[i + 1],
                kernel_sizes[i],
                stride=strides[i],
                padding=paddings[i],
                use_bias=use_bias,
            )
            for i in range(n)
        )
        norms = tuple(
            LayerNorm.init(c, eps=norm_eps) if layer_norm else None for c in channels
        )
        return cls(layers=layers, norms=norms, act=act)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: [..., H, W, C] — leading batch dims are folded around the convs
        (batch-major for sequence batches, see _fold_rows)."""
        x, unfold = _fold_rows(x)
        act = activation(self.act)
        for i, layer in enumerate(self.layers):
            norm = self.norms[i]
            x = layer(x)
            if norm is not None:
                x = norm(x)
            x = act(x)
        return unfold(x)


class DeCNN(Module):
    """ConvTranspose2d stack (NHWC). By default the last layer has no
    norm/activation (decoder-output convention); `act_last=True` activates
    every layer like the reference DeCNN (models.py:204-287), for use as an
    inner trunk (e.g. the SAC-AE decoder)."""

    layers: tuple[ConvTranspose2d, ...]
    norms: tuple[LayerNorm | None, ...]
    act: Activation = static(default="relu")
    act_last: bool = static(default=False)

    @classmethod
    def init(
        cls,
        key,
        in_channels: int,
        channels: Sequence[int],
        kernel_sizes: Sequence[int],
        strides: Sequence[int],
        *,
        paddings: Sequence[str | int] | None = None,
        act: Activation = "relu",
        layer_norm: bool = False,
        use_bias: bool = True,
        act_last: bool = False,
        norm_eps: float = 1e-5,
    ):
        n = len(channels)
        if paddings is None:
            paddings = ["SAME"] * n
        chans = [in_channels, *channels]
        keys = _split(key, n)
        layers = tuple(
            ConvTranspose2d.init(
                keys[i],
                chans[i],
                chans[i + 1],
                kernel_sizes[i],
                stride=strides[i],
                padding=paddings[i],
                use_bias=use_bias,
            )
            for i in range(n)
        )
        # norm/act after the final deconv only when act_last
        norms = tuple(
            LayerNorm.init(c, eps=norm_eps)
            if (layer_norm and (act_last or i < n - 1))
            else None
            for i, c in enumerate(channels)
        )
        return cls(layers=layers, norms=norms, act=act, act_last=act_last)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: [..., H, W, C] latent grid -> [..., H', W', C'] image
        (leading dims folded batch-major, see _fold_rows)."""
        x, unfold = _fold_rows(x)
        act = activation(self.act)
        last = len(self.layers) - 1
        for i, layer in enumerate(self.layers):
            norm = self.norms[i]
            x = layer(x)
            if norm is not None:
                x = norm(x)
            if i != last or self.act_last:
                x = act(x)
        return unfold(x)


class NatureCNN(Module):
    """DQN-Nature encoder (3 convs + fc), NHWC
    (/root/reference/sheeprl/models/models.py:287-327)."""

    cnn: CNN
    fc: Linear
    act: Activation = static(default="relu")

    @classmethod
    def init(
        cls,
        key,
        in_channels: int,
        features_dim: int,
        *,
        screen_size: int = 64,
        channels_multiplier: int = 1,
    ):
        if channels_multiplier <= 0:
            raise ValueError(
                f"channels_multiplier must be greater than zero, given {channels_multiplier}"
            )
        ckey, fkey = jax.random.split(key)
        cnn = CNN.init(
            ckey,
            in_channels,
            channels=[32 * channels_multiplier, 64 * channels_multiplier, 64 * channels_multiplier],
            kernel_sizes=[8, 4, 3],
            strides=[4, 2, 1],
            paddings=["VALID"] * 3,
            act="relu",
        )
        # probe the flattened conv output size without running real compute
        probe = jax.eval_shape(
            cnn, jax.ShapeDtypeStruct((1, screen_size, screen_size, in_channels), jnp.float32)
        )
        flat = math.prod(probe.shape[1:])
        fc = Linear.init(fkey, flat, features_dim)
        return cls(cnn=cnn, fc=fc)

    def __call__(self, x: jax.Array) -> jax.Array:
        lead = x.shape[:-3]
        y = self.cnn(x)
        y = y.reshape(lead + (-1,))
        return activation(self.act)(self.fc(y))

    @property
    def output_dim(self) -> int:
        return self.fc.out_features


class MultiEncoder(Module):
    """Fuse a CNN encoder (over channel-concatenated image keys) and an MLP
    encoder (over feature-concatenated vector keys) of a dict observation
    (/root/reference/sheeprl/models/models.py:405-460). Either may be None."""

    cnn_encoder: Module | None
    mlp_encoder: Module | None
    cnn_keys: tuple[str, ...] = static(default=())
    mlp_keys: tuple[str, ...] = static(default=())

    def __call__(self, obs: dict, **kwargs) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None:
            cnn_in = jnp.concatenate([obs[k] for k in self.cnn_keys], axis=-1)
            feats.append(self.cnn_encoder(cnn_in))
        if self.mlp_encoder is not None:
            mlp_in = jnp.concatenate([obs[k] for k in self.mlp_keys], axis=-1)
            feats.append(self.mlp_encoder(mlp_in, **kwargs))
        return jnp.concatenate(feats, axis=-1)


class MultiDecoder(Module):
    """Per-key reconstruction heads over a latent: a deconv trunk whose output
    channels are split across image keys, and per-key MLP heads for vectors
    (/root/reference/sheeprl/models/models.py:463-489)."""

    cnn_decoder: Module | None
    mlp_decoder: Module | None
    mlp_heads: dict[str, Linear]
    cnn_keys: tuple[str, ...] = static(default=())
    mlp_keys: tuple[str, ...] = static(default=())
    cnn_channels: tuple[int, ...] = static(default=())

    def __call__(self, latent: jax.Array, **kwargs) -> dict:
        out: dict = {}
        if self.cnn_decoder is not None:
            img = self.cnn_decoder(latent)
            splits = jnp.split(img, jnp.cumsum(jnp.array(self.cnn_channels))[:-1], axis=-1)
            out.update(dict(zip(self.cnn_keys, splits)))
        if self.mlp_decoder is not None:
            trunk = self.mlp_decoder(latent, **kwargs)
            for k in self.mlp_keys:
                out[k] = self.mlp_heads[k](trunk)
        return out
