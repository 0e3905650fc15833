"""DreamerV3 agent: world model (encoder / RSSM / decoder / reward / continue),
actor, critic and the environment-interaction player.

Capability parity with /root/reference/sheeprl/algos/dreamer_v3/agent.py.
TPU-first deviations:
  - every model is a frozen pytree Module; the whole train step (world-model
    scan, imagination, three optimizer updates, EMA) compiles to ONE XLA
    program (the reference runs a Python loop over T with per-step kernel
    launches, dreamer_v3.py:117-124);
  - the RSSM `dynamic` sequence runs under `jax.lax.scan` with the
    `is_first` state resets expressed as masked arithmetic inside the scan
    body (reference per-step masking, agent.py:373-378);
  - convolutions are NHWC (native TPU layout); the reference's
    `LayerNormChannelLast` permutation shim disappears;
  - the player is functional: its recurrent state is an explicit
    `PlayerState` pytree threaded through a jitted step, instead of module
    attributes mutated under `torch.no_grad` (agent.py:500-583).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.scan import checkpoint_body, scan_unroll
from ... import nn
from ...nn.inits import init_xavier
from ...ops.distributions import (
    Bernoulli,
    Independent,
    Normal,
    OneHotCategorical,
    TanhNormal,
    TruncatedNormal,
    unimix_logits,
)
from ...ops.math import symlog

__all__ = [
    "CNNEncoder",
    "MLPEncoder",
    "CNNDecoder",
    "MLPDecoder",
    "Encoder",
    "Decoder",
    "RecurrentModel",
    "RSSM",
    "Actor",
    "MinedojoActor",
    "WorldModel",
    "PlayerState",
    "PlayerDV3",
    "compute_stochastic_state",
    "build_models",
]


def compute_stochastic_state(
    logits: jax.Array, discrete: int, key=None
) -> jax.Array:
    """Sample the straight-through one-hot stochastic state from flat logits
    `[..., S*D]` -> `[..., S, D]`; mode when `key` is None
    (/root/reference/sheeprl/algos/dreamer_v2/utils.py:21-38)."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = OneHotCategorical.from_logits(logits)
    return dist.rsample(key) if key is not None else dist.mode


class CNNEncoder(nn.Module):
    """4-stage stride-2 conv encoder 64x64 -> 4x4, channels [1,2,4,8] x
    multiplier, LayerNorm(eps=1e-3) + SiLU (reference agent.py:31-81).
    Image keys are concatenated on the channel axis."""

    model: nn.CNN
    keys: tuple[str, ...] = nn.static(default=())
    output_dim: int = nn.static(default=0)

    @classmethod
    def init(
        cls,
        key,
        keys: Sequence[str],
        input_channels: int,
        image_size: tuple[int, int],
        channels_multiplier: int,
        *,
        layer_norm: bool = True,
        activation: str = "silu",
    ):
        model = nn.CNN.init(
            key,
            input_channels,
            channels=[channels_multiplier * m for m in (1, 2, 4, 8)],
            kernel_sizes=[4] * 4,
            strides=[2] * 4,
            act=activation,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        probe = jax.eval_shape(
            model,
            jax.ShapeDtypeStruct((1, *image_size, input_channels), jnp.float32),
        )
        return cls(model=model, keys=tuple(keys), output_dim=math.prod(probe.shape[1:]))

    def __call__(self, obs: dict) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        y = self.model(x)
        return y.reshape(*y.shape[:-3], -1)


class MLPEncoder(nn.Module):
    """Vector encoder with symlog-squashed inputs (reference agent.py:84-134)."""

    model: nn.MLP
    keys: tuple[str, ...] = nn.static(default=())
    symlog_inputs: bool = nn.static(default=True)

    @classmethod
    def init(
        cls,
        key,
        keys: Sequence[str],
        input_dim: int,
        *,
        mlp_layers: int = 4,
        dense_units: int = 512,
        layer_norm: bool = True,
        activation: str = "silu",
        symlog_inputs: bool = True,
    ):
        model = nn.MLP.init(
            key,
            input_dim,
            [dense_units] * mlp_layers,
            act=activation,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        return cls(model=model, keys=tuple(keys), symlog_inputs=symlog_inputs)

    @property
    def output_dim(self) -> int:
        return self.model.output_dim

    def __call__(self, obs: dict) -> jax.Array:
        x = jnp.concatenate(
            [symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys],
            axis=-1,
        )
        return self.model(x)


class Encoder(nn.Module):
    """Fused CNN+MLP encoder over the dict observation; either may be None."""

    cnn_encoder: CNNEncoder | None
    mlp_encoder: MLPEncoder | None

    @property
    def output_dim(self) -> int:
        dim = 0
        if self.cnn_encoder is not None:
            dim += self.cnn_encoder.output_dim
        if self.mlp_encoder is not None:
            dim += self.mlp_encoder.output_dim
        return dim

    def __call__(self, obs: dict) -> jax.Array:
        feats = []
        if self.cnn_encoder is not None:
            feats.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            feats.append(self.mlp_encoder(obs))
        return jnp.concatenate(feats, axis=-1)


class CNNDecoder(nn.Module):
    """Inverse of CNNEncoder: latent -> Linear -> [4,4,8m] -> 4 deconv stages
    -> 64x64 image dict, `+ 0.5` output shift (reference agent.py:137-203)."""

    proj: nn.Linear
    model: nn.DeCNN
    keys: tuple[str, ...] = nn.static(default=())
    output_channels: tuple[int, ...] = nn.static(default=())

    @classmethod
    def init(
        cls,
        key,
        keys: Sequence[str],
        output_channels: Sequence[int],
        channels_multiplier: int,
        latent_state_size: int,
        cnn_encoder_output_dim: int,
        *,
        layer_norm: bool = True,
        activation: str = "silu",
    ):
        k_proj, k_cnn, k_last = jax.random.split(key, 3)
        proj = nn.Linear.init(k_proj, latent_state_size, cnn_encoder_output_dim)
        model = nn.DeCNN.init(
            k_cnn,
            8 * channels_multiplier,
            channels=[channels_multiplier * m for m in (4, 2, 1)] + [sum(output_channels)],
            kernel_sizes=[4] * 4,
            strides=[2] * 4,
            act=activation,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        if layer_norm:
            # the final deconv keeps its bias even when LN is on elsewhere
            # (reference agent.py:184-189: last layer_args has default bias)
            last = nn.ConvTranspose2d.init(
                k_last,
                model.layers[-1].kernel.shape[2],
                model.layers[-1].kernel.shape[3],
                4,
                stride=2,
                padding="SAME",
                use_bias=True,
            )
            model = model.replace(layers=(*model.layers[:-1], last))
        return cls(
            proj=proj,
            model=model,
            keys=tuple(keys),
            output_channels=tuple(output_channels),
        )

    def __call__(self, latent: jax.Array) -> dict:
        x = self.proj(latent)
        x = x.reshape(*x.shape[:-1], 4, 4, -1)
        img = self.model(x) + 0.5
        splits = jnp.split(img, np.cumsum(self.output_channels)[:-1], axis=-1)
        return dict(zip(self.keys, splits))


class MLPDecoder(nn.Module):
    """Per-key vector reconstruction heads over a shared MLP trunk
    (reference agent.py:206-254)."""

    model: nn.MLP
    heads: dict[str, nn.Linear]
    keys: tuple[str, ...] = nn.static(default=())

    @classmethod
    def init(
        cls,
        key,
        keys: Sequence[str],
        output_dims: Sequence[int],
        latent_state_size: int,
        *,
        mlp_layers: int = 4,
        dense_units: int = 512,
        layer_norm: bool = True,
        activation: str = "silu",
    ):
        k_trunk, *k_heads = jax.random.split(key, len(keys) + 1)
        model = nn.MLP.init(
            k_trunk,
            latent_state_size,
            [dense_units] * mlp_layers,
            act=activation,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        heads = {
            k: nn.Linear.init(hk, dense_units, dim)
            for k, dim, hk in zip(keys, output_dims, k_heads)
        }
        return cls(model=model, heads=heads, keys=tuple(keys))

    def __call__(self, latent: jax.Array) -> dict:
        x = self.model(latent)
        return {k: self.heads[k](x) for k in self.keys}


class Decoder(nn.Module):
    """The observation model: merges per-key CNN and MLP reconstructions."""

    cnn_decoder: CNNDecoder | None
    mlp_decoder: MLPDecoder | None

    def __call__(self, latent: jax.Array) -> dict:
        out: dict = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latent))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latent))
        return out


class RecurrentModel(nn.Module):
    """Dense pre-projection + LayerNorm-GRU — the deterministic-state update
    (reference agent.py:257-306)."""

    mlp: nn.MLP
    rnn: nn.LayerNormGRUCell

    @classmethod
    def init(
        cls,
        key,
        input_size: int,
        recurrent_state_size: int,
        dense_units: int,
        *,
        layer_norm: bool = True,
        activation: str = "silu",
    ):
        k_mlp, k_rnn = jax.random.split(key)
        mlp = nn.MLP.init(
            k_mlp,
            input_size,
            [dense_units],
            act=activation,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        rnn = nn.LayerNormGRUCell.init(
            k_rnn, dense_units, recurrent_state_size, layer_norm=True, use_bias=False
        )
        return cls(mlp=mlp, rnn=rnn)

    def __call__(self, x: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        return self.rnn(self.mlp(x), recurrent_state)


class RSSM(nn.Module):
    """Recurrent State-Space Model with discrete (S x D) stochastic state,
    1% unimix, and `is_first` episode-boundary resets
    (reference agent.py:309-445)."""

    recurrent_model: RecurrentModel
    representation_model: nn.MLP
    transition_model: nn.MLP
    discrete: int = nn.static(default=32)
    unimix: float = nn.static(default=0.01)

    def _uniform_mix(self, logits: jax.Array) -> jax.Array:
        shaped = logits.reshape(*logits.shape[:-1], -1, self.discrete)
        mixed = unimix_logits(shaped, self.unimix)
        return mixed.reshape(logits.shape)

    def _mix_sample(self, raw: jax.Array, key, out_dtype):
        """Raw head output -> (unimixed f32 logits, sampled one-hot state in
        the compute dtype). The fp32 island shared by the plain-XLA heads
        and the fused Pallas step (which emits raw logits already in f32)."""
        logits = self._uniform_mix(raw.astype(jnp.float32))
        state = compute_stochastic_state(logits, self.discrete, key)
        return logits, state.astype(out_dtype)

    def _transition(self, recurrent_out: jax.Array, key=None):
        """-> (prior_logits [..., S*D], prior [..., S, D]); mode when key=None.

        Logits/unimix/sampling run in f32 even under bf16 compute (the KL and
        straight-through gradients need the precision); the sampled one-hot
        state is cast back to the compute dtype for the recurrent path."""
        return self._mix_sample(
            self.transition_model(recurrent_out), key, recurrent_out.dtype
        )

    def _representation(self, recurrent_state: jax.Array, embedded_obs: jax.Array, key=None):
        return self._mix_sample(
            self.representation_model(
                jnp.concatenate([recurrent_state, embedded_obs], axis=-1)
            ),
            key,
            recurrent_state.dtype,
        )

    def _fused_step_weights(self, x: jax.Array, embedded_obs: jax.Array):
        """The fused-kernel weight tuple when this RSSM's module structure
        matches the kernel's contract (ops/pallas_kernels.fused_rssm_step),
        else None -> the caller stays on the plain-XLA path. Either way the
        decision is recorded (`kernel.select`, family rssm).

        Contract: single-hidden-layer LN MLPs without hidden biases (the
        DV3 `use_bias=not layer_norm` layout), a bias-free LN-GRU, one
        shared activation, and a weight set that fits the VMEM budget."""
        from ...ops.pallas_kernels import fused_rssm_supported, select, use_pallas

        if not use_pallas("rssm", x, embedded_obs, self.recurrent_model):
            return None
        rm, tm, pm = self.recurrent_model, self.transition_model, self.representation_model
        mlp = getattr(rm, "mlp", None)
        rnn = getattr(rm, "rnn", None)
        norm = getattr(rnn, "norm", None)

        def one_hidden(m):
            return (
                len(m.layers) == 1
                and m.norms[0] is not None
                and m.norms[0].scale is not None
                and m.layers[0].bias is None
            )

        fits = (
            x.ndim == 2
            and mlp is not None
            and rnn is not None
            and one_hidden(mlp) and one_hidden(tm) and one_hidden(pm)
            and mlp.head is None and tm.head is not None and pm.head is not None
            and tm.head.bias is not None and pm.head.bias is not None
            and norm is not None and norm.scale is not None
            and rnn.proj.bias is None
            and mlp.act == tm.act == pm.act
        )
        if not fits:
            select("rssm", False)
            return None
        dt = x.dtype
        weights = (
            mlp.layers[0].weight.astype(dt),
            mlp.norms[0].scale,
            mlp.norms[0].offset,
            rnn.proj.weight.astype(dt),
            norm.scale,
            norm.offset,
            tm.layers[0].weight.astype(dt),
            tm.norms[0].scale,
            tm.norms[0].offset,
            tm.head.weight.astype(dt),
            tm.head.bias,
            pm.layers[0].weight.astype(dt),
            pm.norms[0].scale,
            pm.norms[0].offset,
            pm.head.weight.astype(dt),
            pm.head.bias,
        )
        if not fused_rssm_supported(mlp.act or "identity", *weights):
            return None
        eps = (mlp.norms[0].eps, norm.eps, tm.norms[0].eps)
        return weights, (mlp.act or "identity"), eps

    def dynamic(
        self,
        posterior: jax.Array,  # [B, S, D]
        recurrent_state: jax.Array,  # [B, R]
        action: jax.Array,  # [B, A]
        embedded_obs: jax.Array,  # [B, E]
        is_first: jax.Array,  # [B, 1]
        key,
    ):
        """One dynamic-learning step (reference agent.py:344-382): where
        `is_first`, the action/recurrent state are zeroed and the posterior is
        re-seeded from the transition prior's mode."""
        k_prior, k_post = jax.random.split(key)
        # the recurrent carry's dtype is the compute dtype; keep every branch
        # of the reset arithmetic in it (a stray f32 would promote the chain)
        dt = recurrent_state.dtype
        is_first = is_first.astype(dt)
        action = (1.0 - is_first) * action.astype(dt)
        recurrent_state = (1.0 - is_first) * recurrent_state
        posterior_flat = posterior.astype(dt).reshape(*posterior.shape[:-2], -1)
        init_post = self._transition(recurrent_state, key=None)[1]
        init_post = init_post.reshape(posterior_flat.shape)
        posterior_flat = (1.0 - is_first) * posterior_flat + is_first * init_post
        x = jnp.concatenate([posterior_flat, action], axis=-1)
        fused = self._fused_step_weights(x, embedded_obs)
        if fused is not None:
            # fused Pallas step (ISSUE 9): pre-MLP + LN-GRU + both head
            # stacks in ONE kernel, VMEM-resident; raw logits come back in
            # f32 and share the same unimix/sampling island as the XLA path
            from ...ops.pallas_kernels import fused_rssm_step

            weights, act, eps = fused
            recurrent_state, prior_raw, post_raw = fused_rssm_step(
                x, recurrent_state, embedded_obs, *weights, act, eps
            )
            prior_logits, prior = self._mix_sample(
                prior_raw, k_prior, recurrent_state.dtype
            )
            posterior_logits, posterior = self._mix_sample(
                post_raw, k_post, recurrent_state.dtype
            )
        else:
            recurrent_state = self.recurrent_model(x, recurrent_state)
            prior_logits, prior = self._transition(recurrent_state, key=k_prior)
            posterior_logits, posterior = self._representation(
                recurrent_state, embedded_obs, key=k_post
            )
        return recurrent_state, posterior, prior, posterior_logits, prior_logits

    def scan_dynamic(
        self,
        posterior0: jax.Array,  # [B, S, D]
        recurrent0: jax.Array,  # [B, R]
        actions: jax.Array,  # [T, B, A]
        embedded_obs: jax.Array,  # [T, B, E]
        is_first: jax.Array,  # [T, B, 1]
        key,
        remat: bool = False,
    ):
        """The full dynamic-learning sequence as ONE `lax.scan` over time —
        the reference's Python loop (dreamer_v3.py:117-124) fused into a
        single compiled recurrence. Returns stacked
        (recurrent_states [T,B,R], priors_logits [T,B,S*D],
        posteriors [T,B,S,D], posteriors_logits [T,B,S*D]).

        `remat=True` rematerializes the step body on the backward pass
        (`jax.checkpoint`): per-step activations of the recurrent/transition/
        representation MLPs are recomputed instead of stored across all T
        steps — HBM footprint of the world-model backward drops from
        O(T x intermediates) to O(T x states), buying batch/sequence size at
        the cost of one extra forward."""
        keys = jax.random.split(key, actions.shape[0])

        def step(carry, inp):
            post, rec = carry
            a, emb, first, k = inp
            rec, post, _, post_logits, prior_logits = self.dynamic(
                post, rec, a, emb, first, k
            )
            return (post, rec), (rec, prior_logits, post, post_logits)

        step = checkpoint_body(step, remat)
        _, outs = jax.lax.scan(
            step,
            (posterior0, recurrent0),
            (actions, embedded_obs, is_first, keys),
            unroll=scan_unroll(),
        )
        return outs

    def imagination(self, prior: jax.Array, recurrent_state: jax.Array, actions: jax.Array, key):
        """One-step latent imagination (reference agent.py:429-445)."""
        recurrent_state = self.recurrent_model(
            jnp.concatenate([prior, actions], axis=-1), recurrent_state
        )
        _, imagined_prior = self._transition(recurrent_state, key=key)
        imagined_prior = imagined_prior.reshape(*imagined_prior.shape[:-2], -1)
        return imagined_prior, recurrent_state


class WorldModel(nn.Module):
    """Encoder + RSSM + observation/reward/continue heads
    (reference dreamer_v2/agent.py WorldModel container)."""

    encoder: Encoder
    rssm: RSSM
    observation_model: Decoder
    reward_model: nn.MLP
    continue_model: nn.MLP


class Actor(nn.Module):
    """DreamerV3 policy head (reference agent.py:586-723): MLP trunk + one
    head per discrete action space (unimix straight-through one-hot) or a
    single 2*A head for continuous control (`trunc_normal` default:
    `TruncatedNormal(tanh(mean), 2*sigmoid((std+init)/2)+min_std, -1, 1)`)."""

    model: nn.MLP
    heads: tuple[nn.Linear, ...]
    actions_dim: tuple[int, ...] = nn.static(default=())
    is_continuous: bool = nn.static(default=False)
    distribution: str = nn.static(default="auto")
    init_std: float = nn.static(default=0.0)
    min_std: float = nn.static(default=0.1)
    unimix: float = nn.static(default=0.01)

    @classmethod
    def init(
        cls,
        key,
        latent_state_size: int,
        actions_dim: Sequence[int],
        is_continuous: bool,
        *,
        init_std: float = 0.0,
        min_std: float = 0.1,
        dense_units: int = 512,
        dense_act: str = "silu",
        mlp_layers: int = 2,
        distribution: str = "auto",
        layer_norm: bool = True,
        unimix: float = 0.01,
    ):
        distribution = distribution.lower()
        if distribution not in ("auto", "normal", "tanh_normal", "discrete", "trunc_normal"):
            raise ValueError(f"unknown actor distribution {distribution!r}")
        if distribution == "discrete" and is_continuous:
            raise ValueError("discrete distribution chosen but action space is continuous")
        if distribution == "auto":
            distribution = "trunc_normal" if is_continuous else "discrete"
        k_trunk, *k_heads = jax.random.split(key, len(actions_dim) + 1)
        model = nn.MLP.init(
            k_trunk,
            latent_state_size,
            [dense_units] * mlp_layers,
            act=dense_act,
            layer_norm=layer_norm,
            use_bias=not layer_norm,
            norm_eps=1e-3,
        )
        if is_continuous:
            heads = (nn.Linear.init(k_heads[0], dense_units, int(sum(actions_dim)) * 2),)
        else:
            heads = tuple(
                nn.Linear.init(k, dense_units, dim)
                for k, dim in zip(k_heads, actions_dim)
            )
        return cls(
            model=model,
            heads=heads,
            actions_dim=tuple(int(d) for d in actions_dim),
            is_continuous=is_continuous,
            distribution=distribution,
            init_std=init_std,
            min_std=min_std,
            unimix=unimix,
        )

    def _head_logits(self, state: jax.Array, mask: dict | None = None) -> list[jax.Array]:
        x = self.model(state)
        # distribution math (log-softmax, unimix, truncated-normal cdfs)
        # always runs in f32, whatever the trunk's compute dtype
        return [head(x).astype(jnp.float32) for head in self.heads]

    def dists(self, state: jax.Array, mask: dict | None = None) -> tuple:
        """The per-head action distributions at `state`."""
        pre = self._head_logits(state, mask)
        if self.is_continuous:
            mean, std = jnp.split(pre[0], 2, axis=-1)
            if self.distribution == "tanh_normal":
                mean = 5.0 * jnp.tanh(mean / 5.0)
                std = jax.nn.softplus(std + self.init_std) + self.min_std
                return (TanhNormal(loc=mean, scale=std),)
            if self.distribution == "normal":
                return (Independent(base=Normal(loc=mean, scale=std), event_ndims=1),)
            # trunc_normal
            std = 2.0 * jax.nn.sigmoid((std + self.init_std) / 2.0) + self.min_std
            base = TruncatedNormal(
                loc=jnp.tanh(mean),
                scale=std,
                low=-jnp.ones_like(mean),
                high=jnp.ones_like(mean),
            )
            return (Independent(base=base, event_ndims=1),)
        return tuple(
            OneHotCategorical.from_logits(unimix_logits(logits, self.unimix))
            for logits in pre
        )

    def __call__(
        self,
        state: jax.Array,
        key=None,
        is_training: bool = True,
        mask: dict | None = None,
    ) -> tuple[tuple[jax.Array, ...], tuple]:
        """-> (actions tuple, distributions tuple). Training draws
        reparameterized / straight-through samples; evaluation takes the mode
        (discrete) or best-of-100 samples (continuous, reference
        agent.py:696-714)."""
        dists = self.dists(state, mask)
        if self.is_continuous:
            d = dists[0]
            if is_training:
                action = d.sample(key)
            else:
                samples = d.sample(key, (100,))
                log_prob = d.log_prob(samples)
                idx = jnp.argmax(log_prob, axis=0)
                action = jnp.take_along_axis(samples, idx[None, ..., None], axis=0)[0]
            return (action,), dists
        actions = []
        for i, d in enumerate(dists):
            if is_training:
                key, sub = jax.random.split(key)
                actions.append(d.rsample(sub))
            else:
                actions.append(d.mode)
        return tuple(actions), dists


class MinedojoActor(Actor):
    """Actor with MineDojo action masking (reference agent.py:726-800):
    head 0 masks invalid functional actions; heads 1/2 mask their argument
    spaces conditioned on the sampled functional action. The reference's
    per-(t,b) Python loops become vectorized `where` masks."""

    def __call__(
        self,
        state: jax.Array,
        key=None,
        is_training: bool = True,
        mask: dict | None = None,
    ):
        x = self.model(state)
        logits_list = [head(x) for head in self.heads]
        actions: list[jax.Array] = []
        dists: list = []
        functional_action = None
        neg_inf = jnp.float32(-1e9)
        for i, logits in enumerate(logits_list):
            if mask is not None:
                if i == 0 and "mask_action_type" in mask:
                    logits = jnp.where(mask["mask_action_type"] > 0, logits, neg_inf)
                elif i == 1 and "mask_craft_smelt" in mask:
                    is_craft = (functional_action == 15)[..., None]
                    logits = jnp.where(
                        is_craft & ~(mask["mask_craft_smelt"] > 0), neg_inf, logits
                    )
                elif i == 2:
                    if "mask_equip/place" in mask:
                        is_equip = jnp.isin(functional_action, jnp.array([16, 17]))[..., None]
                        logits = jnp.where(
                            is_equip & ~(mask["mask_equip/place"] > 0), neg_inf, logits
                        )
                    if "mask_destroy" in mask:
                        is_destroy = (functional_action == 18)[..., None]
                        logits = jnp.where(
                            is_destroy & ~(mask["mask_destroy"] > 0), neg_inf, logits
                        )
            d = OneHotCategorical.from_logits(logits)
            dists.append(d)
            if is_training:
                key, sub = jax.random.split(key)
                actions.append(d.rsample(sub))
            else:
                actions.append(d.mode)
            if functional_action is None:
                functional_action = jnp.argmax(actions[0], axis=-1)
        return tuple(actions), tuple(dists)


class PlayerState(nn.Module):
    """The player's recurrent interaction state, one row per env."""

    actions: jax.Array  # [N, sum(actions_dim)]
    recurrent_state: jax.Array  # [N, R]
    stochastic_state: jax.Array  # [N, S*D]


def exploration_actions(
    actions: tuple[jax.Array, ...],
    is_continuous: bool,
    expl_amount: jax.Array,
    key,
) -> jax.Array:
    """Add exploration noise and concatenate the per-head actions: clipped
    Gaussian noise for continuous control, epsilon-uniform one-hot swaps per
    discrete head (reference agent.py:524-554; shared by every Dreamer
    player)."""
    if is_continuous:
        cat = jnp.concatenate(actions, axis=-1)
        noise = expl_amount * jax.random.normal(key, cat.shape)
        return jnp.clip(cat + noise, -1.0, 1.0)
    expl_actions = []
    for act in actions:
        key, k_u, k_s = jax.random.split(key, 3)
        rand_idx = jax.random.randint(k_u, act.shape[:-1], 0, act.shape[-1])
        rand_one_hot = jax.nn.one_hot(rand_idx, act.shape[-1], dtype=act.dtype)
        take_rand = (jax.random.uniform(k_s, act.shape[:-1]) < expl_amount)[..., None]
        expl_actions.append(jnp.where(take_rand, rand_one_hot, act))
    return jnp.concatenate(expl_actions, axis=-1)


class PlayerDV3(nn.Module):
    """Environment-interaction model sharing parameters with the training
    graph (reference agent.py:448-583). `step` is pure and jittable; the
    recurrent state lives in an explicit PlayerState."""

    encoder: Encoder
    rssm: RSSM
    actor: Actor
    actions_dim: tuple[int, ...] = nn.static(default=())
    stochastic_size: int = nn.static(default=32)
    discrete_size: int = nn.static(default=32)
    recurrent_state_size: int = nn.static(default=512)
    is_continuous: bool = nn.static(default=False)
    # "bfloat16" runs the encoder/recurrent/latent path in bf16 (actions are
    # still sampled from f32 logits — Actor heads always cast)
    compute_dtype: str = nn.static(default="float32")

    def init_states(self, n_envs: int) -> PlayerState:
        """Zero actions, zero recurrent state, transition-mode stochastic
        state (reference agent.py:501-522)."""
        dt = jnp.dtype(self.compute_dtype)
        recurrent = jnp.zeros((n_envs, self.recurrent_state_size), dt)
        stochastic = self.rssm._transition(recurrent, key=None)[1]
        return PlayerState(
            actions=jnp.zeros((n_envs, int(sum(self.actions_dim))), dt),
            recurrent_state=recurrent,
            stochastic_state=stochastic.reshape(n_envs, -1),
        )

    def reset_states(self, state: PlayerState, reset_mask: jax.Array) -> PlayerState:
        """Re-initialize the rows where `reset_mask` ([N] bool/float) is set."""
        m = reset_mask.reshape(-1, 1).astype(state.recurrent_state.dtype)
        fresh = self.init_states(state.actions.shape[0])
        return PlayerState(
            actions=(1 - m) * state.actions + m * fresh.actions,
            recurrent_state=(1 - m) * state.recurrent_state + m * fresh.recurrent_state,
            stochastic_state=(1 - m) * state.stochastic_state + m * fresh.stochastic_state,
        )

    def step(
        self,
        state: PlayerState,
        obs: dict,
        key,
        expl_amount: jax.Array,
        is_training: bool = True,
        mask: dict | None = None,
    ) -> tuple[PlayerState, jax.Array]:
        """One greedy+exploration action step (reference agent.py:524-583).
        `expl_amount` is a traced scalar so exploration decay never
        recompiles. Returns (new_state, actions [N, sum(actions_dim)])."""
        k_repr, k_act, k_expl = jax.random.split(key, 3)
        dt = jnp.dtype(self.compute_dtype)
        obs = {k: v.astype(dt) for k, v in obs.items()}
        embedded = self.encoder(obs)
        recurrent = self.rssm.recurrent_model(
            jnp.concatenate([state.stochastic_state, state.actions], axis=-1),
            state.recurrent_state,
        )
        _, stochastic = self.rssm._representation(recurrent, embedded, key=k_repr)
        stochastic = stochastic.reshape(*stochastic.shape[:-2], -1)
        latent = jnp.concatenate([stochastic, recurrent], axis=-1)
        actions, _ = self.actor(latent, key=k_act, is_training=is_training, mask=mask)
        cat = exploration_actions(actions, self.is_continuous, expl_amount, k_expl)
        new_state = PlayerState(
            actions=cat.astype(dt), recurrent_state=recurrent,
            stochastic_state=stochastic,
        )
        return new_state, cat


def _reinit_head(module: nn.MLP, key, mode: str) -> nn.MLP:
    return module.replace(head=init_xavier(module.head, key, mode))


def build_models(
    key,
    actions_dim: Sequence[int],
    is_continuous: bool,
    args,
    obs_space: dict,
    cnn_keys: Sequence[str],
    mlp_keys: Sequence[str],
) -> tuple[WorldModel, Actor, nn.MLP, nn.MLP]:
    """Build (world_model, actor, critic, target_critic) with the Hafner
    initialization pass (reference agent.py:803-1058): Xavier-normal
    everywhere; Xavier-uniform on the distribution output layers
    (actor heads, transition/representation, continue, decoders); zeros on
    the reward and critic heads."""
    if args.cnn_channels_multiplier <= 0:
        raise ValueError("cnn_channels_multiplier must be greater than zero")
    if args.dense_units <= 0:
        raise ValueError("dense_units must be greater than zero")
    stochastic_size = args.stochastic_size * args.discrete_size
    latent_state_size = stochastic_size + args.recurrent_state_size
    keys = jax.random.split(key, 12)

    cnn_encoder = None
    if cnn_keys:
        cnn_encoder = CNNEncoder.init(
            keys[0],
            cnn_keys,
            input_channels=sum(obs_space[k].shape[-1] for k in cnn_keys),
            image_size=obs_space[cnn_keys[0]].shape[:2],
            channels_multiplier=args.cnn_channels_multiplier,
            layer_norm=args.layer_norm,
            activation=args.cnn_act,
        )
    mlp_encoder = None
    if mlp_keys:
        mlp_encoder = MLPEncoder.init(
            keys[1],
            mlp_keys,
            input_dim=sum(obs_space[k].shape[0] for k in mlp_keys),
            mlp_layers=args.mlp_layers,
            dense_units=args.dense_units,
            layer_norm=args.layer_norm,
            activation=args.dense_act,
        )
    encoder = Encoder(cnn_encoder=cnn_encoder, mlp_encoder=mlp_encoder)

    recurrent_model = RecurrentModel.init(
        keys[2],
        int(sum(actions_dim)) + stochastic_size,
        args.recurrent_state_size,
        args.dense_units,
        layer_norm=args.layer_norm,
        activation=args.dense_act,
    )
    representation_model = nn.MLP.init(
        keys[3],
        args.recurrent_state_size + encoder.output_dim,
        [args.hidden_size],
        stochastic_size,
        act=args.dense_act,
        layer_norm=args.layer_norm,
        use_bias=not args.layer_norm,
        norm_eps=1e-3,
    )
    transition_model = nn.MLP.init(
        keys[4],
        args.recurrent_state_size,
        [args.hidden_size],
        stochastic_size,
        act=args.dense_act,
        layer_norm=args.layer_norm,
        use_bias=not args.layer_norm,
        norm_eps=1e-3,
    )
    rssm = RSSM(
        recurrent_model=recurrent_model,
        representation_model=representation_model,
        transition_model=transition_model,
        discrete=args.discrete_size,
        unimix=args.unimix,
    )

    cnn_decoder = None
    if cnn_keys:
        cnn_decoder = CNNDecoder.init(
            keys[5],
            cnn_keys,
            output_channels=[obs_space[k].shape[-1] for k in cnn_keys],
            channels_multiplier=args.cnn_channels_multiplier,
            latent_state_size=latent_state_size,
            cnn_encoder_output_dim=cnn_encoder.output_dim,
            layer_norm=args.layer_norm,
            activation=args.cnn_act,
        )
    mlp_decoder = None
    if mlp_keys:
        mlp_decoder = MLPDecoder.init(
            keys[6],
            mlp_keys,
            output_dims=[obs_space[k].shape[0] for k in mlp_keys],
            latent_state_size=latent_state_size,
            mlp_layers=args.mlp_layers,
            dense_units=args.dense_units,
            layer_norm=args.layer_norm,
            activation=args.dense_act,
        )
    observation_model = Decoder(cnn_decoder=cnn_decoder, mlp_decoder=mlp_decoder)

    mlp_kwargs = dict(
        act=args.dense_act,
        layer_norm=args.layer_norm,
        use_bias=not args.layer_norm,
        norm_eps=1e-3,
    )
    reward_model = nn.MLP.init(
        keys[7], latent_state_size, [args.dense_units] * args.mlp_layers, args.bins, **mlp_kwargs
    )
    continue_model = nn.MLP.init(
        keys[8], latent_state_size, [args.dense_units] * args.mlp_layers, 1, **mlp_kwargs
    )
    world_model = WorldModel(
        encoder=encoder,
        rssm=rssm,
        observation_model=observation_model,
        reward_model=reward_model,
        continue_model=continue_model,
    )
    actor_cls = MinedojoActor if "minedojo" in args.env_id else Actor
    actor = actor_cls.init(
        keys[9],
        latent_state_size,
        actions_dim,
        is_continuous,
        init_std=args.actor_init_std,
        min_std=args.actor_min_std,
        dense_units=args.dense_units,
        dense_act=args.dense_act,
        mlp_layers=args.mlp_layers,
        distribution=args.actor_distribution,
        layer_norm=args.layer_norm,
        unimix=args.unimix,
    )
    critic = nn.MLP.init(
        keys[10], latent_state_size, [args.dense_units] * args.mlp_layers, args.bins, **mlp_kwargs
    )

    # base Xavier-normal pass over everything (reference init_weights applies)
    ik = jax.random.split(keys[11], 10)
    world_model = init_xavier(world_model, ik[0], "normal")
    actor = init_xavier(actor, ik[1], "normal")
    critic = init_xavier(critic, ik[2], "normal")

    if args.hafner_initialization:
        actor = actor.replace(
            heads=tuple(
                init_xavier(h, jax.random.fold_in(ik[3], i), "uniform")
                for i, h in enumerate(actor.heads)
            )
        )
        critic = _reinit_head(critic, ik[4], "zero")
        rssm = world_model.rssm
        rssm = rssm.replace(
            transition_model=_reinit_head(rssm.transition_model, ik[5], "uniform"),
            representation_model=_reinit_head(rssm.representation_model, ik[6], "uniform"),
        )
        world_model = world_model.replace(
            rssm=rssm,
            reward_model=_reinit_head(world_model.reward_model, ik[7], "zero"),
            continue_model=_reinit_head(world_model.continue_model, ik[8], "uniform"),
        )
        om = world_model.observation_model
        if om.mlp_decoder is not None:
            om = om.replace(
                mlp_decoder=om.mlp_decoder.replace(
                    heads={
                        k: init_xavier(h, jax.random.fold_in(ik[9], i), "uniform")
                        for i, (k, h) in enumerate(sorted(om.mlp_decoder.heads.items()))
                    }
                )
            )
        if om.cnn_decoder is not None:
            dec = om.cnn_decoder.model
            dec = dec.replace(
                layers=(
                    *dec.layers[:-1],
                    init_xavier(dec.layers[-1], jax.random.fold_in(ik[9], 101), "uniform"),
                )
            )
            om = om.replace(cnn_decoder=om.cnn_decoder.replace(model=dec))
        world_model = world_model.replace(observation_model=om)

    # deep copy: distinct buffers so critic and target can live in the same
    # donated train state (reference deepcopy, agent.py:1054)
    target_critic = jax.tree_util.tree_map(jnp.copy, critic)
    return world_model, actor, critic, target_critic
