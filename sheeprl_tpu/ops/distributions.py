"""Distributions as frozen pytree dataclasses — constructible inside `jit`.

JAX-native replacements for the reference's torch.distributions usage and
custom classes (/root/reference/sheeprl/utils/distribution.py): Normal,
Independent, tanh-squashed Normal (SAC), Categorical / one-hot categorical
with straight-through gradients and unimix (Dreamer), truncated normal
(DreamerV1), and the DreamerV3 trio Symlog / MSE / TwoHotEncoding.

Everything is pure: `sample(key)` threads explicit PRNG keys and is
reparameterized wherever the reference's `rsample` was.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..nn.core import Module, static
from .math import symexp, symlog, two_hot

_LOG_SQRT_2PI = 0.5 * math.log(2 * math.pi)
_LOG_SQRT_2PI_E = 0.5 * math.log(2 * math.pi * math.e)


def _sum_last(x: jax.Array, ndims: int) -> jax.Array:
    if ndims == 0:
        return x
    return x.sum(axis=tuple(range(-ndims, 0)))


class Distribution(Module):
    """Base marker class; subclasses are pytrees (array fields = leaves)."""


# ---------------------------------------------------------------------------
# Gaussian family
# ---------------------------------------------------------------------------


class Normal(Distribution):
    loc: jax.Array
    scale: jax.Array

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        shape = sample_shape + jnp.broadcast_shapes(self.loc.shape, self.scale.shape)
        eps = jax.random.normal(key, shape, dtype=jnp.result_type(self.loc))
        return self.loc + self.scale * eps

    def log_prob(self, x):
        z = (x - self.loc) / self.scale
        return -0.5 * jnp.square(z) - jnp.log(self.scale) - _LOG_SQRT_2PI

    def entropy(self):
        return _LOG_SQRT_2PI_E + jnp.log(self.scale) * jnp.ones_like(self.loc)

    @property
    def mean(self):
        return self.loc

    @property
    def mode(self):
        return self.loc

    @property
    def stddev(self):
        return self.scale * jnp.ones_like(self.loc)


class Independent(Distribution):
    """Reinterpret the trailing `event_ndims` batch dims as event dims."""

    base: Distribution
    event_ndims: int = static(default=1)

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        return self.base.sample(key, sample_shape)

    def log_prob(self, x):
        return _sum_last(self.base.log_prob(x), self.event_ndims)

    def entropy(self):
        return _sum_last(self.base.entropy(), self.event_ndims)

    @property
    def mean(self):
        return self.base.mean

    @property
    def mode(self):
        return self.base.mode


class TanhNormal(Distribution):
    """tanh(Normal) with the analytic log-det-Jacobian correction — the SAC
    actor distribution (/root/reference/sheeprl/algos/sac/agent.py:102-134).
    Event dim is the trailing axis (log_probs summed over it)."""

    loc: jax.Array
    scale: jax.Array

    def sample_and_log_prob(self, key, sample_shape: tuple[int, ...] = ()):
        shape = sample_shape + jnp.broadcast_shapes(self.loc.shape, self.scale.shape)
        u = self.loc + self.scale * jax.random.normal(key, shape, jnp.result_type(self.loc))
        a = jnp.tanh(u)
        base_lp = -0.5 * jnp.square((u - self.loc) / self.scale) - jnp.log(self.scale) - _LOG_SQRT_2PI
        # log(1 - tanh(u)^2) = 2 * (log 2 - u - softplus(-2u)), numerically stable
        correction = 2.0 * (math.log(2.0) - u - jax.nn.softplus(-2.0 * u))
        log_prob = (base_lp - correction).sum(axis=-1)
        return a, log_prob

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        return self.sample_and_log_prob(key, sample_shape)[0]

    def log_prob(self, value):
        # f32 throughout: in bf16 the clip bound 1 - 1e-6 rounds to exactly
        # 1.0 and arctanh(1.0) = inf would poison the loss
        value = value.astype(jnp.float32)
        eps = 1e-6
        u = jnp.arctanh(jnp.clip(value, -1.0 + eps, 1.0 - eps))
        base_lp = (
            -0.5 * jnp.square((u - self.loc) / self.scale)
            - jnp.log(self.scale)
            - _LOG_SQRT_2PI
        )
        correction = 2.0 * (math.log(2.0) - u - jax.nn.softplus(-2.0 * u))
        return (base_lp - correction).sum(axis=-1)

    @property
    def mode(self):
        return jnp.tanh(self.loc)

    @property
    def mean(self):
        return jnp.tanh(self.loc)


class TruncatedStandardNormal(Distribution):
    """Standard normal truncated to [a, b]
    (/root/reference/sheeprl/utils/distribution.py:22-110)."""

    a: jax.Array
    b: jax.Array

    @staticmethod
    def _little_phi(x):
        return jnp.exp(-0.5 * jnp.square(x)) / math.sqrt(2 * math.pi)

    @staticmethod
    def _big_phi(x):
        return 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))

    @staticmethod
    def _inv_big_phi(x):
        return math.sqrt(2.0) * jax.lax.erf_inv(2.0 * x - 1.0)

    def _z(self):
        eps = jnp.finfo(jnp.float32).eps
        return jnp.maximum(self._big_phi(self.b) - self._big_phi(self.a), eps)

    def log_prob(self, x):
        return -_LOG_SQRT_2PI - jnp.log(self._z()) - 0.5 * jnp.square(x)

    def cdf(self, x):
        return jnp.clip((self._big_phi(x) - self._big_phi(self.a)) / self._z(), 0.0, 1.0)

    def icdf(self, p):
        return self._inv_big_phi(self._big_phi(self.a) + p * self._z())

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        eps = jnp.finfo(jnp.float32).eps
        shape = sample_shape + jnp.broadcast_shapes(self.a.shape, self.b.shape)
        p = jax.random.uniform(key, shape, minval=eps, maxval=1.0 - eps)
        return self.icdf(p)

    def entropy(self):
        z = self._z()
        phi_a, phi_b = self._little_phi(self.a), self._little_phi(self.b)
        lpbb = (phi_b * self.b - phi_a * self.a) / z
        return _LOG_SQRT_2PI_E + jnp.log(z) - 0.5 * lpbb

    @property
    def mean(self):
        return -(self._little_phi(self.b) - self._little_phi(self.a)) / self._z()


class TruncatedNormal(Distribution):
    """Normal(loc, scale) truncated to [low, high]
    (/root/reference/sheeprl/utils/distribution.py:113-144)."""

    loc: jax.Array
    scale: jax.Array
    low: jax.Array
    high: jax.Array

    def _std(self) -> TruncatedStandardNormal:
        return TruncatedStandardNormal(
            a=(self.low - self.loc) / self.scale, b=(self.high - self.loc) / self.scale
        )

    def log_prob(self, x):
        return self._std().log_prob((x - self.loc) / self.scale) - jnp.log(self.scale)

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        return self._std().sample(key, sample_shape) * self.scale + self.loc

    def entropy(self):
        return self._std().entropy() + jnp.log(self.scale)

    @property
    def mean(self):
        return self._std().mean * self.scale + self.loc

    @property
    def mode(self):
        return jnp.clip(self.loc, self.low, self.high)


# ---------------------------------------------------------------------------
# Categorical family
# ---------------------------------------------------------------------------


class Categorical(Distribution):
    """Categorical over the trailing axis. Accepts unnormalized logits:
    log_prob/entropy normalize internally (log_softmax is idempotent, so
    pre-normalized logits are fine too)."""

    logits: jax.Array

    @classmethod
    def from_logits(cls, logits):
        return cls(logits=logits)

    @property
    def log_probs(self):
        return jax.nn.log_softmax(self.logits, axis=-1)

    @property
    def probs(self):
        return jax.nn.softmax(self.logits, axis=-1)

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        shape = sample_shape + self.logits.shape[:-1]
        return jax.random.categorical(key, self.logits, shape=shape)

    def log_prob(self, x):
        return jnp.take_along_axis(
            self.log_probs, x[..., None].astype(jnp.int32), axis=-1
        )[..., 0]

    def entropy(self):
        lp = self.log_probs
        return -jnp.sum(jnp.exp(lp) * lp, axis=-1)

    @property
    def mode(self):
        return jnp.argmax(self.logits, axis=-1)


class OneHotCategorical(Distribution):
    """One-hot categorical; `StraightThrough` sampling passes gradients to the
    probabilities (Dreamer stochastic state,
    /root/reference/sheeprl/algos/dreamer_v2/utils.py:21-38). Accepts
    unnormalized logits (normalized internally where it matters)."""

    logits: jax.Array

    @classmethod
    def from_logits(cls, logits):
        return cls(logits=logits)

    @property
    def log_probs(self):
        return jax.nn.log_softmax(self.logits, axis=-1)

    @property
    def probs(self):
        return jax.nn.softmax(self.logits, axis=-1)

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        idx = jax.random.categorical(
            key, self.logits, shape=sample_shape + self.logits.shape[:-1]
        )
        return jax.nn.one_hot(idx, self.logits.shape[-1], dtype=self.logits.dtype)

    def rsample(self, key, sample_shape: tuple[int, ...] = ()):
        """Straight-through gradient sample: forward = one-hot draw,
        backward = d/d(probs)."""
        sample = self.sample(key, sample_shape)
        probs = self.probs
        return sample + probs - jax.lax.stop_gradient(probs)

    def log_prob(self, x):
        return jnp.sum(self.log_probs * x, axis=-1)

    def entropy(self):
        lp = self.log_probs
        return -jnp.sum(jnp.exp(lp) * lp, axis=-1)

    @property
    def mode(self):
        return jax.nn.one_hot(
            jnp.argmax(self.logits, axis=-1), self.logits.shape[-1], dtype=self.logits.dtype
        )


def unimix_logits(logits: jax.Array, unimix: float = 0.01) -> jax.Array:
    """Mix categorical probs with `unimix` uniform mass and return new logits
    (DreamerV3's 1% unimix, /root/reference/sheeprl/algos/dreamer_v3/agent.py:384-396)."""
    if unimix <= 0.0:
        return logits
    probs = jax.nn.softmax(logits, axis=-1)
    uniform = jnp.ones_like(probs) / probs.shape[-1]
    probs = (1.0 - unimix) * probs + unimix * uniform
    return jnp.log(probs)


class Bernoulli(Distribution):
    """Bernoulli from logits; `mode` is the safe >0.5 threshold (the continue
    head's BernoulliSafeMode in the reference)."""

    logits: jax.Array

    @property
    def probs(self):
        return jax.nn.sigmoid(self.logits)

    def sample(self, key, sample_shape: tuple[int, ...] = ()):
        shape = sample_shape + self.logits.shape
        return jax.random.bernoulli(key, self.probs, shape).astype(jnp.float32)

    def log_prob(self, x):
        # -BCE-with-logits, numerically stable
        return -(jax.nn.softplus(-self.logits) * x + jax.nn.softplus(self.logits) * (1.0 - x))

    def entropy(self):
        p = self.probs
        return jax.nn.softplus(self.logits) - self.logits * p

    @property
    def mode(self):
        return (self.probs > 0.5).astype(jnp.float32)

    @property
    def mean(self):
        return self.probs


# ---------------------------------------------------------------------------
# DreamerV3 trio
# ---------------------------------------------------------------------------


class SymlogDistribution(Distribution):
    """MSE (or L1) in symlog space
    (/root/reference/sheeprl/utils/distribution.py:148-189)."""

    _mode: jax.Array
    dims: int = static(default=1)
    dist: str = static(default="mse")
    agg: str = static(default="sum")
    tol: float = static(default=1e-8)

    def log_prob(self, value):
        if self.dist == "mse":
            distance = jnp.square(self._mode - symlog(value))
        elif self.dist == "abs":
            distance = jnp.abs(self._mode - symlog(value))
        else:
            raise NotImplementedError(self.dist)
        distance = jnp.where(distance < self.tol, 0.0, distance)
        if self.agg == "mean":
            loss = distance.mean(axis=tuple(range(-self.dims, 0)))
        else:
            loss = _sum_last(distance, self.dims)
        return -loss

    @property
    def mode(self):
        return symexp(self._mode)

    @property
    def mean(self):
        return symexp(self._mode)


class MSEDistribution(Distribution):
    """Plain MSE pseudo-likelihood
    (/root/reference/sheeprl/utils/distribution.py:192-217)."""

    _mode: jax.Array
    dims: int = static(default=1)
    agg: str = static(default="sum")

    def log_prob(self, value):
        distance = jnp.square(self._mode - value)
        if self.agg == "mean":
            loss = distance.mean(axis=tuple(range(-self.dims, 0)))
        else:
            loss = _sum_last(distance, self.dims)
        return -loss

    @property
    def mode(self):
        return self._mode

    @property
    def mean(self):
        return self._mode


class TwoHotEncodingDistribution(Distribution):
    """255-bin two-hot over symlog values — DreamerV3's reward/critic heads
    (/root/reference/sheeprl/utils/distribution.py:220-266). `log_prob(x)`
    cross-entropies a two-hot target against the logits; mean/mode decode via
    symexp(probs . bins)."""

    logits: jax.Array
    dims: int = static(default=1)
    low: float = static(default=-20.0)
    high: float = static(default=20.0)

    @property
    def bins(self):
        return jnp.linspace(self.low, self.high, self.logits.shape[-1])

    @property
    def probs(self):
        return jax.nn.softmax(self.logits, axis=-1)

    @property
    def mean(self):
        # keepdim so the event shape stays (..., 1) like the reference
        val = jnp.sum(self.probs * self.bins, axis=-1, keepdims=True)
        if self.dims > 1:
            val = _sum_last(val[..., 0], self.dims - 1)[..., None]
        return symexp(val)

    @property
    def mode(self):
        return self.mean

    def log_prob(self, x):
        # x: [..., 1] raw-scale targets
        from .pallas_kernels import select, two_hot_log_prob, use_pallas

        if use_pallas("two_hot", self.logits, x) and select("two_hot", True):
            k = self.logits.shape[-1]
            lp = two_hot_log_prob(
                symlog(x).reshape(-1, 1).astype(jnp.float32),
                self.logits.reshape(-1, k),
                self.bins[None],
            ).reshape(x.shape[:-1] + (1,))
            return _sum_last(lp, self.dims)
        target = two_hot(symlog(x)[..., 0], self.bins)
        log_pred = jax.nn.log_softmax(self.logits, axis=-1)
        return _sum_last((target * log_pred).sum(axis=-1)[..., None], self.dims)


# ---------------------------------------------------------------------------
# KL divergences (Dreamer KL balancing)
# ---------------------------------------------------------------------------


def kl_categorical(p_logits: jax.Array, q_logits: jax.Array, event_ndims: int = 1):
    """KL(p || q) between categoricals over the trailing axis, then summed over
    `event_ndims` trailing batch dims (the 32x32 discrete latent)."""
    p_log = jax.nn.log_softmax(p_logits, axis=-1)
    q_log = jax.nn.log_softmax(q_logits, axis=-1)
    kl = jnp.sum(jnp.exp(p_log) * (p_log - q_log), axis=-1)
    return _sum_last(kl, event_ndims)


def kl_normal(p: Normal, q: Normal, event_ndims: int = 1):
    """KL(p || q) between diagonal Gaussians, summed over trailing event dims."""
    var_ratio = jnp.square(p.scale / q.scale)
    t1 = jnp.square((p.loc - q.loc) / q.scale)
    kl = 0.5 * (var_ratio + t1 - 1.0 - jnp.log(var_ratio))
    return _sum_last(kl, event_ndims)
