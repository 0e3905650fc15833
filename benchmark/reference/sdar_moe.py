"""The plain reference of `sdar_30b_a3b_ep8`: SDAR-30B-A3B-Chat's layer
(`model_type: sdar_moe`), its block-diffusion policy step and the clipped
policy-gradient train step, in straightforward `jax.numpy`, float32, under
`highest` matmul precision. No cache, no kernel, no batching: one sequence at
a time, one layer after another, every held expert over every token.

Departures from the published model, each the configuration's (`reduced`,
`assumed` in `benchmark/configs/sdar_30b_a3b_ep8.json`):
  - depth: `num_hidden_layers` of the 48;
  - the chip's share: experts `first_expert .. first_expert + experts_held`
    of the 128 are held; routing is over all 128, the weights are normalised
    over all 8 picks, and what the absent experts would add is left out;
  - the vocabulary is the slice of `vocab_size` ids, the mask token among them;
  - assumed, the config giving none of them: block length and denoising steps,
    per-head RMSNorm on q and k, rotate-half RoPE over the whole head, and
    that the mask token is never sampled (its logit is left out of the
    distribution a step samples from and the update scores).

`quant` is the control's knob (every product's operands rounded to that
type); `fault` plants one of two errors: "drop_expert" leaves the last held
expert out, "causal" makes the block mask causal inside a block.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
ADAM_B1, ADAM_B2 = 0.9, 0.999


# ------------------------------------------------------------------ weights
def param_spec(c: dict) -> dict[str, tuple]:
    h, d, f = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    q, kv, held = c["num_attention_heads"] * d, c["num_key_value_heads"] * d, c["experts_held"]
    layer = {
        "attn_norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h), "q_norm": (d,), "k_norm": (d,),
        "mlp_norm": (h,), "router": (h, c["num_experts"]), "w_gate": (held, h, f), "w_up": (held, h, f), "w_down": (held, f, h),
    }
    spec = {"embed": (c["vocab_size"], h)}
    for i in range(c["num_hidden_layers"]):
        spec.update({f"layers.{i}.{k}": v for k, v in layer.items()})
    spec.update({"final_norm": (h,), "lm_head": (h, c["vocab_size"])})
    return spec


def make_leaf(seed: int, name: str, shape: tuple) -> jax.Array:
    """One leaf from the seed and its own name: matrices N(0, 1/fan_in) (an
    embedding row N(0, 1)), norm scales 1 + 0.1 N, so every leaf has a gradient."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31)), zlib.crc32(name.encode()) % (2**31))
    noise = jax.random.normal(key, shape, F32)
    if len(shape) == 1:
        return 1.0 + 0.1 * noise
    if name == "embed":
        return noise
    return noise / math.sqrt(shape[-2])


def make_params(seed: int, c: dict) -> dict[str, jax.Array]:
    return {name: make_leaf(seed, name, shape) for name, shape in param_spec(c).items()}


# ------------------------------------------------------------------- layers
def _q(x, quant):
    return x if quant is None else x.astype(quant).astype(F32)


def _mm(x, w, quant):
    return jnp.dot(_q(x, quant), _q(w, quant))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [S, heads, D], rotate-half over all D."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, pre, x, positions, mask, c, quant):
    S, d, heads, kv = x.shape[0], c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"]
    u = rms_norm(x, p[pre + "attn_norm"], c["rms_norm_eps"])
    q = _mm(u, p[pre + "wq"], quant).reshape(S, heads, d)
    k = _mm(u, p[pre + "wk"], quant).reshape(S, kv, d)
    v = _mm(u, p[pre + "wv"], quant).reshape(S, kv, d)
    q = rope(rms_norm(q, p[pre + "q_norm"], c["rms_norm_eps"]), positions, c["rope_theta"])
    k = rope(rms_norm(k, p[pre + "k_norm"], c["rms_norm_eps"]), positions, c["rope_theta"])
    group = heads // kv  # query head h reads key/value head h // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant)) / math.sqrt(d)
    weights = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    weights = jnp.where(mask.any(-1, keepdims=True)[None], weights, 0.0)  # padding attends nothing
    out = jnp.einsum("hqk,khd->qhd", _q(weights, quant), _q(v, quant)).reshape(S, heads * d)
    return x + _mm(out, p[pre + "wo"], quant)


def routing(p, pre, r, c, quant):
    """-> [S, num_experts]: w_e for the picked experts, 0 elsewhere."""
    probs = jax.nn.softmax(_mm(r, p[pre + "router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[jnp.arange(r.shape[0])[:, None], idx].set(top)


def experts(p, pre, x, c, quant, fault=None, with_picks=False):
    """`with_picks`: also [S, experts_held] bool, the held experts each position picks."""
    r = rms_norm(x, p[pre + "mlp_norm"], c["rms_norm_eps"])
    w = routing(p, pre, r, c, quant)
    first, held = c.get("first_expert", 0), c["experts_held"]
    n = held - 1 if fault == "drop_expert" else held
    # every held expert over every token; a token's weight is 0 where it did not pick the expert
    gate = jnp.einsum("sh,ehf->esf", _q(r, quant), _q(p[pre + "w_gate"][:n], quant))
    up = jnp.einsum("sh,ehf->esf", _q(r, quant), _q(p[pre + "w_up"][:n], quant))
    out = jnp.einsum("esf,efh->esh", _q(jax.nn.silu(gate) * up, quant), _q(p[pre + "w_down"][:n], quant))
    y = x + jnp.einsum("se,esh->sh", w[:, first : first + n], out)
    return (y, w[:, first : first + held] > 0) if with_picks else y


def forward(p, ids, positions, mask, c, quant=None, fault=None, with_picks=False):
    """ids, positions [S], mask [S, S] (query, key) -> logits [S, V]; `with_picks`:
    also [S, experts_held] bool, the held experts a position picks in some layer."""
    x = p["embed"][ids]
    picks = jnp.zeros((x.shape[0], c["experts_held"]), bool)
    for i in range(c["num_hidden_layers"]):
        def layer(x, lp, pre=f"layers.{i}."):
            return experts(lp, pre, attention(lp, pre, x, positions, mask, c, quant), c, quant, fault, with_picks=True)

        lp = {k: v for k, v in p.items() if k.startswith(f"layers.{i}.")}
        x, picked = jax.checkpoint(layer)(x, lp)
        picks = picks | picked
    logits = _mm(rms_norm(x, p["final_norm"], c["rms_norm_eps"]), p["lm_head"], quant)
    return (logits, picks) if with_picks else logits


# -------------------------------------------------------------------- masks
def block_mask(positions, c, fault=None):
    """Position i attends j iff block(j) <= block(i); the planted fault makes it causal."""
    if fault == "causal":
        return positions[None, :] <= positions[:, None]
    b = positions // c["block_length"]
    return b[None, :] <= b[:, None]


def layout_mask(copy, block, fault=None):
    """The update's mask over [prompt ; response clean ; copy 1 ; copy 2 ...]:
    `copy` is 0 on clean positions, k on copy k's, -1 on padding; `block` the
    block of the token a position is or stands for. Clean i attends clean j of
    blocks <= its own; a copy's position attends the clean blocks before its
    own and its own copy's own block; padding neither attends nor is attended."""
    ci, cj, bi, bj = copy[:, None], copy[None, :], block[:, None], block[None, :]
    clean = (cj == 0) & ((bj < bi) | ((ci == 0) & (bj == bi)))
    own = (cj == ci) & (ci > 0) & (bj == bi)
    mask = (clean | own) & (ci >= 0) & (cj >= 0)
    if fault == "causal":  # inside a block, only the positions up to one's own
        idx = jnp.arange(copy.shape[0])
        mask = mask & ((bj < bi) | (idx[None, :] <= idx[:, None]))
    return mask


# -------------------------------------------------------------- policy step
def policy_logits(p, ids, n, c, quant=None, fault=None, with_picks=False):
    """Logits at the last block of the first `n` of `ids` (the clean prefix,
    then the block as the step saw it; what follows is padding): one whole
    forward pass, no cache. `n` may be traced: one program for every length.
    `with_picks`: also the held experts each of the block's positions picks."""
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    mask = block_mask(positions, c, fault) & (positions[None, :] < n) & (positions[:, None] < n)
    out = forward(p, jnp.asarray(ids, jnp.int32), positions, mask, c, quant, fault, with_picks)
    block = lambda x: jax.lax.dynamic_slice_in_dim(x, n - c["block_length"], c["block_length"], axis=0)
    return tuple(map(block, out)) if with_picks else block(out)


# --------------------------------------------------------------- train step
def sequence_logprobs(p, seq, c, quant=None, fault=None):
    """log-probability of each target at its loss position, [R_max]."""
    logits = forward(p, seq["ids"], seq["positions"], layout_mask(seq["copy"], seq["block"], fault), c, quant, fault)
    logits = logits[seq["loss_pos"]].at[:, c["mask_token_id"]].set(-jnp.inf)  # the mask token is never a choice
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, seq["targets"][:, None], axis=-1)[:, 0]


def batch_loss(p, batch, c, quant=None, fault=None):
    """Clipped surrogate, mean over the batch's committed tokens; the
    sequences one at a time."""
    def one(seq):
        logp = sequence_logprobs(p, seq, c, quant, fault)
        ratio = jnp.exp(logp - seq["logprob_old"])
        adv = seq["advantages"]
        clipped = jnp.clip(ratio, 1.0 - c["clip_coef"], 1.0 + c["clip_coef"])
        return jnp.sum(-jnp.minimum(adv * ratio, adv * clipped) * seq["loss_mask"]), logp

    sums, logps = jax.lax.map(jax.checkpoint(one), batch)
    return jnp.sum(sums) / jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0), logps


def init_state(params):
    zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"params": params, "mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}


def adam(state, grads, c):
    """Global-norm clip, then Adam, then the step: as the program's chain."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, c["max_grad_norm"] / (norm + 1e-6)) if c["max_grad_norm"] > 0 else 1.0
    count = state["count"] + 1
    t = count.astype(F32)
    new = {"params": {}, "mu": {}, "nu": {}, "count": count}
    for k, g in grads.items():
        g = g * scale
        mu = ADAM_B1 * state["mu"][k] + (1.0 - ADAM_B1) * g
        nu = ADAM_B2 * state["nu"][k] + (1.0 - ADAM_B2) * g * g
        step = (mu / (1.0 - ADAM_B1**t)) / (jnp.sqrt(nu / (1.0 - ADAM_B2**t)) + c["adam_eps"])
        new["params"][k], new["mu"][k], new["nu"][k] = state["params"][k] - c["lr"] * step, mu, nu
    return new


def run_steps(params, batches, c, quant=None, fault=None, on_step=None):
    """Follow the train steps from `params`. -> (final state, per step
    {"loss", "logp"}); `on_step(i, state, grads)` sees each step's gradient
    before it is given up (the first is what the comparison reads)."""
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(lambda p, b: batch_loss(p, b, c, quant, fault), has_aux=True))
        update = jax.jit(lambda s, g: adam(s, g, c), donate_argnums=(0, 1))
        state, outs = init_state(params), []
        for i, batch in enumerate(batches):
            (loss, logp), grads = grad(state["params"], batch)
            if on_step is not None:
                on_step(i, state, grads)
            state = update(state, grads)
            outs.append({"loss": loss, "logp": logp})
    return state, outs
