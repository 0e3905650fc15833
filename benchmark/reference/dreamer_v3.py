"""Plain DreamerV3 train step: float32 `jax.numpy`, no kernels, no scan tricks.

The benchmark's yardstick for `correct`. It imports nothing of the program
and takes nothing the program has made: weights come from `make_params`
(from the seed), the batch from the benchmark's own record of what its
environments emitted, the update from the Adam written out below. What it
shares with the program is the published model (arXiv:2301.04104, size S as
the configuration's `args` give it) and the random stream: the noise key of each step is an
input like the seed, and is split the way the model's sampling sites are
ordered (posterior per time step, action and prior per imagination step).

`quant` is the control's knob: every matmul and convolution rounds its
operands to that dtype first (bfloat16, float8_e4m3fn), accumulating in f32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ADAM_B1, ADAM_B2 = 0.9, 0.999
BINS_LOW, BINS_HIGH = -20.0, 20.0


# ---------------------------------------------------------------- parameters
def param_spec(c: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter of the three models, by name, with its shape."""
    m, dense, hid, rec = c["cnn_channels_multiplier"], c["dense_units"], c["hidden_size"], c["recurrent_state_size"]
    stoch = c["stochastic_size"] * c["discrete_size"]
    act, bins, layers = c["actions"], c["bins"], c["mlp_layers"]
    latent = stoch + rec
    spec: dict[str, tuple[int, ...]] = {}

    def ln(name, n):
        spec[f"{name}.ln_s"] = (n,)
        spec[f"{name}.ln_b"] = (n,)

    chans = [c["image_channels"], m, 2 * m, 4 * m, 8 * m]
    for i in range(4):
        spec[f"wm.enc.conv{i}.w"] = (4, 4, chans[i], chans[i + 1])
        ln(f"wm.enc.conv{i}", chans[i + 1])
    embed = 4 * 4 * 8 * m
    spec["wm.rec.dense.w"] = (stoch + act, dense)
    ln("wm.rec.dense", dense)
    spec["wm.rec.gru.w"] = (dense + rec, 3 * rec)
    ln("wm.rec.gru", 3 * rec)
    for name, n_in in (("wm.repr", rec + embed), ("wm.trans", rec)):
        spec[f"{name}.dense.w"] = (n_in, hid)
        ln(f"{name}.dense", hid)
        spec[f"{name}.head.w"] = (hid, stoch)
        spec[f"{name}.head.b"] = (stoch,)
    spec["wm.dec.proj.w"] = (latent, embed)
    spec["wm.dec.proj.b"] = (embed,)
    dchans = [8 * m, 4 * m, 2 * m, m, c["image_channels"]]
    for i in range(4):
        spec[f"wm.dec.deconv{i}.w"] = (4, 4, dchans[i], dchans[i + 1])
        if i < 3:
            ln(f"wm.dec.deconv{i}", dchans[i + 1])
    spec["wm.dec.deconv3.b"] = (dchans[4],)
    for name, n_out in (("wm.reward", bins), ("wm.cont", 1), ("actor", act), ("critic", bins)):
        n_in = latent
        for j in range(layers):
            spec[f"{name}.l{j}.w"] = (n_in, dense)
            ln(f"{name}.l{j}", dense)
            n_in = dense
        spec[f"{name}.head.w"] = (dense, n_out)
        spec[f"{name}.head.b"] = (n_out,)
    return spec


def make_params(seed: int, c: dict) -> dict[str, jax.Array]:
    """All weights on the device in one jitted call from the seed: matrices
    and filters N(0, 1/fan_in), LayerNorm scales 1 + 0.1 N, offsets and biases
    0.1 N — no leaf is left at a value (0, 1) that would hide its gradient."""
    spec = param_spec(c)

    def build(key):
        out = {}
        for i, (name, shape) in enumerate(spec.items()):
            noise = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            if name.endswith(".w"):
                out[name] = noise / math.sqrt(math.prod(shape[:-1]))
            elif name.endswith(".ln_s"):
                out[name] = 1.0 + 0.1 * noise
            else:
                out[name] = 0.1 * noise
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2**31)))


def split_models(params: dict) -> dict[str, dict]:
    """The three separately optimised groups: world model, actor, critic."""
    groups: dict[str, dict] = {"wm": {}, "actor": {}, "critic": {}}
    for name, v in params.items():
        groups[name.split(".")[0]][name] = v
    return groups


# --------------------------------------------------------------------- layers
class Net:
    """The model's layers over one flat parameter dict."""

    def __init__(self, params: dict, c: dict, quant=None):
        self.p, self.c, self.quant = params, c, quant

    def _q(self, x):
        return x if self.quant is None else x.astype(self.quant).astype(F32)

    def mm(self, x, name):
        return jnp.dot(self._q(x), self._q(self.p[name]))

    def ln(self, x, name, eps=1e-3):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * self.p[f"{name}.ln_s"] + self.p[f"{name}.ln_b"]

    def dense_block(self, x, name):
        return jax.nn.silu(self.ln(self.mm(x, f"{name}.w"), name))

    def mlp(self, x, name):
        for j in range(self.c["mlp_layers"]):
            x = self.dense_block(x, f"{name}.l{j}")
        return self.mm(x, f"{name}.head.w") + self.p[f"{name}.head.b"]

    def head(self, x, name):
        x = self.dense_block(x, f"{name}.dense")
        return self.mm(x, f"{name}.head.w") + self.p[f"{name}.head.b"]

    def encoder(self, img):
        """[N, 64, 64, C] in [0, 1] -> [N, 4*4*8m]."""
        x = img
        for i in range(4):
            x = jax.lax.conv_general_dilated(
                self._q(x), self._q(self.p[f"wm.enc.conv{i}.w"]), (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            x = jax.nn.silu(self.ln(x, f"wm.enc.conv{i}"))
        return x.reshape(x.shape[0], -1)

    def decoder(self, latent):
        """[N, latent] -> [N, 64, 64, C]."""
        x = self.mm(latent, "wm.dec.proj.w") + self.p["wm.dec.proj.b"]
        x = x.reshape(x.shape[0], 4, 4, -1)
        for i in range(4):
            x = jax.lax.conv_transpose(
                self._q(x), self._q(self.p[f"wm.dec.deconv{i}.w"]), (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            if i < 3:
                x = jax.nn.silu(self.ln(x, f"wm.dec.deconv{i}"))
        return x + self.p["wm.dec.deconv3.b"] + 0.5

    def gru(self, x, h):
        """Dense + LayerNorm-GRU with the update gate biased by -1."""
        x = self.dense_block(x, "wm.rec.dense")
        parts = self.ln(self.mm(jnp.concatenate([x, h], -1), "wm.rec.gru.w"), "wm.rec.gru", eps=1e-5)
        r, c, u = jnp.split(parts, 3, -1)
        cand = jnp.tanh(jax.nn.sigmoid(r) * c)
        update = jax.nn.sigmoid(u - 1.0)
        return update * cand + (1.0 - update) * h

    def stoch_logits(self, raw):
        """Raw [N, S*D] head output -> unimixed logits [N, S, D]."""
        return unimix(raw.reshape(raw.shape[0], self.c["stochastic_size"], self.c["discrete_size"]), self.c["unimix"])


def unimix(logits, mix):
    probs = jax.nn.softmax(logits, -1)
    return jnp.log((1.0 - mix) * probs + mix / logits.shape[-1])


def st_sample(key, logits):
    """Straight-through one-hot draw from `logits` [..., D]."""
    idx = jax.random.categorical(key, logits, shape=logits.shape[:-1])
    probs = jax.nn.softmax(logits, -1)
    return jax.nn.one_hot(idx, logits.shape[-1], dtype=F32) + probs - jax.lax.stop_gradient(probs)


def mode_onehot(logits):
    return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1], dtype=F32)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def twohot_mean(logits):
    bins = jnp.linspace(BINS_LOW, BINS_HIGH, logits.shape[-1])
    return symexp(jnp.sum(jax.nn.softmax(logits, -1) * bins, -1, keepdims=True))


def twohot_logprob(logits, x):
    """Cross-entropy of the two-hot code of symlog(x) [...] against logits [..., K]."""
    k = logits.shape[-1]
    bins = jnp.linspace(BINS_LOW, BINS_HIGH, k)
    y = symlog(x)[..., None]
    below = jnp.clip(jnp.sum(bins <= y, -1) - 1, 0, k - 1)
    above = jnp.clip(k - jnp.sum(bins > y, -1), 0, k - 1)
    same = below == above
    d_below = jnp.where(same, 1.0, jnp.abs(bins[below] - y[..., 0]))
    d_above = jnp.where(same, 1.0, jnp.abs(bins[above] - y[..., 0]))
    total = d_below + d_above
    target = (
        jax.nn.one_hot(below, k) * (d_above / total)[..., None]
        + jax.nn.one_hot(above, k) * (d_below / total)[..., None]
    )
    return jnp.sum(target * jax.nn.log_softmax(logits, -1), -1)


def kl_cat(p_logits, q_logits):
    """KL(p || q) over the last axis, summed over the one before it."""
    p_log, q_log = jax.nn.log_softmax(p_logits, -1), jax.nn.log_softmax(q_logits, -1)
    return jnp.sum(jnp.exp(p_log) * (p_log - q_log), (-2, -1))


# ----------------------------------------------------------------- the losses
def world_loss(wm_params, batch, key, c, quant):
    """-> (loss, (posteriors [T,B,S*D], recurrent states [T,B,R]))."""
    net = Net(wm_params, c, quant)
    T, B = batch["dones"].shape[:2]
    S, D, R = c["stochastic_size"], c["discrete_size"], c["recurrent_state_size"]
    target = batch["rgb"].astype(F32) / 255.0
    embed = net.encoder(target.reshape(T * B, *target.shape[2:])).reshape(T, B, -1)
    is_first = batch["is_first"].at[0].set(1.0)
    prev_actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    keys = jax.random.split(key, T)

    def step(carry, xs):
        post, rec = carry
        first, prev_action, emb, k = xs
        _, k_post = jax.random.split(k)
        action = (1.0 - first) * prev_action
        rec = (1.0 - first) * rec
        reset_post = mode_onehot(net.stoch_logits(net.head(rec, "wm.trans"))).reshape(B, S * D)
        post = (1.0 - first) * post + first * reset_post
        rec = net.gru(jnp.concatenate([post, action], -1), rec)
        prior_l = net.stoch_logits(net.head(rec, "wm.trans"))
        post_l = net.stoch_logits(net.head(jnp.concatenate([rec, emb], -1), "wm.repr"))
        post = st_sample(k_post, post_l).reshape(B, S * D)
        return (post, rec), (rec, post, prior_l, post_l)

    start = (jnp.zeros((B, S * D), F32), jnp.zeros((B, R), F32))
    _, (recs, posts, prior_logits, post_logits) = jax.lax.scan(step, start, (is_first, prev_actions, embed, keys))

    latent = jnp.concatenate([posts, recs], -1).reshape(T * B, -1)
    recon = net.decoder(latent).reshape(target.shape)
    obs_loss = jnp.sum((recon - target) ** 2, (-3, -2, -1))
    reward_loss = -twohot_logprob(net.mlp(latent, "wm.reward"), batch["rewards"].reshape(T * B)).reshape(T, B)
    cont_logit = net.mlp(latent, "wm.cont").reshape(T, B)
    cont_target = 1.0 - batch["dones"][..., 0]
    cont_loss = jax.nn.softplus(-cont_logit) * cont_target + jax.nn.softplus(cont_logit) * (1.0 - cont_target)
    sg = jax.lax.stop_gradient
    dyn = c["kl_dynamic"] * jnp.maximum(kl_cat(sg(post_logits), prior_logits), c["kl_free_nats"])
    rep = c["kl_representation"] * jnp.maximum(kl_cat(post_logits, sg(prior_logits)), c["kl_free_nats"])
    loss = jnp.mean(dyn + rep + obs_loss + reward_loss + cont_loss)
    return loss, (posts, recs)


def imagine(actor_params, wm_params, start_post, start_rec, key, c, quant):
    """H imagination steps -> latents [H+1, N, L], actions [H+1, N, A]."""
    actor, wm = Net(actor_params, c, quant), Net(wm_params, c, quant)
    S, D = c["stochastic_size"], c["discrete_size"]
    keys = jax.random.split(key, c["horizon"] + 1)
    sg = jax.lax.stop_gradient

    def act(latent, k):
        _, sub = jax.random.split(k)
        return st_sample(sub, unimix(actor.mlp(sg(latent), "actor"), c["unimix"]))

    def step(carry, k):
        prior, rec = carry
        latent = jnp.concatenate([prior, rec], -1)
        k_act, k_trans = jax.random.split(k)
        action = act(latent, k_act)
        rec = wm.gru(jnp.concatenate([prior, action], -1), rec)
        prior = st_sample(k_trans, wm.stoch_logits(wm.head(rec, "wm.trans"))).reshape(-1, S * D)
        return (prior, rec), (latent, action)

    (prior, rec), (latents, actions) = jax.lax.scan(step, (start_post, start_rec), keys[: c["horizon"]])
    latent = jnp.concatenate([prior, rec], -1)
    latents = jnp.concatenate([latents, latent[None]], 0)
    actions = jnp.concatenate([actions, act(latent, keys[c["horizon"]])[None]], 0)
    return latents, actions


def lambda_returns(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1.0 - lmbda)
    out, nxt = [], values[-1]
    for t in reversed(range(rewards.shape[0])):
        nxt = interm[t] + continues[t] * lmbda * nxt
        out.append(nxt)
    return jnp.stack(out[::-1])


def actor_loss(actor_params, wm_params, critic_params, moments, posts, recs, dones, key, c, quant):
    T, B = dones.shape[:2]
    sg = jax.lax.stop_gradient
    flat = lambda x: jnp.swapaxes(sg(x), 0, 1).reshape(T * B, -1)
    traj, actions = imagine(actor_params, wm_params, flat(posts), flat(recs), key, c, quant)
    wm, critic, actor = Net(wm_params, c, quant), Net(critic_params, c, quant), Net(actor_params, c, quant)
    H1, N = traj.shape[:2]
    rows = traj.reshape(H1 * N, -1)
    values = twohot_mean(critic.mlp(rows, "critic")).reshape(H1, N, 1)
    rewards = twohot_mean(wm.mlp(rows, "wm.reward")).reshape(H1, N, 1)
    continues = (jax.nn.sigmoid(wm.mlp(rows, "wm.cont")) > 0.5).astype(F32).reshape(H1, N, 1)
    continues = jnp.concatenate([flat(1.0 - dones)[None], continues[1:]], 0)
    lam = lambda_returns(rewards[1:], values[1:], continues[1:] * c["gamma"], c["lmbda"])
    discount = sg(jnp.cumprod(continues * c["gamma"], 0) / c["gamma"])

    lam_flat = sg(lam).reshape(-1)
    low = c["moments_decay"] * moments["low"] + (1.0 - c["moments_decay"]) * jnp.quantile(lam_flat, c["moments_percentile_low"])
    high = c["moments_decay"] * moments["high"] + (1.0 - c["moments_decay"]) * jnp.quantile(lam_flat, c["moments_percentile_high"])
    invscale = jnp.maximum(1.0 / c["moment_max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale

    logp_all = jax.nn.log_softmax(unimix(actor.mlp(sg(rows), "actor"), c["unimix"]), -1).reshape(H1, N, -1)
    log_prob = jnp.sum(logp_all * sg(actions), -1, keepdims=True)
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1, keepdims=True)
    objective = log_prob[:-1] * sg(advantage) + c["actor_ent_coef"] * entropy[:-1]
    loss = -jnp.mean(discount[:-1] * objective)
    return loss, (traj, lam, discount, {"low": low, "high": high})


def critic_loss(critic_params, target_params, traj, lam, discount, c, quant):
    sg = jax.lax.stop_gradient
    H, N = lam.shape[:2]
    rows = sg(traj[:-1]).reshape(H * N, -1)
    logits = Net(critic_params, c, quant).mlp(rows, "critic")
    target_values = twohot_mean(Net(target_params, c, quant).mlp(rows, "critic"))
    loss = -twohot_logprob(logits, sg(lam).reshape(-1)) - twohot_logprob(logits, sg(target_values)[:, 0])
    return jnp.mean(loss.reshape(H, N) * discount[:-1, :, 0])


# ------------------------------------------------------------------ optimiser
def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(v * v) for v in tree.values()))


def adam_update(params, grads, opt, lr, eps, clip):
    """Clip by global norm, then Adam with bias correction.
    -> (params, opt, the gradient as Adam got it)."""
    norm = global_norm(grads)
    grads = {k: jnp.where(norm < clip, g, g / norm * clip) for k, g in grads.items()}
    count = opt["count"] + 1
    mu = {k: ADAM_B1 * opt["mu"][k] + (1.0 - ADAM_B1) * g for k, g in grads.items()}
    nu = {k: ADAM_B2 * opt["nu"][k] + (1.0 - ADAM_B2) * g * g for k, g in grads.items()}
    c1, c2 = 1.0 - ADAM_B1**count, 1.0 - ADAM_B2**count
    new = {k: params[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps) for k in params}
    return new, {"count": count, "mu": mu, "nu": nu}, grads


def init_state(params: dict) -> dict:
    groups = split_models(params)
    zeros = lambda g: {k: jnp.zeros_like(v) for k, v in g.items()}
    return {
        "params": groups,
        "target": dict(groups["critic"]),
        "opt": {n: {"count": jnp.zeros((), F32), "mu": zeros(g), "nu": zeros(g)} for n, g in groups.items()},
        "moments": {"low": jnp.zeros((), F32), "high": jnp.zeros((), F32)},
    }


def train_step(state, batch, key, first, c, quant=None):
    """One update of world model, actor and critic. `first` is 1.0 on the
    very first step (the target critic copies the critic), else 0.0."""
    P = state["params"]
    k_wm, k_img = jax.random.split(key)
    tau = first + (1.0 - first) * c["critic_tau"]
    target = {k: tau * P["critic"][k] + (1.0 - tau) * state["target"][k] for k in P["critic"]}

    (wm_l, (posts, recs)), wm_g = jax.value_and_grad(world_loss, has_aux=True)(P["wm"], batch, k_wm, c, quant)
    wm, wm_opt, wm_g = adam_update(P["wm"], wm_g, state["opt"]["wm"], c["world_lr"], 1e-8, c["world_clip_gradients"])

    (ac_l, (traj, lam, discount, moments)), ac_g = jax.value_and_grad(actor_loss, has_aux=True)(
        P["actor"], wm, P["critic"], state["moments"], posts, recs, batch["dones"], k_img, c, quant
    )
    actor, ac_opt, ac_g = adam_update(P["actor"], ac_g, state["opt"]["actor"], c["actor_lr"], 1e-5, c["actor_clip_gradients"])

    cr_l, cr_g = jax.value_and_grad(critic_loss)(P["critic"], target, traj, lam, discount, c, quant)
    critic, cr_opt, cr_g = adam_update(P["critic"], cr_g, state["opt"]["critic"], c["critic_lr"], 1e-5, c["critic_clip_gradients"])

    new = {
        "params": {"wm": wm, "actor": actor, "critic": critic},
        "target": target,
        "opt": {"wm": wm_opt, "actor": ac_opt, "critic": cr_opt},
        "moments": moments,
    }
    out = {
        "loss": {"wm": wm_l, "actor": ac_l, "critic": cr_l},
        "grads": {"wm": wm_g, "actor": ac_g, "critic": cr_g},
    }
    return new, out


def run_steps(params, batches, keys, c, quant=None):
    """Follow the first steps from `params`. -> (final state, per-step outs).
    Traced under `highest` matmul precision: on a TPU a float32 product is
    otherwise rounded to bfloat16 passes."""
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda s, b, k, f: train_step(s, b, k, f, c, quant))
        state, outs = init_state(params), []
        for i, (batch, key) in enumerate(zip(batches, keys)):
            state, out = step(state, batch, key, jnp.float32(1.0 if i == 0 else 0.0))
            outs.append(out)
    return state, outs
