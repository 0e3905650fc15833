"""Pallas kernel numerics: every kernel must match its plain-XLA twin (value
and gradient) in interpret mode on CPU — the correctness gate before the
on-chip benchmark decides which kernels stay enabled (VERDICT r1 #4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.nn.recurrent import LayerNormGRUCell
from sheeprl_tpu.ops import pallas_kernels as pk
from sheeprl_tpu.ops.distributions import TwoHotEncodingDistribution
from sheeprl_tpu.ops.math import two_hot


@pytest.fixture
def pallas_interpret():
    pk.set_pallas(True, interpret=True)
    yield
    pk.set_pallas(None, interpret=False)


def test_gru_kernel_matches_reference(pallas_interpret):
    rng = np.random.default_rng(0)
    B, Dx, H = 4, 6, 8
    x = jnp.asarray(rng.normal(size=(B, Dx)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(Dx + H, 3 * H)).astype(np.float32) * 0.2)
    scale = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) + 1.0)
    offset = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1)

    got = pk.layernorm_gru_cell(x, h, w, scale, offset, 1e-5)
    want = pk._gru_reference(x, h, w, scale, offset, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_gru_kernel_gradients(pallas_interpret):
    rng = np.random.default_rng(1)
    B, Dx, H = 3, 5, 4
    args = (
        jnp.asarray(rng.normal(size=(B, Dx)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(Dx + H, 3 * H)).astype(np.float32) * 0.3),
        jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) + 1.0),
        jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * 0.1),
    )
    g_kernel = jax.grad(
        lambda *a: pk.layernorm_gru_cell(*a, 1e-5).sum(), argnums=(0, 1, 2, 3, 4)
    )(*args)
    g_ref = jax.grad(
        lambda *a: pk._gru_reference(*a, 1e-5).sum(), argnums=(0, 1, 2, 3, 4)
    )(*args)
    for gk, gr in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-5)


def test_gru_cell_module_pallas_path_matches_plain(pallas_interpret):
    cell = LayerNormGRUCell.init(jax.random.PRNGKey(0), 6, 8, use_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    h = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
    with_pallas = cell(x, h)
    pk.set_pallas(False)
    without = cell(x, h)
    np.testing.assert_allclose(np.asarray(with_pallas), np.asarray(without), atol=1e-5)


def test_two_hot_log_prob_matches_dense(pallas_interpret):
    rng = np.random.default_rng(2)
    N, K = 12, 17
    bins = jnp.linspace(-20.0, 20.0, K)
    x = jnp.asarray(rng.uniform(-25, 25, size=(N, 1)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))

    got = pk.two_hot_log_prob(x, logits, bins[None])
    target = two_hot(x[:, 0], bins)
    want = (target * jax.nn.log_softmax(logits, axis=-1)).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_two_hot_log_prob_logits_gradient(pallas_interpret):
    rng = np.random.default_rng(3)
    N, K = 6, 9
    bins = jnp.linspace(-20.0, 20.0, K)
    x = jnp.asarray(rng.uniform(-20, 20, size=(N, 1)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))

    g_kernel = jax.grad(lambda l: pk.two_hot_log_prob(x, l, bins[None]).sum())(logits)

    def dense(l):
        target = two_hot(x[:, 0], bins)
        return (target * jax.nn.log_softmax(l, axis=-1)).sum()

    g_ref = jax.grad(dense)(logits)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_ref), atol=1e-5)


def test_two_hot_distribution_paths_agree(pallas_interpret):
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(5, 3, 255)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-30, 30, size=(5, 3, 1)).astype(np.float32))
    d = TwoHotEncodingDistribution(logits=logits)
    with_pallas = d.log_prob(x)
    pk.set_pallas(False)
    without = d.log_prob(x)
    np.testing.assert_allclose(np.asarray(with_pallas), np.asarray(without), atol=1e-4)


def test_pallas_disabled_on_cpu_by_default():
    # auto mode: CPU backend -> kernels off, the plain paths serve
    pk.set_pallas(None)
    assert not pk.use_pallas()


# =============================================================================
# Fused RSSM dynamic step (ISSUE 9 tentpole b)
# =============================================================================


def _rssm_fixture(dtype=jnp.float32, seed=0):
    """A DV3-shaped RSSM (single-hidden LN MLPs, bias-free LN-GRU) plus a
    random dynamic-step input batch."""
    from sheeprl_tpu import nn
    from sheeprl_tpu.algos.dreamer_v3.agent import RSSM, RecurrentModel

    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    R, D, Hd, S, Dd, A, E, B = 16, 12, 10, 4, 4, 3, 8, 5
    rm = RecurrentModel.init(ks[0], S * Dd + A, R, D, layer_norm=True, activation="silu")
    tm = nn.MLP.init(ks[1], R, [Hd], S * Dd, act="silu", layer_norm=True,
                     use_bias=False, norm_eps=1e-3)
    pm = nn.MLP.init(ks[2], R + E, [Hd], S * Dd, act="silu", layer_norm=True,
                     use_bias=False, norm_eps=1e-3)
    rssm = RSSM(recurrent_model=rm, representation_model=pm,
                transition_model=tm, discrete=Dd, unimix=0.01)
    batch = dict(
        post=jax.random.normal(ks[3], (B, S, Dd), dtype),
        rec=jax.random.normal(ks[4], (B, R), dtype),
        act=jax.random.normal(ks[5], (B, A), dtype),
        emb=jax.random.normal(ks[6], (B, E), dtype),
        first=jnp.zeros((B, 1), jnp.float32),
        key=ks[7],
    )
    return rssm, batch


def _fused_args(rssm, x, emb):
    weights, act, eps = rssm._fused_step_weights(x, emb)
    return weights, act, eps


def test_fused_rssm_forward_matches_reference(pallas_interpret):
    rssm, b = _rssm_fixture()
    x = jnp.concatenate([b["post"].reshape(b["post"].shape[0], -1), b["act"]], -1)
    weights, act, eps = _fused_args(rssm, x, b["emb"])
    got = pk.fused_rssm_step(x, b["rec"], b["emb"], *weights, act, eps)
    want = pk.rssm_step_reference(x, b["rec"], b["emb"], *weights, act, eps)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_fused_rssm_vjp_matches_reference(pallas_interpret):
    rssm, b = _rssm_fixture(seed=1)
    x = jnp.concatenate([b["post"].reshape(b["post"].shape[0], -1), b["act"]], -1)
    weights, act, eps = _fused_args(rssm, x, b["emb"])

    def total(fn, *leading):
        out = fn(*leading, *weights, act, eps)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in out)

    # d/d(x, h, emb) and d/d(every weight)
    argnums = tuple(range(3 + len(weights)))

    def total_all(fn, *args):
        out = fn(*args, act, eps)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in out)

    g_kernel = jax.grad(lambda *a: total_all(pk.fused_rssm_step, *a), argnums)(
        x, b["rec"], b["emb"], *weights
    )
    g_ref = jax.grad(lambda *a: total_all(pk.rssm_step_reference, *a), argnums)(
        x, b["rec"], b["emb"], *weights
    )
    for gk, gr in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-4)


def test_fused_rssm_dynamic_dispatch_matches_xla_path(pallas_interpret):
    """RSSM.dynamic with the fused kernel vs the plain module path: same
    states/logits (value AND gradient) — the swap-in is behavior-preserving."""
    rssm, b = _rssm_fixture(seed=2)
    inputs = (b["post"], b["rec"], b["act"], b["emb"], b["first"], b["key"])

    pk.set_pallas(False)
    ref = rssm.dynamic(*inputs)
    pk.set_pallas(True, interpret=True)
    fused = rssm.dynamic(*inputs)
    for r, f in zip(ref, fused):
        np.testing.assert_allclose(np.asarray(r), np.asarray(f), atol=1e-5)

    def loss(mod, use):
        pk.set_pallas(use, interpret=use)
        out = mod.dynamic(*inputs)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in out)

    g_ref = jax.grad(lambda m: loss(m, False))(rssm)
    g_fused = jax.grad(lambda m: loss(m, True))(rssm)
    for a, c in zip(
        jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_fused)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4)


def test_fused_rssm_bf16_dtypes(pallas_interpret):
    """bf16-aware block contract: compute-dtype state out, f32 raw logits
    out (the fp32 island starts INSIDE the kernel — no extra upcasts)."""
    rssm, b = _rssm_fixture(dtype=jnp.bfloat16, seed=3)
    out = rssm.dynamic(
        b["post"], b["rec"], b["act"], b["emb"], b["first"], b["key"]
    )
    recurrent, posterior, prior, post_logits, prior_logits = out
    assert recurrent.dtype == jnp.bfloat16
    assert posterior.dtype == jnp.bfloat16 and prior.dtype == jnp.bfloat16
    assert post_logits.dtype == jnp.float32 and prior_logits.dtype == jnp.float32


def test_fused_rssm_dispatch_falls_back_on_mismatch(pallas_interpret):
    """A module shape outside the kernel contract (biased GRU projection)
    must return None from the dispatch guard — the XLA path serves."""
    from sheeprl_tpu import nn

    rssm, b = _rssm_fixture(seed=4)
    biased = rssm.recurrent_model.rnn.replace(
        proj=nn.Linear.init(jax.random.PRNGKey(9), 16 + 12, 3 * 16, use_bias=True)
    )
    rssm_biased = rssm.replace(
        recurrent_model=rssm.recurrent_model.replace(rnn=biased)
    )
    x = jnp.concatenate([b["post"].reshape(b["post"].shape[0], -1), b["act"]], -1)
    assert rssm_biased._fused_step_weights(x, b["emb"]) is None
    # and the full step still runs (plain path)
    out = rssm_biased.dynamic(
        b["post"], b["rec"], b["act"], b["emb"], b["first"], b["key"]
    )
    assert all(np.all(np.isfinite(np.asarray(o, dtype=np.float32))) for o in out)


def test_default_width_fused_rssm_refusal_is_recorded(pallas_interpret, tmp_path):
    """At the default DreamerV3 width the fused RSSM step is never selected
    (its six weights outgrow the whole-weights-in-VMEM budget) — and that
    decision is a `kernel.select` telemetry event, not a silent fall-through."""
    import json

    from sheeprl_tpu import nn
    from sheeprl_tpu.algos.dreamer_v3.agent import RSSM, RecurrentModel
    from sheeprl_tpu.telemetry import Telemetry

    # DreamerV3Args defaults: 32x32 latent + 2 actions, recurrent/dense/hidden
    # 512, cnn x32 on 64x64 -> 4096-wide embedding
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rm = RecurrentModel.init(ks[0], 32 * 32 + 2, 512, 512, layer_norm=True, activation="silu")
    tm = nn.MLP.init(ks[1], 512, [512], 32 * 32, act="silu", layer_norm=True,
                     use_bias=False, norm_eps=1e-3)
    pm = nn.MLP.init(ks[2], 512 + 4096, [512], 32 * 32, act="silu", layer_norm=True,
                     use_bias=False, norm_eps=1e-3)
    rssm = RSSM(recurrent_model=rm, representation_model=pm, transition_model=tm,
                discrete=32, unimix=0.01)
    telem = Telemetry(str(tmp_path))
    try:
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jnp.zeros((16, 32 * 32 + 2), dtype)
            emb = jnp.zeros((16, 4096), dtype)
            assert rssm._fused_step_weights(x, emb) is None
    finally:
        telem.close()
    with open(tmp_path / "telemetry.jsonl") as fh:
        events = [json.loads(line) for line in fh]
    picks = [e for e in events if e["event"] == "kernel.select" and e["family"] == "rssm"]
    assert len(picks) == 2
    for e in picks:
        assert e["selected"] is False and e["reason"] == "vmem"
        assert e["bytes"] > e["budget"] == pk._FUSED_VMEM_BUDGET_BYTES
    # f32 weights are twice the bf16 ones (22.0 vs 11.0 MiB against 10 MiB)
    assert picks[0]["bytes"] > picks[1]["bytes"]


def test_partitioned_jit_takes_the_xla_twin_and_says_so(tmp_path):
    """Mosaic cannot auto-partition a kernel ("wrap the call in a shard_map"),
    so a dispatch site whose operand is typed with a multi-device mesh takes
    the XLA twin — recorded as `kernel.select reason=partitioned`. Kernels are
    forced on WITHOUT the interpreter here: reaching Mosaic would fail on CPU."""
    import json

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sheeprl_tpu.telemetry import Telemetry

    cell = LayerNormGRUCell.init(jax.random.PRNGKey(0), 6, 8, use_bias=False)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (8, 6)), NamedSharding(mesh, P("data"))
    )
    h = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (8, 8)), NamedSharding(mesh, P("data"))
    )
    want = cell(x, h)  # auto mode on CPU: kernels off
    # the policy-step shape of the same hazard: inputs straight from the host
    # (no mesh in their type), params replicated over the mesh
    cell_r = jax.device_put(cell, NamedSharding(mesh, P()))
    x_host, h_host = jnp.asarray(np.asarray(x)), jnp.asarray(np.asarray(h))
    telem = Telemetry(str(tmp_path))
    pk.set_pallas(True, interpret=False)
    try:
        got = jax.jit(lambda c, a, b: c(a, b))(cell, x, h)
        got_r = jax.jit(lambda c, a, b: c(a, b))(cell_r, x_host, h_host)
    finally:
        pk.set_pallas(None, interpret=False)
        telem.close()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want), atol=1e-6)
    with open(tmp_path / "telemetry.jsonl") as fh:
        picks = [e for e in map(json.loads, fh) if e["event"] == "kernel.select"]
    assert [(e["family"], e["selected"], e["reason"], e["devices"]) for e in picks] == [
        ("gru", False, "partitioned", 4)
    ] * 2


# =============================================================================
# Decode attention over a contiguous key/value cache (the looped LM's policy step)
# =============================================================================

_S_MAX = 2 * pk.DECODE_BLOCK
_DECODE_POSITIONS = {
    "first_slot": [0, 0],
    "block_end": [pk.DECODE_BLOCK - 1] * 2,
    "next_block": [pk.DECODE_BLOCK] * 2,
    "last_slot": [_S_MAX - 1] * 2,
    "write_dropped": [_S_MAX] * 2,  # a row past its cache: every slot seen
    "mixed": [0, 5, pk.DECODE_BLOCK - 1, pk.DECODE_BLOCK, 200, _S_MAX - 1, _S_MAX, 130],
}


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["heads_per_kv_1", "heads_per_kv_2"])
@pytest.mark.parametrize("where", list(_DECODE_POSITIONS), ids=list(_DECODE_POSITIONS))
def test_decode_attention_matches_the_twin_and_reads_nothing_past_pos(pallas_interpret, where, kv_heads):
    """The kernel against `decode_attend`'s XLA twin on bf16 caches: slots
    past a row's position hold large garbage in the kernel's caches and zeros
    in the twin's, so an output that agrees has read nothing there."""
    from sheeprl_tpu.algos.ppo_lm.agent import decode_attend
    from sheeprl_tpu.nn.attention import Attention

    heads, d = 4, 128
    pos = np.asarray(_DECODE_POSITIONS[where], np.int32)
    b = len(pos)
    rng = np.random.default_rng(len(where) + kv_heads)
    attn = Attention.init(jax.random.PRNGKey(0), 64, heads, kv_heads, d, qk_norm=False)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, d)), jnp.bfloat16)
    past = (np.arange(_S_MAX)[None, :] > pos[:, None])[..., None, None]
    ck, cv = (rng.normal(size=(b, _S_MAX, kv_heads, d)) for _ in range(2))
    clean = [jnp.asarray(np.where(past, 0.0, c), jnp.bfloat16) for c in (ck, cv)]
    garbage = [jnp.asarray(np.where(past, fill, c), jnp.bfloat16) for c, fill in ((ck, 3e4), (cv, -3e4))]
    want = decode_attend(attn, q, *clean, jnp.asarray(pos), kernel=False)
    got = decode_attend(attn, q, *garbage, jnp.asarray(pos), kernel=True)
    assert got.shape == want.shape == (b, 1, heads * d) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=1e-2, rtol=1e-2)
    twin_on_garbage = decode_attend(attn, q, *garbage, jnp.asarray(pos), kernel=False)
    np.testing.assert_allclose(np.asarray(twin_on_garbage, np.float32), np.asarray(want, np.float32), atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("shape,reason", [
    ((4, 4, 256, 128), None),
    ((4, 4, 200, 128), "S_max 200"),
    ((4, 4, 256, 64), "head_dim 64"),
    ((6, 4, 256, 128), "6 heads"),
], ids=["inside", "cache_not_whole_blocks", "narrow_head", "uneven_groups"])
def test_decode_attention_contract(shape, reason):
    why = pk.decode_attention_refusal(*shape)
    assert why is None if reason is None else why.startswith(reason)
