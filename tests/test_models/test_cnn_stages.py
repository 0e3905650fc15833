"""The Dreamer CNN stages (k4/s2/SAME conv or transposed conv, no bias +
affine LayerNorm + SiLU) as `CNN` / `DeCNN` run them, held to a reference
written out here from `jax.lax` alone, at the DreamerV3-S channel pairs.

They are XLA's own code on every backend: the fused Pallas stages lost their
chip measurement and are gone (PERF.md §6, PR 30). Under `--precision
bfloat16` a stage convolves in bf16 (f32 accumulation, bf16 result), takes
the LayerNorm moments in f32 and applies SiLU in bf16, as the official
DreamerV3 does; the tests below pin that too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.nn.blocks import CNN, DeCNN
from sheeprl_tpu.ops import pallas_kernels as pk

EPS = 1e-3
HIGHEST = jax.lax.Precision.HIGHEST


def _reference(kind, x, k, scale, offset):
    """float32 throughout, nothing of `sheeprl_tpu.nn` in it."""
    x, k = x.astype(jnp.float32), k.astype(jnp.float32)
    if kind == "enc":
        pre = jax.lax.conv_general_dilated(
            x, k, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    else:
        pre = jax.lax.conv_transpose(
            x, k, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    mean = pre.mean(-1, keepdims=True)
    var = jnp.square(pre - mean).mean(-1, keepdims=True)
    z = (pre - mean) / jnp.sqrt(var + EPS) * scale + offset
    return z / (1.0 + jnp.exp(-z))


def _block(kind, cin, cout, act_last=True):
    init = CNN.init if kind == "enc" else lambda *a, **kw: DeCNN.init(*a, act_last=act_last, **kw)
    return init(jax.random.PRNGKey(0), cin, channels=[cout], kernel_sizes=[4], strides=[2],
                act="silu", layer_norm=True, use_bias=False, norm_eps=EPS)


def _through_block(kind, block, x, k, scale, offset):
    layer = block.layers[0].replace(kernel=k)
    norm = block.norms[0].replace(scale=scale, offset=offset)
    return block.replace(layers=(layer,), norms=(norm,))(x)


def _stage_args(seed, n, h, cin, cout, dtype):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(n, h, h, cin)).astype(np.float32)).astype(dtype),
        jnp.asarray(rng.normal(size=(4, 4, cin, cout)).astype(np.float32) * (4.0 / (16 * cin)) ** 0.5),
        jnp.asarray(rng.normal(size=(cout,)).astype(np.float32) * 0.1 + 1.0),
        jnp.asarray(rng.normal(size=(cout,)).astype(np.float32) * 0.1),
    )


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (kind, n, h, cin, cout): the DreamerV3-S pairs, and a width (M's 48) that fills no whole lanes
S_STAGES = [
    ("enc", 2, 8, 3, 32), ("enc", 2, 8, 32, 64), ("enc", 3, 4, 64, 128), ("enc", 2, 8, 3, 48),
    ("dec", 2, 4, 128, 64), ("dec", 3, 4, 64, 32), ("dec", 2, 4, 96, 48),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind,n,h,cin,cout", S_STAGES, ids=[f"{s[0]}_{s[3]}_{s[4]}" for s in S_STAGES])
def test_a_stage_and_its_four_gradients_match_the_reference(kind, n, h, cin, cout, dtype):
    block = _block(kind, cin, cout)
    args = _stage_args(cin + cout, n, h, cin, cout, dtype)
    got = _through_block(kind, block, *args)
    want = _reference(kind, *args)
    side = h // 2 if kind == "enc" else 2 * h
    assert got.dtype == dtype and got.shape == want.shape == (n, side, side, cout)
    # bf16 carries 8 bits: one rounding of the pre-activation and one of the result
    tol = 5e-6 if dtype == jnp.float32 else 1e-2
    assert _rel(got, want) < tol

    def loss(fn):
        return lambda *a: jnp.square(fn(*a).astype(jnp.float32)).mean()

    g_got = jax.grad(loss(lambda *a: _through_block(kind, block, *a)), argnums=(0, 1, 2, 3))(*args)
    g_want = jax.grad(loss(lambda *a: _reference(kind, *a)), argnums=(0, 1, 2, 3))(*args)
    for name, gg, gw in zip(("x", "kernel", "scale", "offset"), g_got, g_want):
        assert gg.dtype == (dtype if name == "x" else jnp.float32), name  # f32 master parameters
        assert _rel(gg, gw) < 2 * tol, name


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_a_bf16_stage_convolves_in_bf16_and_takes_its_moments_in_f32():
    """The configuration's stated precision, read off the jaxpr: the conv's
    result is bf16 (the MXU accumulates in f32 either way), every reduction of
    the LayerNorm is over f32, the activation's input and result are bf16."""
    for kind, cin, cout in (("enc", 32, 64), ("dec", 64, 32)):
        block = _block(kind, cin, cout)
        x = jnp.zeros((2, 4, 4, cin), jnp.bfloat16)
        eqns = list(_eqns(jax.make_jaxpr(block)(x).jaxpr))
        convs = [e for e in eqns if e.primitive.name == "conv_general_dilated"]
        assert [e.outvars[0].aval.dtype for e in convs] == [jnp.bfloat16]
        reductions = [e for e in eqns if e.primitive.name.startswith("reduce_")]
        assert reductions and all(e.invars[0].aval.dtype == jnp.float32 for e in reductions)
        silu = [e for e in eqns if e.primitive.name == "logistic"]
        assert [e.invars[0].aval.dtype for e in silu] == [jnp.bfloat16]


@pytest.mark.parametrize("stack", ["encoder", "decoder"])
def test_no_stage_of_the_s_stacks_is_a_kernel_whatever_the_gate_says(stack):
    """DreamerV3-S channel counts, differentiated, with the Pallas gate forced
    on: the CNN stages are convolutions XLA lays out itself, not `pallas_call`s."""
    common = dict(kernel_sizes=[4] * 4, strides=[2] * 4, act="silu", layer_norm=True, use_bias=False, norm_eps=EPS)
    if stack == "encoder":
        net, x = CNN.init(jax.random.PRNGKey(0), 3, channels=[32, 64, 128, 256], **common), jnp.zeros((2, 64, 64, 3), jnp.bfloat16)
    else:
        net, x = DeCNN.init(jax.random.PRNGKey(0), 256, channels=[128, 64, 32, 3], **common), jnp.zeros((2, 4, 4, 256), jnp.bfloat16)
    was = pk._FORCED, pk._INTERPRET
    pk.set_pallas(True, interpret=True)
    try:
        jaxpr = jax.make_jaxpr(jax.grad(lambda m, v: jnp.square(m(v).astype(jnp.float32)).sum()))(net, x)
    finally:
        pk.set_pallas(*was)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert "pallas_call" not in names
    # four stages forward, four kernel gradients, three input gradients (the stack's own input is not differentiated)
    assert names.count("conv_general_dilated") == 11


def test_sequence_batch_fold_through_cnn():
    """[T, B, H, W, C] inputs (batch-major fold) agree with per-frame calls."""
    cnn = _block("enc", 3, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8, 8, 3))
    seq = cnn(x)
    per_frame = jnp.stack([
        jnp.stack([cnn(x[t, b]) for b in range(2)]) for t in range(3)
    ])
    np.testing.assert_allclose(np.asarray(seq), np.asarray(per_frame), atol=1e-5)


def test_the_decoders_last_stage_has_no_norm_and_no_activation():
    """`act_last=False` (the decoder-output convention): the last transposed
    conv's result leaves as it is, so a reconstruction can be negative."""
    dec = DeCNN.init(jax.random.PRNGKey(0), 8, channels=[4, 3], kernel_sizes=[4, 4], strides=[2, 2],
                     act="silu", layer_norm=True, use_bias=False, norm_eps=EPS)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4, 8))
    hidden = _reference("dec", x, dec.layers[0].kernel, dec.norms[0].scale, dec.norms[0].offset)
    want = jax.lax.conv_transpose(hidden, dec.layers[1].kernel, (2, 2), "SAME",
                                  dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    assert dec.norms[1] is None
    np.testing.assert_allclose(np.asarray(dec(x)), np.asarray(want), atol=2e-5)
