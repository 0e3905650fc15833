"""Pallas TPU kernels for the DreamerV3 CNN encoder/decoder stages — the
fourth north-star kernel family (BASELINE.md; reference hot path
/root/reference/sheeprl/algos/dreamer_v3/agent.py:31-203 and
/root/reference/sheeprl/models/models.py:121-284).

Encoder stage = Conv2d(k4, s2, SAME, no bias) -> LayerNorm(C) -> SiLU.
Decoder stage = ConvTranspose2d(k4, s2, SAME, no bias) -> LayerNorm(C) -> SiLU,
computed in the subpixel formulation (dense 2x2 conv + depth-to-space, the
same regrouping as nn.layers.ConvTranspose2d._subpixel_k4s2).

What the fusion buys: the conv pre-activation, the LayerNorm moments and the
SiLU stay entirely in VMEM — XLA stages the conv output through HBM before
the channel-reduction LayerNorm can run.

Kernel shape discipline (learned against real-Mosaic, not interpret mode):
strided vector slices, concatenation of offset slices, minor-dim slicing and
non-tile-aligned reshapes are all rejected or fragile in Mosaic, so the
kernels see only 2-D row-block matmuls and leading-axis indexing:

  - the caller space-to-depth-packs the padded input (k4/s2 -> k2/s1 over
    phases) and pre-flattens the four 2x2-window tap matrices to
    [rows, Cin'] in XLA;
  - the kernel computes the conv as a sum of four 2-D matmuls (one per
    tap; weights arrive as leading-indexed [4|16, Cin', Cout] blocks),
    then LayerNorm+SiLU on the [rows, Cout] block;
  - for the decoder, LN/SiLU apply per-phase (each output pixel maps to
    exactly one phase, LN is per-pixel over channels), and the subpixel
    interleave happens XLA-side after the kernel.

Differentiation follows the GRU kernel's policy (pallas_kernels.py): the
forward-with-residuals kernel additionally emits the raw conv
pre-activation; the backward is plain XLA — it recomputes the LN stats
from the pre-activation with the forward's exact ops, then elementwise
LN/SiLU math plus XLA's own conv VJP for dx/dW — so training numerics are
exactly those of the unfused path.

Keep-decision: bench.py measures duty cycles with the family toggled via
SHEEPRL_TPU_PALLAS_CNN and keeps the winner, like every other family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_kernels import _VMEM, _cdiv, _interpret_mode, select, use_pallas

__all__ = ["conv_ln_silu", "deconv_ln_silu", "cnn_stage_supported"]


# rows of conv output aimed at one grid step (M dimension of the MXU matmul)
_ROWS_BLOCK = 2048
# VMEM budget for one grid step's tap + output blocks (bytes); Mosaic's
# scoped-vmem limit is 16 MiB and blocks are double-buffered across steps
_VMEM_ROW_BUDGET = 4 * 1024 * 1024


def _pad128(c: int) -> int:
    return -(-c // 128) * 128


def _pick_blk(rows: int, row_bytes: int) -> int:
    """Row-block size: target _ROWS_BLOCK, shrink to the VMEM budget
    (row_bytes = f32 bytes per row across all tap and output blocks,
    lane-padding included), keep a sublane multiple."""
    blk = min(rows, _ROWS_BLOCK, max(_VMEM_ROW_BUDGET // max(row_bytes, 1), 8))
    return max(8 * (blk // 8), min(rows, 8))


def cnn_stage_supported(kernel_shape, stride, padding, block_ok, act, *operands) -> bool:
    """Eligibility for the fused stage: the Dreamer k4/s2/SAME LayerNorm-SiLU
    miniblock exactly (callers take plain XLA otherwise). `block_ok` carries
    the caller's own conditions (affine LayerNorm, bias-free conv, shapes the
    kernel reproduces); `operands` are the stage's input and kernel (see
    `use_pallas`). Records the decision (`kernel.select`, family cnn)."""
    if not use_pallas("cnn", *operands):
        return False
    fits = (
        bool(block_ok)
        and tuple(kernel_shape[:2]) == (4, 4)
        and tuple(stride) == (2, 2)
        and padding == "SAME"
        and act == "silu"
    )
    return select("cnn", fits)


def _silu(z):
    return z * jax.nn.sigmoid(z)


def _ln_stats(pre, eps):
    """LN normalized activations + inverse stddev — the ONE definition both
    the forward kernels and the XLA backward recompute from, so their
    numerics cannot de-sync."""
    mean = jnp.mean(pre, axis=-1, keepdims=True)
    centered = pre - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return centered * rstd, rstd


def _ln_silu(pre, scale, offset, eps):
    """LayerNorm + SiLU on a [rows, C] block, f32 moments."""
    hat, _ = _ln_stats(pre, eps)
    return _silu(hat * scale + offset)


# =============================================================================
# encoder stage: conv k4/s2/SAME + LayerNorm + SiLU
# =============================================================================


def _enc_kernel(t0, t1, t2, t3, w_ref, scale_ref, offset_ref, y_ref, *, eps,
                residuals=False, pre_ref=None):
    """One [rows, Cout] block: sum of four 2-D tap matmuls + LN + SiLU.
    With residuals, the raw pre-activation is the single saved tensor (the
    backward recomputes the LN stats from it — one output instead of a
    [rows, Cout] + a 128-lane-padded [rows, 1])."""
    pre = None
    for uv, tap in enumerate((t0, t1, t2, t3)):
        d = jnp.dot(tap[:], w_ref[uv], preferred_element_type=jnp.float32)
        pre = d if pre is None else pre + d
    y_ref[:] = _ln_silu(pre, scale_ref[:], offset_ref[:], eps).astype(y_ref.dtype)
    if residuals:
        pre_ref[:] = pre


def _enc_taps(x):
    """Pad for SAME k4/s2, space-to-depth-pack the 2x2 phases into channels
    (k4/s2 -> k2/s1 over the phase grid), and flatten the four 2x2-window
    taps to [N*Ho*Wo, 4*Cin] row matrices — all XLA-side."""
    n, h, w, cin = x.shape
    ho, wo = h // 2, w // 2
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # H+2 = 2*(ho+1): the padded grid splits into phases exactly
    xp = (
        xp.reshape(n, ho + 1, 2, wo + 1, 2, cin)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(n, ho + 1, wo + 1, 4 * cin)
    )
    return [
        jax.lax.slice(xp, (0, u, v, 0), (n, u + ho, v + wo, 4 * cin)).reshape(
            n * ho * wo, 4 * cin
        )
        for u in range(2)
        for v in range(2)
    ]


def _enc_call(x, w3, scale, offset, eps, residuals):
    n, h, w, cin = x.shape
    ho, wo = h // 2, w // 2
    cout = w3.shape[-1]
    taps = _enc_taps(x)
    rows = n * ho * wo
    itemsize = 2 if x.dtype == jnp.bfloat16 else 4
    row_bytes = (
        4 * _pad128(4 * cin) * itemsize  # taps
        + _pad128(cout) * itemsize  # y
        + residuals * _pad128(cout) * 4  # saved pre-activation (f32)
    )
    blk = _pick_blk(rows, row_bytes)
    tap_spec = pl.BlockSpec((blk, 4 * cin), lambda i: (i, 0), memory_space=_VMEM)
    out_shape = [jax.ShapeDtypeStruct((rows, cout), x.dtype)]
    out_specs = [pl.BlockSpec((blk, cout), lambda i: (i, 0), memory_space=_VMEM)]
    if residuals:
        out_shape.append(jax.ShapeDtypeStruct((rows, cout), jnp.float32))
        out_specs.append(
            pl.BlockSpec((blk, cout), lambda i: (i, 0), memory_space=_VMEM)
        )
    kernel = functools.partial(_enc_kernel, eps=eps, residuals=residuals)
    if residuals:
        body = lambda a, b, c, d, wr, sr, or_, yr, pr: kernel(
            a, b, c, d, wr, sr, or_, yr, pre_ref=pr
        )
    else:
        body = kernel
    out = pl.pallas_call(
        body,
        grid=(_cdiv(rows, blk),),
        out_shape=tuple(out_shape) if residuals else out_shape[0],
        in_specs=[tap_spec] * 4
        + [
            pl.BlockSpec(w3.shape, lambda i: (0, 0, 0), memory_space=_VMEM),
            pl.BlockSpec(scale.shape, lambda i: (0,), memory_space=_VMEM),
            pl.BlockSpec(offset.shape, lambda i: (0,), memory_space=_VMEM),
        ],
        out_specs=tuple(out_specs) if residuals else out_specs[0],
        interpret=_interpret_mode(),
        name="cnn_enc_fwd_res" if residuals else "cnn_enc_fwd",
    )(*taps, w3, scale, offset)
    if residuals:
        y, pre = out
        return y.reshape(n, ho, wo, cout), pre.reshape(n, ho, wo, cout)
    return out.reshape(n, ho, wo, cout)


def _enc_w3(w):
    """[4, 4, Cin, Cout] conv kernel -> [4, 4*Cin, Cout] leading-indexed tap
    blocks matching _enc_taps' layout: tap (u, v) outer, space-to-depth
    phase (a, b) + channel minor (kh = 2u+a, kw = 2v+b)."""
    cin, cout = w.shape[2], w.shape[3]
    kk = w.reshape(2, 2, 2, 2, cin, cout)  # [u, a, v, b, cin, cout]
    return kk.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4 * cin, cout)


def _enc_conv(x, w):
    """The bare conv (XLA) — its VJP supplies dx/dW in the backward."""
    return jax.lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        window_strides=(2, 2),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _ln_silu_bwd(dy, pre, scale, offset, eps):
    """Grad of SiLU(LayerNorm(pre)) wrt pre / scale / offset. Recomputes the
    LN stats from the saved pre-activation via the forward's _ln_stats."""
    dy = dy.astype(jnp.float32)
    hat, rstd = _ln_stats(pre, eps)
    z = hat * scale + offset
    sig = jax.nn.sigmoid(z)
    dz = dy * (sig * (1.0 + z * (1.0 - sig)))  # SiLU'
    dscale = jnp.sum(dz * hat, axis=tuple(range(dz.ndim - 1)))
    doffset = jnp.sum(dz, axis=tuple(range(dz.ndim - 1)))
    g = dz * scale
    dpre = rstd * (
        g
        - jnp.mean(g, axis=-1, keepdims=True)
        - hat * jnp.mean(g * hat, axis=-1, keepdims=True)
    )
    return dpre, dscale, doffset


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def conv_ln_silu(x, w, scale, offset, eps=1e-3):
    """Fused Dreamer encoder stage. x: [N, H, W, Cin] (H, W even),
    w: [4, 4, Cin, Cout] conv kernel, scale/offset: LayerNorm affine."""
    return _enc_call(x, _enc_w3(w), scale, offset, eps, False)


def _conv_ln_silu_fwd(x, w, scale, offset, eps):
    y, pre = _enc_call(x, _enc_w3(w), scale, offset, eps, True)
    return y, (x, w, scale, offset, pre)


def _conv_ln_silu_bwd(eps, res, dy):
    x, w, scale, offset, pre = res
    dpre, dscale, doffset = _ln_silu_bwd(dy, pre, scale, offset, eps)
    _, conv_vjp = jax.vjp(_enc_conv, x, w)
    dx, dw = conv_vjp(dpre.astype(x.dtype))
    return dx, dw.astype(w.dtype), dscale.astype(scale.dtype), doffset.astype(offset.dtype)


conv_ln_silu.defvjp(_conv_ln_silu_fwd, _conv_ln_silu_bwd)


# =============================================================================
# decoder stage: subpixel deconv k4/s2/SAME + LayerNorm + SiLU
# =============================================================================


def _dec_kernel(t0, t1, t2, t3, w_ref, scale_ref, offset_ref, y_ref, *, eps,
                residuals=False, pre_ref=None):
    """Four output phases, each a sum of four 2-D tap matmuls + LN + SiLU
    (w_ref: [16, Cin, Cout] blocks indexed p*4 + ab). LN/SiLU apply in
    phase layout — each output pixel maps to exactly one phase — and the
    subpixel interleave happens XLA-side after."""
    taps = (t0[:], t1[:], t2[:], t3[:])
    for p in range(4):  # output phase (dh, dw) = divmod(p, 2)
        pre = None
        for ab in range(4):
            d = jnp.dot(
                taps[ab], w_ref[p * 4 + ab], preferred_element_type=jnp.float32
            )
            pre = d if pre is None else pre + d
        y_ref[p] = _ln_silu(pre, scale_ref[:], offset_ref[:], eps).astype(
            y_ref.dtype
        )
        if residuals:
            pre_ref[p] = pre


def _dec_taps(x):
    """Pad and flatten the four 2x2-window taps of the dense phase conv to
    [N*(H+1)*(W+1), Cin] row matrices — all XLA-side."""
    n, h, w, cin = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return [
        jax.lax.slice(xp, (0, a, b, 0), (n, a + h + 1, b + w + 1, cin)).reshape(
            n * (h + 1) * (w + 1), cin
        )
        for a in range(2)
        for b in range(2)
    ]


def _interleave_phases(ph, n, h, w):
    """[4, N*(h+1)*(w+1), C] phase rows -> [N, 2h, 2w, C] subpixel output
    (phase p = dh*2+dw; same selection as ConvTranspose2d._subpixel_k4s2)."""
    c = ph.shape[-1]
    ph = ph.reshape(4, n, h + 1, w + 1, c)
    row0 = jnp.stack([ph[0][:, :h, :w], ph[1][:, :h, 1:]], axis=3)
    row1 = jnp.stack([ph[2][:, 1:, :w], ph[3][:, 1:, 1:]], axis=3)
    return jnp.stack([row0, row1], axis=2).reshape(n, 2 * h, 2 * w, c)


def _dec_call(x, w3, scale, offset, eps, residuals):
    n, h, w, cin = x.shape
    cout = w3.shape[-1]
    taps = _dec_taps(x)
    rows = n * (h + 1) * (w + 1)
    itemsize = 2 if x.dtype == jnp.bfloat16 else 4
    row_bytes = 4 * _pad128(cin) * itemsize + 4 * _pad128(cout) * (
        itemsize + 4 * residuals
    )
    blk = _pick_blk(rows, row_bytes)
    tap_spec = pl.BlockSpec((blk, cin), lambda i: (i, 0), memory_space=_VMEM)
    out_shape = [jax.ShapeDtypeStruct((4, rows, cout), x.dtype)]
    out_specs = [
        pl.BlockSpec((4, blk, cout), lambda i: (0, i, 0), memory_space=_VMEM)
    ]
    if residuals:
        out_shape.append(jax.ShapeDtypeStruct((4, rows, cout), jnp.float32))
        out_specs.append(
            pl.BlockSpec((4, blk, cout), lambda i: (0, i, 0), memory_space=_VMEM)
        )
    kernel = functools.partial(_dec_kernel, eps=eps, residuals=residuals)
    if residuals:
        body = lambda a, b, c, d, wr, sr, or_, yr, pr: kernel(
            a, b, c, d, wr, sr, or_, yr, pre_ref=pr
        )
    else:
        body = kernel
    out = pl.pallas_call(
        body,
        grid=(_cdiv(rows, blk),),
        out_shape=tuple(out_shape) if residuals else out_shape[0],
        in_specs=[tap_spec] * 4
        + [
            pl.BlockSpec(w3.shape, lambda i: (0, 0, 0), memory_space=_VMEM),
            pl.BlockSpec(scale.shape, lambda i: (0,), memory_space=_VMEM),
            pl.BlockSpec(offset.shape, lambda i: (0,), memory_space=_VMEM),
        ],
        out_specs=tuple(out_specs) if residuals else out_specs[0],
        interpret=_interpret_mode(),
        name="cnn_dec_fwd_res" if residuals else "cnn_dec_fwd",
    )(*taps, w3, scale, offset)
    if residuals:
        y, pre = out
        return _interleave_phases(y, n, h, w), _interleave_phases(pre, n, h, w)
    return _interleave_phases(out, n, h, w)


def _dec_wmat(k):
    """[4, 4, Cin, Cout] transposed-conv kernel -> [4*Cin, 4*Cout] dense 2x2
    phase matrix, ordering matched to _dec_deconv's cols/phases (identical to
    ConvTranspose2d._subpixel_k4s2's regrouping)."""
    cin, cout = k.shape[2], k.shape[3]
    kk = k.reshape(2, 2, 2, 2, cin, cout)  # [a, dh, b, dw, cin, cout]
    return kk.transpose(0, 2, 4, 1, 3, 5).reshape(4 * cin, 4 * cout)


def _dec_w3(k):
    """[4, 4, Cin, Cout] transposed-conv kernel -> [16, Cin, Cout] blocks
    indexed p*4 + ab (p = output phase dh*2+dw, ab = tap a*2+b) — the
    leading-indexed layout _dec_kernel consumes (no minor-dim slicing)."""
    cin, cout = k.shape[2], k.shape[3]
    kk = k.reshape(2, 2, 2, 2, cin, cout)  # [a, dh, b, dw, cin, cout]
    return kk.transpose(1, 3, 0, 2, 4, 5).reshape(16, cin, cout)


def _dec_deconv(x, k):
    """The bare transposed conv (XLA subpixel formulation) — VJP source for
    the backward."""
    n, h, w, cin = x.shape
    cout = k.shape[3]
    kk = _dec_wmat(k.astype(x.dtype)).reshape(2, 2, cin, 4 * cout)
    ph = jax.lax.conv_general_dilated(
        x, kk, window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ).reshape(n, h + 1, w + 1, 2, 2, cout)
    row0 = jnp.stack([ph[:, :h, :w, 0, 0], ph[:, :h, 1:, 0, 1]], axis=3)
    row1 = jnp.stack([ph[:, 1:, :w, 1, 0], ph[:, 1:, 1:, 1, 1]], axis=3)
    return jnp.stack([row0, row1], axis=2).reshape(n, 2 * h, 2 * w, cout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def deconv_ln_silu(x, k, scale, offset, eps=1e-3):
    """Fused Dreamer decoder stage. x: [N, H, W, Cin],
    k: [4, 4, Cin, Cout] transposed-conv kernel, scale/offset: LN affine."""
    return _dec_call(x, _dec_w3(k), scale, offset, eps, False)


def _deconv_ln_silu_fwd(x, k, scale, offset, eps):
    y, pre = _dec_call(x, _dec_w3(k), scale, offset, eps, True)
    return y, (x, k, scale, offset, pre)


def _deconv_ln_silu_bwd(eps, res, dy):
    x, k, scale, offset, pre = res
    dpre, dscale, doffset = _ln_silu_bwd(dy, pre, scale, offset, eps)
    _, vjp = jax.vjp(_dec_deconv, x, k)
    dx, dk = vjp(dpre.astype(x.dtype))
    return dx, dk.astype(k.dtype), dscale.astype(scale.dtype), doffset.astype(offset.dtype)


deconv_ln_silu.defvjp(_deconv_ln_silu_fwd, _deconv_ln_silu_bwd)
