"""Pallas TPU kernels for the framework's hot ops.

The kernel targets: the LayerNorm-GRU cell (the RSSM scan body, reference
/root/reference/sheeprl/models/models.py:330-402) and the two-hot log-prob
(reference utils/distribution.py:220-266). The CNN encoder/decoder stages
lost their chip measurement and are gone (PR 30): XLA lays a stage's arrays
out with the batch in the lanes and does conv + LayerNorm + SiLU 1.6 - 10 x
faster than a kernel in Mosaic's row-major layout plus the relayouts around
it; nn/blocks.py runs the plain layers. ISSUE 9 adds the whole RSSM dynamic
step (pre-MLP + LN-GRU + prior/posterior head stacks) as ONE kernel,
`fused_rssm_step` below; `decode_attention` reads a language model's
key/value caches one query token a row, only up to each row's position.
Each kernel here

  - fuses what XLA would otherwise stage through HBM: the GRU kernel keeps the
    [B, 3H] pre-activation entirely in VMEM between the MXU matmul, the
    layernorm moments, and the gate math; the two-hot kernel never
    materializes the [N, K] two-hot target at all;
  - differentiates: forward runs the kernel, backward is an analytic VJP
    (two-hot) or a recompute-in-XLA VJP (GRU) so training numerics
    stay exact;
  - is gated: `use_pallas()` is on when the default backend is a TPU, the
    SHEEPRL_TPU_PALLAS env var forces on/off, and interpret mode runs the
    same kernels on CPU for numerics tests (`set_pallas(True,
    interpret=True)`; tracing a kernel that way on a TPU backend raises).

Callers (nn.recurrent.LayerNormGRUCell, ops.distributions.TwoHotEncoding-
Distribution, ...) take their plain-XLA paths whenever the kernels are
disabled or the shapes are unsupported, so behavior is identical either way —
and every such dispatch decision lands in telemetry as one `kernel.select`
event per trace (:func:`select`), so a run's record says which program ran.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..telemetry.core import emit as _telemetry_emit

_VMEM = pltpu.VMEM

__all__ = [
    "KERNEL_NAMES",
    "use_pallas",
    "pallas_enabled",
    "set_pallas",
    "select",
    "layernorm_gru_cell",
    "fused_rssm_step",
    "rssm_step_reference",
    "fused_int8_trunk",
    "int8_trunk_reference",
    "fused_int8_trunk_supported",
    "two_hot_log_prob",
    "DECODE_BLOCK",
    "decode_attention",
    "decode_attention_refusal",
]

# The names the kernels carry on the device, by family (the `kind` of
# `use_pallas` / `select`). `pallas_call(name=)` names the HLO instruction
# (`%gru_fwd_res.7`), and a profiler trace names the kernel's events by it, so
# a reduction finds a family by these prefixes whatever the shapes are.
# `<x>_fwd` is the forward as called outside differentiation (a policy step),
# `<x>_fwd_res` the forward under differentiation, which also writes the
# residuals its backward reads. Every family's backward is plain XLA: no
# kernel is a `_bwd`.
KERNEL_NAMES: dict[str, tuple[str, ...]] = {
    "gru": ("gru_fwd", "gru_fwd_res"),
    "rssm": ("rssm_step_fwd",),
    "two_hot": ("two_hot_fwd",),
    "sac_trunk": ("int8_trunk_fwd",),
    "decode_attn": ("decode_attn_fwd",),
}


_FORCED: bool | None = None
_INTERPRET = False  # tests flip this to run kernels on CPU


def set_pallas(enabled: bool | None, interpret: bool = False) -> None:
    """Force kernels on/off (None = auto: on when the default backend is
    TPU). `interpret=True` runs kernels in the Pallas interpreter (CPU)."""
    global _FORCED, _INTERPRET
    _FORCED, _INTERPRET = enabled, interpret


@functools.cache
def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_mode() -> bool:
    """The interpret flag every `pallas_call` here passes, read at trace
    time (flips made after import must be seen). On a TPU backend a kernel
    traced under the interpreter is an error, not a slow path."""
    if _INTERPRET and _backend_is_tpu():
        raise RuntimeError(
            "a Pallas kernel is being traced with interpret=True on a TPU "
            "backend; interpret mode is for CPU numerics tests only "
            "(set_pallas(..., interpret=False))"
        )
    return _INTERPRET


def select(family: str, selected: bool, reason: str | None = None, **detail) -> bool:
    """Record one trace-time dispatch decision of a kernel family as a
    `kernel.select` telemetry event (no-op without a live Telemetry) and
    return `selected`. `reason` is "ok" when the kernel runs, else why the
    XLA twin does: "disabled" (gate off), "partitioned" (the jit spans
    several devices, see :func:`use_pallas`), "structure" (module/shape
    outside the kernel's contract — the default for a refusal) or "vmem"
    (with `bytes` vs `budget`)."""
    _telemetry_emit(
        "kernel.select", family=family, selected=bool(selected),
        reason=reason or ("ok" if selected else "structure"),
        interpret=_INTERPRET, **detail,
    )
    return bool(selected)


def _env_flag(name: str) -> bool | None:
    env = os.environ.get(name, "").lower()
    if env in ("1", "on", "true"):
        return True
    if env in ("0", "off", "false"):
        return False
    return None


def pallas_enabled(kind: str | None = None) -> bool:
    """The gate's switches alone, recording nothing: the master
    (`set_pallas`, SHEEPRL_TPU_PALLAS, else on a TPU backend) refined per
    kernel family via SHEEPRL_TPU_PALLAS_<KIND>."""
    if _FORCED is not None:
        enabled = _FORCED
    else:
        master = _env_flag("SHEEPRL_TPU_PALLAS")
        enabled = _backend_is_tpu() if master is None else master
    if enabled and kind is not None:
        per_kind = _env_flag(f"SHEEPRL_TPU_PALLAS_{kind.upper()}")
        if per_kind is not None:
            enabled = per_kind
    return enabled


def use_pallas(kind: str | None = None, *operands) -> bool:
    """Master gate, optionally refined per kernel family via
    SHEEPRL_TPU_PALLAS_<KIND> (KIND in GRU|RSSM|TWO_HOT|SAC_TRUNK|DECODE_ATTN),
    so a chip run can set one family's XLA twin against its kernel.

    With `kind` (a dispatch site asking for its family) a refusal is
    recorded (:func:`select`): "disabled", or "partitioned" when any leaf
    of `operands` — the kernel's inputs AND weights: a policy step's pixels
    come untyped from the host while its replicated params span the mesh —
    is typed with a mesh of more than one device. jax carries the mesh in
    the aval of everything computed from a mesh-placed jit input, and
    Mosaic refuses such a call: "NotImplementedError:
    Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map." (jax 0.9.0; `dreamer_v3 --num_devices 4` on the
    v5e 2x2 host, PR 21). Until the kernels are wrapped in shard_map
    (ROADMAP S4), a jit partitioned over several devices takes the XLA
    twins; the interpreter lowers to plain XLA ops and is not affected."""
    if kind is None:
        return pallas_enabled()
    if not pallas_enabled(kind):
        return select(kind, False, "disabled")
    if not _INTERPRET:
        devices = max(
            (jax.typeof(leaf).sharding.mesh.size
             for leaf in jax.tree_util.tree_leaves(operands)),
            default=0,
        )
        if devices > 1:
            return select(kind, False, "partitioned", devices=devices)
    return True


def _block_all(shape_dtypes):
    return [pl.BlockSpec(memory_space=_VMEM) for _ in shape_dtypes]


# =============================================================================
# LayerNorm-GRU cell
# =============================================================================


def _gru_kernel(x_ref, h_ref, w_ref, scale_ref, offset_ref, out_ref, *, eps):
    """One fused step: [x,h] @ W -> layernorm -> reset/cand/update gates.

    Everything after the MXU matmul is VPU work on a [B, 3H] block that never
    leaves VMEM — the fusion XLA can't be relied on to produce inside a scan
    body (it re-materializes the pre-activation in HBM between the matmul and
    the normalization reductions)."""
    xh = jnp.concatenate([x_ref[:], h_ref[:]], axis=-1)
    parts = jnp.dot(xh, w_ref[:], preferred_element_type=jnp.float32)
    mean = jnp.mean(parts, axis=-1, keepdims=True)
    centered = parts - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    parts = centered * jax.lax.rsqrt(var + eps) * scale_ref[:] + offset_ref[:]
    hidden = h_ref.shape[-1]
    r = parts[:, :hidden]
    c = parts[:, hidden : 2 * hidden]
    u = parts[:, 2 * hidden :]
    update = jax.nn.sigmoid(u - 1.0)  # Hafner update-bias trick
    cand = jnp.tanh(jax.nn.sigmoid(r) * c)
    out = update * cand + (1.0 - update) * h_ref[:].astype(jnp.float32)
    out_ref[:] = out.astype(out_ref.dtype)


def _gru_kernel_with_residuals(
    x_ref, h_ref, w_ref, scale_ref, offset_ref, out_ref, hat_ref, rstd_ref, *, eps
):
    """Forward used under differentiation: additionally writes the normalized
    pre-gate activations and the per-row inverse stddev, from which the
    backward reconstructs everything with elementwise math + two matmuls
    (no full recompute)."""
    xh = jnp.concatenate([x_ref[:], h_ref[:]], axis=-1)
    parts = jnp.dot(xh, w_ref[:], preferred_element_type=jnp.float32)
    mean = jnp.mean(parts, axis=-1, keepdims=True)
    centered = parts - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    hat = centered * rstd
    post = hat * scale_ref[:] + offset_ref[:]
    hidden = h_ref.shape[-1]
    r = post[:, :hidden]
    c = post[:, hidden : 2 * hidden]
    u = post[:, 2 * hidden :]
    update = jax.nn.sigmoid(u - 1.0)
    cand = jnp.tanh(jax.nn.sigmoid(r) * c)
    out = update * cand + (1.0 - update) * h_ref[:].astype(jnp.float32)
    out_ref[:] = out.astype(out_ref.dtype)
    hat_ref[:] = hat
    rstd_ref[:] = rstd


def _gru_forward_with_residuals(x, h, w, scale, offset, eps):
    batch, hidden = h.shape
    dx = x.shape[-1]
    bn = min(_GRU_BLOCK_ROWS, batch)
    return pl.pallas_call(
        functools.partial(_gru_kernel_with_residuals, eps=eps),
        grid=(_cdiv(batch, bn),),
        out_shape=(
            jax.ShapeDtypeStruct((batch, hidden), x.dtype),
            jax.ShapeDtypeStruct((batch, 3 * hidden), jnp.float32),
            jax.ShapeDtypeStruct((batch, 1), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((bn, dx), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, hidden), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec(w.shape, lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec(scale.shape, lambda i: (0,), memory_space=_VMEM),
            pl.BlockSpec(offset.shape, lambda i: (0,), memory_space=_VMEM),
        ],
        out_specs=(
            pl.BlockSpec((bn, hidden), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, 3 * hidden), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=_VMEM),
        ),
        interpret=_interpret_mode(),
        name="gru_fwd_res",
    )(x, h, w, scale, offset)


def _gru_reference(x, h, w, scale, offset, eps):
    """Plain-XLA twin of the kernel (used for the recompute backward and as
    the numerics oracle in tests)."""
    parts = jnp.concatenate([x, h], axis=-1) @ w
    parts32 = parts.astype(jnp.float32)
    mean = jnp.mean(parts32, axis=-1, keepdims=True)
    var = jnp.var(parts32, axis=-1, keepdims=True)
    parts = ((parts32 - mean) * jax.lax.rsqrt(var + eps) * scale + offset).astype(
        x.dtype
    )
    r, c, u = jnp.split(parts, 3, axis=-1)
    update = jax.nn.sigmoid(u - 1.0)
    cand = jnp.tanh(jax.nn.sigmoid(r) * c)
    return update * cand + (1.0 - update) * h


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


_GRU_BLOCK_ROWS = 256  # VMEM budget: [256, 3H] blocks + the full weight


def _gru_forward(x, h, w, scale, offset, eps):
    batch, hidden = h.shape
    dx = x.shape[-1]
    bn = min(_GRU_BLOCK_ROWS, batch)
    return pl.pallas_call(
        functools.partial(_gru_kernel, eps=eps),
        grid=(_cdiv(batch, bn),),
        out_shape=jax.ShapeDtypeStruct((batch, hidden), x.dtype),
        in_specs=[
            pl.BlockSpec((bn, dx), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, hidden), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec(w.shape, lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec(scale.shape, lambda i: (0,), memory_space=_VMEM),
            pl.BlockSpec(offset.shape, lambda i: (0,), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((bn, hidden), lambda i: (i, 0), memory_space=_VMEM),
        interpret=_interpret_mode(),
        name="gru_fwd",
    )(x, h, w, scale, offset)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def layernorm_gru_cell(x, h, w, scale, offset, eps=1e-5):
    """Fused LayerNorm-GRU step: x [B, Dx], h [B, H], w [Dx+H, 3H],
    scale/offset [3H] -> new h [B, H]. Forward is the Pallas kernel; backward
    recomputes through the XLA twin (exact, and the [B, 3H] residual never
    needs saving)."""
    return _gru_forward(x, h, w, scale, offset, eps)


def _gru_fwd(x, h, w, scale, offset, eps):
    out, hat, rstd = _gru_forward_with_residuals(x, h, w, scale, offset, eps)
    return out, (x, h, w, scale, offset, hat, rstd)


def _gru_bwd(eps, residuals, g):
    """Analytic backward from the saved normalized activations: elementwise
    gate/LN chain rules plus the two unavoidable matmuls (dW, dxh)."""
    x, h, w, scale, offset, hat, rstd = residuals
    hidden = h.shape[-1]
    g = g.astype(jnp.float32)

    post = hat * scale + offset
    r = post[:, :hidden]
    c = post[:, hidden : 2 * hidden]
    u = post[:, 2 * hidden :]
    sr = jax.nn.sigmoid(r)
    pre_tanh = sr * c
    cand = jnp.tanh(pre_tanh)
    update = jax.nn.sigmoid(u - 1.0)

    d_update = g * (cand - h)
    d_cand = g * update
    dh_direct = g * (1.0 - update)
    d_u = d_update * update * (1.0 - update)
    d_pre = d_cand * (1.0 - cand * cand)
    d_c = d_pre * sr
    d_r = d_pre * c * sr * (1.0 - sr)
    dpost = jnp.concatenate([d_r, d_c, d_u], axis=-1)

    dscale = jnp.sum(dpost * hat, axis=0)
    doffset = jnp.sum(dpost, axis=0)
    dhat = dpost * scale
    # layernorm backward given hat and rstd
    m1 = jnp.mean(dhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dhat * hat, axis=-1, keepdims=True)
    dparts = rstd * (dhat - m1 - hat * m2)

    xh = jnp.concatenate([x, h], axis=-1)
    dw = xh.astype(jnp.float32).T @ dparts
    dxh = dparts @ w.astype(jnp.float32).T
    dx = dxh[:, : x.shape[-1]].astype(x.dtype)
    dh = (dxh[:, x.shape[-1] :] + dh_direct).astype(h.dtype)
    return dx, dh, dw.astype(w.dtype), dscale.astype(scale.dtype), doffset.astype(offset.dtype)


layernorm_gru_cell.defvjp(_gru_fwd, _gru_bwd)


# =============================================================================
# Fused RSSM dynamic step (ISSUE 9 tentpole b)
# =============================================================================
#
# The DreamerV3 dynamic step is six tiny matmuls with elementwise/LN glue:
#
#   z        = act(LN(x @ Wm))                      # RecurrentModel.mlp
#   h'       = LayerNormGRU(z, h; Wg, sg, og)       # the recurrence
#   prior    = (act(LN(h' @ Wt1)) @ Wt2) + bt2      # transition head
#   post     = (act(LN([h', emb] @ Wr1)) @ Wr2)+br2 # representation head
#
# At RSSM shapes ([B=16] rows through 512-wide layers, T=64 sequential scan
# steps) each stage is far below the MXU's efficient arithmetic intensity
# and XLA stages every intermediate through HBM inside the scan body — the
# per-step launch+memory overhead rivals the math (the round-4 duty-cycle
# analysis; same diagnosis as the RL-kernel fusion results of
# arXiv:2311.09445). This kernel runs the whole step out of VMEM: matmul
# operands stay in the input dtype (bf16 under the mixed-precision policy —
# the MXU's native reduced-precision path), every accumulation/normalization
# runs in f32 (`preferred_element_type`), and only three arrays leave the
# kernel: h' in the compute dtype and the two raw head outputs in f32 (the
# unimix/sampling fp32 island consumes them directly, so the bf16 audit
# sees no extra upcasts).
#
# The backward differentiates `rssm_step_reference` — a plain-XLA twin with
# IDENTICAL accumulation semantics — via jax.vjp (recompute-in-XLA, the
# same policy as the GRU kernel's documented backward): gradients are exact
# w.r.t. the twin, and the [B, ·] residuals never need saving.

_FUSED_VMEM_BUDGET_BYTES = 10 * 1024 * 1024  # weights must co-reside in VMEM

_KERNEL_ACTS = {
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "identity": lambda x: x,
}


def _ln(x32, scale, offset, eps):
    """f32 layernorm over the trailing axis (in-kernel and in the twin)."""
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centered = x32 - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    return centered * jax.lax.rsqrt(var + eps) * scale + offset


def _rssm_step_math(
    x, h, emb, wm, sm, om, wg, sg, og,
    wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    act, eps,
):
    """The shared step math: matmul operands in the input dtype, f32
    accumulations/normalizations/gates. Used verbatim by the Pallas kernel
    body and the XLA reference twin so the two are the same function."""
    act_fn = _KERNEL_ACTS[act]
    mlp_eps, gru_eps, head_eps = eps
    dt = x.dtype

    # RecurrentModel.mlp: Linear -> LN -> act
    z = jnp.dot(x, wm, preferred_element_type=jnp.float32)
    z = act_fn(_ln(z, sm, om, mlp_eps)).astype(dt)

    # LayerNorm-GRU (the _gru_kernel math)
    xh = jnp.concatenate([z, h], axis=-1)
    parts = _ln(
        jnp.dot(xh, wg, preferred_element_type=jnp.float32), sg, og, gru_eps
    )
    hidden = h.shape[-1]
    r = parts[:, :hidden]
    c = parts[:, hidden : 2 * hidden]
    u = parts[:, 2 * hidden :]
    update = jax.nn.sigmoid(u - 1.0)  # Hafner update-bias trick
    cand = jnp.tanh(jax.nn.sigmoid(r) * c)
    h_new32 = update * cand + (1.0 - update) * h.astype(jnp.float32)
    h_new = h_new32.astype(dt)

    # transition head (prior): MLP hidden -> LN -> act -> logits Linear
    t1 = jnp.dot(h_new, wt1, preferred_element_type=jnp.float32)
    t1 = act_fn(_ln(t1, st1, ot1, head_eps)).astype(dt)
    prior_raw = jnp.dot(t1, wt2, preferred_element_type=jnp.float32) + bt2

    # representation head (posterior): same shape over [h', emb]
    he = jnp.concatenate([h_new, emb], axis=-1)
    r1 = jnp.dot(he, wr1, preferred_element_type=jnp.float32)
    r1 = act_fn(_ln(r1, sr1, or1, head_eps)).astype(dt)
    post_raw = jnp.dot(r1, wr2, preferred_element_type=jnp.float32) + br2

    return h_new, prior_raw, post_raw


def _fused_rssm_kernel(
    x_ref, h_ref, emb_ref, wm_ref, sm_ref, om_ref, wg_ref, sg_ref, og_ref,
    wt1_ref, st1_ref, ot1_ref, wt2_ref, bt2_ref,
    wr1_ref, sr1_ref, or1_ref, wr2_ref, br2_ref,
    h_out_ref, prior_ref, post_ref, *, act, eps,
):
    h_new, prior_raw, post_raw = _rssm_step_math(
        x_ref[:], h_ref[:], emb_ref[:],
        wm_ref[:], sm_ref[:], om_ref[:],
        wg_ref[:], sg_ref[:], og_ref[:],
        wt1_ref[:], st1_ref[:], ot1_ref[:], wt2_ref[:], bt2_ref[:],
        wr1_ref[:], sr1_ref[:], or1_ref[:], wr2_ref[:], br2_ref[:],
        act, eps,
    )
    h_out_ref[:] = h_new.astype(h_out_ref.dtype)
    prior_ref[:] = prior_raw
    post_ref[:] = post_raw


def rssm_step_reference(
    x, h, emb, wm, sm, om, wg, sg, og,
    wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    act="silu", eps=(1e-3, 1e-5, 1e-3),
):
    """Plain-XLA twin of the fused kernel: the numerics oracle for the
    parity tests and the function the custom VJP differentiates."""
    return _rssm_step_math(
        x, h, emb, wm, sm, om, wg, sg, og,
        wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
        act, tuple(eps),
    )


_RSSM_BLOCK_ROWS = 128  # [128 rows x (3R + heads)] f32 working set in VMEM


def _fused_rssm_forward(
    x, h, emb, wm, sm, om, wg, sg, og,
    wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    act, eps,
):
    batch, hidden = h.shape
    sd = wt2.shape[-1]
    bn = min(_RSSM_BLOCK_ROWS, batch)

    def rows(a):
        return pl.BlockSpec((bn, a.shape[-1]), lambda i: (i, 0), memory_space=_VMEM)

    def whole(a):
        if a.ndim == 1:
            return pl.BlockSpec(a.shape, lambda i: (0,), memory_space=_VMEM)
        return pl.BlockSpec(a.shape, lambda i: (0, 0), memory_space=_VMEM)

    return pl.pallas_call(
        functools.partial(_fused_rssm_kernel, act=act, eps=eps),
        grid=(_cdiv(batch, bn),),
        out_shape=(
            jax.ShapeDtypeStruct((batch, hidden), x.dtype),
            jax.ShapeDtypeStruct((batch, sd), jnp.float32),
            jax.ShapeDtypeStruct((batch, sd), jnp.float32),
        ),
        in_specs=[
            rows(x), rows(h), rows(emb),
            whole(wm), whole(sm), whole(om),
            whole(wg), whole(sg), whole(og),
            whole(wt1), whole(st1), whole(ot1), whole(wt2), whole(bt2),
            whole(wr1), whole(sr1), whole(or1), whole(wr2), whole(br2),
        ],
        out_specs=(
            pl.BlockSpec((bn, hidden), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, sd), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, sd), lambda i: (i, 0), memory_space=_VMEM),
        ),
        interpret=_interpret_mode(),
        name="rssm_step_fwd",
    )(
        x, h, emb, wm, sm, om, wg, sg, og,
        wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(19, 20))
def fused_rssm_step(
    x, h, emb, wm, sm, om, wg, sg, og,
    wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    act="silu", eps=(1e-3, 1e-5, 1e-3),
):
    """One fused RSSM dynamic step.

    x [B, Dx] (posterior_flat ++ action), h [B, R], emb [B, E]; weights in
    the compute dtype (callers cast their f32 masters, like the Linear
    layers do), LN scales/offsets and head biases in f32.
    Returns (h' [B, R] compute dtype, prior_raw [B, S*D] f32,
    post_raw [B, S*D] f32) — raw pre-unimix logits; sampling stays outside
    (it needs PRNG keys and the f32 island).
    `eps` is (mlp_eps, gru_eps, head_eps); `act` must be a _KERNEL_ACTS key.
    """
    return _fused_rssm_forward(
        x, h, emb, wm, sm, om, wg, sg, og,
        wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
        act, tuple(eps),
    )


def _fused_rssm_fwd(
    x, h, emb, wm, sm, om, wg, sg, og,
    wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    act, eps,
):
    out = _fused_rssm_forward(
        x, h, emb, wm, sm, om, wg, sg, og,
        wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
        act, tuple(eps),
    )
    residuals = (
        x, h, emb, wm, sm, om, wg, sg, og,
        wt1, st1, ot1, wt2, bt2, wr1, sr1, or1, wr2, br2,
    )
    return out, residuals


def _fused_rssm_bwd(act, eps, residuals, g):
    """Recompute-in-XLA backward: one extra forward through the twin, exact
    gradients w.r.t. the kernel's accumulation semantics."""
    _, vjp = jax.vjp(
        lambda *args: _rssm_step_math(*args, act, tuple(eps)), *residuals
    )
    return vjp(g)


fused_rssm_step.defvjp(_fused_rssm_fwd, _fused_rssm_bwd)


def _fits_vmem(family: str, weights) -> bool:
    """The whole-weights-in-VMEM guard of the fused kernels; records the
    verdict (selected / refused with bytes vs budget)."""
    total = sum(int(w.size) * w.dtype.itemsize for w in weights)
    ok = total <= _FUSED_VMEM_BUDGET_BYTES
    return select(
        family, ok, None if ok else "vmem",
        bytes=total, budget=_FUSED_VMEM_BUDGET_BYTES,
    )


def fused_rssm_supported(act: str, *weights) -> bool:
    """Trace-time dispatch guard shared with the RSSM module: the activation
    must have an in-kernel implementation and the step's weights must
    co-reside in VMEM with room for the row blocks."""
    if act not in _KERNEL_ACTS:
        return select("rssm", False, detail=f"activation {act!r}")
    return _fits_vmem("rssm", weights)


# =============================================================================
# Fused int8 SAC trunk (ISSUE 20 tentpole c)
# =============================================================================
#
# The quantized SAC serve trunk is three int8 matmuls with relu glue:
#
#   a0   = relu((q(x  / s0) @ W0q) * ws0 + b0)     # trunk layer 0
#   a1   = relu((q(a0 / s1) @ W1q) * ws1 + b1)     # trunk layer 1
#   mean =      (q(a1 / sm) @ Wmq) * wsm + bm      # fc_mean head
#
# (q = round-to-nearest symmetric int8, ops/quant.py). At serve rung shapes
# ([B<=8] rows through 256-wide layers) every stage is far below the MXU's
# efficient arithmetic intensity and XLA stages each dequantized f32
# activation through HBM between layers — the same per-step overhead
# diagnosis as the fused RSSM step above. This kernel keeps the whole trunk
# in VMEM: int8 x int8 matmuls accumulate in int32 on the MXU's native
# int8 path, dequant/requant between layers is VPU work on blocks that
# never leave VMEM, and only the f32 `mean` leaves the kernel (the
# tanh * action_scale + action_bias squash stays outside in the f32
# island, exactly like sampling stays outside the RSSM kernel).
#
# Inference-only: no custom VJP — the serve tier never differentiates the
# policy, and the quality receipt in compile/decisions.py is measured
# against `int8_trunk_reference`, the plain-XLA twin sharing this math
# function verbatim (integer matmuls + same-order f32 ops, so kernel vs
# twin parity is exact, not approximate).


def _int8_trunk_math(
    x, s0, w0, ws0, b0, s1, w1, ws1, b1, sm, wm, wsm, bm
):
    """The shared trunk math, used verbatim by the Pallas kernel body and
    the XLA reference twin. Layer boundaries are f32 islands; matmuls are
    int8 x int8 with int32 accumulation (`ops.quant.int8_linear`)."""
    from .quant import int8_linear

    a0 = jax.nn.relu(int8_linear(x, s0, w0, ws0, b0))
    a1 = jax.nn.relu(int8_linear(a0, s1, w1, ws1, b1))
    return int8_linear(a1, sm, wm, wsm, bm)


def _fused_int8_kernel(
    x_ref, s0_ref, w0_ref, ws0_ref, b0_ref,
    s1_ref, w1_ref, ws1_ref, b1_ref,
    sm_ref, wm_ref, wsm_ref, bm_ref, out_ref,
):
    out_ref[:] = _int8_trunk_math(
        x_ref[:], s0_ref[:], w0_ref[:], ws0_ref[:], b0_ref[:],
        s1_ref[:], w1_ref[:], ws1_ref[:], b1_ref[:],
        sm_ref[:], wm_ref[:], wsm_ref[:], bm_ref[:],
    )


def int8_trunk_reference(x, s0, w0, ws0, b0, s1, w1, ws1, b1, sm, wm, wsm, bm):
    """Plain-XLA twin of the fused kernel: the numerics oracle for the
    parity tests and the fallback when the kernel is gated off."""
    return _int8_trunk_math(
        x, s0, w0, ws0, b0, s1, w1, ws1, b1, sm, wm, wsm, bm
    )


_INT8_BLOCK_ROWS = 128  # int8 min tile is (32, 128); row blocks stay modest


def fused_int8_trunk(x, s0, w0, ws0, b0, s1, w1, ws1, b1, sm, wm, wsm, bm):
    """One fused quantized SAC trunk step: x [B, Dx] f32, per layer
    (in_scale [Din] f32, w_q [Din, Dout] int8, w_scale [Dout] f32,
    bias [Dout] f32) -> raw mean [B, A] f32 (pre-squash)."""
    batch = x.shape[0]
    out_dim = wm.shape[-1]
    bn = min(_INT8_BLOCK_ROWS, batch)

    def rows(a):
        return pl.BlockSpec((bn, a.shape[-1]), lambda i: (i, 0), memory_space=_VMEM)

    def whole(a):
        if a.ndim == 1:
            return pl.BlockSpec(a.shape, lambda i: (0,), memory_space=_VMEM)
        return pl.BlockSpec(a.shape, lambda i: (0, 0), memory_space=_VMEM)

    return pl.pallas_call(
        _fused_int8_kernel,
        grid=(_cdiv(batch, bn),),
        out_shape=jax.ShapeDtypeStruct((batch, out_dim), jnp.float32),
        in_specs=[
            rows(x),
            whole(s0), whole(w0), whole(ws0), whole(b0),
            whole(s1), whole(w1), whole(ws1), whole(b1),
            whole(sm), whole(wm), whole(wsm), whole(bm),
        ],
        out_specs=pl.BlockSpec(
            (bn, out_dim), lambda i: (i, 0), memory_space=_VMEM
        ),
        interpret=_interpret_mode(),
        name="int8_trunk_fwd",
    )(x, s0, w0, ws0, b0, s1, w1, ws1, b1, sm, wm, wsm, bm)


def fused_int8_trunk_supported(*weights) -> bool:
    """Trace-time dispatch guard (the fused_rssm_supported pattern): the
    trunk's quantized weights + scales + biases must co-reside in VMEM
    with room for the row blocks."""
    return _fits_vmem("sac_trunk", weights)


# =============================================================================
# Two-hot cross-entropy (the DreamerV3 reward/critic log-prob)
# =============================================================================


def _two_hot_log_prob_kernel(x_ref, logits_ref, bins_ref, out_ref):
    """log p(x) under a categorical over `bins` with two-hot targets, without
    materializing the [N, K] target: for each row, find the bracketing bins
    by comparison counts, turn distances into the two interpolation weights,
    and contract against the log-softmax row on the fly."""
    x = x_ref[:]  # [N, 1]
    logits = logits_ref[:]  # [N, K]
    bins = bins_ref[:]  # [1, K]
    k = logits.shape[-1]

    log_z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1, keepdims=True)
    log_probs = logits.astype(jnp.float32) - log_z  # [N, K]

    below = jnp.sum((bins <= x).astype(jnp.int32), axis=-1, keepdims=True) - 1
    above = k - jnp.sum((bins > x).astype(jnp.int32), axis=-1, keepdims=True)
    below = jnp.clip(below, 0, k - 1)
    above = jnp.clip(above, 0, k - 1)
    equal = below == above

    idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)  # [N, K]
    below_onehot = (idx == below).astype(jnp.float32)
    above_onehot = (idx == above).astype(jnp.float32)
    bin_below = jnp.sum(bins * below_onehot, axis=-1, keepdims=True)
    bin_above = jnp.sum(bins * above_onehot, axis=-1, keepdims=True)
    d_below = jnp.where(equal, 1.0, jnp.abs(bin_below - x))
    d_above = jnp.where(equal, 1.0, jnp.abs(bin_above - x))
    total = d_below + d_above
    w_below = d_above / total
    w_above = d_below / total

    lp_below = jnp.sum(log_probs * below_onehot, axis=-1, keepdims=True)
    lp_above = jnp.sum(log_probs * above_onehot, axis=-1, keepdims=True)
    out_ref[:] = w_below * lp_below + w_above * lp_above


_TWO_HOT_BLOCK_ROWS = 1024  # [1024, K~255] f32 working set stays well under VMEM


def _two_hot_forward(x, logits, bins):
    n, k = logits.shape
    bn = min(_TWO_HOT_BLOCK_ROWS, n)
    return pl.pallas_call(
        _two_hot_log_prob_kernel,
        grid=(_cdiv(n, bn),),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((bn, k), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=_VMEM),
        interpret=_interpret_mode(),
        name="two_hot_fwd",
    )(x, logits, bins)


@jax.custom_vjp
def two_hot_log_prob(x, logits, bins):
    """x [N, 1] scalar targets, logits [N, K], bins [1, K] -> log-prob [N, 1].

    Gradient flows to `logits` only (the DreamerV3 losses treat the two-hot
    target as a constant): d/dlogits = (target - softmax(logits)) * g."""
    return _two_hot_forward(x, logits, bins)


def _two_hot_fwd(x, logits, bins):
    return _two_hot_forward(x, logits, bins), (x, logits, bins)


def _two_hot_bwd(residuals, g):
    from .math import two_hot as dense_two_hot

    x, logits, bins = residuals
    target = dense_two_hot(x[:, 0], bins[0])  # [N, K]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dlogits = ((target - probs) * g).astype(logits.dtype)
    return jnp.zeros_like(x), dlogits, jnp.zeros_like(bins)


two_hot_log_prob.defvjp(_two_hot_fwd, _two_hot_bwd)


# =============================================================================
# Decode attention over a contiguous key/value cache
# =============================================================================
#
# One query token a row against the row's cache [S_max, kv heads, D], where
# only the slots up to the row's own position `pos` hold its sequence. The
# XLA twin (`algos/ppo_lm/agent.py`) scores all S_max slots and masks the
# ones past `pos` after reading them; at a mean context of ~40 % of S_max
# that streams 2.5 x the bytes a row holds. The kernel walks the rows in
# turn and, for each, only the blocks of DECODE_BLOCK slots that hold a slot
# <= pos: manual double-buffered DMAs from HBM, the next block's copy (the
# next row's first, at a row's last) started before the current one is
# waited on, so no grid step is spent on a block nobody reads. Per block:
# the scores on the VPU in float32 (q * k summed over D), slots past `pos`
# set to NEG as the twin does, and an online softmax with float32 running
# max, sum and accumulator. Inference only: no VJP (the update reads whole
# sequences through `LoopedLM.hidden`).

# cache slots a DMA brings in: 512 KiB of K and of V at 16 heads x 128 in bf16.
# 256 streams a full cache 5 % faster on a v5e but reads 64 more slots of
# each row's last block, a loss at contexts of ~40 % of S_max (PERF.md)
DECODE_BLOCK = 128
NEG = -1e30  # a masked score, as `nn.attention.NEG`


def decode_attention_refusal(num_heads: int, num_kv_heads: int, s_max: int, head_dim: int) -> str | None:
    """Why a cache of this shape is outside the kernel's contract; None where it is inside."""
    if s_max % DECODE_BLOCK:
        return f"S_max {s_max} is not a multiple of {DECODE_BLOCK}"
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of 128"
    if num_heads % num_kv_heads:
        return f"{num_heads} heads do not share {num_kv_heads} key/value heads evenly"
    return None


def _decode_attn_kernel(pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, *, scale):
    """Grid step b: row b's attention over its valid blocks. q_ref / o_ref
    [1, g, kv heads, D] (query head kv * g + i at [i, kv]), k_buf / v_buf
    [2, DECODE_BLOCK, kv heads, D], slot_ref the buffer the next wait finds."""
    b, rows = pl.program_id(0), pl.num_programs(0)
    s_max = k_hbm.shape[1]
    g = q_ref.shape[1]

    def copies(row, j, slot):
        span = pl.ds(pl.multiple_of(j * DECODE_BLOCK, DECODE_BLOCK), DECODE_BLOCK)
        return (pltpu.make_async_copy(k_hbm.at[row, span], k_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[row, span], v_buf.at[slot], sems.at[1, slot]))

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    pos = pos_ref[b]
    blocks = jnp.minimum(pos, s_max - 1) // DECODE_BLOCK + 1  # pos >= S_max: a dropped write, every slot seen
    q = q_ref[0].astype(jnp.float32) * scale  # [g, kv heads, D]

    def block(j, carry):
        slot = slot_ref[0]

        @pl.when(j + 1 < blocks)
        def _():
            for c in copies(b, j + 1, 1 - slot):
                c.start()

        @pl.when(jnp.logical_and(j + 1 == blocks, b + 1 < rows))
        def _():
            for c in copies(jnp.minimum(b + 1, rows - 1), 0, 1 - slot):
                c.start()

        for c in copies(b, j, slot):
            c.wait()
        slot_ref[0] = 1 - slot
        k = k_buf[slot].astype(jnp.float32)  # [DECODE_BLOCK, kv heads, D]
        v = v_buf[slot].astype(jnp.float32)
        out = []
        for i in range(g):
            m, l, acc = carry[i]
            s = jnp.sum(k * q[i][None], axis=-1, keepdims=True)  # [DECODE_BLOCK, kv heads, 1]
            seen = j * DECODE_BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) <= pos
            s = jnp.where(seen, s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[None])
            out.append((m_new, alpha * l + jnp.sum(e, axis=0), alpha * acc + jnp.sum(e * v, axis=0)))
        return tuple(out)

    kv_heads, d = q.shape[1:]
    start = (jnp.full((kv_heads, 1), -jnp.inf, jnp.float32), jnp.zeros((kv_heads, 1), jnp.float32), jnp.zeros((kv_heads, d), jnp.float32))
    done = jax.lax.fori_loop(0, blocks, block, (start,) * g)
    for i, (_, l, acc) in enumerate(done):
        o_ref[0, i] = (acc / l).astype(o_ref.dtype)



def decode_attention(q, cache_k, cache_v, pos):
    """q [B, heads, D] (turned), cache_k / cache_v [B, S_max, kv heads, D],
    pos [B] int32 -> [B, heads * D] in q's dtype: each row's softmax(q k^T /
    sqrt(D)) v over its slots 0..pos, reading only the DECODE_BLOCK-slot
    blocks that hold them. The shape must pass `decode_attention_refusal`."""
    B, heads, d = q.shape
    s_max, kv_heads = cache_k.shape[1], cache_k.shape[2]
    g = heads // kv_heads
    q = q.reshape(B, kv_heads, g, d).transpose(0, 2, 1, 3)  # [B, g, kv heads, D]
    row = pl.BlockSpec((1, g, kv_heads, d), lambda b, pos_ref: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=1.0 / float(d) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, DECODE_BLOCK, kv_heads, d), cache_k.dtype),
                pltpu.VMEM((2, DECODE_BLOCK, kv_heads, d), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, g, kv_heads, d), q.dtype),
        # sequential: a row's last block starts the next row's first copy
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret_mode(),
        name="decode_attn_fwd",
    )(pos.astype(jnp.int32), q, cache_k, cache_v)
    return out.transpose(0, 2, 1, 3).reshape(B, heads * d)
