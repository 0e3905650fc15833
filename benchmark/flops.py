"""Operations and bytes the algorithm needs, from shapes alone.

Counted as the mathematics requires them (2 per multiply-add; a backward
pass twice its forward), not as a compiler emitted them: recomputation and
padding do not count, so a share of the peak worked out from these can only
be too low, never above 100 %.
"""

from __future__ import annotations

import math
import re


def dense(rows: int, n_in: int, n_out: int) -> float:
    return 2.0 * rows * n_in * n_out


def conv(rows: int, out_hw: int, k: int, c_in: int, c_out: int) -> float:
    """A k x k convolution producing out_hw x out_hw x c_out per row."""
    return 2.0 * rows * out_hw * out_hw * k * k * c_in * c_out


def deconv_k4s2(rows: int, in_hw: int, c_in: int, c_out: int) -> float:
    """A 4x4 stride-2 transposed convolution: each of the (2 in_hw)^2 output
    pixels sees 2 x 2 input pixels."""
    return 2.0 * rows * (2 * in_hw) ** 2 * 2 * 2 * c_in * c_out


def mlp(rows: int, n_in: int, units: int, layers: int, n_out: int) -> float:
    total, width = 0.0, n_in
    for _ in range(layers):
        total += dense(rows, width, units)
        width = units
    return total + dense(rows, width, n_out)


def dreamer_v3_parts(c: dict) -> dict[str, float]:
    """Forward operations of each part of the model for ONE row (one time
    step of one sequence, or one imagined state)."""
    m, units, hid, rec = c["cnn_channels_multiplier"], c["dense_units"], c["hidden_size"], c["recurrent_state_size"]
    stoch = c["stochastic_size"] * c["discrete_size"]
    latent, embed, layers = stoch + rec, 4 * 4 * 8 * m, c["mlp_layers"]
    chans = [c["image_channels"], m, 2 * m, 4 * m, 8 * m]
    return {
        "encoder": sum(conv(1, 32 >> i, 4, chans[i], chans[i + 1]) for i in range(4)),
        "recurrent": dense(1, stoch + c["actions"], units) + dense(1, units + rec, 3 * rec),
        "transition": dense(1, rec, hid) + dense(1, hid, stoch),
        "representation": dense(1, rec + embed, hid) + dense(1, hid, stoch),
        "decoder": dense(1, latent, embed) + sum(deconv_k4s2(1, 4 << i, chans[4 - i], chans[3 - i]) for i in range(4)),
        "reward": mlp(1, latent, units, layers, c["bins"]),
        "continue": mlp(1, latent, units, layers, 1),
        "actor": mlp(1, latent, units, layers, c["actions"]),
        "critic": mlp(1, latent, units, layers, c["bins"]),
    }


def dreamer_v3_train_step(c: dict) -> float:
    """Forward and backward operations of one DreamerV3 update on a batch of
    B sequences of T steps with an imagination horizon H.

    World model on T*B rows, forward and backward (3 x forward). Imagination
    from every one of the T*B states: H steps of the recurrent and transition
    models and H+1 of the actor, forward only (the trajectory carries no
    gradient). On the (H+1)*T*B imagined states: reward, continue and critic
    heads forward; the actor once more forward and backward for its loss;
    on H*T*B of them the target critic forward and the critic forward and
    backward.
    """
    p = dreamer_v3_parts(c)
    rows = c["per_rank_batch_size"] * c["per_rank_sequence_length"]
    h = c["horizon"]
    world = 3.0 * rows * (
        p["encoder"] + p["recurrent"] + p["transition"] + p["representation"] + p["decoder"] + p["reward"] + p["continue"]
    )
    imagine = rows * (h * (p["recurrent"] + p["transition"]) + (h + 1) * p["actor"])
    heads = rows * (h + 1) * (p["reward"] + p["continue"] + p["critic"])
    actor = 3.0 * rows * (h + 1) * p["actor"]
    critic = rows * h * (p["critic"] + 3.0 * p["critic"])
    return world + imagine + heads + actor + critic


def layernorm_gru_cell(rows: int, n_in: int, hidden: int) -> float:
    """Operations of one LayerNorm-GRU cell call: the [rows, n_in + hidden] x
    [n_in + hidden, 3 hidden] product. (Its bytes are read off the trace,
    `hbm_bytes`: which operands the compiler keeps on the chip differs from
    call site to call site.)"""
    return dense(rows, n_in + hidden, 3 * hidden)


DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "u8": 1, "s8": 1, "f16": 2, "pred": 1}
SHAPE = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")


def hbm_bytes(text: str) -> float:
    """Bytes that one executed operation moves over the chip's main-memory
    bus, from its text in the trace: every result and operand whose layout
    names no memory space. One that does (`{1,0:T(8,128)S(1)}`) was placed by
    the compiler in on-chip memory, e.g. a weight that a loop carries, and
    costs the bus nothing in this call."""
    head = text.split("custom_call_target")[0]
    return float(sum(
        DTYPE_BYTES[dt] * math.prod(int(d) for d in dims.split(",") if d)
        for dt, dims, layout in SHAPE.findall(head)
        if "S(" not in layout and dt in DTYPE_BYTES
    ))
