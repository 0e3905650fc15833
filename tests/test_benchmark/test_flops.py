"""The operation and byte counts against hand-worked values at size S."""

import pytest

from benchmark import flops

from .conftest import ROOT, load

S = {**load(f"{ROOT}/benchmark/configs/dv3_s_pixel_bf16.json")["args"], "actions": 18, "image_channels": 3}


def test_parts_at_s_by_hand():
    p = flops.dreamer_v3_parts(S)
    # encoder: 32x32x(16*3*32) + 16x16x(16*32*64) + 8x8x(16*64*128) + 4x4x(16*128*256) multiply-adds
    assert p["encoder"] == 2 * (1024 * 1536 + 256 * 32768 + 64 * 131072 + 16 * 524288) == 53_477_376
    # decoder: 1536x4096 projection, then 8^2, 16^2, 32^2, 64^2 output pixels of 2x2 taps
    assert p["decoder"] == 2 * (1536 * 4096 + 64 * 4 * 256 * 128 + 256 * 4 * 128 * 64 + 1024 * 4 * 64 * 32 + 4096 * 4 * 32 * 3)
    assert p["recurrent"] == 2 * ((1024 + 18) * 512 + 1024 * 1536)
    assert p["representation"] == 2 * ((512 + 4096) * 512 + 512 * 1024)
    assert p["critic"] == p["reward"] == 2 * (1536 * 512 + 512 * 512 + 512 * 255)


def test_train_step_at_s_is_the_sum_of_its_passes():
    p = flops.dreamer_v3_parts(S)
    rows, h = 16 * 64, 15
    world = 3 * rows * (p["encoder"] + p["recurrent"] + p["transition"] + p["representation"] + p["decoder"] + p["reward"] + p["continue"])
    behaviour = rows * (
        h * (p["recurrent"] + p["transition"]) + (h + 1) * (4 * p["actor"] + p["reward"] + p["continue"] + p["critic"]) + h * 4 * p["critic"]
    )
    total = flops.dreamer_v3_train_step(S)
    assert total == pytest.approx(world + behaviour, rel=1e-12)
    assert 0.85e12 < total < 0.95e12  # 0.90 TFLOP an update


SCAN_CALL = (
    "%closed_call.169 = (f32[16,512]{1,0:T(8,128)S(1)}, f32[16,1536]{1,0:T(8,128)S(1)}, f32[16,1]{1,0:T(8,128)S(1)}) "
    "custom-call(f32[16,512]{1,0:T(8,128)S(1)} %x, f32[16,512]{1,0:T(8,128)S(1)} %h, f32[1024,1536]{1,0:T(8,128)S(1)} %w, "
    "f32[1536]{0:T(1024)S(1)} %s, f32[1536]{0:T(1024)S(1)} %o), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={f32[16,512]{1,0}, f32[16,512]{1,0}, f32[1024,1536]{1,0}}"
)
IMAGINE_CALL = (
    "%closed_call.168 = (bf16[1024,512]{1,0:T(8,128)(2,1)S(1)}, f32[1024,1536]{1,0:T(8,128)}, f32[1024,1]{1,0:T(8,128)}) "
    "custom-call(bf16[1024,512]{1,0:T(8,128)(2,1)S(1)} %x, bf16[1024,512]{1,0:T(8,128)(2,1)} %h, bf16[1024,1536]{1,0:T(8,128)(2,1)S(1)} %w)"
)


@pytest.mark.parametrize("text,moved,bound", [
    (SCAN_CALL, 0, "operations"),  # the loop carries every operand on the chip
    (IMAGINE_CALL, 4 * (1024 * 1536 + 1024) + 2 * 1024 * 512, "operations"),
    ("%copy.9 = u8[86016,4,64,64,3]{3,2,4,1,0:T(8,128)(4,1)} copy(u8[86016,4,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} %ring)", 2 * 86016 * 4 * 64 * 64 * 3, None),
])
def test_bytes_over_the_bus_count_only_what_the_trace_places_in_main_memory(text, moved, bound):
    assert flops.hbm_bytes(text) == moved
    if bound:
        rows = int(text.split("[")[1].split(",")[0])
        ops = flops.layernorm_gru_cell(rows, 512, 512)
        assert ops == 2 * rows * 1024 * 1536
        assert ("operations" if ops / 197e12 > moved / 819e9 else "bytes") == bound
