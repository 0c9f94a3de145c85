"""Operations and bytes counted from shapes, against hand counts."""
import json
import os

import pytest

from families import m3vit

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def spec(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


# per image, by hand: patch projection 196*768*d*2; per block q/k/v/out
# 197*d*4d*2 and attention 4*197^2*d; MLP 197*d*4d*2*2 (6 dense blocks);
# router 197*d*16*2 and two experts 2*197*d*4d*2*2 (6 expert blocks);
# head d*1000*2.
@pytest.mark.parametrize("name,d,hand", [
    ("m3vit-small", 384,
     115_605_504 + 12 * (232_390_656 + 59_610_624) + 6 * 464_781_312
     + 6 * (2_420_736 + 929_562_624) + 768_000),
    ("m3vit-tiny", 192,
     57_802_752 + 12 * (58_097_664 + 29_805_312) + 6 * 116_195_328
     + 6 * (1_210_368 + 232_390_656) + 384_000),
])
def test_flops_per_image_matches_hand_count(name, d, hand):
    s = spec(name)
    assert s["d_model"] == d
    assert m3vit.flops_per_image(s) == hand


def test_small_and_tiny_totals():
    assert m3vit.flops_per_image(spec("m3vit-small")) == 12_000_976_896
    assert round(m3vit.flops_per_image(spec("m3vit-tiny")) / 1e9, 2) == 3.21


@pytest.mark.parametrize("name", ["m3vit-small", "m3vit-tiny"])
@pytest.mark.parametrize("batch", [1, 8, 32])
def test_kernel_flops_cover_the_step(name, batch):
    """The kernels' operations are the whole step's but the patch
    projection (a plain XLA dot), and one step makes 67 int8, 12 attention
    and 12 grouped calls."""
    s = spec(name)
    calls = m3vit.kernel_flops(s, batch)
    assert [len(calls[k]) for k in ("int8_matmul", "streaming_attention",
                                    "grouped_matmul")] == [67, 12, 12]
    ops = sum(f for c in calls.values() for f in c)
    patch = 2 * (s["image_tokens"] - 1) * s["patch_dim"] * s["d_model"]
    assert ops == batch * (m3vit.flops_per_image(s) - patch)


def test_grouped_call_counts_routed_rows_only():
    f = m3vit.kernel_flops(spec("m3vit-small"), 32)["grouped_matmul"][0]
    assert f == 2 * (32 * 197 * 2) * 384 * 1536


# an int8 matmul call as a v5e trace names it (measured on one v5e chip): the weight,
# the activations, the per-channel scale and the result were kept in the
# chip's own memory (S(1)); only the [1, 1] activation scale is in HBM
INT8_OP = (
    "%closed_call.180 = f32[6400,512]{1,0:T(8,128)S(1)} custom-call("
    "s8[6400,1536]{1,0:T(8,128)(4,1)S(1)} %pad.455, s8[1536,512]"
    "{1,0:T(8,128)(4,1)S(1)} %pad.456, f32[1,1]{1,0:T(1,128)} %bitcast.381, "
    "f32[1,512]{1,0:T(1,128)S(1)} %pad.457), custom_call_target="
    '"tpu_custom_call", operand_layout_constraints={s8[6400,1536]{1,0}, '
    "s8[1536,512]{1,0}, f32[1,1]{1,0}, f32[1,512]{1,0}}")


def test_hbm_bytes_skip_what_the_compiler_keeps_on_chip():
    from harness.measures import hbm_bytes

    assert hbm_bytes(INT8_OP) == 4
    in_hbm = INT8_OP.replace("S(1)", "")
    assert hbm_bytes(in_hbm) == (6400 * 512 * 4 + 6400 * 1536 + 1536 * 512
                                 + 4 + 512 * 4)
