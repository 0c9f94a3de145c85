"""Roofline share of 4-bit log-sqrt2 attention (kernels/quant_attention.py) over float32 q, k, v [B, H, S, 64]: for each call in the traced window, the larger
of its operations over the chip's int8 peak and its bytes over HBM
bandwidth (the family's ``kernel_calls``, at the call's shapes, no tile
padding), summed, over the device time those calls took, in %."""
from harness.measures import roofline_share

KERNEL = "streaming_attention"
# the kernel's custom calls, by the HLO text the trace carries for each op
MATCH = (
    r'custom_call_target="tpu_custom_call", operand_layout_constra'
    r'ints=\{s32\[\d+\]\{0\}, s32\[\d+\]\{0\}, (f32\[\d+,\d+,\d+,\d+\]\{3,2,1,0\}(, )?){3}\}')


def read(ctx):
    return roofline_share(ctx, KERNEL, MATCH)
