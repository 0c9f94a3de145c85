"""Roofline share of the W8A8 linears (kernels/int8_matmul.py): int8 activations [M, K] times int8 weights [K, N] into float32: for each call in the traced window, the larger
of its operations over the chip's int8 peak and its bytes over HBM
bandwidth (the family's ``kernel_calls``, at the call's shapes, no tile
padding), summed, over the device time those calls took, in %."""
from harness.measures import roofline_share

KERNEL = "int8_matmul"
# the kernel's custom calls, by the HLO text the trace carries for each op
MATCH = (
    r'custom_call_target="tpu_custom_call", operand_layout_constra'
    r'ints=\{s8\[\d+,\d+\]\{1,0\}, s8\[\d+,\d+\]\{1,0\}, f32\[1,1\]')


def read(ctx):
    return roofline_share(ctx, KERNEL, MATCH)
