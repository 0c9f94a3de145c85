"""Roofline share of the expert stacks (kernels/expert_linear.py): int8 rows sorted by expert times every expert's int8 weights [G, K, N]: for each call in the traced window, the larger
of its operations over the chip's int8 peak and its bytes over HBM
bandwidth (the family's ``kernel_calls``, at the call's shapes, no tile
padding), summed, over the device time those calls took, in %."""
from harness.measures import roofline_share

KERNEL = "grouped_matmul"
# the kernel's custom calls, by the HLO text the trace carries for each op
MATCH = (
    r'custom_call_target="tpu_custom_call", operand_layout_constra'
    r'ints=\{(s32\[\d+\]\{0\}, ){4}s8\[\d+,\d+\]\{1,0\}, s8\[\d+,\d+,\d+\]')


def read(ctx):
    return roofline_share(ctx, KERNEL, MATCH)
