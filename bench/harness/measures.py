"""Arithmetic the metric readers share: latency percentiles of an open-loop
window, and a kernel's roofline share from the trace.

A kernel's roofline share here is max(sum of operations / peak, sum of HBM
bytes / bandwidth) over the calls' summed device time. The max of the sums
is at most the sum of each call's max, so the share stays a lower bound on
what the chip could do and cannot pass 100% for a correct count."""
from __future__ import annotations

import re

import numpy as np

from harness import trace as tr

MISSING_MS = 1e9  # the latency a refused or unanswered request reads as


def latency_percentile_ms(ctx, q: float):
    """q-th percentile (numpy's linear rule) of due-to-delivery latency over
    every request due in an open-loop window; None for a closed loop."""
    due = ctx.window.get("due_at")
    if due is None or not len(due):
        return None
    got = ctx.system.delivered
    lat = np.array([(got[i] - due[i]) * 1e3 if i in got else MISSING_MS
                    for i in range(len(due))])
    return float(np.percentile(lat, q))


_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([\d,]*)\](\{[^}]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def hbm_bytes(hlo: str) -> int:
    """Bytes of a custom call's result and operands that live in HBM, from
    its HLO text: the compiler marks an array it keeps in the chip's own
    memory with ``S(1)`` in its layout, and moves it there by a copy of its
    own, outside the call."""
    head = hlo.split(", custom_call_target", 1)[0]
    total = 0
    for dtype, dims, layout in _ARRAY.findall(head):
        if "S(1)" in (layout or ""):
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def roofline_share(ctx, kernel: str, match: str):
    """Least time the chip could take for the kernel's calls in the traced
    window, over the device time they took, in %: the larger of their
    operations over the peak (the family's ``kernel_flops``, the
    algorithm's work at the largest bucket, no tile padding) and their HBM
    bytes over the bandwidth (``hbm_bytes`` of each call as compiled).
    Every batch of the run must have been a full largest bucket, else
    None."""
    if ctx.trace is None:
        return None
    c = ctx.counters
    big = max(ctx.spec["batch_buckets"])
    if not c.get("batches") or c.get("padded_frames", 0) or \
            c.get("frames", 0) != c["batches"] * big:
        return None
    flops = ctx.family.kernel_flops(ctx.spec, big)[kernel]
    seconds, count = tr.op_time(ctx.trace)
    mine = tr.matching(ctx.trace, match)
    took, n = float(seconds[mine].sum()), int(count[mine].sum())
    if not n or took <= 0:
        return None
    ops = sum(flops) * n / len(flops)
    moved = sum(hbm_bytes(ctx.trace.names[i]) * int(count[i])
                for i in mine.nonzero()[0])
    p = ctx.peaks
    return 100.0 * max(ops / p["ops_per_s"], moved / p["bytes_per_s"]) / took
