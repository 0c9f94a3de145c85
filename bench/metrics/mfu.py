"""The whole step's share of the chip's highest peak: model operations of
the real images answered in the measured window of the traced run
(``flops_per_image``: every matmul, routed top-k experts only, no pad
rows) over the window's length and the int8 peak."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    w = ctx.window
    if "completed_in_window" not in w:
        return None
    ops = ctx.family.flops_per_image(ctx.spec) * w["completed_in_window"]
    return 100.0 * ops / w["seconds"] / ctx.peaks["ops_per_s"]
