"""Share of the traced window in which no operation ran on the chip."""
from harness import trace as tr


def read(ctx):
    if ctx.trace is None or tr.window_s(ctx.trace) <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(ctx.trace) / tr.window_s(ctx.trace))
