"""Device time of one bucket-32 classify execution: the mean length of the
classify program's events on the chip's ``XLA Modules`` line inside the
traced window. Read only where every batch of the run was a full bucket of
32, so every execution counted is one."""
import re

from harness import trace as tr

MODULE = r"^jit__lambda"
BUCKET = 32


def read(ctx):
    if ctx.trace is None:
        return None
    c = ctx.counters
    if not c.get("batches") or c.get("frames", 0) != c["batches"] * BUCKET:
        return None
    pat = re.compile(MODULE)
    mods = [m for m in tr.in_window(ctx.trace, ctx.trace.modules)
            if pat.search(m[0])]
    if not mods:
        return None
    return 1e-6 * sum(e - s for _, s, e in mods) / len(mods)
