"""Set-up: from process start to the end of the engine's warmup (weights
made on the device, image pool, engine built, every bucket compiled or
loaded from the compile cache and run once)."""


def read(ctx):
    return ctx.setup_s
