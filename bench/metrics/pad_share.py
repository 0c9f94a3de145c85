"""Share of the dispatched batch rows that were padding: the engine's
``padded_frames`` over the sum of the batches' padded sizes (counted in
patch tokens, which every row carries alike)."""


def read(ctx):
    pad = ctx.counters.get("pack_pad_tokens", 0)
    real = ctx.counters.get("pack_real_tokens", 0)
    return 100.0 * pad / (pad + real) if pad + real else None
