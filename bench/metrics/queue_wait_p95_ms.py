"""95th percentile of the engine's admission wait (its ``queue_wait``
tracker: from the request's ``submitted_at``, stamped at its due time, to
the dispatch of its batch)."""


def read(ctx):
    t = ctx.engine_metrics.queue_wait
    return 1e3 * t.percentile(95) if len(t) else None
