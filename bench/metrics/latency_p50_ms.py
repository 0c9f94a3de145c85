"""Median latency of every request due in the window, from its due time to
the delivery of its answer; a refused or unanswered request counts as
missing (infinitely late)."""
from harness.measures import latency_percentile_ms


def read(ctx):
    return latency_percentile_ms(ctx, 50)
