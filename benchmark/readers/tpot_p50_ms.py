"""Time per output token after the first, per request, 50th percentile."""

from benchmark.readers._latency import pct, tpot_ms


def read(ctx):
    return pct(ctx, tpot_ms, 50, need=lambda r: r["tokens"] > 1)
