"""Per-request latencies from the server's own times in the response body."""

from benchmark.common import percentile


def tpot_ms(r):
    return (r["latency_ms"] - r["ttft_ms"]) / (r["tokens"] - 1)


def pct(ctx, fn, q, need=lambda r: True):
    if ctx.kind != "serve":
        return None
    values = [fn(r) for r in ctx.requests if need(r)]
    return percentile(values, q) if values else None
