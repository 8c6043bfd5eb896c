"""The gap between two successive tokens of one request
(`decode.token_gap_ms`, from the engine's `token_walls`), 99th percentile
over the window's tokens: the stall a neighbour's inline prefill puts
between two tokens."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.token_gap_ms", "p99")
