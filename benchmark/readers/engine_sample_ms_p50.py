"""Host sampling of every live row, one span a step (`decode.sample_ms`).
Median over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.sample_ms", "p50")
