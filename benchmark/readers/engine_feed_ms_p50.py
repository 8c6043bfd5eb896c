"""Building the step's three feed arrays and handing them to the device
(`decode.feed_ms`). Median over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.feed_ms", "p50")
