"""Accepting the step's tokens on the host, one span a step
(`decode.sample_ms`): appending, timestamping and `finished()` for every
live row; the step program has chosen them. Median over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.sample_ms", "p50")
