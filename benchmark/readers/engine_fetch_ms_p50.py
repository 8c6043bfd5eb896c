"""The logits' device-to-host copy alone (`decode.fetch_ms`, the closing part
of `decode.step_ms`): it waits for the step program, then moves the rows.
Median over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.fetch_ms", "p50")
