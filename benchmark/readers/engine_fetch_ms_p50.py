"""The fetch of the step's chosen tokens alone (`decode.fetch_ms`, the closing
part of `decode.step_ms`): `[slots]` int32, 32 bytes at 8 slots, so it is
where the host waits out the step program, sampler included. Median over
the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.fetch_ms", "p50")
