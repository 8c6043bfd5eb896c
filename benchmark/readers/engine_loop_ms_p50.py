"""One iteration of the engine's loop that ran a step, `_admit` to the watchdog
tick (`decode.loop_ms`): the time a token costs every live slot, less the
prefills' share. Median over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.loop_ms", "p50")
