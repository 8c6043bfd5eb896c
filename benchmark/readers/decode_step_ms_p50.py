"""The engine's `decode.step_ms` timer (closed by the logits' fetch), median
over the window."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.step_ms", "p50")
