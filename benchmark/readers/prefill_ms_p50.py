"""The engine's `decode.prefill_ms` timer, median over the window. Prefill
runs inline: every running slot waits for it."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.prefill_ms", "p50")
