"""Share of the step's slots that held a live request, mean over steps."""

from benchmark.readers._telemetry import hist


def read(ctx):
    v = hist(ctx, "decode.batch_occupancy", "avg")
    return None if v is None else 100.0 * v
