"""The jitted step call returning (`executor.call_ms`): argument handling and
the enqueue, not the device's time. Median over the run's steady-state steps."""

from benchmark.readers._executor import p50


def read(ctx):
    return p50(ctx, "executor.call_ms")
