"""`Executor.run` casting the feeds and putting them on the device
(`executor.feed_ms`). Median over the run's steady-state steps."""

from benchmark.readers._executor import p50


def read(ctx):
    return p50(ctx, "executor.feed_ms")
