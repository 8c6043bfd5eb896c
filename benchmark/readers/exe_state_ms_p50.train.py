"""`Executor.run` between the feeds and the jitted call (`executor.state_ms`):
the cache key, the state and read-only arrays gathered from the scope, the
donation-aliasing check. Median over the run's steady-state steps."""

from benchmark.readers._executor import p50


def read(ctx):
    return p50(ctx, "executor.state_ms")
