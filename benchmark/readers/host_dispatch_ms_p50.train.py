"""Time Executor.run(sync_fetch=False) takes to return: feed conversion,
the host-to-device copy's enqueue and the step's dispatch. Median."""

from benchmark.common import percentile


def read(ctx):
    return percentile(ctx.dispatch_ms, 50) if ctx.dispatch_ms else None
