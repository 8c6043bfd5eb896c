"""One training step closed by block_until_ready, median. Traced run only:
closing every step keeps the host from running ahead."""

from benchmark.common import percentile


def read(ctx):
    return percentile(ctx.step_ms, 50) if ctx.step_ms else None
