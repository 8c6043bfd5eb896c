"""A histogram of the engine's own times as a share of the window."""

from benchmark.readers._telemetry import hist


def window_share(ctx, name):
    """100 x the sum of the histogram `name` (ms) over the window's length;
    None outside a serving run and on a program that never observed it (the
    parent commit). The runner clears the registry when the load starts, so
    the sum holds the ramp's observations too, as `prefill_time_share.serve`
    does: in a loop at saturation a share of the window reads high by about
    ramp / window."""
    if ctx.kind != "serve" or not ctx.window_s:
        return None
    total = hist(ctx, name, "total")
    return None if total is None else 100.0 * total / 1e3 / ctx.window_s
