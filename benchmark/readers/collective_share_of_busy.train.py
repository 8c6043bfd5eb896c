"""Device seconds of the collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute and their asynchronous
-start / -done halves) over the device's busy seconds, averaged over the
planes of the traced window. A share of busy time and named so: what of it
runs beside compute is not told apart, so no overlap is claimed. A step on
one device has no collective and reports nothing."""

from benchmark.readers._kernel import seconds

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(ctx):
    if ctx.kind != "train" or not ctx.trace:
        return None
    total = seconds(ctx, COLLECTIVES)
    return None if total is None else 100.0 * total / ctx.trace["busy_s"]
