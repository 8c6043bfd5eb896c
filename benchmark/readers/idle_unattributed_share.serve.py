"""Share of the traced window in which no operation runs on the device and
the program has no span of its own open: what its spans still cannot see."""

from benchmark.readers._idle_split import NO_SPAN, share


def read(ctx):
    return share(ctx, "serve", NO_SPAN)
