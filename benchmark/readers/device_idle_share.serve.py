"""1 - union of the device's operation intervals over the traced window."""

from benchmark.readers._trace import idle_share


def read(ctx):
    return idle_share(ctx, "serve")
