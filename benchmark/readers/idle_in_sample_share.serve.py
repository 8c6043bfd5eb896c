"""Share of the traced window in which no operation runs on the device and
`decode.sample_ms` is the innermost span the engine has open: the device
waiting while the host accepts the step's tokens."""

from benchmark.readers._idle_split import share


def read(ctx):
    return share(ctx, "serve", "decode.sample_ms")
