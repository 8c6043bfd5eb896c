"""Share of the traced window in which no operation runs on the device and
`decode.fetch_ms` is the innermost span the engine has open: the logits
crossing to the host after the step program has ended."""

from benchmark.readers._idle_split import share


def read(ctx):
    return share(ctx, "serve", "decode.fetch_ms")
