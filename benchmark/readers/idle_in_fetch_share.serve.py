"""Share of the traced window in which no operation runs on the device and
`decode.fetch_ms` is the innermost span the engine has open: the step
program has ended and its 32 bytes of tokens have not reached the host yet.
The trace's alignment of the device's clock to the host's moves by about
1 ms between runs, so read it together with the idle under `decode.step_ms`
(the `idle_split` line)."""

from benchmark.readers._idle_split import share


def read(ctx):
    return share(ctx, "serve", "decode.fetch_ms")
