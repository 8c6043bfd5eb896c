"""Share of the window in which every live slot stood still for an
admission: the sum of `decode.prefill_wait_ms`, the host's wait for a prefill
program with no step's tokens left to accept, over the window's length.
`prefill_time_share.serve` reads the whole `decode.prefill_ms` span, which
since the loop runs a step ahead also holds what was left of the step in
flight before the prefill; this is the part that is the prefill program's
own. It reads under the program's device time by the host's accept of that
step (tenths of a ms a prefill). None on a program without the histogram."""

from benchmark.readers._window_share import window_share


def read(ctx):
    return window_share(ctx, "decode.prefill_wait_ms")
