"""How much of one core the engine thread uses: the sum of `decode.cpu_ms`
(the thread's own CPU time, `time.thread_time`, over the iterations
`decode.loop_ms` covers) over the window's length. 100 is a loop the host
bounds; what is far under it waits, for the device or for the interpreter
lock (`engine_wait_unexplained_share.serve`). Where the thread's clock ticks
coarsely (10 ms on the chip's machine) the sum is an estimate, and aliases
with a loop whose period is the tick's. None on a program without the
histogram."""

from benchmark.readers._window_share import window_share


def read(ctx):
    return window_share(ctx, "decode.cpu_ms")
