"""Share of the window in which the engine thread was inside its loop and
neither ran nor waited for the device at one of its two known blocking
points: `decode.loop_ms` less `decode.cpu_ms` less `decode.fetch_ms` (the
wait for a step's tokens) less `decode.prefill_wait_ms` (the wait for a
prefill's logits), sums over the window's length. What is left the thread
spent off the CPU elsewhere: waiting for the interpreter lock behind the
callers' threads, for a core, or at a third blocking point (the feed's
transfers, the launch). Nothing is clamped: a negative reading is CPU
counted inside a wait (a spin, or a coarse thread clock's sampling error).
None on a program without the histograms."""

from benchmark.readers._window_share import window_share


def read(ctx):
    loop, cpu, fetch, wait = (window_share(ctx, "decode." + name) for name in (
        "loop_ms", "cpu_ms", "fetch_ms", "prefill_wait_ms"))
    if None in (loop, cpu, fetch, wait):
        return None
    return loop - cpu - fetch - wait
