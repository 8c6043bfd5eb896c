"""What an iteration spends outside its five phases (`decode.other_ms` =
loop - admit - feed - step - sample - retire): the deadline scan, the
journal tick, the gauge, the watchdog, the timers themselves. Median."""

from benchmark.readers._telemetry import hist


def read(ctx):
    return hist(ctx, "decode.other_ms", "p50")
