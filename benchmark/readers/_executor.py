"""`Executor.run`'s phase timers, read from the program's own registry: the
train runner hands over no telemetry, so the whole run counts, warm-up
included (a median tolerates it)."""

from types import SimpleNamespace

from benchmark.readers._telemetry import hist


def p50(ctx, name):
    if ctx.kind != "train":
        return None
    from paddle_tpu.core import telemetry

    return hist(SimpleNamespace(telemetry=telemetry.snapshot()), name, "p50")
