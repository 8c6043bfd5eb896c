"""The residual path's share of the device's work, read, not reckoned: the
device seconds of the two kernels of the streams' residual path (`mhc_pre`
and `mhc_post`, ops/pallas/mhc_mix.py) over the traced window's busy
seconds. Lower is better: the path computes nothing a plain residual would
not. None where the trace holds no such kernel (a program without the
path, or one whose path took its stock lowering)."""

from benchmark.readers._kernel import seconds

KERNELS = ("mhc_pre", "mhc_post")


def read(ctx):
    if ctx.kind != "serve" or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    kernel_s = seconds(ctx, KERNELS)
    if kernel_s is None:
        return None
    return 100.0 * kernel_s / ctx.trace["busy_s"]
