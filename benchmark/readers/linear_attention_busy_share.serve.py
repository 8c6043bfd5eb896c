"""The gated delta rule's share of the device's work, read, not reckoned:
the device seconds of the `gated_delta_state_update` kernel (and of a chunk
kernel, `gated_delta_chunk_scan`, once there is one: the prefill's chunked
rule is XLA products under `fusion` today and not in this share) over the
traced window's busy seconds. None where the trace holds no such kernel."""

from benchmark.readers._kernel import seconds

KERNELS = ("gated_delta_state_update", "gated_delta_chunk_scan")


def read(ctx):
    if ctx.kind != "serve" or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    kernel_s = seconds(ctx, KERNELS)
    if kernel_s is None:
        return None
    return 100.0 * kernel_s / ctx.trace["busy_s"]
