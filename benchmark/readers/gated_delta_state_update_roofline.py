"""The gated_delta_state_update kernel against HBM bandwidth: the matrix
states a step must move (benchmark/flops_qwen3_next.py `state_bytes`: a live
row's state of a DeltaNet layer read once and written once, times the
window's `decode.state_rows_updated` a step; a padding row's scratch state,
the rows' q, k, v, gates and o left out) over the peak bandwidth, divided by
the kernel's device seconds in one decode step of the traced sub-window.
Memory-bound; counted low, never high; None where the trace holds no such
kernel or the program no such counter."""

from benchmark import flops_qwen3_next
from benchmark.readers._decode_step import decode_step
from benchmark.readers._kernel import seconds


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, rows = c.get("decode.steps"), c.get("decode.state_rows_updated")
    if ctx.kind != "serve" or not steps or not rows:
        return None
    prog = decode_step(ctx)
    kernel_s = seconds(ctx, ("gated_delta_state_update",))
    if not prog or not kernel_s:
        return None
    per_step_s = kernel_s / prog["runs"]    # the kernel runs in steps alone
    return 100.0 * flops_qwen3_next.state_bytes(ctx.config) \
        * rows / steps / ctx.peaks["hbm_bytes_per_s"] / per_step_s
