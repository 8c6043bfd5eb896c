"""The paged_gqa_attention kernel against HBM bandwidth: the K/V bytes of
the keys a step attends (benchmark/flops_afmoe.py, from the window's
`decode.kv_tokens_attended`: a ring layer's rows count their window) over
the peak bandwidth, divided by the kernel's device seconds in one decode
step of the traced sub-window. Memory-bound; counted low, never high; None
where the trace holds no such kernel or the program no such counter."""

from benchmark import flops_afmoe
from benchmark.readers._kernel import seconds_per_run


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, keys = c.get("decode.steps"), c.get("decode.kv_tokens_attended")
    if ctx.kind != "serve" or not steps or not keys:
        return None
    per_step_s = seconds_per_run(ctx, "paged_gqa_attention")
    if not per_step_s:
        return None
    return 100.0 * flops_afmoe.paged_gqa_bytes(ctx.config, keys / steps) \
        / ctx.peaks["hbm_bytes_per_s"] / per_step_s
