"""The paged_gqa_attention kernel against HBM bandwidth in a cell of the
`lfm2` family (head 64: two K/V heads a lane tile, the kernel's packed
form): the K and V bytes of the keys a step attends
(benchmark/flops_lfm2.py `kv_bytes_per_token_layer`, times the window's
`decode.kv_tokens_attended` a step, which counts a cached token once an
attention layer) over the peak bandwidth, divided by the kernel's device
seconds in one decode step of the traced sub-window. The fourth copy of
this arithmetic (`paged_gqa_attention_roofline` reads the afmoe family's
keys, `hybrid_paged_gqa_attention_roofline` the falcon_h1 family's): the
fold into one reader that takes a family's K/V bytes is a `benchmark` PR's.
Memory-bound; the queries, the output and the page table left out: counted
low, never high; None where the trace holds no such kernel, or the program
no tail counter (another family's cell)."""

from benchmark import flops_lfm2
from benchmark.readers._decode_step import decode_step
from benchmark.readers._kernel import seconds


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, keys = c.get("decode.steps"), c.get("decode.kv_tokens_attended")
    if ctx.kind != "serve" or not steps or not keys \
            or not c.get("decode.conv_rows_updated"):
        return None
    prog, kernel_s = decode_step(ctx), seconds(ctx, ("paged_gqa_attention",))
    if not prog or not kernel_s:
        return None
    per_step_s = kernel_s / prog["runs"]    # the kernel runs in steps alone
    return 100.0 * flops_lfm2.kv_bytes_per_token_layer(ctx.config) \
        * keys / steps / ctx.peaks["hbm_bytes_per_s"] / per_step_s
