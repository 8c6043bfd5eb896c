"""The paged-attention kernel against HBM bandwidth: the K/V bytes of the
live contexts (benchmark/flops.py: every cached token's K and V over all
layers, once; queries, page tables and outputs are small beside them and
left out, so the share is counted low, never high) over the peak bandwidth,
divided by the kernel's device seconds in one decode step. Memory-bound;
cannot pass 100%. The live contexts are the window's mean, the kernel's
seconds the traced sub-window's, as for `decode_step_roofline`."""

from benchmark import flops
from benchmark.readers._kernel import seconds_per_run


def read(ctx):
    if ctx.kind != "serve" or not ctx.live_context_tokens:
        return None
    per_step_s = seconds_per_run(ctx, "paged_attention")
    if not per_step_s:
        return None
    kv_bytes = ctx.live_context_tokens * flops.decoder_kv_bytes_per_token(
        ctx.config["d_model"], ctx.config["num_layers"])
    return 100.0 * kv_bytes / ctx.peaks["hbm_bytes_per_s"] / per_step_s
