"""The mla_prefill_attention kernel against the chip's peak bfloat16 rate:
the operations of the work the kernel is GIVEN in the traced sub-window
over the peak, divided by the kernel's device seconds there. A prefill
program hands the kernel its whole padded bucket (the op has no lengths:
causality alone keeps a padded tail out of real rows), so the work is the
causal triangle of the bucket over the latent layers, for each run of each
prefill program the trace holds (`trace["programs"]`, `prefill_p<bucket>`);
counted low: the diagonal blocks' upper halves, which the kernel computes
and masks, and the softmax are left out (benchmark/flops_kimi_k2.py). Read
from the trace alone: the real lengths of the few prompts of a 3 s
sub-window are in no counter, and the window's average of a quantity that
grows with the square of the length misreads them by half or double.
Compute-bound: per block it reads 0.4 MB for 0.2 GFLOP. None where the
trace holds no such kernel or no prefill program."""

import re

from benchmark import flops_kimi_k2
from benchmark.readers._kernel import seconds


def read(ctx):
    if ctx.kind != "serve" or "kv_lora_rank" not in ctx.config:
        return None
    kernel_s = seconds(ctx, ("mla_prefill_attention",))
    if not kernel_s:
        return None
    flops = 0.0
    for name, prog in ((ctx.trace or {}).get("programs") or {}).items():
        bucket = re.search(r"prefill_p(\d+)", name)
        if bucket:
            flops += prog["runs"] * flops_kimi_k2.mla_prefill_flops(
                ctx.config,
                flops_kimi_k2.prefill_pairs(ctx.config, int(bucket.group(1))))
    if not flops:
        return None
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / kernel_s
