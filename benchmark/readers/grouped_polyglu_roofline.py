"""The grouped_polyglu kernel against HBM bandwidth: the expert weights it
must stream for the work it is GIVEN in the traced sub-window, over the peak
bandwidth and the kernel's device seconds there. A decode step streams the
held experts that were HIT (`decode.moe_experts_hit` a step, summed over the
MoE layers, times an expert's three matrices: benchmark/flops_motif3.py
`expert_bytes`, 31,457,280 B at 4096 x 1280 bfloat16), for each run of the
decode-step program; a prefill program hands the kernel its whole bucket,
1,024 tokens or more, whose pairs reach every held expert (21 rows an expert
at the least bucket), so each run streams every held expert once a MoE
layer. The rows' activations, and a prefill's products (which bound a
16,384 bucket, not its weights), are left out: counted low, but for the
program in flight when the profiler starts and the one when it stops,
which `trace["programs"]` counts as whole runs (PERF.md section 7): at
most two prefill runs, 12 GB, 2 points of a window of steps and 9 of a
window of prefills alone, where the share reads ~60. None where the trace holds no such kernel or no program that runs it, or
the configuration has no PolyNorm experts."""

import re

from benchmark import flops_motif3
from benchmark.readers._kernel import seconds


def read(ctx):
    if ctx.kind != "serve" or "polynorm_output_scale" not in ctx.config:
        return None
    kernel_s = seconds(ctx, ("grouped_polyglu",))
    if not kernel_s:
        return None
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, hit = c.get("decode.steps"), c.get("decode.moe_experts_hit")
    held = flops_motif3.moe_layers(ctx.config) \
        * ctx.config["experts_held"][1]
    experts = 0.0
    for name, prog in ((ctx.trace or {}).get("programs") or {}).items():
        if re.search(r"prefill_p\d+", name):
            experts += prog["runs"] * held
        elif "decode_step" in name and steps and hit:
            experts += prog["runs"] * hit / steps
    if not experts:
        return None
    return 100.0 * experts * flops_motif3.expert_bytes(ctx.config) \
        / ctx.peaks["hbm_bytes_per_s"] / kernel_s
