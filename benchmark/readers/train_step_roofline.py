"""The training step against the MXU's peak: model FLOPs of one step
(benchmark/flops.py: forward + backward, no recomputation) over the bf16
peak, divided by the device time of one execution of the step program in
the trace. Compute-bound; cannot pass 100%."""

from benchmark.readers._trace import main_program


def read(ctx):
    prog = main_program(ctx) if ctx.kind == "train" else None
    if not prog:
        return None
    per_step_s = prog["seconds"] / prog["runs"]
    least_s = (ctx.flops_per_token * ctx.tokens_per_step
               / (ctx.chips * ctx.peaks["bf16_flops_per_s"]))
    return 100.0 * least_s / per_step_s
