"""The decode step against HBM bandwidth: the bytes one step must read
(benchmark/flops.py: the weights once plus the K/V of the live contexts)
over the peak bandwidth, divided by the device time of one execution of the
decode-step program in the trace. Memory-bound; cannot pass 100%."""

from benchmark.readers._trace import main_program


def read(ctx):
    prog = main_program(ctx) if ctx.kind == "serve" else None
    if not prog:
        return None
    per_step_s = prog["seconds"] / prog["runs"]
    return 100.0 * ctx.step_bytes / ctx.peaks["hbm_bytes_per_s"] / per_step_s
