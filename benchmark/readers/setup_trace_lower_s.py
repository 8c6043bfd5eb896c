"""jax's trace of the programs (Python through `run_block`) and their
lowering to MLIR, Mosaic kernels included: the sum of `trace_s + lower_s`
over the run's set-up records."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.seconds(ctx, "trace_s", "lower_s")
