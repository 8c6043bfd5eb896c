"""The window / grouped-head flash kernels against the MXU's peak: the
score-sized products a pass requires over the (query, key) pairs INSIDE
the causal window only (benchmark/flops_mellum.py), over the bf16 peak,
divided by the kernels' device seconds in one step of the traced
sub-window. A kernel that visits masked blocks reads low; none reads over
100."""

from benchmark import flops_mellum
from benchmark.readers._kernel import seconds_per_run


def share(ctx, kernels, products):
    """None where the trace lacks one of `kernels` (by `name=`) or the
    configuration is not one of held layers with a window."""
    if ctx.kind != "train" or "layers_held" not in ctx.config:
        return None
    per_step_s = [seconds_per_run(ctx, k) for k in kernels]
    if not all(per_step_s):
        return None
    least_s = flops_mellum.window_attention_step_flops(
        ctx.config, ctx.traffic["batch_per_replica"], ctx.traffic["seq_len"],
        products) / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / sum(per_step_s)
