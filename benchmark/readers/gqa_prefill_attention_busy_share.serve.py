"""Whole-prompt grouped attention's share of the device's work, read, not
reckoned: the device seconds of the flash forward kernel that
`gqa_prefill_attention` hands a long padded prompt (`flash_fwd_window`,
ops/pallas/flash_window.py; the op's shape rule, ops/llm_ops.py) over the
traced window's busy seconds. Lower is better: the kernel computes what the
XLA form's loop of products computed inside `while`. None where the trace
holds no such kernel: a program from before the dispatch, a window whose
prompts all stayed under the threshold, or kernels off."""

from benchmark.readers._kernel import seconds

KERNELS = ("flash_fwd_window",)


def read(ctx):
    if ctx.kind != "serve" or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    kernel_s = seconds(ctx, KERNELS)
    if kernel_s is None:
        return None
    return 100.0 * kernel_s / ctx.trace["busy_s"]
