"""The paged_mla_attention kernel against the chip's peaks: the LARGER of
the latent bytes a step attends over the peak HBM bandwidth and the
kernel's operations over the peak bfloat16 rate (benchmark/flops_kimi_k2.py,
from the window's `decode.kv_tokens_attended`: rows x layers x context;
both counted low), divided by the kernel's device seconds in one decode
step of the traced sub-window. At 64 heads on one 576-wide row the kernel
does ~121 operations a byte against the chip's ridge of ~240, so the bytes
bound it and the operations are half of theirs. None where the trace holds
no such kernel or the program no such counter."""

from benchmark import flops_kimi_k2
from benchmark.readers._kernel import seconds_per_run


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, rows = c.get("decode.steps"), c.get("decode.kv_tokens_attended")
    if ctx.kind != "serve" or not steps or not rows \
            or "kv_lora_rank" not in ctx.config:
        return None
    per_step_s = seconds_per_run(ctx, "paged_mla_attention")
    if not per_step_s:
        return None
    least_s = max(
        flops_kimi_k2.paged_mla_bytes(ctx.config, rows / steps)
        / ctx.peaks["hbm_bytes_per_s"],
        flops_kimi_k2.paged_mla_flops(ctx.config, rows / steps)
        / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least_s / per_step_s
