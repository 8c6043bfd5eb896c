"""Distinct held routed experts with at least one token, a decode step and
MoE layer: the step program's `decode.moe_experts_hit` (summed over the MoE
layers of a step) over steps and MoE layers. Each one hit is one expert's
weights read; None on a program without the counter."""

from benchmark import flops_afmoe


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, hit = c.get("decode.steps"), c.get("decode.moe_experts_hit")
    if not steps or hit is None:
        return None
    return hit / steps / flops_afmoe.moe_layers(ctx.config)
