"""Rows an expert that was read had to itself, a decode step and routed
layer: the step program's `decode.moe_pairs_held` (the live rows' pairs on
held experts) over `decode.moe_experts_hit` (the held experts with at least
one such pair), both summed over the routed layers of a step. An expert's
weights are read once whatever its rows: the more rows share the read, the
less of a step's bytes a token costs. None on a program without the
counters."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    hit = c.get("decode.moe_experts_hit")
    if ctx.kind != "serve" or not hit:
        return None
    return c.get("decode.moe_pairs_held", 0) / hit
