"""The decode step of a model with routed experts against HBM bandwidth:
the bytes one step must read, from the window's counters (the family's
`step_bytes`: the non-expert weights once, the weights of each held expert
that a live row was routed to, the K/V of the keys attended), over the
peak bandwidth, divided by the device time of one execution of the
decode-step program in the trace. `decode_step_roofline`'s arithmetic
under a name of its own, because it moves another end-to-end metric: a
cell at saturation reports tokens per second, not `tpot_p90_ms`. None on a
program without the routing counters."""

from benchmark.readers._trace import main_program


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    if ctx.kind != "serve" or "decode.moe_experts_hit" not in c:
        return None
    prog = main_program(ctx)
    if not prog:
        return None
    per_step_s = prog["seconds"] / prog["runs"]
    return 100.0 * ctx.step_bytes / ctx.peaks["hbm_bytes_per_s"] / per_step_s
