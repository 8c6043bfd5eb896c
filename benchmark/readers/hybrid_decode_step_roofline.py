"""The decode step of a model with recurrent state against HBM bandwidth:
the bytes one step must move, from the window's counters (the family's
`step_bytes`: the weights once, the K/V of the keys attended, the state of
every live row of every layer read and written), over the peak bandwidth,
divided by the device time of one execution of the decode-step program in
the trace. `decode_step_roofline`'s arithmetic under a name of its own,
because it moves another end-to-end metric (a cell at saturation reports
tokens per second) and counts bytes that are WRITTEN too. Only bytes that
must move: it cannot pass 100. None on a program without the state
counter."""

from benchmark.readers._decode_step import decode_step


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    if ctx.kind != "serve" or not c.get("decode.state_rows_updated"):
        return None
    prog = decode_step(ctx)
    if not prog:
        return None
    per_step_s = prog["seconds"] / prog["runs"]
    return 100.0 * ctx.step_bytes / ctx.peaks["hbm_bytes_per_s"] / per_step_s
