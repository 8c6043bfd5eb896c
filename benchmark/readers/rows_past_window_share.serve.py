"""Share of the window's decoded rows whose context was past the sliding
window (`decode.rows_past_window` over `decode.tokens`): for them a ring
layer reads the window, not the context. None on a program without the
counter."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    rows, past = c.get("decode.tokens"), c.get("decode.rows_past_window")
    if not rows or past is None:
        return None
    return 100.0 * past / rows
