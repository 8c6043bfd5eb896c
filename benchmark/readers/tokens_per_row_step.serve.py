"""Tokens a stepped row took a step: the engine's `decode.tokens` (delivered
by steps) over `decode.rows_stepped` in the window. 1 without a draft
module; with one, 1 + the share of drafts accepted, less what was thrown
away at a request's end. None on a program that drafts nothing."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    rows = c.get("decode.rows_stepped")
    if ctx.kind != "serve" or not rows:
        return None
    return c.get("decode.tokens", 0) / rows
