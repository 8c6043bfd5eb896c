"""Share of the window the engine spent in inline prefill: the sum of its
`decode.prefill_ms` timer over the window's length. Every running slot
waits while a prompt prefills."""


def read(ctx):
    h = ((ctx.telemetry or {}).get("hists") or {}).get("decode.prefill_ms")
    if not h or not h["count"] or not ctx.window_s:
        return None
    return 100.0 * h["count"] * h["avg"] / 1e3 / ctx.window_s
