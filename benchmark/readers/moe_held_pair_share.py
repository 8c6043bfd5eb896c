"""Share of the window's routed (token, expert) pairs that landed on the
experts this chip holds: `decode.moe_pairs_held` over
`decode.moe_pairs_total`. With an eighth of evenly routed experts held it
reads near 12.5; None on a program without the counters."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    total = c.get("decode.moe_pairs_total")
    if not total:
        return None
    return 100.0 * c.get("decode.moe_pairs_held", 0) / total
