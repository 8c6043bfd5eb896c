"""The program's own histograms, read by name from its telemetry."""


def hist(ctx, name, field):
    if not ctx.telemetry:
        return None
    h = ctx.telemetry["hists"].get(name)
    return h[field] if h and h["count"] else None
