"""Readings of the reduced trace (benchmark/trace_reduce.py)."""


def idle_share(ctx, kind):
    if ctx.kind != kind or not ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def main_program(ctx):
    """The program that held the device longest in the traced window: the
    training step, or the decode step (prefills are far fewer, and the
    feeds' little conversion programs take microseconds)."""
    progs = (ctx.trace or {}).get("programs")
    if not progs:
        return None
    return max(progs.values(), key=lambda p: p["seconds"])
