"""Requests answered correctly inside the window per second: in a closed
loop, the highest rate the server sustains at this slot count."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    return len(ctx.requests) / ctx.window_s
