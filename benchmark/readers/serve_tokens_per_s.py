"""Output tokens generated inside the window over the window's seconds.

A request's tokens are spread evenly from its first token to its response
(both on the client's clock: the response's arrival, less the server's own
time from first token to reply), and the part of that stretch inside the
window counts. So the rate holds all the work of the window and no more: a
request cut by either edge counts by its part inside, whichever side of the
edge it was answered on. Only requests answered 200 with the asked number of
tokens count."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    lo, hi = ctx.window
    tokens = 0.0
    for r in ctx.answered:
        first, done = r["first"], r["done"]
        if done <= first:                      # a single-token answer
            tokens += r["tokens"] * (lo <= done <= hi)
            continue
        inside = min(done, hi) - max(first, lo)
        if inside > 0:
            tokens += r["tokens"] * inside / (done - first)
    return tokens / (hi - lo)
