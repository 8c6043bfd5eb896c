"""Process start to the window's opening: imports, weights, compiles or
cache reads, warm-up and the correctness check. Host clock."""


def read(ctx):
    return ctx.setup_s
