"""Tokens of every step completed in the window over the window's seconds;
the window closes on block_until_ready of a state array. Host clock."""


def read(ctx):
    if ctx.kind != "train":
        return None
    return ctx.steps * ctx.tokens_per_step / ctx.window_s
