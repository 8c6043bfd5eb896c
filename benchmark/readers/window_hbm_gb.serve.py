"""Bytes held on the fullest chip when the window closes: weights, the K/V
pool and the programs' temporaries, as the server holds them while it
serves. `peak_hbm_gb.serve` is higher by the second, throw-away K/V pool of
the engine's warm-up, which is gone before the window opens."""


def read(ctx):
    if ctx.kind != "serve" or not ctx.window_hbm_bytes:
        return None
    return ctx.window_hbm_bytes / 1e9
