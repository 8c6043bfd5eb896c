"""Peak bytes held on the fullest chip, read after the window: the
allocator's peak in use plus the region reserved for the programs'
temporaries (benchmark/common.py:peak_hbm)."""


def read(ctx):
    if ctx.kind != "train" or not ctx.peak_hbm_bytes:
        return None
    return ctx.peak_hbm_bytes / 1e9
