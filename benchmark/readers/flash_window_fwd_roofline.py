"""The flash_fwd_window kernel against the MXU's peak: its two score-sized
products (q k^T, p v) inside the causal window only
(readers/_flash_window.py)."""

from benchmark.readers._flash_window import share


def read(ctx):
    return share(ctx, ("flash_fwd_window",), 2)
