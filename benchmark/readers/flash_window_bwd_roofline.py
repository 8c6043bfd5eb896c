"""The flash_bwd_window_dkv and flash_bwd_window_dq kernels together
against the MXU's peak: the four score-sized products a backward requires
(dV, dP, dQ, dK) inside the causal window only; the scores both kernels
compute again are time and no work (readers/_flash_window.py)."""

from benchmark.readers._flash_window import share


def read(ctx):
    return share(ctx, ("flash_bwd_window_dkv", "flash_bwd_window_dq"), 4)
