"""Latent attention's share of the device's work, read, not reckoned: the
device seconds of the two attention kernels of a latent model (the decode
step's `paged_mla_attention` over latent pages and the prefill's
`mla_prefill_attention` in the expanded form) over the traced window's busy
seconds. The projections around them (W_qa, W_qb, W_kva, W_kvb, W_o, the
absorbed query and the expanded output) are XLA products that the trace
holds under `fusion` beside every other product: not in this share. None
where the trace holds no such kernel."""

from benchmark.readers._kernel import seconds

KERNELS = ("paged_mla_attention", "mla_prefill_attention")


def read(ctx):
    if ctx.kind != "serve" or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    kernel_s = seconds(ctx, KERNELS)
    if kernel_s is None:
        return None
    return 100.0 * kernel_s / ctx.trace["busy_s"]
