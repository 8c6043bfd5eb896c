"""Rows a held routed expert computes a training step and layer: the
window's `moe.train.pairs_held` over steps, routed layers and held
experts. 2,048 at 16,384 tokens, top-8 of 64, when routing is even; the
largest group's rows are the histogram `moe.train.max_group_rows`. None on
a program without the counter."""

from benchmark.readers._routed_train import held_pairs_per_step


def read(ctx):
    held = held_pairs_per_step(ctx)
    if held is None or "experts_held" not in ctx.config:
        return None
    return held / len(ctx.config["layers_held"]) \
        / ctx.config["experts_held"][1]
