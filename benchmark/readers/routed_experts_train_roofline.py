"""The routed layers' grouped products against the MXU's peak, whatever
implements them: the nine products of a step's held pairs (three forward,
six backward; benchmark/flops_mellum.py, from the window's
`moe.train.pairs_held`) over the bf16 peak, divided by the device seconds,
in one step of the traced sub-window, of the operation families that
compute them: the `grouped_swiglu` kernel and XLA's `ragged-dot`s (a
branch of a conditional is traced under its operations' own names). The
gate and up products the backward makes again are time and no work here.
None where the trace holds neither or the program no counter."""

from benchmark import flops_mellum
from benchmark.readers._kernel import seconds
from benchmark.readers._routed_train import held_pairs_per_step
from benchmark.readers._trace import main_program

FAMILIES = ("grouped_swiglu", "ragged-dot", "ragged_dot")


def read(ctx):
    held, prog = held_pairs_per_step(ctx), main_program(ctx)
    total = seconds(ctx, FAMILIES)
    if held is None or not prog or not total:
        return None
    least_s = flops_mellum.routed_train_step_flops(ctx.config, held) \
        / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (total / prog["runs"])
