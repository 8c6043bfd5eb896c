"""The training window's routing counters (`moe.train.*`, published by
Executor.run from the step program's own int vectors): a step's pairs on
held experts, summed over the routed layers."""


def held_pairs_per_step(ctx):
    """None on a program without the counters."""
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, held = c.get("moe.train.steps"), c.get("moe.train.pairs_held")
    if ctx.kind != "train" or not steps or held is None:
        return None
    return held / steps
