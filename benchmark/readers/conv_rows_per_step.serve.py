"""Rows whose conv tail a decode step moved: `decode.conv_rows_updated`
(live rows x the layers whose whole state is a conv tail, a step) over
`decode.steps` and the convolution layers held (benchmark/flops_lfm2.py
`layers_of`). It equals the mean live rows a step; None on a program
without the counter (a model whose layers keep no tail alone)."""

from benchmark import flops_lfm2


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, rows = c.get("decode.steps"), c.get("decode.conv_rows_updated")
    if ctx.kind != "serve" or not steps or not rows:
        return None
    return rows / steps / flops_lfm2.layers_of(ctx.config)[0]
