"""Set-up that is not a compiled program's: `setup_s` less
`setup_programs_s`: imports, the device's first answer, weights, the check,
the ramp."""

from benchmark.readers import _setup


def read(ctx):
    inside = _setup.seconds(ctx, "total_s")
    return None if inside is None else ctx.setup_s - inside
