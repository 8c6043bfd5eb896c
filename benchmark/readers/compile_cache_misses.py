"""Programs JAX compiled because its persistent cache did not hold them,
over the whole run (jax.monitoring cache events). 0 once a checkout has
run the cell."""


def read(ctx):
    return ctx.cache["misses"] if ctx.cache else None
