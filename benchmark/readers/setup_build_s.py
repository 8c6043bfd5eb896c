"""The programs' construction: the sum of `build_s` over the run's set-up
records: the Program's ops appended, a shape inference each, and the Python
function around it, up to `jax.jit`."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.seconds(ctx, "build_s")
