"""The backend's compiles, or the reads of jax's persistent cache in their
place: the sum of `compile_s + cache_read_s` over the run's set-up
records."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.seconds(ctx, "compile_s", "cache_read_s")
