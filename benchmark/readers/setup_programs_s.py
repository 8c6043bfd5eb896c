"""Set-up inside the program's compiled-program path: the sum of `total_s`
over the run's set-up records (readers/_setup.py), one a compiled program."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.seconds(ctx, "total_s")
