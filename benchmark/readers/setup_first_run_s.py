"""The programs' first executions (the engine's throw-away runs, a trainer's
first step, an interpreted start-up program's ops), their transfers and
what no other phase of a record holds: the sum of `first_run_s`."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.seconds(ctx, "first_run_s")
