"""Every shape inference of `Block.append_op` in the process: inside a
set-up record (then a part of `setup_build_s`) or before one (a trainer's
Program is built by the runner before any program compiles). Nothing builds
a program inside the window."""

from benchmark.readers import _setup


def read(ctx):
    return _setup.infer_shape_seconds(ctx)
