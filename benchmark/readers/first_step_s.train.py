"""The first Executor.run of the main program: trace, lower and compile, or
a read of the persistent cache. Host clock, inside set-up."""


def read(ctx):
    return ctx.first_step_s
