"""One family of device operations (a kernel under its `name=`, or an XLA
instruction without its number: `trace_reduce.op_family`) in the traced
window."""

from benchmark.readers._trace import main_program


def seconds(ctx, families):
    """Device seconds, averaged over the planes, of the families of
    operations whose name starts with one of `families`; None where the
    trace holds none of them."""
    found = [s for name, s in ((ctx.trace or {}).get("op_seconds")
                               or {}).items() if name.startswith(families)]
    return sum(found) if found else None


def seconds_per_run(ctx, family):
    """Device seconds of one family a run of the main program: a kernel that
    runs once a layer adds up over the layers of one step."""
    prog, total = main_program(ctx), seconds(ctx, (family,))
    if not prog or total is None:
        return None
    return total / prog["runs"]
