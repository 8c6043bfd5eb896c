"""Where set-up went, by compiled program: the program's own set-up records
(`paddle_tpu.core.telemetry.compile_records()`: one a program that
`Executor._compile_and_run`, a first interpreted run or `DecodeEngine._entry`
brought to its first execution, with its seconds split into build, jax's
trace and lowering, the backend's compile or the cache read, the cost
capture and the first run). `telemetry.reset()`, which the serve runner
calls as the load starts, leaves them alone.

A record counts when it closed inside set-up: `t1`, on the perf_counter
that `common.since_start` counts from `common._T0`, no later than
`ctx.setup_s`. Nothing else says where a run begins: a process that runs
several cells (the tests' rehearsal) clears the records before each. A
program without such records (the parent commit) reads None everywhere.
"""

from __future__ import annotations

import functools

from benchmark import common

SECONDS = ("total_s", "build_s", "infer_shape_s", "trace_s", "lower_s",
           "compile_s", "cache_read_s", "capture_s", "first_run_s")


def _telemetry():
    from paddle_tpu.core import telemetry

    return telemetry


@functools.lru_cache(maxsize=1)
def _records_of_run(setup_s: float):
    """Read once for the readers of one run, and printed once: the line
    `setup.programs` holds the table the `setup_*` metrics are sums of."""
    read = getattr(_telemetry(), "compile_records", None)
    if read is None:
        return None
    records = [r for r in read() if r["t1"] - common._T0 <= setup_s]
    if not records:
        return None
    common.log("setup.programs", programs=[
        dict({k: round(r[k], 3) for k in SECONDS},
             name=r["name"], kind=r["kind"], ops=r["ops"],
             cache_hit=r["cache_hit"],
             t0=round(r["t0"] - common._T0, 2),
             t1=round(r["t1"] - common._T0, 2)) for r in records])
    return records


def records(ctx):
    """This run's set-up records, oldest first; None where there is none."""
    if ctx.setup_s is None:
        return None
    return _records_of_run(float(ctx.setup_s))


def seconds(ctx, *fields):
    """The sum of `fields` over the run's records."""
    recs = records(ctx)
    if recs is None:
        return None
    return sum(r[f] for r in recs for f in fields)


def infer_shape_seconds(ctx):
    """Every shape inference of the process, inside a record or before
    one: `telemetry.infer_shape_totals()`'s seconds."""
    if records(ctx) is None:
        return None
    return _telemetry().infer_shape_totals()[0]
