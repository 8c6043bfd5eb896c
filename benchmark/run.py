"""python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process, one cell: loads, warms, measures for --seconds, checks the
outputs, and prints one JSON object as the last line of stdout. With
--trace 0 the metrics are the cell's end-to-end metrics; with --trace 1 its
per-layer metrics, a device busy time from the profiler's trace and a
breakdown. Any platform but a TPU, or fewer chips than the cell asks for, is
a non-zero exit and no result: there is no CPU number.
"""

from __future__ import annotations

from .common import since_start     # first: set-up is counted from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
from types import SimpleNamespace  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK_DIR = os.path.join(CHECKOUT, ".bench_work")
NO_DEVICE_EXIT = 4


def _prepare_environment():
    """Before JAX is imported: the compile cache at a fixed path inside the
    checkout unless the machine names one, every program in it, and none
    thrown out of it. A size cap under one cell's programs makes JAX's LRU
    eviction drop each program shortly before the next run asks for it: at
    a machine's 192 MiB the four-chip cell missed all 87 programs in each of
    three runs of one checkout, 630 s of set-up every time (my chip runs,
    PR 27)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CHECKOUT, ".jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_platform: str = "tpu") -> dict:
    """Runs one cell and returns the result object of the last line.
    `require_platform=None` is for the CPU rehearsal in the tests only."""
    from . import flops
    from .common import CompileWatch, device_info, log
    from .manifest import Manifest
    from . import runners

    man = Manifest(root)
    cell = man.cell(workload)
    config = man.config_doc(cell["config"])
    traffic = man.traffic_doc(cell["traffic"])

    device = device_info()
    log("device", **device)
    if require_platform is not None:
        if device["platform"] != require_platform:
            raise SystemExit(_refuse(
                f"need platform {require_platform!r}, JAX found "
                f"{device['platform']!r}: no result"))
        if device["count"] < cell["chips"]:
            raise SystemExit(_refuse(
                f"cell {workload} needs {cell['chips']} chip(s), JAX found "
                f"{device['count']}: no result"))
        peaks = flops.peaks(device["kind"])
    else:
        peaks = {"bf16_flops_per_s": float("nan"),
                 "hbm_bytes_per_s": float("nan")}

    work_dir = os.path.join(WORK_DIR, workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir, exist_ok=True)
    job = SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=float(seconds), trace=bool(trace), chips=cell["chips"],
        clock=since_start, work_dir=work_dir, platform=device["platform"],
        watch=CompileWatch(), peaks=peaks, manifest=man)
    ctx = _on_a_fresh_stack(runners.load(config["kind"]).run, job)
    ctx.config, ctx.traffic, ctx.peaks = config, traffic, peaks
    ctx.chips, ctx.device, ctx.cache = cell["chips"], device, job.watch.snapshot()
    log("compile_cache", dir=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        **ctx.cache)
    for note in ctx.notes:
        log("incorrect", why=note)
    if ctx.trace:
        log("trace.op_seconds", **ctx.trace["op_seconds"])

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in man.metrics_of(workload, group):
        value = man.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": bool(ctx.correct), "attempted": int(ctx.attempted),
           "failed": int(ctx.failed), "metrics": metrics,
           "device": dict(device, count=cell["chips"],
                          memory_peak_bytes=ctx.peak_hbm_bytes)}
    if trace and ctx.trace:
        out["device"]["busy_s"] = ctx.trace["busy_s"]
        out["device"]["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                            "idle_gaps": ctx.trace["idle_gaps"]}
    _say_what_was_compared(ctx)
    return out


def _say_what_was_compared(ctx):
    """The last lines of stderr: each number the check compared beside its
    limit, then why the run is not correct, if it is not."""
    for name, value, limit in ctx.compared:
        print(f"compared {name} = {value} (limit {limit})", file=sys.stderr)
    for note in ctx.notes:
        print(f"incorrect: {note}", file=sys.stderr)
    sys.stderr.flush()


def _on_a_fresh_stack(fn, *args):
    """Runs the runner in a thread of its own and hands back its result.

    Tracing under JAX costs time for every Python frame it is called under:
    building the ERNIE-large program took 23.5 s from a script's top level,
    36 s two frames down, 124 s from here and 186 s twelve frames down (my
    chip runs, PR 23). A new thread starts with an empty stack, so the system
    under test is called at the depth a user's own script calls it from, not
    at the harness's."""
    import threading

    box = {}

    def work():
        try:
            box["out"] = fn(*args)
        except BaseException as e:     # handed to the caller's thread
            box["err"] = e

    th = threading.Thread(target=work, name="bench-runner")
    th.start()
    th.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


def _refuse(msg: str) -> int:
    print(msg, file=sys.stderr, flush=True)
    return NO_DEVICE_EXIT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare_environment()
    out = run_cell(CHECKOUT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
