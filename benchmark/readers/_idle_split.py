"""The device's idle time in a traced window, split by the program's own
spans: every instant in which no operation runs on the device goes to the
innermost span the program had open then (its `telemetry.timer`s, which are
`TraceAnnotation`s of the same name), or to no span at all: one gap of a
decode step runs across fetch, sample, retire, admit and feed. The rule is
`trace_reduce.innermost`'s, as for the breakdown's `idle_gaps`; here the
benchmark's own `bench.` spans are left out, so that what a program span
does not cover is under no span.

`ctx` carries neither the cell's name nor the trace's path, so the run's
`.xplane.pb` is looked for: newest first under `run.WORK_DIR/*/trace`, the
first file whose `bench.window` span is exactly `ctx.trace["window_s"]`
long (another test worker may be tracing another cell beside this one).
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.common import log
from benchmark.trace_reduce import innermost, split

PROGRAM_PREFIXES = ("decode.", "executor.")
NO_SPAN = "no program span"


def idle_shares(device: Dict[str, List[trace_reduce.Event]],
                spans: Sequence[trace_reduce.Event],
                window: Tuple[int, int]) -> Dict[str, float]:
    """% of the window, averaged over device planes, that the device idles
    under each innermost span name and under NO_SPAN. They add up to the
    idle share."""
    lo, hi = window
    segments = innermost(spans, lo, hi)
    shares: Dict[str, float] = {NO_SPAN: 0.0}
    for events in device.values():
        busy = trace_reduce.union(
            trace_reduce.clip([(s, e) for _, s, e in events], lo, hi))
        for name, ns in split(trace_reduce.gaps(busy, lo, hi),
                              segments).items():
            key = NO_SPAN if name is None else name
            shares[key] = shares.get(key, 0.0) + ns
    scale = 100.0 / ((hi - lo) * len(device))
    return {k: v * scale for k, v in shares.items()}


def _planes(platform: str) -> dict:
    """The planes and lines `trace_reduce.reduce_trace` takes for the device."""
    if platform == "tpu":
        return dict(
            is_device_plane=lambda n: n.startswith(
                trace_reduce.DEVICE_PLANE_PREFIX),
            is_ops_line=lambda n: n == trace_reduce.OPS_LINE)
    return dict(is_device_plane=lambda n: n == "/host:CPU",
                is_ops_line=lambda n: n.startswith("tf_XLA"))


@functools.lru_cache(maxsize=1)
def _shares_of_run(platform: str, window_s: float):
    """Parsed once for the readers of one run; None when no trace of that
    window is found or the program put no span of its own into it."""
    from benchmark import run

    paths = glob.glob(os.path.join(run.WORK_DIR, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            device, _, spans = trace_reduce.read_events(
                path, **_planes(platform))
        except Exception:          # another worker's file, half written
            continue
        windows = [(s, e) for name, s, e in spans
                   if name == trace_reduce.WINDOW_SPAN]
        if not windows or (windows[-1][1] - windows[-1][0]) / 1e9 != window_s:
            continue
        own = [ev for ev in spans if ev[0].startswith(PROGRAM_PREFIXES)]
        if not own or not any(device.values()):
            return None
        shares = idle_shares(device, own, windows[-1])
        log("idle_split", trace=os.path.relpath(path, run.WORK_DIR),
            idle_share=sum(shares.values()), under=shares)
        return shares
    return None


def share(ctx, kind: str, span: str):
    """The idle share under `span` in this run's trace, 0 where the span is
    in the trace and the device never idled under it."""
    if ctx.kind != kind or not ctx.trace or not ctx.device:
        return None
    shares = _shares_of_run(ctx.device["platform"], ctx.trace["window_s"])
    return None if shares is None else shares.get(span, 0.0)
