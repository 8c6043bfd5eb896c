"""The device's idle time in a traced window, split by the program's own
spans: every instant in which no operation runs on the device goes to the
innermost span the program had open then (its `telemetry.timer`s, which are
`TraceAnnotation`s of the same name), or to no span at all. Not the
winner-takes-the-gap rule of `trace_reduce.attribute`: one gap of a decode
step runs across fetch, sample, retire, admit and feed.

`ctx` carries neither the cell's name nor the trace's path, so the run's
`.xplane.pb` is looked for: newest first under `run.WORK_DIR/*/trace`, the
first file whose `bench.window` span is exactly `ctx.trace["window_s"]`
long (another test worker may be tracing another cell beside this one).
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace_reduce
from benchmark.common import log

PROGRAM_PREFIXES = ("decode.", "executor.")
NO_SPAN = "no program span"
Segment = Tuple[int, int, Optional[str]]      # start_ns, end_ns, span name


def innermost(spans: Sequence[trace_reduce.Event], lo: int,
              hi: int) -> List[Segment]:
    """[lo, hi] cut at every span edge; each piece named for the open span
    that started last (of two that started together, the one that ends
    first), or None where no span is open."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_now = [(s, -e, name) for name, s, e in spans
                    if s <= a and e >= b]
        out.append((a, b, max(open_now)[2] if open_now else None))
    return out


def split(idle: Sequence[Tuple[int, int]],
          segments: Sequence[Segment]) -> Dict[Optional[str], int]:
    """ns of the sorted, disjoint `idle` intervals under each segment name."""
    sums: Dict[Optional[str], int] = {}
    i = 0
    for a, b, name in segments:
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:   # one that crosses b stays
            sums[name] = (sums.get(name, 0)
                          + min(idle[j][1], b) - max(idle[j][0], a))
            j += 1
    return sums


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
                path, span_prefix=(trace_reduce.SPAN_PREFIX,)
                + PROGRAM_PREFIXES, **_planes(platform))
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
