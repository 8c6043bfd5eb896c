"""From a profiler trace (.xplane.pb) to busy time, idle gaps and top operations.

Reads the file with `jax.profiler.ProfileData` and nothing else. The
arithmetic (interval union, gap attribution, per-name sums) works on plain
`(name, start_ns, end_ns)` tuples, so it is testable on any recorded trace:
which planes and lines count as "the device" is a parameter.

On a TPU the device planes are named `/device:TPU:<n>`; their `XLA Ops`
line holds one event per executed operation and `XLA Modules` one per
program execution. Host spans are `jax.profiler.TraceAnnotation`s: the
benchmark's around its own calls (`bench.`) and the program's
`telemetry.timer`s, which are annotations of the same name (`decode.`,
`executor.`). Every idle instant goes to the innermost span open then.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, end_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "decode.", "executor.")
WINDOW_SPAN = "bench.window"
Segment = Tuple[int, int, Optional[str]]      # start_ns, end_ns, span name
_NUMBER_SUFFIX = re.compile(r"(\.\d+)+$")


def start_trace(trace_dir: str):
    """Starts the profiler without its Python call tracer: hooking every
    Python call slows the host loops whose gaps the trace is read for."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path: str, is_device_plane: Callable[[str], bool],
                is_ops_line: Callable[[str], bool],
                is_modules_line: Callable[[str], bool] = lambda n: False):
    """-> (device: {plane: [Event]}, modules: {plane: [Event]}, spans: [Event])"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        on_device = is_device_plane(plane.name)
        for line in plane.lines:
            if on_device and is_ops_line(line.name):
                bucket = device.setdefault(plane.name, [])
            elif on_device and is_modules_line(line.name):
                bucket = modules.setdefault(plane.name, [])
            else:
                bucket = None          # a host line: only spans are kept
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if bucket is not None:
                    bucket.append((ev.name, start, end))
                elif ev.name.startswith(SPAN_PREFIXES):
                    spans.append((ev.name, start, end))
    return device, modules, spans


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int):
    """The complement of a merged busy list inside [lo, hi]."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def innermost(spans: Sequence[Event], lo: int, hi: int) -> List[Segment]:
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


def op_family(name: str) -> str:
    """`%copy-done.195 = f32[2048]{0:T(1024)} copy-done(...)` -> `copy-done`:
    the instruction's name without its number, so that the 24 layers' copies
    of one operation add up under one entry."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return _NUMBER_SUFFIX.sub("", head) or name


def family_sums(events: Sequence[Event], lo: int, hi: int) -> Dict[str, int]:
    """ns inside [lo, hi] of every family of operations."""
    sums: Dict[str, int] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = op_family(name)
            sums[key] = sums.get(key, 0) + d
    return sums


def _by_size(item):
    return -item[1], item[0]


def reduce_events(device: Dict[str, List[Event]], spans: Sequence[Event],
                  modules: Optional[Dict[str, List[Event]]] = None,
                  default_gap_label: str = "host, no benchmark span",
                  window: Optional[Tuple[int, int]] = None) -> dict:
    """Busy and idle over the traced window, averaged over device planes:
    `op_seconds` of every family of operations (`device_ops`: the ten
    largest), and the idle time by the innermost span open then, the window
    span never naming any (`idle_gaps`).

    The window is the `bench.window` span when the trace holds one, else the
    `window` argument, else first start to last end of the device events."""
    if not device or not any(device.values()):
        raise ValueError("the trace holds no device operation")
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            window = (s, e)
    if window is None:
        window = (min(s for evs in device.values() for _, s, _ in evs),
                  max(e for evs in device.values() for _, _, e in evs))
    lo, hi = window
    segments = innermost([ev for ev in spans if ev[0] != WINDOW_SPAN],
                         lo, hi)
    busy_s, all_ops, gap_sums = [], [], {}
    for plane in sorted(device):
        evs = device[plane]
        merged = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        all_ops.extend(evs)
        for name, ns in split(gaps(merged, lo, hi), segments).items():
            label = default_gap_label if name is None else name
            gap_sums[label] = gap_sums.get(label, 0) + ns
    n_dev = len(device)
    ranked_ops = sorted(family_sums(all_ops, lo, hi).items(), key=_by_size)
    ranked_gaps = sorted(gap_sums.items(), key=_by_size)
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_dev,
        "busy_s_per_device": busy_s,
        "op_seconds": {k: v / 1e9 / n_dev for k, v in ranked_ops},
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in ranked_ops[:10]],
        "idle_gaps": [[k, v / 1e9 / n_dev] for k, v in ranked_gaps[:10]],
        "devices": n_dev,
    }
    if modules:
        # per program: executions inside the window (one cut by its edge
        # counts by the part inside) and their seconds, averaged over devices
        progs: Dict[str, List[float]] = {}
        for evs in modules.values():
            for name, s, e in evs:
                inside = min(e, hi) - max(s, lo)
                if inside > 0 and e > s:
                    rec = progs.setdefault(name, [0.0, 0.0])
                    rec[0] += inside / (e - s)
                    rec[1] += inside
        out["programs"] = {k: {"runs": c / n_dev, "seconds": ns / 1e9 / n_dev}
                           for k, (c, ns) in progs.items()}
    return out


def reduce_trace(trace_dir: str, default_gap_label: str,
                 platform: str = "tpu") -> dict:
    """The reading of a trace directory written by jax.profiler. On a TPU
    the device's own planes are read; "cpu" (the tests' rehearsal) takes
    XLA's CPU worker threads for the device and has no program line."""
    if platform == "tpu":
        planes = dict(
            is_device_plane=lambda n: n.startswith(DEVICE_PLANE_PREFIX),
            is_ops_line=lambda n: n == OPS_LINE,
            is_modules_line=lambda n: n == MODULES_LINE)
    elif platform == "cpu":
        planes = dict(is_device_plane=lambda n: n == "/host:CPU",
                      is_ops_line=lambda n: n.startswith("tf_XLA"))
    else:
        raise ValueError(f"no trace reading for platform {platform!r}")
    device, modules, spans = read_events(newest_xplane(trace_dir), **planes)
    return reduce_events(device, spans, modules, default_gap_label)
