"""From a profiler trace (.xplane.pb) to busy time, idle gaps and top operations.

Reads the file with `jax.profiler.ProfileData` and nothing else. The
arithmetic (interval union, gap attribution, per-name sums) works on plain
`(name, start_ns, end_ns)` tuples, so it is testable on any recorded trace:
which planes and lines count as "the device" is a parameter.

On a TPU the device planes are named `/device:TPU:<n>`; their `XLA Ops`
line holds one event per executed operation and `XLA Modules` one per
program execution. Host spans are the `jax.profiler.TraceAnnotation`s the
benchmark wraps around its own calls (names starting with `bench.`).
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
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
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
                is_modules_line: Callable[[str], bool] = lambda n: False,
                span_prefix: str = SPAN_PREFIX):
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
                elif ev.name.startswith(span_prefix):
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


def attribute(gap: Tuple[int, int], spans: Sequence[Event],
              default: str) -> str:
    """The host span that covers most of the gap, if it covers over half
    of it; the window span itself never names a gap."""
    best, best_ns = default, 0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best if 2 * best_ns > gap[1] - gap[0] else default


def op_family(name: str) -> str:
    """`%copy-done.195 = f32[2048]{0:T(1024)} copy-done(...)` -> `copy-done`:
    the instruction's name without its number, so that the 24 layers' copies
    of one operation add up under one entry."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return _NUMBER_SUFFIX.sub("", head) or name


def top_sums(events: Sequence[Event], lo: int, hi: int, n: int = 10):
    sums: Dict[str, int] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = op_family(name)
            sums[key] = sums.get(key, 0) + d
    ranked = sorted(sums.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[k, v / 1e9] for k, v in ranked]


def reduce_events(device: Dict[str, List[Event]], spans: Sequence[Event],
                  modules: Optional[Dict[str, List[Event]]] = None,
                  default_gap_label: str = "host, no benchmark span",
                  window: Optional[Tuple[int, int]] = None) -> dict:
    """Busy and idle over the traced window, averaged over device planes.

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
    busy_s, all_ops, gap_sums = [], [], {}
    for plane in sorted(device):
        evs = device[plane]
        merged = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        all_ops.extend(evs)
        for g in gaps(merged, lo, hi):
            label = attribute(g, spans, default_gap_label)
            gap_sums[label] = gap_sums.get(label, 0) + (g[1] - g[0])
    n_dev = len(device)
    ranked_gaps = sorted(gap_sums.items(), key=lambda kv: (-kv[1], kv[0]))
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_dev,
        "busy_s_per_device": busy_s,
        "device_ops": [[k, v / n_dev] for k, v in top_sums(all_ops, lo, hi)],
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
