"""Profiler — host spans, summary tables, chrome-trace export, jax bridge.

Capability mirror of the reference profiler stack:
* ``RecordEvent`` RAII spans (platform/profiler.h:127; pushed per op run,
  framework/operator.cc:195) — here a context manager feeding a global
  event store;
* ``start_profiler``/``stop_profiler``/``reset_profiler`` + the
  ``profiler()`` context and sorted summary table
  (python/paddle/fluid/profiler.py, platform/profiler.cc PrintProfiler);
* chrome://tracing JSON export (tools/timeline.py) via
  ``export_chrome_tracing``;
* device-side tracing (platform/device_tracer.cc CUPTI) maps to the jax
  profiler (XPlane/TensorBoard): ``start_trace``/``stop_trace``.

The executor pushes spans automatically: per-op in the interpreting path
(RecordEvent in run_op), per-step (compile + run) in the compiled path,
where they come from its telemetry timers (``_timer_event``).
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Dict, List, Optional

from .core import flags as _flags
from .core import telemetry as _telemetry
from .core.analysis import lockdep as _lockdep

_lock = _lockdep.lock("profiler.events")
_enabled = False
# {name, ts, dur, tid} — bounded ring: FLAGS_profiler_max_events caps the
# store so long training runs can't grow host memory without limit; when
# full, the OLDEST span is dropped (and counted in telemetry as
# profiler.events_dropped)
_events: "collections.deque[dict]" = collections.deque()


def _now_us() -> float:
    return time.perf_counter() * 1e6


class RecordEvent:
    """reference: platform/profiler.h:127 — RAII span; usable as a context
    manager or via push/pop."""

    def __init__(self, name: str):
        self.name = name
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()

    def begin(self):
        if _enabled:
            self._t0 = _now_us()

    def end(self):
        if self._t0 is None:
            return
        dur = _now_us() - self._t0
        dropped = 0
        cap = int(_flags.flag("profiler_max_events"))
        with _lock:
            while cap > 0 and len(_events) >= cap:
                _events.popleft()
                dropped += 1
            _events.append({"name": self.name, "ts": self._t0, "dur": dur,
                            "tid": threading.get_ident()})
        self._t0 = None
        if dropped:
            # outside _lock: counter_add takes the telemetry lock, and
            # telemetry.flush() takes locks in the opposite order
            _telemetry.counter_add("profiler.events_dropped", dropped)


@contextlib.contextmanager
def record_event(name: str):
    with RecordEvent(name):
        yield


def _timer_event(name: str, attrs) -> Optional[RecordEvent]:
    """What telemetry.timer(span=name) records while the profiler is on:
    the executor's per-step events (executor::run, executor::compile)
    come from its timers."""
    return RecordEvent(name) if _enabled else None


_telemetry.attach_span(_timer_event)


def is_profiler_enabled() -> bool:
    return _enabled


def start_profiler(state: str = "All", tracer_option: str = "Default"):
    """reference: profiler.py start_profiler / EnableProfiler
    (profiler.h:209). `state`/`tracer_option` kept for API parity."""
    global _enabled
    reset_profiler()
    _enabled = True


def reset_profiler():
    with _lock:
        _events.clear()


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None):
    """Disable profiling, print the summary table, optionally dump the
    chrome trace (reference: DisableProfiler + PrintProfiler)."""
    global _enabled
    _enabled = False
    summary = summarize()
    _print_summary(summary, sorted_key)
    if profile_path:
        export_chrome_tracing(profile_path)
    return summary


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None):
    """with profiler.profiler(): ... (reference: fluid/profiler.py)."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


profile = profiler  # alias


def events() -> List[dict]:
    with _lock:
        return list(_events)


def summarize() -> Dict[str, dict]:
    """Aggregate events by name → {calls, total_us, avg_us, max_us, min_us}."""
    agg: Dict[str, dict] = {}
    for e in events():
        s = agg.setdefault(e["name"], {"calls": 0, "total_us": 0.0,
                                       "max_us": 0.0, "min_us": float("inf")})
        s["calls"] += 1
        s["total_us"] += e["dur"]
        s["max_us"] = max(s["max_us"], e["dur"])
        s["min_us"] = min(s["min_us"], e["dur"])
    for s in agg.values():
        s["avg_us"] = s["total_us"] / s["calls"]
    return agg


def _print_summary(summary: Dict[str, dict], sorted_key: Optional[str]):
    if not summary:
        return
    key = {"total": "total_us", "calls": "calls", "max": "max_us",
           "min": "min_us", "ave": "avg_us", "avg": "avg_us"}.get(
               sorted_key or "total", "total_us")
    rows = sorted(summary.items(), key=lambda kv: kv[1][key], reverse=True)
    print(f"{'Event':<40}{'Calls':>8}{'Total(us)':>14}{'Avg(us)':>12}"
          f"{'Max(us)':>12}{'Min(us)':>12}")
    for name, s in rows:
        print(f"{name[:39]:<40}{s['calls']:>8}{s['total_us']:>14.1f}"
              f"{s['avg_us']:>12.1f}{s['max_us']:>12.1f}{s['min_us']:>12.1f}")


def export_chrome_tracing(path: str):
    """chrome://tracing JSON (reference: tools/timeline.py output format)."""
    trace = {"traceEvents": [
        {"name": e["name"], "ph": "X", "ts": e["ts"], "dur": e["dur"],
         "pid": 0, "tid": e["tid"], "cat": "op"}
        for e in events()
    ]}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


# -- device-side tracing: the jax profiler (XPlane → TensorBoard) replaces
#    the reference's CUPTI DeviceTracer ------------------------------------

def start_trace(log_dir: str):
    import jax

    jax.profiler.start_trace(log_dir)


def stop_trace():
    import jax

    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str):
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()


# -- per-op device attribution ---------------------------------------------
#
# The jax profiler's device trace is what located the 183 ms attention
# backward in round 4, so the framework exposes it as a first-class
# tool: run a program a few steps under the trace and attribute EXCLUSIVE
# device time to each XLA operation by name. Reference analog: the
# profiler's per-op device tables + tools/timeline.py.

def _device_ops(log_dir: str):
    """The device's operation events in the newest ``.xplane.pb`` under
    ``log_dir`` (what jax.profiler writes), read with
    ``jax.profiler.ProfileData``: on a TPU the ``XLA Ops`` line of every
    ``/device:TPU:<n>`` plane, on the CPU the events of XLA's worker
    threads (``tf_XLA*`` lines of ``/host:CPU``) that carry an ``hlo_op``.
    One dict an event: ``pid`` (plane), ``tid`` (line), ``name``, ``ts``
    and ``dur`` in microseconds, ``args`` (the event's stats)."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"),
                   key=os.path.getmtime)
    if not paths:
        raise RuntimeError(
            f"device_profile: no .xplane.pb under {log_dir} — the jax "
            f"profiler produced no dump (trace layout change, or "
            f"start_trace failed)")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        on_tpu = plane.name.startswith("/device:TPU:")
        if not on_tpu and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not (line.name == "XLA Ops" if on_tpu
                    else line.name.startswith("tf_XLA")):
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                if on_tpu or "hlo_op" in stats:
                    out.append({"pid": plane.name, "tid": line.name,
                                "name": ev.name, "ts": ev.start_ns / 1e3,
                                "dur": ev.duration_ns / 1e3, "args": stats})
    return out


def _exclusive_times(events):
    """Per-event exclusive duration: XLA while/fusion events nest, so a
    parent's time minus its children's is what IT cost."""
    import collections as _c

    by_tid = _c.defaultdict(list)
    for e in events:
        if "dur" in e:
            # tids are process-scoped: key by (pid, tid) or a
            # multi-device trace would interleave devices' timelines
            # into one nesting stack (negative exclusive times)
            by_tid[(e.get("pid"), e.get("tid"))].append(e)
    excl = {}
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"]:
                stack.pop()
            if stack:
                p = stack[-1]
                # only subtract PROPERLY CONTAINED children: a partially
                # overlapping (non-nested) event would otherwise be
                # deducted from the wrong parent, silently skewing the
                # attribution — malformed traces degrade to inclusive
                # times instead (ADVICE r4)
                if e["ts"] + e["dur"] <= p["ts"] + p["dur"]:
                    excl[id(p)] = excl.get(id(p), p["dur"]) - e["dur"]
                else:
                    continue
            stack.append(e)
    return excl


def device_profile(run_step, steps: int = 3, log_dir: Optional[str] = None):
    """Profile `run_step()` (any callable that executes one device step —
    typically a closure over Executor.run) and return rows attributing
    exclusive device time to the framework ops it came from.

    Returns {"ms_per_step": float, "rows": [(op, ms_per_step), ...]}
    sorted by cost. A row is keyed by the event's own name, the HLO
    instruction with its number folded: ``dot_general``, ``fusion``, and a
    Mosaic kernel under its ``name=`` (``paged_attention``,
    ``flash_fwd_packed``). The scope path ``run_op``'s ``jax.named_scope``
    gives each operation (``jit(train_step)/mul/dot_general``) is in the
    HLO's ``op_name`` metadata, where ``compiled.as_text()`` shows it, and
    in no stat of jax 0.9.0's plane events, on the CPU or on a v5e."""
    import re
    import shutil
    import tempfile

    import collections as _c

    cleanup = log_dir is None
    log_dir = log_dir or tempfile.mkdtemp(prefix="pt_device_profile_")
    try:
        with trace(log_dir):
            for _ in range(steps):
                run_step()
        events = _device_ops(log_dir)
    finally:
        if cleanup:
            shutil.rmtree(log_dir, ignore_errors=True)
    excl = _exclusive_times(events)
    by_op = _c.defaultdict(float)
    total = 0.0
    for e in events:
        d = excl.get(id(e), e["dur"])
        # on a TPU the name is the instruction's text: `%fusion.4 = f32[..`
        head = e["name"].split(" = ", 1)[0].lstrip("%")
        by_op[re.sub(r"(\.\d+)+$", "", head)] += d
        total += d
    rows = sorted(((k, v / 1e3 / steps) for k, v in by_op.items()),
                  key=lambda kv: -kv[1])
    return {"ms_per_step": total / 1e3 / steps, "rows": rows}
