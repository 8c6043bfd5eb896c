"""Goodput ledger — wall-clock attribution of the training loop.

The question nothing in PRs 1/6/10/14 could answer: *what fraction of
wall-clock was productive training, and where did the rest go?* The
reference reads this off the profiler's timeline by hand; large-fleet
practice (T5X/MLPerf "goodput" accounting) makes it a first-class
metric. This module is the ledger: it attributes the wall-clock of a
training run to phases using the timers the framework already emits
plus two new instrumentation points:

* ``productive`` — the jitted step call returning
  (``executor.call_ms``, around the compiled callable on every cache-hit
  dispatch). Dispatch is asynchronous: this is the device's time only
  where the caller fetches synchronously or the device's queue is full,
  and the enqueue alone where the host runs ahead;
* ``data_wait`` — the training loop blocked on the reader/feed path
  (``reader.data_wait_ms``: the DataLoader consumer's queue wait and
  train_from_dataset's batch-iterator wait);
* ``host_dispatch`` — host-side booking after the call returns
  (``executor.book_ms``: cost booking, collective accounting, watchdog
  and goodput ticks);
* ``compile`` — trace+XLA compile (``executor.compile_ms``, PR 1);
* ``checkpoint`` — crash-consistent saves (``ckpt.save_ms``, PR 5);
* ``collective`` — host-measured collective time when a backend
  exposes it (``sharding.collective_ms``; embedded in device compute
  on the fused single-process path, so usually 0 here);
* ``recovery`` — restore/restart cost (``ckpt.restore_ms``);
* ``other`` — the untracked remainder (python loop, logging, idle).

Phases are measured in the SAME thread as the loop, so they are
disjoint by construction and their sum (including ``other``) equals the
measured wall time. The ledger is delta-based: ``start_run()`` snapshots
the telemetry totals, ``breakdown()`` reports everything since. Without
an explicit start, breakdown falls back to process lifetime — a bench
row always has *something* honest to embed.

Emits ``goodput.productive_ms`` / ``goodput.badput_<phase>_ms`` /
``goodput.wall_ms`` counters and the ``goodput.ratio`` gauge (live on
/metrics via :func:`tick`, subscribed to ``telemetry.tick()`` on the
executor hot path); the flight
recorder's incident dumps bundle :func:`breakdown` so a postmortem
shows where the time went *at the moment of the trip*. Rendered by
tools/perf_report.py ("Goodput" section) and tools/fleet_report.py.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from . import flags as _flags
from . import telemetry

#: badput phase -> (source kind, metric name). "hist" reads the
#: histogram's cumulative total ms; "counter" reads a cumulative ms
#: counter. Order is the render order.
BADPUT_SOURCES = (
    ("data_wait", "hist", "reader.data_wait_ms"),
    ("host_dispatch", "hist", "executor.book_ms"),
    ("compile", "counter", "executor.compile_ms"),
    ("checkpoint", "hist", "ckpt.save_ms"),
    ("collective", "hist", "sharding.collective_ms"),
    ("recovery", "hist", "ckpt.restore_ms"),
)

PRODUCTIVE_SOURCE = ("hist", "executor.call_ms")

PHASES = tuple(p for p, _k, _m in BADPUT_SOURCES) + ("other",)

_PROCESS_T0 = time.monotonic()


def _totals() -> Dict[str, float]:
    """Cumulative ms per source metric from the live registry."""
    snap = telemetry.snapshot()
    hists = snap["hists"]
    counters = snap["counters"]
    out: Dict[str, float] = {}
    for _phase, kind, metric in BADPUT_SOURCES + (
            ("productive",) + PRODUCTIVE_SOURCE,):
        if kind == "hist":
            h = hists.get(metric)
            out[metric] = float(h["total"]) if h else 0.0
        else:
            v = counters.get(metric, 0)
            out[metric] = float(v) if isinstance(v, (int, float)) else 0.0
    return out


class GoodputLedger:
    """Delta-based wall-clock attribution window over the telemetry
    registry. Thread-safe; one per process is plenty (module singleton
    below)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._t0 = _PROCESS_T0
        self._base: Dict[str, float] = {}
        self._started = False
        self._last_publish = 0.0

    def start(self, reset: bool = True):
        """Open an attribution window NOW (baseline = current telemetry
        totals). With ``reset=False``, a no-op when a window is already
        open — train_from_dataset uses that so an outer caller-opened
        window survives nested calls."""
        with self._lock:
            if self._started and not reset:
                return
            self._t0 = time.monotonic()
            self._base = _totals()
            self._started = True

    def started(self) -> bool:
        with self._lock:
            return self._started

    def breakdown(self) -> Dict[str, Any]:
        """Wall-clock attribution since start (or process start):
        ``{"wall_ms", "productive_ms", "ratio", "phases": {phase: ms}}``.
        Tracked phases are same-thread disjoint, so
        productive + sum(phases) == wall up to measurement noise
        ("other" is the explicit untracked remainder, clamped >= 0)."""
        with self._lock:
            t0, base, started = self._t0, dict(self._base), self._started
        now_totals = _totals()
        wall_ms = max((time.monotonic() - t0) * 1e3, 1e-9)

        def delta(metric):
            return max(0.0, now_totals.get(metric, 0.0)
                       - base.get(metric, 0.0))

        phases = {phase: round(delta(metric), 3)
                  for phase, _kind, metric in BADPUT_SOURCES}
        productive = round(delta(PRODUCTIVE_SOURCE[1]), 3)
        tracked = productive + sum(phases.values())
        phases["other"] = round(max(0.0, wall_ms - tracked), 3)
        ratio = min(1.0, max(0.0, productive / wall_ms))
        return {"wall_ms": round(wall_ms, 3),
                "productive_ms": productive,
                "badput_ms": round(sum(phases.values()), 3),
                "ratio": round(ratio, 4),
                "phases": phases,
                "window": "run" if started else "process"}

    def publish(self) -> Dict[str, Any]:
        """Land the current breakdown in the registry: goodput.* ms
        counters + the goodput.ratio gauge (live on /metrics)."""
        b = self.breakdown()
        telemetry.counter_set("goodput.productive_ms", b["productive_ms"])
        telemetry.counter_set("goodput.wall_ms", b["wall_ms"])
        for phase, ms in b["phases"].items():
            telemetry.counter_set(f"goodput.badput_{phase}_ms", ms)
        telemetry.gauge_set("goodput.ratio", b["ratio"])
        with self._lock:
            self._last_publish = time.monotonic()
        return b

    def tick(self, now: Optional[float] = None):
        """Hot-path hook (telemetry.tick() after every executor dispatch):
        publish at most every FLAGS_goodput_publish_s once a window is
        open; two reads otherwise."""
        with self._lock:
            if not self._started:
                return
            last = self._last_publish
        if now is None:
            now = time.monotonic()
        try:
            period = float(_flags.flag("goodput_publish_s"))
        except Exception:
            period = 2.0
        if now - last < max(period, 0.05):
            return
        self.publish()

    def reset(self):
        with self._lock:
            self._t0 = _PROCESS_T0
            self._base = {}
            self._started = False
            self._last_publish = 0.0


_ledger = GoodputLedger()


def ledger() -> GoodputLedger:
    return _ledger


def start_run():
    """Open a fresh attribution window (explicit callers: tests, bench
    harnesses)."""
    _ledger.start(reset=True)


def ensure_run():
    """Open a window only if none is open (train_from_dataset's
    ``loop_begin`` — an outer start_run() window is preserved)."""
    _ledger.start(reset=False)


def breakdown() -> Dict[str, Any]:
    return _ledger.breakdown()


def publish() -> Dict[str, Any]:
    return _ledger.publish()


def tick(now: Optional[float] = None):
    _ledger.tick(now)


def reset():
    _ledger.reset()


def _on_tick(event: str):
    """telemetry.tick()'s subscriber: a training loop's begin opens a
    window unless its caller did, every step refreshes the published
    numbers (throttled), the loop's end lands them."""
    if event == "step":
        tick()
    elif event == "loop_begin":
        ensure_run()
    elif event == "loop_end":
        publish()


telemetry.on_tick(_on_tick)
