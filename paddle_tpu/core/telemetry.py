"""Structured runtime telemetry — the unified observability registry.

Capability mirror of the reference's monitoring tier, extended into one
subsystem:

* counters/gauges (platform/monitor.h StatRegistry:77, STAT_ADD:130) —
  absorbed here; ``core.monitor`` keeps ``stat_add``/``stat_get`` as thin
  aliases over this registry;
* histograms/timers for step-time and RPC-latency percentiles (the
  reference reads these off the profiler's summary tables instead);
* a thread-safe JSONL event sink — the persistent per-run record the
  reference gets from CUPTI dumps + tools/timeline.py. Enabled via
  ``FLAGS_telemetry_path`` (or the ``PT_TELEMETRY_LOG`` env var); every
  line is one record of the fixed schema below. ``tools/perf_report.py``
  renders a run log back into tables.

JSONL schema (one object per line)::

    {"ts": <unix seconds>, "kind": <str>, "name": <str>,
     "value": <number|null>, "attrs": {<str>: <json>}}

kinds emitted by the framework: ``counter`` (value = new cumulative,
attrs.delta = increment), ``gauge``, ``timer``/``hist`` (value = sample,
ms for timers), ``compile`` (value = wall ms, attrs.cause = recompile
cause; the attrs also hold the program's set-up record, CompileRecord
below: build, trace, lower, compile, cache read, capture, first run),
``step`` (hapi per-step metrics), ``metric`` (bench results),
``fallback`` (degraded-path latches), ``fault`` (one injected fault from
the core/faults.py harness: name = site, value = per-site injection
count, attrs.exc = raised type — pairs with the ``faults.injected``
counter so chaos runs are auditable), ``span`` (one finished distributed-
tracing span from core/trace.py: value = duration ms, attrs = trace/
span/parent ids + start + pid — merged across processes by
tools/trace_view.py), ``incident`` (one anomaly dump from the unified
incident pipeline in core/incidents.py: a tripped SLO watchdog rule or
an OOM/stall/thread-death, bundling the flight-recorder ring + HBM
ledger + active traces — rendered by tools/incident_report.py),
``snapshot`` (full registry dump at flush/exit), ``profiler_summary``
(one line per profiler.summarize row).

In-memory aggregation (counters/gauges/histograms) is ALWAYS on — it is
a few dict updates per executor run, invisible next to a device step.
JSONL records are written only when a sink path is configured; the sink
batches lines in memory and flushes when the buffer reaches
``FLAGS_telemetry_buffer_lines``, every ``FLAGS_telemetry_flush_s``
seconds (a lazy daemon flusher), on ``flush_sink()``/``flush()``, on a
path change, and atexit. Sink write failures NEVER raise into the
instrumented thread — they are counted in ``telemetry.dropped_records``.

Live metrics plane: every counter increment and histogram observation is
also tracked in a rolling window (1-second delta buckets / timestamped
sample rings), so ``windowed()`` yields last-``FLAGS_metrics_window_s``
rates and p50/p95/p99 while the run is live, ``prometheus_text()``
renders them in Prometheus exposition format, and
``start_metrics_server(port)`` serves ``GET /metrics`` from any process
(trainer, pserver, serving worker) — the pull-based scrape surface the
cluster control plane (ROADMAP item 2) load-balances on.

Mergeable histograms: every histogram additionally counts observations
into FIXED log-spaced buckets (``HIST_BUCKET_BOUNDS`` — identical in
every process by construction), exported as cumulative
``pt_<name>_bucket{le="..."}`` series alongside the window summaries.
Bucket counts merge EXACTLY across processes by addition — the fleet
aggregator (core/fleetobs.py) computes fleet-level percentiles from
pooled bucket counts (``merge_bucket_counts`` + ``bucket_quantile``)
instead of the unsound average-of-quantiles.
"""

from __future__ import annotations

import atexit
import bisect
import contextlib
import json
import math
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from . import flags as _flags
from .analysis import lockdep as _lockdep

SCHEMA_FIELDS = ("ts", "kind", "name", "value", "attrs")

# flight-recorder tap (core/incidents.py installs it): every emit()
# record is fed to the hook — an always-on bounded in-memory ring —
# whether or not the JSONL sink is configured. The hook must be cheap
# and must never raise; it is called under the registry lock and uses
# only a plain internal lock, so it cannot create an order cycle.
_blackbox = [None]


def set_blackbox(fn):
    _blackbox[0] = fn

_HIST_SAMPLE_CAP = 8192  # per-histogram retained samples (sliding ring)
_WIN_BUCKET_CAP = 600    # rolling-window 1 s counter buckets (10 min cap)
_WIN_SAMPLE_CAP = 8192   # rolling-window retained histogram samples

#: Fixed log-spaced histogram bucket upper bounds, 4 per decade from
#: 1e-3 to 1e7 (ms-scale timers land mid-range; byte-ish values still
#: fit). The SAME tuple in every process is what makes cross-process
#: bucket counts addable — never derive bounds from runtime state.
HIST_BUCKET_BOUNDS: tuple = tuple(
    round(10.0 ** (i / 4.0) * 1e-3, 9) for i in range(41))


def bucket_index(v: float) -> int:
    """Index of the bucket counting ``v`` (le semantics: first bound
    >= v); len(HIST_BUCKET_BOUNDS) means the +Inf overflow bucket."""
    return bisect.bisect_left(HIST_BUCKET_BOUNDS, float(v))


def merge_bucket_counts(counts_seq: Sequence[Sequence[int]]) -> List[int]:
    """Element-wise sum of per-bucket (NON-cumulative) count vectors —
    the exact cross-registry histogram merge. Short vectors are treated
    as zero-padded (forward compatibility)."""
    out = [0] * (len(HIST_BUCKET_BOUNDS) + 1)
    for counts in counts_seq:
        for i, c in enumerate(counts):
            if i < len(out):
                out[i] += int(c)
    return out


def bucket_quantile(counts: Sequence[int], q: float) -> float:
    """Quantile estimate from per-bucket counts: the UPPER bound of the
    bucket holding the q-th sample (so the true value is within one
    bucket boundary below). Overflow samples clamp to the last finite
    bound — the estimate stays JSON-safe. 0.0 when empty."""
    total = sum(int(c) for c in counts)
    if total <= 0:
        return 0.0
    # same rank rule as the sample-ring percentile: 0-based index
    rank = min(total - 1, int(q * (total - 1) + 0.5))
    cum = 0
    for i, c in enumerate(counts):
        cum += int(c)
        if cum > rank:
            return HIST_BUCKET_BOUNDS[min(i, len(HIST_BUCKET_BOUNDS) - 1)]
    return HIST_BUCKET_BOUNDS[-1]


_TRACE_ANNOTATION = [None]   # jax.profiler.TraceAnnotation, once resolved
_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str, **attrs):
    """A profiler annotation for one timed region; a null context in a
    process that has not imported jax: a pserver or a tool that only counts
    must not start to. An annotation outside a running trace costs one
    TraceMe check. ``attrs`` are the event's stats in the trace (a
    request's ``rid``), formatted only while one runs; its name stays
    ``name``."""
    cls = _TRACE_ANNOTATION[0]
    if cls is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION
        from jax.profiler import TraceAnnotation as cls

        _TRACE_ANNOTATION[0] = cls
    return cls(name, **attrs)


# Who else records a timer's span: callables (span name, attrs) -> a context
# manager, or None when they are not recording now. The layers above attach
# themselves (core/trace.py, paddle_tpu/profiler.py); the hot paths below
# know only timer().
_span_recorders: List[Any] = []


def attach_span(recorder):
    if recorder not in _span_recorders:
        _span_recorders.append(recorder)


def _attached(span: str, attrs: Dict[str, Any]):
    """What the attached recorders open for one span, as one context
    manager; the shared null context when nobody is recording."""
    opened = [cm for cm in (recorder(span, attrs)
                            for recorder in _span_recorders)
              if cm is not None]
    if not opened:
        return _NO_ANNOTATION
    with contextlib.ExitStack() as stack:
        for cm in opened:
            stack.enter_context(cm)
        return stack.pop_all()


# The one per-step hook. A hot loop calls tick() once a step (the executor
# after a dispatch, the decode engine after a step) and, around a training
# loop, tick("loop_begin") / tick("loop_end"); the planes built on top
# (core/incidents.py, core/goodput.py) subscribe from their own modules
# and keep their own throttles. A subscriber must be cheap when it has
# nothing to do and must not raise.
_tick_subscribers: List[Any] = []


def on_tick(subscriber):
    if subscriber not in _tick_subscribers:
        _tick_subscribers.append(subscriber)


def tick(event: str = "step"):
    for subscriber in _tick_subscribers:
        subscriber(event)


# -- set-up records: one a compiled program ----------------------------------
# Where a program's first life goes: its construction, jax's trace, the
# lowering, the backend's compile or the read of the persistent cache, the
# cost capture and the first execution. Executor._compile_and_run,
# Executor._run_interpreted (a program's first interpreted run) and
# DecodeEngine._entry each open one around that whole life and hand it to
# the ``compile`` event as its attrs. Always made: a handful of clock reads
# a compiled program, none on a steady-state step.

_COMPILE_RECORD_CAP = 512
_compile_records: deque = deque(maxlen=_COMPILE_RECORD_CAP)
_setup_lock = threading.Lock()     # the list and the shape-inference pair
_open_records = threading.local()  # .stack: the records open on this thread
_infer_shape = [0.0, 0]            # seconds, calls: the process's lifetime
_jax_listening = [False]

# jax.monitoring's durations of one compile, by the record's field. They
# nest (the backend's holds the cache retrieval; a jitted function traced
# inside another's trace reports its own), so each is stored less what
# ended inside it: the parts are disjoint.
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s"}
_PHASES = ("build_s", "trace_s", "lower_s", "compile_s", "cache_read_s",
           "capture_s", "first_run_s")
#: a record's fields in seconds that add up over records (the phases tile
#: ``total_s``; ``infer_shape_s`` is a part of ``build_s``)
COMPILE_RECORD_SECONDS = ("total_s",) + _PHASES + ("infer_shape_s",)


def _open_record():
    stack = getattr(_open_records, "stack", None)
    return stack[-1] if stack else None


def _on_jax_duration(event, duration, **_):
    field = _JAX_DURATIONS.get(event)
    rec = _open_record() if field else None
    if rec is not None:
        rec._jax(field, float(duration))


def _on_jax_event(event, **_):
    rec = _open_record()
    if rec is None:
        return
    if event == "/jax/compilation_cache/cache_hits":
        rec.cache_hit = rec.cache_hit is not False
    elif event == "/jax/compilation_cache/cache_misses":
        rec.cache_hit = False


class CompileRecord:
    """One program's first life, on ``time.perf_counter()`` (``t0``/``t1``;
    every other field in seconds). ``build_s``: the Program's construction
    and the Python function around it, shape inference (``infer_shape_s``,
    a part of it) and whatever jax traced for it included. ``trace_s``,
    ``lower_s``, ``compile_s``, ``cache_read_s``: jax's own phases, disjoint
    (``compile_s`` is the backend's time less the cache retrieval).
    ``capture_s``: ``costmodel.capture`` less the jax phases inside it.
    ``first_run_s``: what is left of ``total_s``: the first execution, its
    transfers, the rest. So the seven tile ``total_s``. ``cache_hit``: every
    program of the record was read from jax's persistent cache (True), one
    was not (False), or the cache said neither (None: it is off, or the
    program is under its thresholds). A record opened inside another on
    one thread takes the durations; the outer's parts and ``total_s`` leave
    the inner's wall time out. Opened by ``with`` on the thread that does
    the work; ``fields`` ride along as they are (``pallas_kernels``);
    ``close()`` inside the block ends and keeps it, and a block left by an
    exception keeps nothing."""

    def __init__(self, kind: str, name: str, **fields):
        self.kind, self.name, self.fields = kind, name, fields
        self.ops = 0
        self.infer_shape_s = 0.0
        self.infer_shape_calls = 0
        self.backend_compiles = 0
        self.cache_hit: Optional[bool] = None
        for f in _PHASES:
            setattr(self, f, 0.0)
        self._region = None      # "build_s" / "capture_s" while inside one
        self._inside = 0.0       # seconds of the region that are not its own
        self._nested = 0.0       # records that closed inside this one
        self._spans: list = []   # (start, end): finished intervals, newest last
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        if not _jax_listening[0]:
            with _setup_lock:
                if not _jax_listening[0]:
                    import jax.monitoring

                    jax.monitoring.register_event_listener(_on_jax_event)
                    jax.monitoring.register_event_duration_secs_listener(
                        _on_jax_duration)
                    _jax_listening[0] = True
        stack = getattr(_open_records, "stack", None)
        if stack is None:
            stack = _open_records.stack = []
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        # a program that failed on the way leaves no record
        stack = _open_records.stack
        if self in stack:
            stack.remove(self)
        return False

    @contextlib.contextmanager
    def phase(self, field: str):
        """Times ``build_s`` or ``capture_s``: the region's wall time less
        what jax's phases and inner records took of it."""
        t, self._region, self._inside = time.perf_counter(), field, 0.0
        try:
            yield self
        finally:
            own = time.perf_counter() - t - self._inside
            setattr(self, field, getattr(self, field) + max(own, 0.0))
            self._region = None

    def _span(self, start: float, end: float) -> float:
        """Books one finished interval and returns its own part: its
        length less the intervals that ended inside it."""
        own = end - start
        while self._spans and self._spans[-1][1] > start:
            inner = self._spans.pop()
            own -= inner[1] - inner[0]
        self._spans.append((start, end))
        return max(own, 0.0)

    def _jax(self, field: str, duration: float):
        if field == "compile_s":
            self.backend_compiles += 1
        if self._region == "build_s":
            return      # a trace for a shape: the build's own seconds
        now = time.perf_counter()
        own = self._span(now - duration, now)
        setattr(self, field, getattr(self, field) + own)
        if self._region is not None:
            self._inside += own

    def close(self) -> Dict[str, Any]:
        """Ends the record at the first execution's return, keeps it in
        the list and returns it as the ``compile`` event's attrs."""
        self.t1 = time.perf_counter()
        self.__exit__()
        wall = self.t1 - self.t0
        total = wall - self._nested
        self.first_run_s = max(0.0, total - sum(
            getattr(self, f) for f in _PHASES if f != "first_run_s"))
        doc = {"kind": self.kind, "name": self.name, "ops": int(self.ops),
               "total_s": total, "infer_shape_s": self.infer_shape_s,
               "infer_shape_calls": self.infer_shape_calls,
               "backend_compiles": self.backend_compiles,
               "cache_hit": self.cache_hit, "t0": self.t0, "t1": self.t1}
        doc.update((f, getattr(self, f)) for f in _PHASES)
        doc.update(self.fields)
        outer = _open_record()
        if outer is not None:
            outer._span(self.t0, self.t1)
            outer._nested += wall
            if outer._region is not None:
                outer._inside += wall
        with _setup_lock:
            _compile_records.append(doc)
        return doc


def note_infer_shape(seconds: float):
    """One shape inference of ``Block.append_op``: into the process's
    pair and into the record open on this thread, if there is one."""
    with _setup_lock:
        _infer_shape[0] += seconds
        _infer_shape[1] += 1
    rec = _open_record()
    if rec is not None:
        rec.infer_shape_s += seconds
        rec.infer_shape_calls += 1


def infer_shape_totals():
    """(seconds, calls) of every shape inference since the process began:
    inside a record (then a part of its ``build_s``) or before one (a
    trainer's Program is built by the user's script). ``reset()`` leaves
    it alone."""
    with _setup_lock:
        return _infer_shape[0], _infer_shape[1]


def compile_records() -> List[Dict[str, Any]]:
    """The newest 512 set-up records, oldest first. ``reset()`` does not
    clear them: set-up is over when a run resets its window's counters."""
    with _setup_lock:
        return [dict(r) for r in _compile_records]


def clear_compile_records():
    with _setup_lock:
        _compile_records.clear()


class _Hist:
    """Running histogram: exact count/sum/min/max + a bounded sample ring
    for percentile estimates (recent-window semantics once full) + fixed
    log-spaced bucket counts (HIST_BUCKET_BOUNDS, exact cross-process
    merge — the pt_*_bucket exposition)."""

    __slots__ = ("count", "total", "vmin", "vmax", "samples", "_next",
                 "buckets")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.samples = []
        self._next = 0
        self.buckets = [0] * (len(HIST_BUCKET_BOUNDS) + 1)

    def observe(self, v: float):
        v = float(v)
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        if math.isfinite(v):
            self.buckets[bisect.bisect_left(HIST_BUCKET_BOUNDS, v)] += 1
        else:
            self.buckets[-1] += 1
        if len(self.samples) < _HIST_SAMPLE_CAP:
            self.samples.append(v)
        else:
            self.samples[self._next] = v
            self._next = (self._next + 1) % _HIST_SAMPLE_CAP

    def summary(self) -> Dict[str, float]:
        s = sorted(self.samples)

        def pct(q):
            if not s:
                return 0.0
            return s[min(len(s) - 1, int(q * (len(s) - 1) + 0.5))]

        return {"count": self.count, "total": round(self.total, 3),
                "min": round(self.vmin, 3) if self.count else 0.0,
                "max": round(self.vmax, 3) if self.count else 0.0,
                "avg": round(self.total / self.count, 3) if self.count else 0.0,
                "p50": round(pct(0.50), 3), "p90": round(pct(0.90), 3),
                "p95": round(pct(0.95), 3), "p99": round(pct(0.99), 3)}


class TelemetryRegistry:
    _instance: Optional["TelemetryRegistry"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        # record=False: the sanitizer books its lock metrics THROUGH this
        # registry — the registry's own lock gets order/re-entry/stall
        # detection but must not book about itself
        self._lock = _lockdep.rlock("telemetry.registry", record=False)
        self._counters: Dict[str, Any] = {}
        self._gauges: Dict[str, Any] = {}
        self._hists: Dict[str, _Hist] = {}
        self._file = None
        self._path: Optional[str] = None
        self._sink_warned = False
        # buffered sink: pending JSONL lines + flush bookkeeping
        self._buf: list = []
        self._last_flush = 0.0
        self._flusher_started = False
        # rolling window: per-counter 1 s delta buckets ([sec, sum]) and
        # per-histogram (ts, value) sample rings — pruned lazily on read
        self._win_counts: Dict[str, deque] = {}
        self._win_samples: Dict[str, deque] = {}

    @classmethod
    def instance(cls) -> "TelemetryRegistry":
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance

    # -- sink ----------------------------------------------------------------
    def _resolve_path(self) -> Optional[str]:
        path = _flags.flag("telemetry_path")
        if not path:
            path = os.environ.get("PT_TELEMETRY_LOG", "")
        return path or None

    def _sink(self):
        """Current sink file (called under self._lock); follows flag/env
        changes so set_flags({'FLAGS_telemetry_path': ...}) takes effect
        mid-run and '' closes the sink (flushing the buffer into the old
        file first — readers of a just-closed log see every record)."""
        path = self._resolve_path()
        if path != self._path:
            if self._file is not None:
                self._flush_buf_locked()
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            self._buf.clear()
            self._path = path
            if path:
                try:
                    self._file = open(path, "a")
                    self._last_flush = time.time()
                except OSError as e:
                    if not self._sink_warned:
                        self._sink_warned = True
                        print(f"[telemetry] cannot open sink {path!r}: {e}",
                              file=sys.stderr)
                    self._path = None
        return self._file

    def _drop_locked(self, n: int):
        """Count records lost to a failing sink — in-memory only (a
        counter_add here would recurse into emit)."""
        self._counters["telemetry.dropped_records"] = \
            self._counters.get("telemetry.dropped_records", 0) + n

    def _flush_buf_locked(self):
        """Write the buffered lines as ONE batched write + flush (called
        under self._lock). A failing filesystem must never raise into the
        executor/serving thread that happened to trigger the flush."""
        if not self._buf or self._file is None:
            return
        batch, self._buf = self._buf, []
        self._last_flush = time.time()
        try:
            self._file.write("\n".join(batch) + "\n")
            self._file.flush()
        except (OSError, ValueError):
            self._drop_locked(len(batch))

    def _ensure_flusher_locked(self):
        """Lazy daemon thread: flushes the sink buffer every
        FLAGS_telemetry_flush_s so a mostly-idle process still lands its
        records without waiting for the next emit or exit."""
        if self._flusher_started:
            return
        self._flusher_started = True

        def loop():
            while True:
                try:
                    delay = float(_flags.flag("telemetry_flush_s"))
                except Exception:
                    delay = 0.25
                time.sleep(max(0.05, delay))
                with self._lock:
                    self._flush_buf_locked()

        threading.Thread(target=loop, name="pt-telemetry-flush",
                         daemon=True).start()

    def flush_sink(self):
        """Force the buffered JSONL lines to disk now (tests, scrapes)."""
        with self._lock:
            self._flush_buf_locked()

    def enabled(self) -> bool:
        return self._resolve_path() is not None

    def configure(self, path: Optional[str]):
        """Point the JSONL sink at `path` (None/'' disables). Equivalent to
        set_flags({'FLAGS_telemetry_path': path}) — the flag wins over the
        PT_TELEMETRY_LOG env var."""
        _flags.set_flags({"telemetry_path": path or ""})
        with self._lock:
            self._sink()

    def emit(self, kind: str, name: str, value=None,
             attrs: Optional[Dict[str, Any]] = None):
        """Append one schema record to the sink (no-op when disabled)
        and to the always-on flight-recorder ring (core/incidents.py)
        when one is installed — the ring sees every record even when no
        JSONL sink is configured. Lines are buffered and batch-written
        (see module docstring); any serialisation/write failure is
        counted, never raised."""
        bb = _blackbox[0]
        with self._lock:
            f = self._sink()
            if f is None and bb is None:
                return
            rec = {"ts": time.time(), "kind": kind, "name": name,
                   "value": value, "attrs": attrs or {}}
            if bb is not None:
                try:
                    bb(rec)
                except Exception:
                    pass
            if f is None:
                return
            try:
                self._buf.append(json.dumps(rec, default=str))
            except (ValueError, TypeError):
                self._drop_locked(1)
                return
            try:
                limit = int(_flags.flag("telemetry_buffer_lines"))
            except Exception:
                limit = 1
            if len(self._buf) >= max(1, limit) or \
                    rec["ts"] - self._last_flush >= \
                    float(_flags.flag("telemetry_flush_s")):
                self._flush_buf_locked()
            self._ensure_flusher_locked()

    # -- metrics -------------------------------------------------------------
    def _window_count_locked(self, name: str, delta, now: float):
        """Fold one counter increment into its 1 s rolling-window bucket
        (called under self._lock)."""
        dq = self._win_counts.get(name)
        if dq is None:
            dq = self._win_counts[name] = deque(maxlen=_WIN_BUCKET_CAP)
        sec = int(now)
        if dq and dq[-1][0] == sec:
            dq[-1][1] += delta
        else:
            dq.append([sec, delta])

    def counter_add(self, name: str, delta=1, **attrs):
        with self._lock:
            val = self._counters.get(name, 0) + delta
            self._counters[name] = val
            self._window_count_locked(name, delta, time.time())
        self.emit("counter", name, val, {"delta": delta, **attrs})
        return val

    def counter_quiet(self, name: str, delta=1):
        """In-memory-only increment: no JSONL record. For accounting that
        must not recurse into (or double the volume of) the sink — span
        counts, sink-failure counts."""
        with self._lock:
            val = self._counters.get(name, 0) + delta
            self._counters[name] = val
            self._window_count_locked(name, delta, time.time())
        return val

    def counter_set(self, name: str, value, **attrs):
        with self._lock:
            self._counters[name] = value
        self.emit("counter", name, value, {"set": True, **attrs})

    def counter_get(self, name: str):
        with self._lock:
            return self._counters.get(name, 0)

    def gauge_set(self, name: str, value, **attrs):
        with self._lock:
            self._gauges[name] = value
        self.emit("gauge", name, value, attrs)

    def observe(self, name: str, value, kind: str = "hist", **attrs):
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Hist()
            h.observe(value)
            dq = self._win_samples.get(name)
            if dq is None:
                dq = self._win_samples[name] = deque(maxlen=_WIN_SAMPLE_CAP)
            dq.append((time.time(), float(value)))
        self.emit(kind, name, round(float(value), 4), attrs)

    def observe_quiet(self, name: str, value):
        """Histogram-only observation: no JSONL record, nothing in the
        flight recorder, no sample in the rolling window (/metrics shows
        the cumulative sum, count and buckets, no window quantiles). For
        samples a hot loop takes many times a step (the decode engine's
        phases and token gaps, the executor's phases): as records they
        would crowd the run log and the recorder's ring out, and as window
        samples every ``windowed()`` pass of the SLO watchdog would sort
        them under this lock. ``counter_quiet``'s twin."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Hist()
            h.observe(value)

    @contextlib.contextmanager
    def timer(self, name: Optional[str] = None,
              into: Optional[Dict[str, float]] = None,
              span: Optional[str] = None, **attrs):
        """The one span primitive of a hot path. Times the region into the
        histogram ``name`` (ms) and, while a jax profiler trace is running,
        marks it there as a ``TraceAnnotation`` of the same name, on the
        clock of the device's own lines, with ``attrs`` as the event's stats
        (the histogram takes none: one series a name). ``into`` defers the sample: the ms
        are added to that dict under ``name`` instead of the histogram, for
        a caller that decides at the end of an iteration whether it counts
        (DecodeEngine._loop, Executor.run). ``span`` names the region for
        whoever attached with :func:`attach_span` (core/trace.py, which
        records while a trace is sampled, paddle_tpu/profiler.py, while it
        is on): they record it under that name with ``attrs``, outside the timed part.
        A region with a span and no histogram leaves ``name`` out."""
        with _attached(span, attrs) if span is not None else _NO_ANNOTATION:
            if name is None:
                yield
                return
            t0 = time.perf_counter()
            try:
                with _annotation(name, **attrs):
                    yield
            finally:
                ms = (time.perf_counter() - t0) * 1e3
                if into is None:
                    self.observe(name, ms, kind="timer", **attrs)
                else:
                    into[name] = into.get(name, 0.0) + ms

    # -- snapshots -----------------------------------------------------------
    def counters(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._counters)

    def gauges(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._gauges)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "hists": {n: h.summary()
                              for n, h in self._hists.items()}}

    def hist_buckets(self) -> Dict[str, List[int]]:
        """Per-histogram NON-cumulative bucket counts over
        HIST_BUCKET_BOUNDS (+ overflow slot) — the mergeable view the
        fleet aggregator pools across registries."""
        with self._lock:
            return {n: list(h.buckets) for n, h in self._hists.items()}

    def reset(self):
        """Clear all in-memory aggregates (tests, a benchmark's window).
        Leaves the sink alone, and the set-up records and the shape-
        inference pair (``compile_records()``, ``infer_shape_totals()``):
        they are read after the window that a reset opens."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._win_counts.clear()
            self._win_samples.clear()

    # -- rolling-window metrics (the live /metrics plane) --------------------
    def windowed(self, window_s: Optional[float] = None,
                 now: Optional[float] = None) -> Dict[str, Any]:
        """Last-N-seconds view of the registry: counter deltas + per-second
        rates, current gauges, and histogram count/rate/p50/p95/p99 over
        the window (default FLAGS_metrics_window_s). Scrapeable while the
        run is live — this is what /metrics and /v1/stats render.

        ONE cutoff rule for both families: an observation is in the
        window iff its timestamp >= now - W, where a counter bucket's
        timestamp is its second-start (bucket granularity: increments in
        the partial boundary bucket are dropped, never double-counted —
        counters and histogram samples used to disagree by up to a whole
        boundary bucket). ``now`` is injectable for deterministic tests.
        """
        W = float(window_s if window_s is not None
                  else _flags.flag("metrics_window_s"))
        W = max(W, 1.0)
        if now is None:
            now = time.time()
        cut = now - W
        with self._lock:
            counters = {}
            for name, dq in self._win_counts.items():
                tot = 0
                for sec, v in dq:
                    if sec >= cut:
                        tot += v
                if tot:
                    counters[name] = {"delta": tot,
                                      "rate": round(tot / W, 6)}
            hists = {}
            for name, dq in self._win_samples.items():
                vals = sorted(v for ts, v in dq if ts >= cut)
                if not vals:
                    continue
                n = len(vals)

                def pct(q, vals=vals, n=n):
                    return round(vals[min(n - 1, int(q * (n - 1) + 0.5))], 4)

                hists[name] = {"count": n, "rate": round(n / W, 6),
                               "avg": round(sum(vals) / n, 4),
                               "p50": pct(0.50), "p95": pct(0.95),
                               "p99": pct(0.99), "max": round(vals[-1], 4)}
            gauges = dict(self._gauges)
        return {"window_s": W, "ts": now, "counters": counters,
                "gauges": gauges, "hists": hists}

    def prometheus_text(self, window_s: Optional[float] = None) -> str:
        """Prometheus text exposition (0.0.4): cumulative counters as
        ``pt_<name>_total``, rolling-window rates as ``pt_<name>_rate``,
        gauges, histograms as summaries whose quantiles are computed
        over the rolling window (cumulative _sum/_count), plus the
        cumulative fixed-bucket ``pt_<name>_bucket{le="..."}`` series
        (le-ordered, ending with +Inf) the fleet aggregator merges
        exactly."""
        win = self.windowed(window_s)
        W = int(win["window_s"])
        with self._lock:
            cum = {n: v for n, v in self._counters.items()
                   if isinstance(v, (int, float))}
            hist_cum = {n: (h.count, h.total, list(h.buckets))
                        for n, h in self._hists.items()}
        lines = []
        for name in sorted(cum):
            m = _prom_name(name)
            lines.append(f"# TYPE {m}_total counter")
            lines.append(f"{m}_total {_prom_num(cum[name])}")
            wc = win["counters"].get(name)
            if wc is not None:
                lines.append(f"# TYPE {m}_rate gauge")
                lines.append(f'{m}_rate{{window="{W}s"}} '
                             f'{_prom_num(wc["rate"])}')
        for name in sorted(win["gauges"]):
            v = win["gauges"][name]
            if not isinstance(v, (int, float)):
                continue
            lines.append(f"# TYPE {_prom_name(name)} gauge")
            lines.append(f"{_prom_name(name)} {_prom_num(v)}")
        for name in sorted(hist_cum):
            cnt, tot, buckets = hist_cum[name]
            m = _prom_name(name)
            wh = win["hists"].get(name)
            lines.append(f"# TYPE {m} summary")
            if wh:
                for q, key in (("0.5", "p50"), ("0.95", "p95"),
                               ("0.99", "p99")):
                    lines.append(f'{m}{{quantile="{q}"}} '
                                 f'{_prom_num(wh[key])}')
            lines.append(f"{m}_sum {_prom_num(round(tot, 4))}")
            lines.append(f"{m}_count {cnt}")
            # cumulative fixed-bucket series: identical le labels in
            # every process (HIST_BUCKET_BOUNDS), so fleet-side merging
            # is pure addition of counts under matching labels. le must
            # be emitted EXACTLY (repr, not _prom_num's 6-decimal
            # rounding): a rounded-up label maps into the next bucket
            # on the scrape side and misaligns the merge
            running = 0
            for bound, c in zip(HIST_BUCKET_BOUNDS, buckets):
                running += c
                lines.append(f'{m}_bucket{{le="{bound!r}"}} {running}')
            lines.append(f'{m}_bucket{{le="+Inf"}} {cnt}')
            if wh:
                lines.append(f"# TYPE {m}_window_rate gauge")
                lines.append(f'{m}_window_rate{{window="{W}s"}} '
                             f'{_prom_num(wh["rate"])}')
        return "\n".join(lines) + "\n"

    def flush(self):
        """Persist a full registry snapshot + the profiler's summary table
        into the sink — called atexit so every run log ends with final
        counter values and the host-span rollup perf_report can render."""
        if not self.enabled():
            return
        # gather the profiler summary BEFORE emitting: emit takes this
        # registry's lock per record and profiler.summarize takes the
        # profiler's — never hold both at once (profiler's ring-buffer
        # drop accounting calls back into counter_add)
        prof_rows = {}
        try:
            from .. import profiler as _prof

            prof_rows = _prof.summarize()
        except Exception:
            pass
        self.emit("snapshot", "telemetry", None, self.snapshot())
        for name, row in prof_rows.items():
            self.emit("profiler_summary", name, row.get("total_us"),
                      {k: v for k, v in row.items() if k != "total_us"})
        self.flush_sink()


def _prom_name(name: str) -> str:
    return "pt_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _prom_num(v) -> str:
    if isinstance(v, float):
        return repr(round(v, 6))
    return str(v)


# live MetricsServer count: costmodel's 'auto' capture level treats a
# process that started a scrape surface as instrumented
_metrics_servers = 0
_metrics_servers_lock = threading.Lock()


def metrics_server_active() -> bool:
    return _metrics_servers > 0


class MetricsServer:
    """Stdlib HTTP scrape surface over the live registry: ``/metrics``
    (Prometheus text) + ``/healthz``. Started by start_metrics_server —
    usable from trainers and pservers, and mirrored by the serving
    server's own /metrics route."""

    def __init__(self, registry: "TelemetryRegistry",
                 host: str = "127.0.0.1", port: int = 0):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        reg = registry

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    self._send(200, reg.prometheus_text().encode(),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif path == "/healthz":
                    self._send(200, b'{"status": "ok"}',
                               "application/json")
                elif path == "/varz":
                    body = json.dumps({"snapshot": reg.snapshot(),
                                       "window": reg.windowed()},
                                      default=str).encode()
                    self._send(200, body, "application/json")
                else:
                    self._send(404, b'{"error": "no route"}',
                               "application/json")

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="pt-metrics-http", daemon=True)
        self._thread.start()
        global _metrics_servers
        with _metrics_servers_lock:
            _metrics_servers += 1
        # a scrape surface marks the run as instrumented: arm the SLO
        # watchdog plane (core/incidents.py, FLAGS_slo_watchdog 'auto')
        try:
            from . import incidents

            incidents.arm()
        except Exception:
            pass

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def shutdown(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
        global _metrics_servers
        with _metrics_servers_lock:
            _metrics_servers = max(0, _metrics_servers - 1)
        try:
            from . import incidents

            incidents.disarm()
        except Exception:
            pass


# -- module-level convenience API (the surface everything instruments
#    against; mirrors monitor.h's free-function STAT_ADD style) -------------

def _reg() -> TelemetryRegistry:
    return TelemetryRegistry.instance()


def counter_add(name: str, delta=1, **attrs):
    return _reg().counter_add(name, delta, **attrs)


def counter_set(name: str, value, **attrs):
    return _reg().counter_set(name, value, **attrs)


def counter_get(name: str):
    return _reg().counter_get(name)


def counter_quiet(name: str, delta=1):
    return _reg().counter_quiet(name, delta)


def gauge_set(name: str, value, **attrs):
    return _reg().gauge_set(name, value, **attrs)


def observe(name: str, value, kind: str = "hist", **attrs):
    return _reg().observe(name, value, kind=kind, **attrs)


def observe_quiet(name: str, value):
    return _reg().observe_quiet(name, value)


def timer(name: Optional[str] = None,
          into: Optional[Dict[str, float]] = None,
          span: Optional[str] = None, **attrs):
    return _reg().timer(name, into=into, span=span, **attrs)


def event(kind: str, name: str, value=None, attrs=None):
    return _reg().emit(kind, name, value, attrs)


def counters() -> Dict[str, Any]:
    return _reg().counters()


def gauges() -> Dict[str, Any]:
    return _reg().gauges()


def snapshot() -> Dict[str, Any]:
    return _reg().snapshot()


def hist_buckets() -> Dict[str, List[int]]:
    return _reg().hist_buckets()


def enabled() -> bool:
    return _reg().enabled()


def configure(path: Optional[str]):
    return _reg().configure(path)


def reset():
    return _reg().reset()


def flush():
    return _reg().flush()


def flush_sink():
    return _reg().flush_sink()


def windowed(window_s: Optional[float] = None,
             now: Optional[float] = None) -> Dict[str, Any]:
    return _reg().windowed(window_s, now=now)


def prometheus_text(window_s: Optional[float] = None) -> str:
    return _reg().prometheus_text(window_s)


def start_metrics_server(port: int = 0,
                         host: str = "127.0.0.1") -> MetricsServer:
    """Serve GET /metrics (Prometheus text) + /healthz + /varz from this
    process's live registry on ``host:port`` (port 0 = ephemeral).
    Returns the started MetricsServer (``.url``, ``.shutdown()``)."""
    return MetricsServer(_reg(), host=host, port=port)


def bench_extra() -> Dict[str, Any]:
    """Key counters for BENCH json `extra` — every BENCH_r*.json carries
    compile/cache/donation accounting from here on (bench.py merges it)."""
    c = counters()
    out = {"telemetry_compiles": int(c.get("executor.compiles", 0)),
           "telemetry_cache_hits": int(c.get("executor.cache_hits", 0)),
           "telemetry_donation_copies":
               int(c.get("executor.donation_copies", 0))}
    # dispatch-amortization accounting (K-step fused execution): how many
    # device steps rode how many host dispatches
    fused_d = int(c.get("executor.fused_dispatches", 0))
    if fused_d:
        out["telemetry_fused_dispatches"] = fused_d
        out["telemetry_fused_steps"] = int(c.get("executor.fused_steps", 0))
    # crash-consistent checkpoint accounting (paddle_tpu/checkpoint.py)
    saves = int(c.get("ckpt.saves", 0))
    if saves:
        out["telemetry_ckpt_saves"] = saves
        out["telemetry_ckpt_bytes"] = int(c.get("ckpt.bytes", 0))
        vf = int(c.get("ckpt.verify_failures", 0))
        if vf:
            out["telemetry_ckpt_verify_failures"] = vf
            out["telemetry_ckpt_fallbacks"] = int(c.get("ckpt.fallbacks", 0))
    # serving-engine accounting (micro-batching runs: bench_serving)
    sreq = int(c.get("serving.requests", 0))
    if sreq:
        out["telemetry_serving_requests"] = sreq
        out["telemetry_serving_batches"] = int(c.get("serving.batches", 0))
        out["telemetry_serving_rejects"] = int(c.get("serving.rejects", 0))
        rows = int(c.get("serving.batched_rows", 0))
        padded = int(c.get("serving.padded_rows", 0))
        if rows:
            out["telemetry_serving_batch_fill"] = round(
                rows / (rows + padded), 4)
    return out


atexit.register(flush)
