"""Distributed tracing — propagated per-request/per-step span contexts.

The reference's deepest observability tier is the profiler + CUPTI
timeline (platform/profiler.h rendered by tools/timeline.py): one
process, post-hoc, no causality across the RPC boundary. This module is
the Dapper-model complement: a *trace* is one logical request or
training step, made of *spans* (named, timed, nested operations) that
share a ``trace_id`` across threads and PROCESSES, so a serving request
can be followed client → HTTP server → admission queue → batch →
predictor, and a PS RPC call and its server-side handler render as one
causal tree in ``tools/trace_view.py``.

Model
-----
* A ``SpanContext`` is ``(trace_id, span_id)`` — 16-hex-digit ids. The
  context rides a ``contextvars.ContextVar``, so nesting follows Python
  call structure per thread and is safe under the serving/http thread
  pools.
* Sampling happens ONCE, at the root: ``span()`` outside any active
  context consults ``FLAGS_trace_sample_rate`` (0 disables — the
  default). A context existing ⟺ the trace is sampled; children and
  remote continuations never re-roll the dice (Dapper §3).
* Off ≈ zero cost: with rate 0 and no inherited context, ``span()``
  returns a shared no-op context manager — one ContextVar read and one
  flag lookup, no allocation, no clock reads, no record.
* Each finished sampled span is emitted as a ``kind:"span"`` telemetry
  JSONL record: ``value`` = duration ms, ``attrs`` = {trace, span,
  parent, start (epoch s), pid, tid, ...user attrs} — exactly what
  ``tools/trace_view.py`` needs to merge multi-process run logs into a
  chrome://tracing file.

Cross-process propagation
-------------------------
``inject()`` serialises the current context to ``"<trace>-<span>"``;
``span_from(header, name)`` opens a child span under that remote parent
(a propagated context is always honoured, even when the local sample
rate is 0 — the caller made the sampling decision). The PS RPC client
rides this on the frame's method field (surviving retries: the retry
loop sits INSIDE one client span, and the server's dedup cache replays
the reply without re-dispatching, so a retried+deduped frame still
yields exactly one handler span); the serving HTTP server accepts an
``X-Request-Id`` header as a forced trace id and returns the trace id
in the response.

Worker threads that serve a request long after ``submit()`` returned
(the serving engine's batch loop) cannot use the contextvar — they use
``record(name, parent, start, end)`` to emit completed spans
retroactively against the context captured at submit time.
"""

from __future__ import annotations

import contextvars
import os
import random
import re
import threading
import time
from typing import Any, Dict, Optional

from . import flags as _flags
from . import telemetry

_ID_RE = re.compile(r"^[A-Za-z0-9_.-]{1,64}$")
_HEADER_RE = re.compile(r"^([A-Za-z0-9_.-]{1,64})-([0-9a-f]{16})$")


class SpanContext:
    """Identity of one sampled span: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def header(self) -> str:
        """Wire form for cross-process propagation (inject/extract)."""
        return f"{self.trace_id}-{self.span_id}"

    def __repr__(self):
        return f"SpanContext(trace={self.trace_id}, span={self.span_id})"


_ctx: "contextvars.ContextVar[Optional[SpanContext]]" = \
    contextvars.ContextVar("pt_trace_ctx", default=None)
_rng = random.Random()   # urandom-seeded; ids need uniqueness, not secrecy

# recently-active traces — (finish ts, trace_id) per finished span, so
# the incident pipeline (core/incidents.py) can name the trace ids that
# were live around a trip point and tools/incident_report.py can pull
# their spans out of the flight-recorder ring. Plain lock + bounded
# deque: a few ns per finished SAMPLED span, nothing when tracing is off.
_recent_lock = threading.Lock()
_recent_traces: "deque" = None  # type: ignore[assignment]


def _note_trace(trace_id: str):
    global _recent_traces
    with _recent_lock:
        if _recent_traces is None:
            from collections import deque

            _recent_traces = deque(maxlen=256)
        _recent_traces.append((time.time(), trace_id))


def recent_trace_ids(window_s: float = 120.0,
                     now: Optional[float] = None) -> list:
    """Unique trace ids whose spans finished within the last
    ``window_s`` seconds, newest first — the "active traces" an
    incident dump correlates its ring spans against."""
    if now is None:
        now = time.time()
    cut = now - max(window_s, 0.0)
    with _recent_lock:
        items = list(_recent_traces) if _recent_traces is not None else []
    out, seen = [], set()
    for ts, tid in reversed(items):
        if ts >= cut and tid not in seen:
            seen.add(tid)
            out.append(tid)
    return out


def _new_id() -> str:
    return f"{_rng.getrandbits(64):016x}"


def _clean_trace_id(raw: str) -> str:
    """An externally supplied trace id (X-Request-Id) must be safe to
    embed in JSONL/headers/filenames; anything odd maps deterministically
    to a hex digest so correlation still works."""
    raw = str(raw).strip()
    if _ID_RE.match(raw):
        return raw
    import hashlib

    return hashlib.md5(raw.encode("utf-8", "replace")).hexdigest()[:16]


class _NullSpan:
    """Shared no-op context manager — the entire cost of tracing-off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, et, ev, tb):
        return False


_NULL = _NullSpan()


class _Span:
    """An open sampled span; emits its record on __exit__."""

    __slots__ = ("name", "ctx", "parent_id", "attrs", "_token", "_start",
                 "_t0")

    def __init__(self, name: str, ctx: SpanContext,
                 parent_id: Optional[str], attrs: Dict[str, Any]):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.attrs = attrs

    def __enter__(self) -> SpanContext:
        self._token = _ctx.set(self.ctx)
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self.ctx

    def __exit__(self, et, ev, tb):
        _ctx.reset(self._token)
        dur_ms = (time.perf_counter() - self._t0) * 1e3
        attrs = {"trace": self.ctx.trace_id, "span": self.ctx.span_id,
                 "parent": self.parent_id,
                 "start": round(self._start, 6),
                 "pid": os.getpid(),
                 "tid": threading.current_thread().name}
        if self.attrs:
            attrs.update(self.attrs)
        if et is not None:
            attrs["error"] = et.__name__
        telemetry.counter_quiet("trace.spans")
        _note_trace(self.ctx.trace_id)
        telemetry.event("span", self.name, round(dur_ms, 4), attrs)
        return False


def _sampled_root() -> bool:
    rate = _flags.flag("trace_sample_rate")
    if rate <= 0.0:
        return False
    return rate >= 1.0 or _rng.random() < rate


# -- the public surface ------------------------------------------------------

def tracing() -> bool:
    """True when spans opened NOW would be recorded (inside a sampled
    trace, or a nonzero sample rate may start one)."""
    return _ctx.get() is not None or _flags.flag("trace_sample_rate") > 0.0


def current() -> Optional[SpanContext]:
    """The active sampled span context of this thread/task, if any."""
    return _ctx.get()


def span(name: str, **attrs):
    """Open a span. Inside an active trace: a child. Outside: a root,
    subject to FLAGS_trace_sample_rate — unsampled/off returns a shared
    no-op context manager whose __enter__ yields None."""
    parent = _ctx.get()
    if parent is None:
        if not _sampled_root():
            return _NULL
        return _Span(name, SpanContext(_new_id(), _new_id()), None, attrs)
    return _Span(name, SpanContext(parent.trace_id, _new_id()),
                 parent.span_id, attrs)


def _timer_span(name: str, attrs: Dict[str, Any]):
    """What telemetry.timer(span=name) opens here: a span as span() would,
    nothing when tracing is off or the trace is not sampled."""
    s = span(name, **attrs)
    return None if s is _NULL else s


telemetry.attach_span(_timer_span)


def root_span(name: str, trace_id: Optional[str] = None,
              force: bool = False, **attrs):
    """Start a NEW trace (ignores any active context). ``trace_id`` pins
    the id (an X-Request-Id-style external correlation key) and
    ``force=True`` bypasses sampling — a caller who names their request
    wants it traced."""
    if not force and not _sampled_root():
        return _NULL
    tid = _clean_trace_id(trace_id) if trace_id else _new_id()
    return _Span(name, SpanContext(tid, _new_id()), None, attrs)


def inject() -> Optional[str]:
    """Serialise the current context for the wire ('' semantics: None
    when no sampled trace is active — callers send nothing)."""
    c = _ctx.get()
    return c.header() if c is not None else None


def extract(header: Optional[str]) -> Optional[SpanContext]:
    """Parse a propagated '<trace>-<span>' header; None on absent or
    malformed input (a bad header must never fail the carrying RPC)."""
    if not header:
        return None
    m = _HEADER_RE.match(str(header).strip())
    if not m:
        return None
    return SpanContext(m.group(1), m.group(2))


def span_from(header: Optional[str], name: str, **attrs):
    """Open a span continuing a REMOTE parent. A valid header is always
    honoured regardless of the local sample rate (the origin sampled);
    an absent/invalid header degrades to a plain local ``span()``."""
    parent = extract(header)
    if parent is None:
        return span(name, **attrs)
    return _Span(name, SpanContext(parent.trace_id, _new_id()),
                 parent.span_id, attrs)


def record(name: str, parent: Optional[SpanContext],
           start_s: float, end_s: float, **attrs) -> Optional[SpanContext]:
    """Emit a COMPLETED span retroactively under ``parent`` (a context
    captured earlier, possibly on another thread — the serving engine's
    batch worker reconstructing a request's queue-wait/batch/predictor
    timeline). Returns the new span's context so callers can parent
    further spans under it; no-op (None) without a parent."""
    if parent is None:
        return None
    ctx = SpanContext(parent.trace_id, _new_id())
    rec_attrs = {"trace": ctx.trace_id, "span": ctx.span_id,
                 "parent": parent.span_id, "start": round(start_s, 6),
                 "pid": os.getpid(),
                 "tid": threading.current_thread().name}
    if attrs:
        rec_attrs.update(attrs)
    telemetry.counter_quiet("trace.spans")
    _note_trace(ctx.trace_id)
    telemetry.event("span", name, round((end_s - start_s) * 1e3, 4),
                    rec_attrs)
    return ctx
