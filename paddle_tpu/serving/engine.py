"""ServingEngine — dynamic micro-batching over a frozen inference program.

The reference ships AnalysisPredictor as a one-caller-at-a-time engine;
real serving (TF-Serving's batch scheduler, Clipper's adaptive batching)
gets its throughput from coalescing concurrent requests into one device
batch. This engine is that layer for paddle_tpu:

* concurrent callers ``submit()`` requests into a bounded
  ``AdmissionQueue`` (admission.py: backpressure + deadlines);
* one worker thread pulls same-shape-signature requests, concatenates
  their rows and PADS the batch up to a bucket boundary (powers of two
  on the leading dim by default) so the predictor's jit cache holds one
  entry per bucket — small and warm — instead of one per exact batch
  size;
* padded rows are sliced off before responses resolve, so every caller
  sees output bitwise-identical to an unbatched
  ``AnalysisPredictor.run`` of its own rows;
* the handler is a ``serving.handler`` fault-injection site
  (core/faults.py): an injected fault fails that batch's requests
  individually and the loop keeps serving — never a wedged queue.

Telemetry: serving.requests / batches / batched_rows / padded_rows /
rejects / deadline_expired / handler_errors counters, serving.batch_fill
histogram, serving.request_ms + serving.batch_ms timers,
serving.queue_depth gauge — rendered by tools/perf_report.py's
"Serving" section.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..core import costmodel, faults, incidents, telemetry, trace
from ..core import flags as _flags
from ..core.analysis import lockdep
from ..core.flags import flag as _flag
from .admission import (AdmissionQueue, EngineClosedError, InferenceRequest,
                        ServingError)
from .health import (DRAINING, READY, STOPPED, SWAPPING, HealthState,
                     ReadyGate)


def _pow2_buckets(max_batch: int) -> List[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


class ServingConfig:
    """Engine knobs; defaults come from the FLAGS_serving_* registry."""

    def __init__(self, max_batch_size: Optional[int] = None,
                 batch_timeout_ms: Optional[float] = None,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 buckets: Optional[Sequence[int]] = None):
        self.max_batch_size = int(
            _flag("serving_max_batch_size") if max_batch_size is None
            else max_batch_size)
        self.batch_timeout_ms = float(
            _flag("serving_batch_timeout_ms") if batch_timeout_ms is None
            else batch_timeout_ms)
        self.max_queue_depth = int(
            _flag("serving_max_queue_depth") if max_queue_depth is None
            else max_queue_depth)
        self.default_deadline_ms = float(
            _flag("serving_default_deadline_ms") if default_deadline_ms is None
            else default_deadline_ms)
        # strict typed parse (core/flags.py): a zero-valued or
        # non-monotonic bucket list raises BucketConfigError instead of
        # being silently reordered
        if buckets is None:
            buckets = _flags.parse_buckets(_flag("serving_buckets"),
                                           "FLAGS_serving_buckets")
        else:
            buckets = _flags.parse_buckets(buckets, "buckets")
        self.buckets = buckets or _pow2_buckets(self.max_batch_size)

    def bucket(self, rows: int) -> int:
        """Smallest boundary >= rows; an oversized request is its own
        bucket (compiles once for that exact size)."""
        for b in self.buckets:
            if rows <= b:
                return b
        return rows


class ServingEngine:
    """Thread-safe micro-batching front end over an AnalysisPredictor.

    Lifecycle: ``start()`` (optionally warming every bucket) → concurrent
    ``submit``/``infer`` → ``close(drain=True)``. Only the single worker
    thread (plus warmup, which runs before it starts) touches the
    predictor, so the predictor itself needs no locking.
    """

    def __init__(self, predictor, config: Optional[ServingConfig] = None,
                 version: int = 0):
        self.predictor = predictor
        self.config = config or ServingConfig()
        self.queue = AdmissionQueue(self.config.max_queue_depth,
                                    self.config.default_deadline_ms)
        self._thread: Optional[threading.Thread] = None
        self._infer_lock = lockdep.lock("engine.infer")
        self._swap_lock = lockdep.lock("engine.swap")
        self._feed_names = list(predictor.feed_names)
        self._fetch_names = list(predictor.fetch_names)
        # liveness/readiness state machine (health.py): STARTING until
        # start() finishes warmup — a router/LB polling /healthz never
        # routes to a cold replica
        self.health = HealthState()
        self.version = int(version)
        # per-bucket cost/memory footprints captured at warmup
        # (core/costmodel.py ProgramCost records, keyed by bucket size)
        self._bucket_costs: Dict[int, Any] = {}

    # -- client surface ------------------------------------------------------
    @property
    def feed_names(self) -> List[str]:
        return list(self._feed_names)

    @property
    def fetch_names(self) -> List[str]:
        return list(self._fetch_names)

    def submit(self, feeds: Dict[str, Any],
               deadline_ms: Optional[float] = None) -> InferenceRequest:
        """Enqueue one request (non-blocking). feeds maps every feed name
        to an array whose dim 0 is the request's rows; all feeds must
        agree on rows. Raises ServerOverloadedError / EngineClosedError."""
        arrs = {}
        rows = None
        for n in self._feed_names:
            if n not in feeds:
                raise ValueError(f"missing input '{n}'; "
                                 f"need {self._feed_names}")
            v = np.asarray(feeds[n])
            if v.ndim == 0:
                raise ValueError(f"input '{n}' needs a leading batch dim")
            if rows is None:
                rows = v.shape[0]
            elif v.shape[0] != rows:
                raise ValueError(
                    f"inputs disagree on rows: '{n}' has {v.shape[0]}, "
                    f"expected {rows}")
            arrs[n] = v
        extra = set(feeds) - set(self._feed_names)
        if extra:
            raise ValueError(f"unknown inputs {sorted(extra)}; "
                             f"feeds are {self._feed_names}")
        # the submitter's sampled trace context (if any) rides the request
        # into the batch worker, which reconstructs the queue-wait/batch/
        # predictor span timeline against it
        return self.queue.submit(arrs, rows, deadline_ms,
                                 trace=trace.current())

    def infer(self, feeds: Dict[str, Any],
              deadline_ms: Optional[float] = None,
              timeout: Optional[float] = None) -> List[np.ndarray]:
        """Blocking submit-and-wait; returns fetches in fetch_names order."""
        return self.submit(feeds, deadline_ms).result(timeout)

    def stats(self) -> Dict[str, Any]:
        """Live stats: cumulative serving.* counters (flat, as before)
        plus request/batch latency percentiles and rolling-window rates —
        the /v1/stats payload."""
        c = telemetry.counters()
        out = {k.split(".", 1)[1]: int(v) for k, v in c.items()
               if k.startswith("serving.") and isinstance(v, (int, float))}
        out["queue_depth"] = self.queue.depth()
        out["model_version"] = self.version
        out["status"] = self.health.state
        out["ready"] = self.health.is_ready()
        out["serving_config"] = {
            "max_batch_size": self.config.max_batch_size,
            "batch_timeout_ms": self.config.batch_timeout_ms,
            "buckets": list(self.config.buckets)}
        hists = telemetry.snapshot()["hists"]
        for key in ("serving.request_ms", "serving.batch_ms"):
            h = hists.get(key)
            if h:
                out[key.split(".", 1)[1]] = {
                    "count": h["count"], "avg": h["avg"], "p50": h["p50"],
                    "p95": h["p95"], "p99": h["p99"], "max": h["max"]}
        win = telemetry.windowed()
        wout = {"seconds": win["window_s"]}
        wc = win["counters"].get("serving.requests")
        if wc:
            wout["request_rate"] = wc["rate"]
        wb = win["counters"].get("serving.batches")
        if wb:
            wout["batch_rate"] = wb["rate"]
        for key in ("serving.request_ms", "serving.batch_ms"):
            wh = win["hists"].get(key)
            if wh:
                short = key.split(".", 1)[1]
                wout[short] = {"count": wh["count"], "rate": wh["rate"],
                               "p50": wh["p50"], "p95": wh["p95"],
                               "p99": wh["p99"]}
        out["window"] = wout
        if self._bucket_costs:
            # per-warmed-bucket cost/memory footprints + the composed
            # HBM ledger (core/costmodel.py) — the capacity-planning
            # numbers a router/operator reads off /v1/stats
            out["memory"] = {
                "buckets": {str(b): {
                    "peak_bytes": rec.peak_bytes,
                    "temp_bytes": rec.temp_bytes,
                    "arg_bytes": rec.arg_bytes,
                    "flops": rec.flops,
                    "roofline": rec.roofline()}
                    for b, rec in sorted(self._bucket_costs.items())},
                "ledger": costmodel.ledger()}
        return out

    # -- lifecycle -----------------------------------------------------------
    def start(self, warmup: bool = True) -> "ServingEngine":
        if self._thread is not None:
            return self
        if self.queue.closed:
            raise EngineClosedError("engine was closed; build a new one")
        if warmup:
            self.warmup()
        self._thread = threading.Thread(target=self._loop,
                                        name="pt-serving-engine",
                                        daemon=True)
        self._thread.start()
        self.health.set(READY)
        return self

    def warmup(self) -> int:
        """Pre-compile every bucket with zero feeds so the first real
        request never pays a compile. Returns the number of fresh
        compiles (serving.warmup_compiles)."""
        fresh, costs = self._warm(self.predictor, locked=True)
        self._publish_bucket_costs(costs)
        return fresh

    def _warm(self, predictor, locked: bool = False):
        """Run every bucket through ``predictor`` once; returns (fresh
        compile count, {bucket: ProgramCost}). ``locked`` guards runs of
        the LIVE predictor with the infer lock; a swap candidate is
        private until the flip, and warming it unlocked keeps the old
        predictor serving (zero downtime) while the new one compiles."""
        specs = predictor.feed_specs()
        for n, (shape, _dtype) in specs.items():
            if any(d is None or d < 0 for d in shape[1:]):
                telemetry.counter_add("serving.warmup_skipped", 1, feed=n)
                return 0, {}   # non-batch dynamic dims: nothing to build
        before = telemetry.counter_get("predictor.compiles")
        costs: Dict[int, Any] = {}
        with telemetry.timer("serving.warmup_ms"):
            for b in self.config.buckets:
                feed = {n: np.zeros((b,) + tuple(shape[1:]), dtype=dtype)
                        for n, (shape, dtype) in specs.items()}
                if locked:
                    with self._infer_lock:
                        # pt-lint: disable=blocking-call-under-lock(warmup of the LIVE predictor must exclude the worker's batches; the lock is exactly what serialises them)
                        predictor.run(feed)
                else:
                    predictor.run(feed)
                # per-bucket cost/memory footprint (captured by the
                # predictor when FLAGS_cost_capture is on)
                rec = getattr(predictor, "_last_cost", None)
                if rec is not None:
                    costs[b] = rec
        fresh = telemetry.counter_get("predictor.compiles") - before
        if fresh:
            telemetry.counter_add("serving.warmup_compiles", fresh)
        return int(fresh), costs

    def _publish_bucket_costs(self, costs: Dict[int, Any]):
        """Publish the warmed buckets' footprints on the HBM ledger:
        mem.serving.bucket<B>_peak_bytes gauges (full capture only — the
        peak needs memory_analysis) + the /v1/stats memory section."""
        if not costs:
            return
        self._bucket_costs = dict(costs)
        for b, rec in costs.items():
            if rec.peak_bytes:
                telemetry.gauge_set(f"mem.serving.bucket{b}_peak_bytes",
                                    int(rec.peak_bytes))
        costmodel.refresh_ledger()

    def swap_predictor(self, predictor, version: Optional[int] = None,
                       warmup: bool = True) -> int:
        """Zero-downtime model swap: warm every bucket on the NEW
        predictor while the old one keeps serving, then flip atomically
        under the infer lock (the in-flight batch completes on the old
        predictor first — every response is served entirely by one
        version, never a mix). Readiness is false (SWAPPING) for the
        duration so a router drains new traffic away from the warming
        replica. Returns the number of fresh warmup compiles; on any
        failure the old predictor stays live and readiness is restored.
        ``replica.swap`` is a fault-injection site (core/faults.py)."""
        with self._swap_lock:
            faults.maybe_fail("replica.swap", version=version)
            # clients feed by NAME and read outputs by the engine's stable
            # fetch schema, so a swap needs identical feed names and fetch
            # arity; fresh auto-generated fetch VAR names (a republished
            # model) are fine — the engine keeps its original output keys
            if list(predictor.feed_names) != self._feed_names or \
                    len(predictor.fetch_names) != len(self._fetch_names):
                raise ValueError(
                    f"swap candidate signature mismatch: feeds "
                    f"{list(predictor.feed_names)} / {len(predictor.fetch_names)} "
                    f"fetches, serving {self._feed_names} / "
                    f"{len(self._fetch_names)} fetches")
            with ReadyGate(self.health, SWAPPING), \
                    telemetry.timer("serving.swap_ms"):
                # pt-lint: disable=blocking-call-under-lock(the swap lock serialises SWAPS only — warmup compiles run unlocked while the old predictor keeps serving; that is the zero-downtime design)
                fresh, costs = self._warm(predictor, locked=False) \
                    if warmup else (0, {})
                with self._infer_lock:
                    self.predictor = predictor
                    if version is not None:
                        self.version = int(version)
                self._publish_bucket_costs(costs)
            telemetry.counter_add("serving.swaps", 1, version=self.version,
                                  warmup_compiles=fresh)
            return fresh

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop admission; with drain=True the worker finishes the backlog
        before exiting, else queued requests fail with EngineClosedError.
        Readiness drops to DRAINING immediately (the router stops routing
        here) and the state ends STOPPED."""
        self.health.set(DRAINING)
        self.queue.close(drain=drain)
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        self.health.set(STOPPED)

    # -- engine loop ---------------------------------------------------------
    def _signature(self, req: InferenceRequest):
        return tuple((n, req.feeds[n].shape[1:], str(req.feeds[n].dtype))
                     for n in self._feed_names)

    def _loop(self):
        while True:
            taken = self.queue.take_batch(self._signature,
                                          self.config.max_batch_size,
                                          self.config.batch_timeout_ms)
            if taken is None:
                return
            # SLO watchdog hook (core/incidents.py): armed replicas
            # evaluate the rule set on the batch cadence
            incidents.tick()
            _sig, batch = taken
            if not batch:
                continue
            try:
                self._serve_batch(batch)
            except BaseException as e:   # the loop must outlive any batch
                telemetry.counter_add("serving.handler_errors", len(batch),
                                      exc=type(e).__name__)
                for req in batch:
                    if not req.done():
                        req.fail(e if isinstance(e, ServingError)
                                 else ServingError(
                                     f"serving handler failed: {e!r}"))

    def _serve_batch(self, batch: List[InferenceRequest]):
        import time as _time

        rows = sum(r.rows for r in batch)
        bucket = self.config.bucket(rows)
        # requests whose submitter was inside a sampled trace get their
        # queue-wait/batch-assembly/predictor spans reconstructed here
        # (the contextvar does not cross into this worker thread)
        traced = [r for r in batch if r.trace is not None]
        t_dequeue = _time.time() if traced else 0.0
        t_run0 = t_run1 = 0.0
        try:
            faults.maybe_fail("serving.handler", batch_rows=rows,
                              requests=len(batch))
            feed = {}
            for n in self._feed_names:
                parts = [r.feeds[n] for r in batch]
                if bucket > rows:
                    pad_shape = (bucket - rows,) + parts[0].shape[1:]
                    parts.append(np.zeros(pad_shape, dtype=parts[0].dtype))
                feed[n] = parts[0] if len(parts) == 1 \
                    else np.concatenate(parts, axis=0)
            if traced:
                t_run0 = _time.time()
            with self._infer_lock, telemetry.timer("serving.batch_ms"):
                # predictor + version read under the lock: a concurrent
                # swap_predictor flips both atomically, so this batch is
                # served entirely by ONE model version
                version = self.version
                # pt-lint: disable=blocking-call-under-lock(the single worker thread IS the serialisation point; a swap flip is the only other holder and must exclude in-flight batches)
                outs = self.predictor.run(feed)
            if traced:
                t_run1 = _time.time()
                for req in traced:
                    trace.record("serving.queue_wait", req.trace,
                                 req.enqueue_wall, t_dequeue)
                    trace.record("serving.batch_assemble", req.trace,
                                 t_dequeue, t_run0, bucket=bucket,
                                 rows=rows, requests=len(batch))
                    trace.record("serving.predictor_run", req.trace,
                                 t_run0, t_run1, bucket=bucket)
        except Exception as e:
            # per-request error responses; the queue keeps moving
            telemetry.counter_add("serving.handler_errors", len(batch),
                                  exc=type(e).__name__)
            for req in traced:
                trace.record("serving.queue_wait", req.trace,
                             req.enqueue_wall, t_dequeue)
                trace.record("serving.batch_error", req.trace, t_dequeue,
                             _time.time(), error=type(e).__name__)
            for req in batch:
                req.fail(e)
            return
        telemetry.counter_add("serving.batches", 1)
        telemetry.counter_add("serving.batched_rows", rows)
        if bucket > rows:
            telemetry.counter_add("serving.padded_rows", bucket - rows)
        telemetry.observe("serving.batch_fill", rows / bucket)
        offset = 0
        now = _time.monotonic()
        for req in batch:
            sliced = [o[offset:offset + req.rows]
                      if getattr(o, "ndim", 0) >= 1 and len(o) == bucket
                      else o   # non-per-row fetch: hand it through whole
                      for o in outs]
            offset += req.rows
            req.served_version = version
            req.resolve(sliced)
            telemetry.observe("serving.request_ms",
                              (now - req.enqueue_t) * 1e3, kind="timer")
