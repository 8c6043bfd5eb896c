"""Cost & memory observability plane — per-compile XLA cost/memory
capture, the HBM ledger, live MFU/roofline gauges, and OOM forensics.

Capability mirror of the reference's profiler + allocator accounting
(platform/profiler.h, memory/allocation stats): the repo already
measures *time* (PR 1 telemetry, PR 6 tracing); this module measures
*flops and bytes*. Three surfaces:

* **Per-compile capture.** Every executor/predictor compile runs the
  XLA AOT analyses over the jitted function, keyed by the existing
  compile-cache entry: ``Lowered.cost_analysis()`` (flops, bytes
  accessed, transcendentals — pre-optimization, nearly free because the
  trace cache is shared with the first execution) and, at capture level
  ``full``, ``Lowered.compile()`` → ``Compiled.cost_analysis()`` +
  ``memory_analysis()`` (post-optimization flops plus peak/argument/
  output/temp bytes — one extra XLA compile, so ``full`` is opt-in).
  Backends that expose neither degrade gracefully: every failed probe
  is COUNTED (``costmodel.unavailable``), never raised — CPU CI stays
  green.

* **HBM ledger + live gauges.** ``mem.param_bytes`` /
  ``mem.opt_state_bytes`` (persistable split measured at capture,
  composing with PR 7's ``sharding.optimizer_state_bytes*`` gauges when
  ZeRO shards the state), ``mem.peak_temp_bytes`` (max scratch over the
  cached programs), ``mem.hbm_total_bytes`` (the composed ledger
  verdict), per-serving-bucket footprints
  (``mem.serving.bucket<B>_peak_bytes``, captured at engine warmup and
  exposed in ``/v1/stats``), the decode engine's preallocated KV page
  pool (``mem.serving.kv_pool_bytes`` / ``kv_used_bytes`` /
  ``kv_high_water_bytes`` — serving/kv_cache.py, what lets decode
  admission refuse would-OOM requests with a typed error), plus a live
  MFU gauge
  (``cost.live_mfu`` = windowed ``cost.dispatch_flops`` rate ÷ peak
  device flops from the device table / ``FLAGS_device_peak_flops``)
  and a per-program roofline verdict (compute- vs memory-bound by
  arithmetic intensity against the device ridge point). All published
  on the live metrics plane (``/metrics`` → ``pt_cost_*``/``pt_mem_*``).

* **OOM forensics.** An allocation failure (RESOURCE_EXHAUSTED) during
  dispatch or compile dumps a ``kind:"oom"`` record into the run log —
  ledger snapshot + top-N cached programs by peak bytes + the offending
  program — and re-raises as a typed ``OutOfMemoryError`` instead of an
  opaque backend error.

Capture levels (``FLAGS_cost_capture``): ``off`` | ``cost`` (lowered
analyses only) | ``full`` (adds the AOT compile for memory stats) |
``auto`` (default — ``cost`` when the run is instrumented, i.e. a
telemetry sink or metrics server is active, else ``off``; bare test
runs pay nothing).

Render a run log's ledger + per-program cost table with
``tools/mem_report.py``; ``tools/perf_report.py`` gains a
"Memory & cost" section.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import telemetry
from .analysis import lockdep as _lockdep
from .flags import flag as _flag

# -- typed OOM error ----------------------------------------------------------


class OutOfMemoryError(RuntimeError):
    """Device allocation failure (RESOURCE_EXHAUSTED), raised after the
    OOM-forensics record landed in the run log. Deliberately NOT an
    RPC-recoverable error: ElasticRunner must not silently restart an
    OOMing step loop."""


_OOM_MARKERS = ("resource_exhausted", "out of memory", "allocation failure")


def is_oom_error(err: BaseException) -> bool:
    msg = f"{type(err).__name__}: {err}".lower()
    return any(m in msg for m in _OOM_MARKERS)


# -- device table -------------------------------------------------------------
# (peak dense bf16 flops/s, peak HBM bytes/s) of one chip by device_kind
# substring, first match wins. Source: Google Cloud TPU documentation
# (system architecture pages per generation). A kind that is not here —
# the CPU included, it is not a v5e — has no peak: set
# FLAGS_device_peak_flops / FLAGS_device_peak_bw to score against one.
_DEVICE_TABLE: List[Tuple[str, float, float]] = [
    ("v5 lite", 197e12, 819e9),      # what a v5e chip reports
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v5 p", 459e12, 2765e9),
    ("v4", 275e12, 1228e9),
    ("v6", 918e12, 1640e9),
    ("trillium", 918e12, 1640e9),
]


class UnknownDevicePeakError(LookupError):
    """The device's kind is not in the peak table and no FLAGS_device_peak_*
    override is set: there is no peak to score against."""


def _table_peaks() -> Tuple[float, float]:
    import jax

    kind = jax.devices()[0].device_kind
    lowered = kind.lower()
    for sub, flops, bw in _DEVICE_TABLE:
        if sub in lowered:
            return flops, bw
    raise UnknownDevicePeakError(
        f"no peak flops/bandwidth known for device_kind {kind!r}; set "
        f"FLAGS_device_peak_flops / FLAGS_device_peak_bw to score "
        f"against a peak of your choosing")


def peak_device_flops() -> float:
    """Peak dense flops/s of one device — FLAGS_device_peak_flops wins
    when > 0, else the device table keyed on jax device_kind; a kind the
    table lacks raises UnknownDevicePeakError."""
    override = float(_flag("device_peak_flops"))
    return override if override > 0 else _table_peaks()[0]


def peak_device_bandwidth() -> float:
    """Peak HBM bytes/s of one device (roofline denominator) —
    FLAGS_device_peak_bw wins when > 0, else the device table."""
    override = float(_flag("device_peak_bw"))
    return override if override > 0 else _table_peaks()[1]


# -- cost-analysis key handling ----------------------------------------------

def normalize_cost_analysis(ca) -> Dict[str, float]:
    """One place that knows XLA's cost_analysis() shape: some backends
    return a list (one dict per partition), keys are 'flops' /
    'bytes accessed' / 'transcendentals' with per-operand variants
    ('bytes accessed0{}') we ignore. Returns a flat
    {flops, bytes_accessed, transcendentals} dict of floats (missing
    keys → 0.0). tools/audit_hlo.py renders through this too."""
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


# -- per-program cost records -------------------------------------------------


class ProgramCost:
    """One compiled program's captured cost/memory record."""

    __slots__ = ("key_id", "kind", "program", "steps_per_dispatch",
                 "flops", "bytes_accessed", "transcendentals",
                 "arg_bytes", "out_bytes", "temp_bytes", "peak_bytes",
                 "generated_code_bytes", "source", "devices")

    def __init__(self, key_id: str, kind: str, program: Any,
                 steps_per_dispatch: int = 1):
        self.key_id = key_id
        self.kind = kind            # "executor" | "predictor"
        self.program = program      # program uid / bucket label
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.transcendentals = 0.0
        self.arg_bytes = 0
        self.out_bytes = 0
        self.temp_bytes = 0
        self.peak_bytes = 0
        self.generated_code_bytes = 0
        self.source = "none"        # "lowered" | "compiled" | "none"
        self.devices = 1

    def flops_per_dispatch(self) -> float:
        """XLA's cost analysis counts a while/scan body ONCE regardless
        of trip count, so a K-step fused program's per-dispatch flops are
        ~body × k (measured: a k=4 scan reports ~1× the single-step
        program)."""
        return self.flops * max(1, self.steps_per_dispatch)

    def bytes_per_dispatch(self) -> float:
        return self.bytes_accessed * max(1, self.steps_per_dispatch)

    # roofline: arithmetic intensity vs the device ridge point
    def intensity(self) -> float:
        return self.flops / self.bytes_accessed if self.bytes_accessed else 0.0

    def roofline(self) -> str:
        if not self.flops or not self.bytes_accessed:
            return "unknown"
        try:
            ridge = peak_device_flops() / max(peak_device_bandwidth(), 1.0)
        except UnknownDevicePeakError:
            return "unknown"        # no peak, no verdict
        return "compute_bound" if self.intensity() >= ridge \
            else "memory_bound"

    def as_attrs(self) -> Dict[str, Any]:
        return {"key": self.key_id, "kind": self.kind,
                "program": self.program,
                "steps_per_dispatch": self.steps_per_dispatch,
                "flops": self.flops,
                "flops_per_dispatch": self.flops_per_dispatch(),
                "bytes_accessed": self.bytes_accessed,
                "transcendentals": self.transcendentals,
                "arg_bytes": self.arg_bytes, "out_bytes": self.out_bytes,
                "temp_bytes": self.temp_bytes,
                "peak_bytes": self.peak_bytes,
                "generated_code_bytes": self.generated_code_bytes,
                "source": self.source, "devices": self.devices,
                "intensity": round(self.intensity(), 4),
                "roofline": self.roofline()}


_PROGRAM_CAP = 256      # bounded registry of captured programs
_programs: "OrderedDict[str, ProgramCost]" = OrderedDict()
_lock = _lockdep.lock("costmodel.programs")
_last_mfu_set = [0.0]   # throttle for the live-MFU gauge refresh


def key_id_for(key: tuple) -> str:
    """Stable-within-the-run short id of an executor compile-cache key
    (crc32 — hash() is salted per process and would not match a reread
    run log)."""
    return f"{zlib.crc32(repr(key).encode()):08x}"


def capture_mode() -> str:
    """Resolve FLAGS_cost_capture: 'auto' means 'cost' when the run is
    instrumented (telemetry sink or metrics server active — the run
    asked for observability), else 'off' so bare CI runs pay nothing."""
    m = str(_flag("cost_capture")).strip().lower()
    if m == "auto":
        if telemetry.enabled() or telemetry.metrics_server_active():
            return "cost"
        return "off"
    return m if m in ("off", "cost", "full") else "off"


def _unavailable(stage: str, err: BaseException):
    telemetry.counter_add("costmodel.unavailable", 1, stage=stage,
                          error=f"{type(err).__name__}: {err}"[:200])


def programs() -> List[ProgramCost]:
    with _lock:
        return list(_programs.values())


def reset():
    """Clear captured program records (tests)."""
    with _lock:
        _programs.clear()
    _last_mfu_set[0] = 0.0


def _remember(rec: ProgramCost):
    with _lock:
        _programs[rec.key_id] = rec
        _programs.move_to_end(rec.key_id)
        while len(_programs) > _PROGRAM_CAP:
            _programs.popitem(last=False)
        peak = max((r.temp_bytes for r in _programs.values()), default=0)
    telemetry.counter_add("cost.captures", 1, kind=rec.kind,
                          source=rec.source)
    if peak:
        telemetry.gauge_set("mem.peak_temp_bytes", int(peak))
    telemetry.event("cost", f"costmodel.{rec.kind}", rec.flops,
                    rec.as_attrs())


def capture(lower_fn, *, key_id: str, kind: str, program: Any,
            steps_per_dispatch: int = 1) -> Optional[ProgramCost]:
    """Run the AOT analyses for one fresh compile-cache entry.

    ``lower_fn`` is a zero-arg callable returning the jax ``Lowered``
    (deferred so an un-lowerable function only costs a counted probe).
    Never raises; returns None when capture is off or nothing could be
    probed."""
    mode = capture_mode()
    if mode == "off":
        return None
    rec = ProgramCost(key_id, kind, program,
                      steps_per_dispatch=steps_per_dispatch)
    try:
        import jax

        rec.devices = max(1, jax.device_count())
    except Exception:
        pass
    try:
        lowered = lower_fn()
    except Exception as e:
        _unavailable("lower", e)
        return None
    try:
        cost = normalize_cost_analysis(lowered.cost_analysis())
        if cost:
            rec.flops = cost.get("flops", 0.0)
            rec.bytes_accessed = cost.get("bytes_accessed", 0.0)
            rec.transcendentals = cost.get("transcendentals", 0.0)
            rec.source = "lowered"
    except Exception as e:
        _unavailable("cost_analysis", e)
    if mode == "full":
        try:
            compiled = lowered.compile()
        except Exception as e:
            if is_oom_error(e):
                raise oom_forensics(program, e, where=f"{kind}.compile") \
                    from e
            _unavailable("compile", e)
            compiled = None
        if compiled is not None:
            try:
                cost = normalize_cost_analysis(compiled.cost_analysis())
                if cost:
                    rec.flops = cost.get("flops", rec.flops)
                    rec.bytes_accessed = cost.get("bytes_accessed",
                                                  rec.bytes_accessed)
                    rec.transcendentals = cost.get("transcendentals",
                                                   rec.transcendentals)
                    rec.source = "compiled"
            except Exception as e:
                _unavailable("compiled_cost_analysis", e)
            try:
                ma = compiled.memory_analysis()
                if ma is not None:
                    rec.arg_bytes = int(
                        getattr(ma, "argument_size_in_bytes", 0) or 0)
                    rec.out_bytes = int(
                        getattr(ma, "output_size_in_bytes", 0) or 0)
                    rec.temp_bytes = int(
                        getattr(ma, "temp_size_in_bytes", 0) or 0)
                    rec.generated_code_bytes = int(
                        getattr(ma, "generated_code_size_in_bytes", 0) or 0)
                    # peak working set of one execution on one device:
                    # live args + outputs + XLA scratch
                    rec.peak_bytes = (rec.arg_bytes + rec.out_bytes +
                                      rec.temp_bytes)
                    rec.source = "compiled"
            except Exception as e:
                _unavailable("memory_analysis", e)
    if rec.source == "none":
        return None
    _remember(rec)
    return rec


# -- ledger -------------------------------------------------------------------

def record_model_bytes(param_bytes: int, opt_state_bytes: int):
    """Book the persistable split measured at executor capture time into
    the ledger gauges (params vs optimizer state/counters)."""
    if param_bytes:
        telemetry.gauge_set("mem.param_bytes", int(param_bytes))
    if opt_state_bytes:
        telemetry.gauge_set("mem.opt_state_bytes", int(opt_state_bytes))
    refresh_ledger()


def split_persistable_bytes(block, names, values) -> Tuple[int, int]:
    """(param_bytes, other_state_bytes) over the named scope residents:
    is_parameter persistables are model weights, the rest (moments,
    lr counters, ...) are optimizer/run state."""
    params = other = 0
    for n, v in zip(names, values):
        if v is None:
            continue
        nbytes = int(getattr(v, "nbytes", 0) or 0)
        if not nbytes:
            try:
                a = np.asarray(v)
                nbytes = int(a.nbytes)
            except Exception:
                continue
        if block is not None and block.has_var(n):
            var = block.var(n)
            if not var.persistable:
                continue
            if getattr(var.desc, "is_parameter", False):
                params += nbytes
                continue
        other += nbytes
    return params, other


def ledger() -> Dict[str, Any]:
    """The composed HBM ledger: persistable params + optimizer state
    (per-device sharded figure from PR 7's gauges when ZeRO is active,
    else the capture-time measurement) + the worst-case compiled-program
    scratch + serving bucket footprints."""
    g = telemetry.gauges()
    param_bytes = int(g.get("mem.param_bytes", 0) or 0)
    opt_global = g.get("sharding.optimizer_state_bytes")
    opt_per_dev = g.get("sharding.optimizer_state_bytes_per_device")
    opt_bytes = int(opt_per_dev if opt_per_dev is not None
                    else g.get("mem.opt_state_bytes", 0) or 0)
    with _lock:
        recs = list(_programs.values())
    peak_temp = max((r.temp_bytes for r in recs), default=0)
    buckets = {n[len("mem.serving.bucket"):-len("_peak_bytes")]: int(v)
               for n, v in g.items()
               if n.startswith("mem.serving.bucket")
               and n.endswith("_peak_bytes")}
    # the decode engine's preallocated KV page pool (serving/kv_cache.py)
    # is RESIDENT for the process lifetime — its full preallocation, not
    # just the used pages, belongs in the composed total
    kv_pool = int(g.get("mem.serving.kv_pool_bytes", 0) or 0)
    # a state-space model's per-slot states beside the pages: resident too
    state_pool = int(g.get("mem.serving.state_pool_bytes", 0) or 0)
    out = {"param_bytes": param_bytes, "opt_state_bytes": opt_bytes,
           "peak_temp_bytes": int(peak_temp),
           "total_bytes": param_bytes + opt_bytes + int(peak_temp)
           + kv_pool + state_pool,
           "programs": len(recs)}
    if opt_global is not None:
        out["opt_state_bytes_global"] = int(opt_global)
    if buckets:
        out["serving_bucket_bytes"] = buckets
        out["serving_peak_bytes"] = max(buckets.values())
    if kv_pool:
        out["serving_kv_pool_bytes"] = kv_pool
        out["serving_kv_used_bytes"] = int(
            g.get("mem.serving.kv_used_bytes", 0) or 0)
        out["serving_kv_high_water_bytes"] = int(
            g.get("mem.serving.kv_high_water_bytes", 0) or 0)
    if state_pool:
        out["serving_state_pool_bytes"] = state_pool
        out["serving_state_used_bytes"] = int(
            g.get("mem.serving.state_pool_bytes.used", 0) or 0)
    # cumulative pool bytes requests did NOT privately allocate thanks
    # to a prefix-cache hit (serving/prefix_store.py) — savings, not
    # residency, so it never joins total_bytes
    kv_saved = int(g.get("mem.serving.kv_prefix_saved_bytes", 0) or 0)
    if kv_saved:
        out["serving_kv_prefix_saved_bytes"] = kv_saved
    return out


def refresh_ledger():
    """Recompute + publish the composed ledger total (called after any
    component gauge moves: executor capture, ZeRO report_state_sharding,
    serving warmup)."""
    led = ledger()
    if led["total_bytes"]:
        telemetry.gauge_set("mem.hbm_total_bytes", led["total_bytes"])


# -- dispatch accounting + live MFU ------------------------------------------

def book_dispatch(rec: Optional[ProgramCost], steps: int = 1):
    """Book one dispatch of a captured program: quiet flop/byte counters
    (per-dispatch volume is too high for per-increment JSONL) feed the
    rolling window that the live MFU gauge reads. flops_per_dispatch
    scales the body by steps_per_dispatch because XLA's cost analysis
    counts a scan/while body once regardless of trip count."""
    if rec is None or not rec.flops:
        return
    telemetry.counter_quiet("cost.dispatch_flops",
                            int(rec.flops_per_dispatch()))
    if rec.bytes_accessed:
        telemetry.counter_quiet("cost.dispatch_bytes",
                                int(rec.bytes_per_dispatch()))
    now = time.time()
    if now - _last_mfu_set[0] >= 1.0:   # 1 Hz gauge refresh, not per step
        _last_mfu_set[0] = now
        # no rounding: small-model MFU values live around 1e-7 and must
        # stay nonzero in the log/gauge
        mfu = live_mfu()
        if mfu is not None:         # no peak known: the gauge is omitted
            telemetry.gauge_set("cost.live_mfu", mfu)


def live_mfu(window_s: Optional[float] = None) -> Optional[float]:
    """Live model-flops utilization: windowed achieved flops/s (the
    cost.dispatch_flops rolling rate) ÷ peak device flops. The PaLM-
    style MFU discipline as a runtime gauge instead of an offline bench
    formula. None where the device kind has no known peak — this is read
    inside Executor.run and bench finalizers, which omit the figure."""
    try:
        peak = peak_device_flops()
    except UnknownDevicePeakError:
        return None
    win = telemetry.windowed(window_s)
    wc = win["counters"].get("cost.dispatch_flops")
    if not wc:
        return 0.0
    return float(wc["rate"]) / max(peak, 1.0)


# -- OOM forensics ------------------------------------------------------------

def oom_forensics(program: Any, err: BaseException,
                  where: str = "dispatch", top_n: int = 8) -> OutOfMemoryError:
    """Dump the forensics record for an allocation failure and return
    the typed error to raise: ledger snapshot + the top-N cached
    programs by peak bytes + the offending program id, as one
    ``kind:"oom"`` JSONL record (and a counted ``mem.oom_events``).
    The dump rides the unified incident pipeline (core/incidents.py):
    the legacy record keeps its exact shape for mem_report, and a
    ``kind:"incident"`` record bundles it with the flight-recorder ring
    + active traces."""
    with _lock:
        recs = sorted(_programs.values(),
                      key=lambda r: -(r.peak_bytes or r.temp_bytes))[:top_n]
    top = [{"key": r.key_id, "kind": r.kind, "program": r.program,
            "peak_bytes": r.peak_bytes, "temp_bytes": r.temp_bytes,
            "arg_bytes": r.arg_bytes, "flops": r.flops} for r in recs]
    led = ledger()
    telemetry.counter_add("mem.oom_events", 1, where=where)
    from . import incidents

    incidents.report_incident(
        "oom", "costmodel.oom", None,
        context={"where": where, "program": program,
                 "error": f"{type(err).__name__}: {err}"[:500],
                 "ledger": led, "top_programs": top},
        legacy_kind="oom")
    telemetry.flush_sink()   # the process may be about to die — land it
    return OutOfMemoryError(
        f"device allocation failure in {where} of program {program!r} "
        f"(HBM ledger: {led['total_bytes']} bytes across "
        f"{led['programs']} cached programs; forensics record written "
        f"to the run log): {err}")
