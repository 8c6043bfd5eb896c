"""Global FLAGS registry — env-settable runtime configuration.

Capability mirror of the reference's gflags tier (platform/flags.cc:33-560,
exported to Python via global_value_getter_setter.cc + init_gflags,
pybind.cc:1696): each flag has a default, is overridable via the
environment (FLAGS_<name>=...) at import, and via set_flags() at runtime
(the paddle.set_flags/get_flags API surface).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Union


class ConfigError(ValueError):
    """Typed configuration-surface error (bad flag name, uncoercible
    value, malformed bucket spec). Subclasses ValueError so pre-existing
    ``except ValueError`` callers keep working."""


class UnknownFlagError(ConfigError):
    """A flag name that is not in the registry — a typo'd override is an
    error, never a silently-ignored setting."""


class BucketConfigError(ConfigError):
    """A bucket-boundary list that is not a strictly increasing sequence
    of positive integers (or fails its coverage requirement)."""


class _Flag:
    __slots__ = ("name", "value", "default", "doc", "type")

    def __init__(self, name, default, doc):
        self.name = name
        self.default = default
        self.value = default
        self.doc = doc
        self.type = type(default)


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(flag: _Flag, val):
    if flag.type is bool:
        if isinstance(val, str):
            return val.lower() in ("1", "true", "yes", "on")
        return bool(val)
    return flag.type(val)


def define_flag(name: str, default, doc: str = ""):
    """DEFINE_bool/int/double/string equivalent (flags.cc)."""
    flag = _Flag(name, default, doc)
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        flag.value = _coerce(flag, env)
    _REGISTRY[name] = flag
    return flag


def _resolve_key(name: str) -> str:
    key = name[6:] if name.startswith("FLAGS_") else name
    if key not in _REGISTRY:
        raise UnknownFlagError(f"unknown flag '{name}' (no FLAGS_{key} "
                               f"registered)")
    return key


def get_flags(flags: Union[str, List[str]]) -> Dict[str, Any]:
    """paddle.get_flags."""
    names = [flags] if isinstance(flags, str) else list(flags)
    return {n: _REGISTRY[_resolve_key(n)].value for n in names}


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags."""
    apply(flags)


def flag(name: str):
    """Fast internal accessor."""
    return _REGISTRY[name].value


def all_flags() -> Dict[str, Any]:
    return {n: f.value for n, f in _REGISTRY.items()}


# -- typed snapshot / apply / scoped-override API ----------------------------
# (application and rollback are validated and exactly reversible — no ad-hoc
# monkeypatching of flag values)

def snapshot() -> Dict[str, Any]:
    """Copy of every flag's CURRENT value, keyed by bare name — the
    config a test rolls back to. ``apply(snapshot())`` is an exact
    restore."""
    return {n: f.value for n, f in _REGISTRY.items()}


def apply(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Validated bulk override: every name is resolved (typed
    UnknownFlagError on a typo) and every value coerced BEFORE any flag
    changes, so a half-applied config is impossible. Returns
    {bare_name: prior_value} of the touched flags — feed it back to
    ``apply`` to roll back."""
    resolved: Dict[str, Any] = {}
    for n, v in overrides.items():
        key = _resolve_key(n)
        f = _REGISTRY[key]
        try:
            resolved[key] = _coerce(f, v)
        except (TypeError, ValueError) as e:
            raise ConfigError(
                f"flag '{key}' cannot take value {v!r} "
                f"({f.type.__name__} expected): {e}") from e
    prior = {k: _REGISTRY[k].value for k in resolved}
    for k, v in resolved.items():
        _REGISTRY[k].value = v
    return prior


@contextmanager
def overrides(mapping: Optional[Dict[str, Any]] = None, **kw):
    """Scoped flag override: ``with flags.overrides(exec_steps_per_dispatch=4):``
    applies the (validated) overrides and restores the exact prior values
    on exit — even when the body raises."""
    ov: Dict[str, Any] = dict(mapping or {})
    ov.update(kw)
    prior = apply(ov)
    try:
        yield prior
    finally:
        apply(prior)


def parse_buckets(spec, name: str = "buckets",
                  cover: Optional[int] = None,
                  cover_exact: bool = False) -> Optional[List[int]]:
    """Parse + validate a bucket-boundary list (a comma-separated flag
    string or a sequence of ints). Boundaries must be POSITIVE integers
    in STRICTLY increasing order — a zero-valued or non-monotonic list
    raises a typed BucketConfigError instead of being silently
    reordered/deduped. ``cover`` demands the last boundary
    reach it (``cover_exact`` demands equality — the decode engine's
    fixed-step-shape contract). Returns None for an empty spec (caller
    default applies)."""
    if spec is None:
        vals: List[int] = []
    elif isinstance(spec, str):
        s = spec.strip()
        try:
            vals = [int(b) for b in s.split(",") if b.strip()] if s else []
        except ValueError as e:
            raise BucketConfigError(
                f"{name}: non-integer bucket boundary in {spec!r}") from e
    else:
        try:
            vals = [int(b) for b in spec]
        except (TypeError, ValueError) as e:
            raise BucketConfigError(
                f"{name}: non-integer bucket boundary in {spec!r}") from e
    if not vals:
        return None
    if vals[0] < 1:
        raise BucketConfigError(
            f"{name}: bucket boundaries must be >= 1, got {vals}")
    for a, b in zip(vals, vals[1:]):
        if b <= a:
            raise BucketConfigError(
                f"{name}: bucket boundaries must be strictly increasing, "
                f"got {vals}")
    if cover is not None:
        if cover_exact and vals[-1] != cover:
            raise BucketConfigError(
                f"{name}: bucket set {vals} must end exactly at {cover}")
        if vals[-1] < cover:
            raise BucketConfigError(
                f"{name}: bucket set {vals} does not cover {cover}")
    return vals


# -- the flag set (reference: platform/flags.cc; TPU-meaningful subset,
#    others kept for API compat) --------------------------------------------

define_flag("check_nan_inf", False,
            "scan every fetched value and updated persistable for NaN/Inf "
            "after each executor run (reference: flags.cc:44, "
            "details/nan_inf_utils_detail.cc)")
define_flag("benchmark", False, "sync + time every executor run")
# Reference flags a ported script may still set: accepted by set_flags and
# the environment, readable by get_flags, read by nothing (XLA owns buffer
# lifetime, preallocation, host threads and determinism).
ACCEPTED_AND_IGNORED = {
    "eager_delete_tensor_gb": 0.0,
    "fraction_of_gpu_memory_to_use": 0.92,
    "paddle_num_threads": 1,
    "use_pinned_memory": True,
    "cudnn_deterministic": False,
    "max_inplace_grad_add": 0,
}
for _name, _default in ACCEPTED_AND_IGNORED.items():
    define_flag(_name, _default, "reference flag: accepted and ignored")
define_flag("verify_program", False,
            "static program verification gate (core/verify.py): every "
            "program an Executor runs is checked once per (program, "
            "version) — structural integrity (vars exist, ops "
            "registered, required attrs), dataflow (def-before-use, "
            "dangling reads vs the actual feed/scope), write-write "
            "hazards and donation safety — raising a typed "
            "ProgramVerifyError BEFORE compile instead of an opaque "
            "pjit error at dispatch. Cheap pure-Python checks only; the "
            "eval_shape propagation check stays opt-in via "
            "verify.verify_program(infer_shapes=True) / tools/"
            "graph_lint.py")
define_flag("verify_passes", True,
            "verify the program after EVERY pass applied through "
            "core.passes.apply_passes (the MLIR pass-verifier "
            "discipline): a pass that leaves a dangling input, an "
            "unregistered op or a write hazard raises ProgramVerifyError "
            "naming the offending pass; VarDescs a pass orphans are "
            "pruned (verifier.pruned_vars). Disable to bisect a "
            "misbehaving pass pipeline without the gate")
define_flag("infer_shape_debug", False,
            "warn (with op type + error) when build-time shape inference "
            "fails instead of silently skipping — surfaces op-lowering bugs "
            "at program-build time rather than at jit time")
define_flag("telemetry_path", "",
            "path of the structured-telemetry JSONL run log (core/"
            "telemetry.py); empty disables the sink. The PT_TELEMETRY_LOG "
            "env var is an alias with lower precedence. Render with "
            "tools/perf_report.py")
define_flag("telemetry_buffer_lines", 64,
            "JSONL sink line-batching: records buffer in memory and are "
            "written as one batched write once this many lines are "
            "pending (or telemetry_flush_s elapses, or flush_sink() is "
            "called); 1 restores write-through. Sink write failures are "
            "counted in telemetry.dropped_records, never raised into the "
            "instrumented thread")
define_flag("telemetry_flush_s", 0.25,
            "max seconds a buffered JSONL record waits before the sink "
            "flushes it (inline on the next emit + a lazy daemon flusher "
            "thread); flush also happens at exit and on path change")
define_flag("metrics_window_s", 60.0,
            "rolling-window length for the live metrics plane "
            "(telemetry.windowed / prometheus_text / the /metrics "
            "endpoints): counter rates and histogram p50/p95/p99 are "
            "computed over the last this-many seconds")
define_flag("cost_capture", "auto",
            "per-compile XLA cost/memory capture level (core/"
            "costmodel.py): 'off' disables; 'cost' runs the lowered-"
            "module cost_analysis (flops/bytes — nearly free, the trace "
            "cache is shared with the first execution); 'full' adds an "
            "AOT compile for memory_analysis (peak/argument/output/temp "
            "bytes — one extra XLA compile per cache entry, opt in for "
            "memory-report runs); 'auto' (default) behaves as 'cost' "
            "when the run is instrumented (telemetry sink or metrics "
            "server active) and 'off' otherwise. Backends lacking the "
            "analysis APIs degrade gracefully (costmodel.unavailable "
            "counted, never raised)")
define_flag("device_peak_flops", 0.0,
            "peak dense flops/s of one device for the live MFU gauge "
            "and roofline verdicts (core/costmodel.py); <= 0 uses the "
            "built-in device table keyed on jax device_kind (a kind "
            "the table lacks, the CPU included, has no peak: the gauge "
            "and verdicts are omitted and peak_device_flops() raises)")
define_flag("device_peak_bw", 0.0,
            "peak HBM bytes/s of one device for the roofline ridge "
            "point (core/costmodel.py); <= 0 uses the built-in device "
            "table")
define_flag("trace_sample_rate", 0.0,
            "distributed-tracing sample rate in [0, 1] (core/trace.py): "
            "the probability a ROOT span starts a sampled trace whose "
            "spans are emitted as kind:'span' JSONL records (merged "
            "across processes by tools/trace_view.py). Children and "
            "propagated remote contexts never re-sample. 0 (default) "
            "disables tracing at ~zero cost; a serving request carrying "
            "an X-Request-Id header is always traced")
define_flag("exec_steps_per_dispatch", 1,
            "K-step fused execution: the static training loops "
            "(Executor.train_from_dataset, tools/bench_models.py) stack K "
            "consecutive batches into one [k, ...] feed and dispatch a "
            "single jitted lax.scan via Executor.run_steps — one Python "
            "dispatch, one feed transfer and one fetch sync per K device "
            "steps (reference analog: ExecutionStrategy."
            "num_iteration_per_drop_scope + py_reader double buffering). "
            "Model.fit uses it as the host-sync cadence of the eager "
            "loop. 1 disables fusion; programs with PS-IO ops fall back "
            "to sequential steps")
define_flag("predictor_cache_capacity", 32,
            "LRU bound on AnalysisPredictor's per-shape jit cache — under "
            "shape churn the oldest compiled entry is evicted instead of "
            "growing host memory without limit (predictor.cache_evictions "
            "counts drops); <= 0 disables the bound")
define_flag("profiler_max_events", 1_000_000,
            "ring-buffer bound on the profiler's host-span store — long "
            "runs overwrite the oldest spans instead of growing host "
            "memory without limit; drops are counted in the "
            "profiler.events_dropped telemetry counter")

# -- fault tolerance (reference analogs: gRPC retry env knobs consumed by
#    operators/distributed/grpc/grpc_client.cc, heart_beat_monitor.h) --------

define_flag("fault_spec", "",
            "deterministic fault-injection spec (core/faults.py grammar: "
            "'site:trigger[:Exc]' clauses, e.g. 'ps.rpc.send:0.1'); the "
            "PT_FAULT_SPEC env var is a lower-precedence alias; empty "
            "disables injection")
define_flag("fault_seed", 0,
            "seed for probabilistic fault-injection rules (PT_FAULT_SEED "
            "env alias when 0); the fire pattern is a pure function of "
            "(seed, per-site call index)")
define_flag("ps_rpc_timeout", 150.0,
            "per-call deadline in seconds for PS RPCs — retries, backoff "
            "and blocking reads all stop when it elapses and the call "
            "raises RpcDeadlineError; must exceed "
            "ps_sync_barrier_timeout so a legitimately-waiting sync recv "
            "is not cut off; <= 0 disables the deadline")
define_flag("ps_rpc_max_retries", 8,
            "max reconnect-and-resend attempts per PS RPC before the "
            "call raises RpcError (retries are deduplicated server-side "
            "by sequence number, so a retried send_grad applies once)")
define_flag("ps_rpc_backoff", 0.05,
            "base seconds for exponential retry backoff (doubles per "
            "attempt, +/-50% jitter, capped at 1s)")
define_flag("ps_sync_barrier_timeout", 120.0,
            "seconds a sync-mode recv_param waits for its version before "
            "the pserver raises BarrierTimeoutError to the trainer")
# -- serving engine (paddle_tpu/serving/: dynamic micro-batching inference;
#    reference analogs: TF-Serving BatchingParameters, Clipper adaptive
#    batching) ----------------------------------------------------------------

define_flag("serving_max_batch_size", 8,
            "upper bound on coalesced rows per engine batch — requests "
            "sharing a shape signature are merged up to this many rows "
            "before dispatch (a single oversized request still runs, in "
            "its own batch)")
define_flag("serving_batch_timeout_ms", 5.0,
            "how long the engine holds a partial batch open for more "
            "same-signature rows before flushing it (measured from the "
            "head request's enqueue); 0 dispatches immediately")
define_flag("serving_max_queue_depth", 256,
            "admission-control bound on queued requests — submits beyond "
            "this raise ServerOverloadedError instead of stalling the "
            "caller (serving.rejects counts them)")
define_flag("serving_default_deadline_ms", 0.0,
            "per-request deadline applied when the caller gives none: a "
            "request still queued past its deadline is failed with "
            "DeadlineExceededError at dequeue instead of wasting a batch "
            "slot; <= 0 means no deadline")
define_flag("serving_buckets", "",
            "comma-separated leading-dim bucket boundaries the engine "
            "pads coalesced batches up to (keeps the jit cache small and "
            "warm); empty = powers of two up to serving_max_batch_size")

# -- generative decode engine (paddle_tpu/serving/decode.py: continuous
#    batching over a paged KV cache; reference analogs: the beam_search /
#    while-op inference decoding programs, Orca continuous batching,
#    vLLM PagedAttention) ------------------------------------------------------

define_flag("decode_max_slots", 8,
            "decode-state slots of the generative engine — the upper "
            "bound on sequences decoded concurrently; the step program "
            "runs at fixed slot-array shapes (decode_buckets) so the jit "
            "cache stays one entry per bucket")
define_flag("decode_buckets", "",
            "comma-separated slot-array sizes the decode step pads the "
            "active set up to; empty = ONE bucket of decode_max_slots "
            "(fixed step shape — keeps continuous-batched generations "
            "bitwise-identical to sequential decode on backends whose "
            "GEMM kernels are batch-size-dependent)")
define_flag("decode_page_size", 16,
            "tokens per KV-cache page: requests allocate/free fixed-size "
            "pages from the preallocated pool (serving/kv_cache.py) "
            "instead of per-request max-length buffers")
define_flag("decode_kv_pages", 64,
            "pages in the preallocated KV pool (per layer, keys+values "
            "together); the pool's bytes book into the HBM ledger as "
            "mem.serving.kv_* and admission refuses requests whose "
            "worst-case page need cannot ever fit (typed "
            "KVCacheExhaustedError, never a device OOM)")
define_flag("decode_max_queue_depth", 256,
            "admission bound on queued generation requests — submits "
            "beyond this raise ServerOverloadedError (decode.rejects)")
define_flag("decode_default_deadline_ms", 0.0,
            "per-request generation deadline when the caller gives none; "
            "checked at STEP granularity mid-generation — an expired "
            "request retires with DeadlineExceededError and frees its "
            "pages without draining the batch; <= 0 means no deadline")
define_flag("decode_max_new_tokens", 64,
            "default generation budget when a request does not set "
            "max_new_tokens (always additionally capped by the model's "
            "max_seq_len)")
define_flag("pallas_kv_chunk_tokens", 1024,
            "KV tokens one chunk of the Pallas paged-attention decode "
            "kernel (ops/pallas/paged_attention.py) streams through "
            "VMEM: a row whose whole context fits one chunk takes the "
            "exact single-pass softmax (bitwise-identical to the "
            "PT_PALLAS=off stock lowering); longer contexts stream "
            "chunks through online-softmax accumulation. Part of "
            "kernels_fingerprint(), so changing it recompiles every "
            "cached program instead of reusing a stale kernel")
define_flag("decode_weight_quant", "none",
            "weight format of the decode engine: 'none' serves fp32 "
            "weights, 'int8' serves per-output-channel weight-only int8 "
            "(ops/quant_ops.py dequantize_weight fused into the consuming "
            "matmul read — half the weight HBM traffic)")
define_flag("decode_prefix_cache", False,
            "content-addressed prefix sharing (serving/prefix_store.py): "
            "admission looks up the longest cached prefix chain and "
            "prefills only the suffix through the page-chunked prefill "
            "program; shared pages are refcounted and read-only to the "
            "step program, so prefix-hit decode stays bitwise-identical "
            "to cold-prefill decode. Off by default: the classic "
            "one-pass flash prefill path is untouched")
define_flag("decode_role", "unified",
            "disaggregated-serving role of a decode replica "
            "(serving/disagg.py): 'prefill' replicas run chunked prefill "
            "and ship serialized KV pages, 'decode' replicas install "
            "shipped pages and run generation steps, 'unified' (default) "
            "does both locally")
define_flag("disagg_prefill_urls", "",
            "comma-separated prefill-tier replica URLs a decode-role "
            "replica fetches KV page shipments from (POST /v1/prefill); "
            "empty = no tier, every prefill runs locally (the "
            "unified-role fallback). On the live cluster path this is "
            "usually the ROUTER url — the router forwards /v1/prefill "
            "to a ready prefill-tier replica, so tier membership "
            "changes never strand a decode replica")
define_flag("decode_step_delay_ms", 0.0,
            "deliberate per-decode-step host-side delay — a chaos/bench "
            "pacing knob (tools/chaos_check.py --orchestrator, "
            "bench_serving --kill-decode) that keeps generations "
            "in-flight long enough to SIGKILL a replica mid-generation; "
            "0 (the default) adds nothing to the serving path")

# -- cluster serving control plane (paddle_tpu/serving/router.py +
#    cluster.py: replicated engines, health-checked routing, zero-downtime
#    model swap; reference analogs: the PS/Fleet elastic-serving promise,
#    TF-Serving + an L7 LB in front) ------------------------------------------

define_flag("router_health_interval_s", 0.2,
            "seconds between router health/stats probes of each replica "
            "(GET /healthz + /v1/stats): readiness gates routing, scraped "
            "queue_depth drives least-loaded balancing")
define_flag("cluster_max_restarts", 5,
            "respawn budget per replica process: a replica that dies is "
            "relaunched (router.replica_restarts) up to this many times "
            "before the controller gives up on the slot")

define_flag("ckpt_verify", True,
            "verify checkpoint integrity before restoring (paddle_tpu/"
            "checkpoint.py): data-file size + sha256 and per-array "
            "crc32/shape/dtype against the COMMIT manifest; corrupt or "
            "uncommitted checkpoints are quarantined and restore_latest "
            "falls back to the newest valid one (ckpt.verify_failures / "
            "ckpt.fallbacks telemetry). Disabling skips only the digest "
            "work — the commit manifest itself is always required")

# -- flight recorder + SLO watchdog plane (core/incidents.py: always-on
#    black-box diagnostics with anomaly-triggered incident dumps; reference
#    analogs: heartbeat monitors + barrier health checks that stop at raw
#    counters) -----------------------------------------------------------------

define_flag("blackbox_max_records", 2048,
            "bound on the always-on flight-recorder ring "
            "(core/incidents.py): the last this-many telemetry records / "
            "trace spans / decode-router events are kept in memory — "
            "independent of any JSONL sink — and bundled into every "
            "kind:'incident' dump; 0 disables the recorder entirely "
            "(incident dumps then carry an empty ring)")
define_flag("blackbox_seconds", 120.0,
            "time horizon of the flight-recorder ring: a snapshot taken "
            "for an incident dump drops records older than this many "
            "seconds even when the ring's record bound has not evicted "
            "them yet")
define_flag("slo_watchdog", "auto",
            "SLO/watchdog rule engine arming (core/incidents.py): 'on' "
            "arms rule evaluation at import, 'off' disarms it "
            "everywhere, 'auto' (default) arms when a serving/metrics "
            "HTTP surface starts or incidents.arm() is called "
            "explicitly. Armed: the hot loops' hook (telemetry.tick() in "
            "the executor and the decode engine, incidents.tick() in "
            "the router and the serving engine) evaluates the rule set "
            "at most every slo_eval_s; disarmed it costs one boolean "
            "read")
define_flag("slo_eval_s", 5.0,
            "min seconds between two SLO rule evaluations (inline "
            "tick() or the pt-incidents-watchdog thread): each "
            "evaluation reads the rolling metrics window once per "
            "distinct rule window")
define_flag("slo_rules", "",
            "declarative SLO rule overrides: a JSON array of rule "
            "objects ({name, metric, kind: counter|hist|gauge, stat, "
            "window_s, threshold | ratio (relative to the warmup-learned "
            "baseline), direction, min_samples, cooldown_s}), or "
            "@/path/to/rules.json; empty uses the built-in rule set "
            "(step-time p99 regression, live-MFU drop, serving/decode "
            "queue saturation, pallas fallback spike, router failover "
            "burst, ckpt verify failures)")
define_flag("incident_rate_limit_s", 30.0,
            "global min spacing between two kind:'incident' run-log "
            "dumps (per-rule cooldowns apply on top): a storm of trips "
            "books incidents.rate_limited instead of flooding the log; "
            "legacy oom/stall/thread_error records are never suppressed")

define_flag("sanitize_locks", False,
            "runtime concurrency sanitizer (core/analysis/lockdep.py, "
            "the lockdep/TSan discipline): the lock factories the "
            "threaded subsystems build their locks through return "
            "instrumented wrappers that record per-thread acquisition "
            "order in one global graph, raise a typed LockOrderError on "
            "a lock-order cycle or a same-thread re-entry of a "
            "non-reentrant lock (potential deadlocks become errors "
            "BEFORE the schedule wedges), book lock.acquires/"
            "lock.contentions counters + per-lock held/wait-ms timers "
            "into telemetry, and register with a stall watchdog. Off "
            "(default): the factories return plain threading primitives "
            "— zero wrapper, zero lock.* records. Read at lock "
            "CONSTRUCTION time; module-level locks pick a flip up via "
            "the env var at import")
define_flag("lock_stall_s", 30.0,
            "deadlock-watchdog threshold (FLAGS_sanitize_locks): an "
            "instrumented lock acquire still waiting after this many "
            "seconds makes the watchdog thread dump EVERY thread's "
            "stack, held locks and waited lock into the run log as one "
            "kind:'stall' record (lock.stalls counts them) — wedged-"
            "process forensics captured while it is still wedged")

# -- fleet observatory + goodput ledger (core/fleetobs.py, core/goodput.py;
#    reference analogs: heart_beat_monitor.h fleet liveness, monitor.h stat
#    aggregation, profiler timeline attribution) ------------------------------

define_flag("fleet_enable", False,
            "start a FleetAggregator inside ClusterController.start() "
            "(scrape every replica + the router into merged fleet "
            "windows, serve /fleet/status + /fleet/metrics on the "
            "router front end). Opt-in: per-process observability stays "
            "the default")
define_flag("fleet_scrape_interval_s", 1.0,
            "seconds between two fleet scrape passes (every member's "
            "/metrics + /v1/stats)")
define_flag("fleet_stale_after_s", 5.0,
            "seconds without a successful scrape before a member is "
            "marked STALE. A stale member keeps its last-known load "
            "(never zeroed) and stops contributing to fleet windows; "
            "the scrape loop never wedges on it")
define_flag("fleet_straggler_zscore", 3.0,
            "per-member latency z-score vs the fleet median above which "
            "a member is flagged a straggler (router pick() deprioritises "
            "flagged replicas; the fleet_straggler_replica rule trips)")
define_flag("fleet_min_members", 3,
            "minimum members with fresh latency evidence before "
            "straggler z-scores are computed — outlier math on 2 "
            "members is a coin flip")
define_flag("goodput_publish_s", 2.0,
            "seconds between goodput-ledger publishes on the executor "
            "hot path (goodput.* counters + the goodput.ratio gauge "
            "refreshed on /metrics while the run is live)")

define_flag("ps_degrade_to_survivors", False,
            "when the HeartBeatMonitor declares a trainer dead, shrink "
            "the sync barrier to the live set (mean over survivors) "
            "instead of stalling to the barrier timeout; a revived "
            "trainer rejoins at the next version. Changes the effective "
            "batch while degraded — opt-in")
define_flag("ps_elastic_admission", True,
            "admit trainer ids the PServer was not constructed with: a "
            "send_grad/heartbeat from an unseen id grows num_trainers "
            "(and the heartbeat monitor's expected set) so the sync "
            "barrier REGROWS at scale-up instead of permanently "
            "excluding new workers (ps.barrier_regrown counter)")

# -- elastic resize + signal-driven autoscaling (distributed/scaler.py,
#    distributed/elastic.py, serving/cluster.py scale_to) -------------------
define_flag("elastic_restart_window_s", 0.0,
            "sliding window (seconds) for the ElasticRunner restart "
            "budget: only restarts inside the window count against "
            "max_restarts, so sustained progress refunds the crash "
            "budget. 0 keeps the legacy lifetime counter")
define_flag("scaler_min_world", 1,
            "lower bound on the world size a ScalerPolicy may target — "
            "ScaleDown decisions clamp here (scaler.clamped counter)")
define_flag("scaler_max_world", 8,
            "upper bound on the world size a ScalerPolicy may target — "
            "ScaleUp decisions clamp here (scaler.clamped counter)")
define_flag("scaler_cooldown_s", 30.0,
            "minimum seconds between two ScalerPolicy decisions: a "
            "decision inside the cooldown is suppressed "
            "(scaler.suppressed_cooldown) so one saturated window "
            "cannot thrash the world size")
define_flag("scaler_window_s", 30.0,
            "metrics window (seconds) a ScalerPolicy reads when "
            "gathering live signals (queue saturation, step-time p99, "
            "heartbeat verdicts) from the telemetry registry")
define_flag("scaler_queue_high_frac", 0.85,
            "queue-saturation fraction (queue depth / admission bound) "
            "at or above which the policy emits ScaleUp "
            "(reason queue_saturation)")
define_flag("scaler_queue_low_frac", 0.10,
            "queue-saturation fraction at or below which the policy "
            "emits ScaleDown (reason underutilized) — only when the "
            "window actually carried traffic evidence")
define_flag("scaler_step_p99_high_ms", 0.0,
            "step-time p99 (ms) over the scaler window above which the "
            "policy emits ScaleUp (reason step_time_p99); 0 disables "
            "the rule")
