#!/usr/bin/env python
"""perf_report — summarize a paddle_tpu JSONL telemetry run log.

Renders the structured run log written by ``paddle_tpu.core.telemetry``
(enable with ``PT_TELEMETRY_LOG=/path/run.jsonl`` or
``FLAGS_telemetry_path``) back into tables:

* step-time percentiles per timer (executor.run_ms, hapi.step_ms,
  ps.rpc_ms, ...);
* every compile event with its wall time and recompile CAUSE (which
  cache-key component changed: program / program_version / feed_names /
  fetch_names / mesh / dp_divisibility);
* set-up by program: each compiled program's first life as its compile
  event carries it (telemetry.CompileRecord): build with its shape
  inference, jax's trace and lowering, the backend's compile or the read
  of the persistent cache (hit / miss), the cost capture, the first run;
* counter deltas over the log (compiles, cache hits, donation copies,
  feed/fetch bytes, RPC traffic) and final gauges;
* a fused-dispatch section when the run used K-step pipelined execution
  (Executor.run_steps / FLAGS_exec_steps_per_dispatch): dispatches,
  steps per dispatch, per-dispatch ms percentiles, and the estimated
  host-dispatch ms the fusion saved;
* a "Serving" section when the run used the micro-batching engine
  (paddle_tpu/serving/): request/batch counts, batch-fill ratio,
  padding overhead, rejects/deadline-drops, and request/batch latency
  percentiles;
* a "Decode" section when the run used the continuous-batching
  generative engine (paddle_tpu/serving/decode.py): tokens/s, slot
  occupancy, prefill-vs-decode-step latency percentiles, what admissions
  cost the loop (the share of its time every slot stood still for a
  prefill, the prefill tokens that were padding, the engine thread's CPU
  share), KV page-pool bytes + high-water mark and the alloc/free page
  balance (a nonzero difference prints as LEAKED);
* a "Checkpointing" section when the run saved/restored through the
  crash-consistent protocol (paddle_tpu/checkpoint.py): commits, bytes,
  verification rejections + fallbacks to older checkpoints, quarantined
  dirs, and save/restore latency percentiles;
* a "Sharding" section when the run used rule-table partitioning / the
  ZeRO ShardingOptimizer (parallel/axis_rules.py, fleet
  meta_optimizers.py): per-kind dp-collective bytes, optimizer-state
  bytes global vs per-device, rule resolutions and reshard-on-load
  events;
* a "Verifier" section when the run ran static program verification
  (core/verify.py — apply_passes post-pass gates, FLAGS_verify_program,
  tools/graph_lint.py): programs verified, checks run, violations,
  orphaned VarDescs pruned, and verify-time percentiles;
* a "Memory & cost" section when the run captured XLA cost/memory
  analyses (core/costmodel.py, FLAGS_cost_capture): capture health, the
  HBM ledger gauges, dispatched flop volume, the live-MFU gauge and
  roofline verdict counts — the full per-program table and OOM
  forensics render with tools/mem_report.py;
* a "Concurrency" section when the run held instrumented locks
  (core/analysis/lockdep.py, FLAGS_sanitize_locks): acquire/contention
  counts, lock-order violations, stall dumps (kind:"stall" all-thread
  stack records from the deadlock watchdog), uncaught worker-thread
  exceptions, and per-lock held/wait-ms percentiles;
* an "Incidents & SLO" section when the run armed the flight-recorder /
  SLO watchdog plane (core/incidents.py): rule trip counts and firing
  states (``slo.<rule>_firing``), incident dumps landed vs rate-limited,
  and a per-incident index — the full postmortems (timeline, counter
  deltas, correlated spans) render with tools/incident_report.py;
* a "Tracing" section when the run emitted distributed-tracing spans
  (core/trace.py, FLAGS_trace_sample_rate): trace/span counts and
  per-span-name duration percentiles — merge multi-process logs with
  tools/trace_view.py for the full causal trees;
* the profiler.summarize() host-span table when the log carries one
  (telemetry.flush() embeds it at exit).

Malformed lines (a SIGKILLed process tears its final line mid-write —
PR 5 chaos runs produce these) are skipped AND counted: the summary
carries ``malformed_lines`` and the report prints the count instead of
the tool crashing on a torn log.

Stdlib-only on purpose: a run log from a TPU worker renders on any
machine, no jax/framework import.

Usage:
    python tools/perf_report.py run.jsonl            # tables
    python tools/perf_report.py run.jsonl --json     # machine-readable
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def load_counted(path):
    """Read a JSONL log, skipping malformed lines (a SIGKILLed run tears
    its final line mid-write — the report must still render). Returns
    (records, malformed_line_count)."""
    recs, malformed = [], 0
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                malformed += 1
                print(f"perf_report: skipping malformed line {ln}",
                      file=sys.stderr)
                continue
            if isinstance(rec, dict):
                recs.append(rec)
            else:
                malformed += 1
    return recs, malformed


def load(path):
    """Records only (compat shim over load_counted)."""
    return load_counted(path)[0]


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[i]


# a program's set-up record as its ``compile`` event carries it
# (paddle_tpu/core/telemetry.py CompileRecord); the table's columns are
# the seconds, from "total_s" on
SETUP_FIELDS = ("name", "kind", "ops", "cache_hit", "total_s", "build_s",
                "infer_shape_s", "trace_s", "lower_s", "compile_s",
                "cache_read_s", "capture_s", "first_run_s")


def summarize_log(recs, malformed=0):
    timers = defaultdict(list)
    hists = defaultdict(list)
    counter_delta = defaultdict(float)
    counter_last = {}
    gauges = {}
    compiles = []
    steps = []
    metrics = []
    profiler_rows = []
    cost_events = []
    oom_events = 0
    stall_events = []
    thread_errors = []
    incident_events = []
    scale_events = []
    spans = defaultdict(list)
    span_traces = set()
    snapshot = None
    ts = [r["ts"] for r in recs if isinstance(r.get("ts"), (int, float))]
    for r in recs:
        kind, name = r.get("kind"), r.get("name")
        v, attrs = r.get("value"), r.get("attrs") or {}
        if kind == "timer" and isinstance(v, (int, float)):
            timers[name].append(float(v))
        elif kind == "hist" and isinstance(v, (int, float)):
            hists[name].append(float(v))
        elif kind == "span":
            if isinstance(v, (int, float)):
                spans[name].append(float(v))
            if attrs.get("trace"):
                span_traces.add(attrs["trace"])
        elif kind == "compile":
            compiles.append({"ts": r.get("ts"), "ms": v,
                             "cause": attrs.get("cause"),
                             "cache_size": attrs.get("cache_size"),
                             "feed_names": attrs.get("feed_names"),
                             "fetch_names": attrs.get("fetch_names"),
                             # the program's set-up record (telemetry.
                             # CompileRecord), where the log carries it
                             "setup": {k: attrs.get(k) for k in SETUP_FIELDS}
                             if "total_s" in attrs else None})
        elif kind == "counter":
            if attrs.get("set"):
                counter_last[name] = v
            else:
                try:
                    counter_delta[name] += float(attrs.get("delta") or 0)
                except (TypeError, ValueError):
                    pass
                counter_last[name] = v
        elif kind == "gauge":
            gauges[name] = v
        elif kind == "step":
            steps.append({"name": name, "value": v, **attrs})
        elif kind == "metric":
            metrics.append({"name": name, "value": v, **attrs})
        elif kind == "profiler_summary":
            profiler_rows.append({"name": name, "total_us": v, **attrs})
        elif kind == "cost":
            cost_events.append(attrs)
        elif kind == "oom":
            oom_events += 1
        elif kind == "stall":
            stall_events.append({"lock": attrs.get("lock"),
                                 "thread": attrs.get("thread"),
                                 "waited_s": attrs.get("waited_s"),
                                 "threads": len(attrs.get("threads")
                                                or [])})
        elif kind == "thread_error":
            thread_errors.append({"thread": name,
                                  "exc": attrs.get("exc")})
        elif kind == "incident":
            incident_events.append({
                "name": name, "ts": r.get("ts"),
                "id": attrs.get("id"), "source": attrs.get("source"),
                "rule": (attrs.get("rule") or {}).get("name"),
                "ring_records": len(attrs.get("ring") or [])})
        elif kind == "scale":
            scale_events.append({
                "name": name, "ts": r.get("ts"),
                "source": attrs.get("source"),
                "event": attrs.get("event"),
                "old_world": attrs.get("old_world"),
                "new_world": attrs.get("new_world"),
                "reason": attrs.get("reason")})
        elif kind == "snapshot":
            snapshot = attrs
    # a final snapshot is authoritative for cumulative counter values
    if snapshot:
        for n, cv in (snapshot.get("counters") or {}).items():
            counter_last[n] = cv
        for n, gv in (snapshot.get("gauges") or {}).items():
            gauges.setdefault(n, gv)
    timer_summary = {}
    for name, vals in timers.items():
        s = sorted(vals)
        timer_summary[name] = {
            "count": len(s), "p50": round(_pct(s, 0.50), 3),
            "p90": round(_pct(s, 0.90), 3), "p99": round(_pct(s, 0.99), 3),
            "max": round(s[-1], 3),
            "mean": round(sum(s) / len(s), 3)}
    hist_summary = {}
    for name, vals in hists.items():
        s = sorted(vals)
        hist_summary[name] = {
            "count": len(s), "p50": round(_pct(s, 0.50), 4),
            "mean": round(sum(s) / len(s), 4)}
    span_s = round(max(ts) - min(ts), 3) if ts else 0.0
    fused = _fused_summary(counter_delta, counter_last, timer_summary)
    serving = _serving_summary(counter_delta, counter_last, timer_summary,
                               gauges)
    decode = _decode_summary(counter_delta, counter_last, timer_summary,
                             gauges, hist_summary, span_s,
                             (snapshot or {}).get("hists") or {})
    router = _router_summary(counter_delta, counter_last, timer_summary)
    ckpt = _ckpt_summary(counter_delta, counter_last, timer_summary)
    sharding = _sharding_summary(counter_delta, counter_last, gauges)
    verifier = _verifier_summary(counter_delta, counter_last, timer_summary)
    memcost = _memcost_summary(counter_delta, counter_last, gauges,
                               cost_events, oom_events)
    concurrency = _concurrency_summary(counter_delta, counter_last,
                                       timer_summary, stall_events,
                                       thread_errors)
    incidents = _incidents_summary(counter_delta, counter_last, gauges,
                                   incident_events)
    goodput = _goodput_summary(counter_delta, counter_last, gauges)
    fleet = _fleet_summary(counter_delta, counter_last, gauges)
    scaler = _scaler_summary(counter_delta, counter_last, scale_events)
    crash_survival = _crash_survival_summary(counter_delta, counter_last)
    tracing = None
    if spans:
        by_name = {}
        for name, vals in sorted(spans.items()):
            s = sorted(vals)
            by_name[name] = {"count": len(s),
                             "p50_ms": round(_pct(s, 0.50), 3),
                             "p99_ms": round(_pct(s, 0.99), 3),
                             "max_ms": round(s[-1], 3)}
        tracing = {"spans": sum(len(v) for v in spans.values()),
                   "traces": len(span_traces),
                   "by_name": by_name}
    return {
        "fused": fused,
        "serving": serving,
        "decode": decode,
        "router": router,
        "checkpoint": ckpt,
        "sharding": sharding,
        "verifier": verifier,
        "memcost": memcost,
        "concurrency": concurrency,
        "incidents": incidents,
        "goodput": goodput,
        "fleet": fleet,
        "scaler": scaler,
        "crash_survival": crash_survival,
        "tracing": tracing,
        "malformed_lines": int(malformed),
        "records": len(recs),
        "span_s": span_s,
        "timers": timer_summary,
        "compiles": compiles,
        "counters": {n: {"delta": counter_delta.get(n, 0.0),
                         "last": counter_last.get(n)}
                     for n in sorted(set(counter_delta) | set(counter_last))},
        "gauges": gauges,
        "steps": steps,
        "metrics": metrics,
        "profiler": profiler_rows,
    }


def _fused_summary(counter_delta, counter_last, timer_summary):
    """K-step fused-dispatch accounting (executor.run_steps): dispatches,
    steps/dispatch, and the host-dispatch time fusion saved — estimated
    as (fused_steps - fused_dispatches) * p50 single-dispatch host ms
    (each fused step beyond the first would otherwise have paid one
    host dispatch)."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    dispatches = cval("executor.fused_dispatches")
    steps = cval("executor.fused_steps")
    if not dispatches:
        return None
    out = {"dispatches": int(dispatches), "fused_steps": int(steps),
           "steps_per_dispatch": round(steps / dispatches, 2)}
    rs = timer_summary.get("executor.run_steps_ms")
    if rs:
        out["dispatch_ms_p50"] = rs["p50"]
        out["ms_per_fused_step_p50"] = round(
            rs["p50"] / max(1.0, steps / dispatches), 3)
    single = timer_summary.get("executor.run_ms")
    if single and steps > dispatches:
        out["host_dispatch_ms_saved"] = round(
            (steps - dispatches) * single["p50"], 1)
    fallback = cval("executor.fused_fallback_steps")
    if fallback:
        out["fallback_steps"] = int(fallback)
    return out


def _serving_summary(counter_delta, counter_last, timer_summary, gauges):
    """Micro-batching engine accounting (paddle_tpu/serving/): how many
    requests rode how many device batches, how full the padded batches
    were, and what admission control rejected/expired."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    requests = cval("serving.requests")
    batches = cval("serving.batches")
    if not requests and not batches:
        return None
    rows = cval("serving.batched_rows")
    padded = cval("serving.padded_rows")
    out = {"requests": int(requests), "batches": int(batches),
           "rejects": int(cval("serving.rejects")),
           "deadline_expired": int(cval("serving.deadline_expired")),
           "handler_errors": int(cval("serving.handler_errors")),
           "warmup_compiles": int(cval("serving.warmup_compiles"))}
    if batches:
        out["rows_per_batch"] = round(rows / batches, 2)
        out["requests_per_batch"] = round(requests / batches, 2)
    if rows:
        out["batch_fill"] = round(rows / (rows + padded), 4)
    for timer, key in (("serving.request_ms", "request_ms"),
                       ("serving.batch_ms", "batch_ms")):
        t = timer_summary.get(timer)
        if t:
            out[key] = {"p50": t["p50"], "p99": t["p99"], "max": t["max"]}
    qd = gauges.get("serving.queue_depth")
    if qd is not None:
        out["last_queue_depth"] = qd
    return out


def _decode_summary(counter_delta, counter_last, timer_summary, gauges,
                    hists, span_s, final_hists):
    """Generative decode engine accounting (paddle_tpu/serving/decode.py
    + kv_cache.py): tokens/s, prefill-vs-decode step latency, slot-array
    occupancy, what admissions cost the loop, and the KV page pool's
    high-water mark. ``final_hists`` are the closing snapshot's histogram
    summaries: the loop's phases are observed quietly and leave no record
    of their own in the log."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    tokens = cval("decode.tokens")
    steps = cval("decode.steps")
    prefills = cval("decode.prefills")
    if not tokens and not prefills:
        return None
    out = {"requests": int(cval("decode.requests")),
           "prefills": int(prefills),
           "prefill_tokens": int(cval("decode.prefill_tokens")),
           "steps": int(steps), "tokens": int(tokens),
           "retired": int(cval("decode.retired")),
           "rejects": int(cval("decode.rejects")),
           "kv_refusals": int(cval("decode.kv_refusals")),
           "deadline_expired": int(cval("decode.deadline_expired")),
           "errors": int(cval("decode.errors")),
           "compiles": int(cval("decode.compiles"))}
    if span_s and tokens:
        out["tokens_per_s"] = round(tokens / span_s, 2)
    if steps:
        out["tokens_per_step"] = round(tokens / steps, 2)
    occ = hists.get("decode.batch_occupancy")
    if occ:
        out["batch_occupancy"] = occ
    # a model with a draft module (serving/decode.py): the share of drafts
    # accepted, the tokens a stepped row took a step, what was thrown away
    proposed = cval("decode.draft_proposed")
    if proposed:
        out["draft_accept_share"] = round(
            100.0 * cval("decode.draft_accepted") / proposed, 2)
        if cval("decode.rows_stepped"):
            out["tokens_per_row_step"] = round(
                tokens / cval("decode.rows_stepped"), 3)
        out["tokens_discarded"] = int(cval("decode.tokens_discarded"))
    # a model with per-slot state: the rows a step advanced, a layer, by
    # kind (a recurrent state; a conv tail that is a layer's whole state)
    for counter, key in (("decode.state_rows_updated", "state_rows"),
                         ("decode.conv_rows_updated", "conv_rows")):
        if cval(counter):
            out[key] = int(cval(counter))
    for timer, key in (("decode.prefill_ms", "prefill_ms"),
                       ("decode.step_ms", "step_ms"),
                       ("decode.request_ms", "request_ms")):
        t = timer_summary.get(timer)
        if t:
            out[key] = {"p50": t["p50"], "p99": t["p99"], "max": t["max"]}
    # what admissions cost, as DecodeEngine.stats() gives it
    # (serving/decode.py admission_cost): shares of the loop's wall time
    loop_ms = (final_hists.get("decode.loop_ms") or {}).get("total")
    for key, name in (("prefill_wait_share", "decode.prefill_wait_ms"),
                      ("engine_cpu_share", "decode.cpu_ms")):
        h = final_hists.get(name)
        if loop_ms and h and h.get("count"):
            out[key] = round(100.0 * h["total"] / loop_ms, 2)
    computed = cval("decode.prefill_bucket_tokens")
    if computed:
        out["prefill_padded_token_share"] = round(
            100.0 * (1.0 - cval("decode.prefill_tokens") / computed), 2)
    kv_pool = gauges.get("mem.serving.kv_pool_bytes")
    if kv_pool is not None:
        out["kv_pool_bytes"] = int(kv_pool)
        out["kv_high_water_bytes"] = int(
            gauges.get("mem.serving.kv_high_water_bytes") or 0)
        out["kv_used_bytes"] = int(
            gauges.get("mem.serving.kv_used_bytes") or 0)
        # a model with window layers has two classes of pages
        # (kv_cache.PagedKVCache): mem.serving.kv_pool_bytes.<class>;
        # a model with latent layers books their one array a layer as
        # mem.serving.kv_pool_bytes.latent
        classes = {name.rsplit(".", 1)[1]: int(v)
                   for name, v in gauges.items()
                   if name.startswith("mem.serving.kv_pool_bytes.")}
        if classes:
            out["kv_pool_bytes_by_class"] = classes
    pages = cval("decode.kv_pages_allocated")
    if pages:
        out["kv_pages_allocated"] = int(pages)
        out["kv_pages_freed"] = int(cval("decode.kv_pages_freed"))
    # Pallas serving-kernel dispatch accounting (ops/pallas/int8_gemm.py
    # + paged_attention.py): counted once per LOWERING — which code path
    # each compiled program variant actually took, not per-step volume
    pallas = {key.split(".", 1)[1]: int(cval(key)) for key in
              ("pallas.int8_gemm_dispatches",
               "pallas.int8_gemm_fallbacks",
               "pallas.paged_attn_dispatches",
               "pallas.paged_attn_fallbacks",
               "pallas.mla_prefill_dispatches",
               "pallas.mla_prefill_fallbacks",
               "pallas.gqa_prefill_dispatches",
               "pallas.gqa_prefill_fallbacks",
               "pallas.ssm_state_update_dispatches",
               "pallas.ssm_state_update_fallbacks",
               "pallas.gated_delta_state_update_dispatches",
               "pallas.gated_delta_state_update_fallbacks",
               "pallas.gated_delta_chunk_scan_dispatches",
               "pallas.gated_delta_chunk_scan_fallbacks",
               "pallas.grouped_swiglu_dispatches",
               "pallas.grouped_swiglu_fallbacks",
               "pallas.grouped_polyglu_dispatches",
               "pallas.grouped_polyglu_fallbacks",
               "pallas.mhc_dispatches",
               "pallas.mhc_fallbacks",
               "pallas.draft_tail_dispatches",
               "pallas.draft_tail_fallbacks",
               "pallas.grouped_swiglu_bwd_dispatches",
               "pallas.grouped_swiglu_bwd_fallbacks",
               "pallas.routed_combine_dispatches",
               "pallas.routed_combine_fallbacks",
               "pallas.routed_spread_dispatches",
               "pallas.routed_spread_fallbacks",
               "pallas.flash_window_dispatches",
               "pallas.flash_window_fallbacks") if cval(key)}
    if pallas:
        out["pallas_kernels"] = pallas
    # content-addressed prefix store accounting (serving/prefix_store.py):
    # sharing rate, prefill bytes the cache skipped, copy-on-write forks,
    # LRU reclaims, and the refcount audit verdict
    prefix = {key.split(".", 1)[1]: int(cval(key)) for key in
              ("kv.prefix_hits", "kv.prefix_misses", "kv.bytes_saved",
               "kv.cow_forks", "kv.reclaims", "kv.audit_failures")
              if cval(key)}
    blocks = gauges.get("kv.prefix_blocks")
    if blocks is not None:
        prefix["prefix_blocks"] = int(blocks)
    saved = gauges.get("mem.serving.kv_prefix_saved_bytes")
    if saved:
        prefix["kv_prefix_saved_bytes"] = int(saved)
    if prefix:
        out["prefix_store"] = prefix
    # disaggregated prefill/decode accounting (serving/disagg.py):
    # shipments produced, installs at the decode tier, CRC rejects and
    # the local re-prefill fallbacks they forced
    disagg = {key.split(".", 1)[1]: int(cval(key)) for key in
              ("disagg.ships", "disagg.ship_bytes", "disagg.installs",
               "disagg.crc_rejects", "disagg.fallback_prefills")
              if cval(key)}
    if disagg:
        out["disagg"] = disagg
    return out


def _router_summary(counter_delta, counter_last, timer_summary):
    """Cluster control-plane accounting (paddle_tpu/serving/router.py +
    cluster.py): routed requests, retries/failovers, replica deaths and
    respawns, model swaps, and the router-observed latency."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    requests = cval("router.requests")
    if not requests:
        return None
    out = {"requests": int(requests),
           "retries": int(cval("router.retries")),
           "failovers": int(cval("router.failovers")),
           "rejects": int(cval("router.rejects")),
           "dedup_hits": int(cval("router.dedup_hits")),
           "dispatch_errors": int(cval("router.dispatch_errors")),
           "deadline_exceeded": int(cval("router.deadline_exceeded")),
           "replica_deaths": int(cval("router.replica_deaths")),
           "replica_restarts": int(cval("router.replica_restarts")),
           "swaps": int(cval("router.swaps")),
           "swap_errors": int(cval("router.swap_errors"))}
    fallback = cval("router.swapping_fallback")
    if fallback:
        out["swapping_fallbacks"] = int(fallback)
    for timer, key in (("router.request_ms", "request_ms"),
                       ("router.dispatch_ms", "dispatch_ms")):
        t = timer_summary.get(timer)
        if t:
            out[key] = {"p50": t["p50"], "p99": t["p99"], "max": t["max"]}
    return out


def _ckpt_summary(counter_delta, counter_last, timer_summary):
    """Crash-consistent checkpoint accounting (paddle_tpu/checkpoint.py):
    commits, bytes, verification rejections + fallbacks, and save/restore
    latency percentiles."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    saves = cval("ckpt.saves")
    restores = cval("ckpt.restores")
    if not saves and not restores:
        return None
    out = {"saves": int(saves), "restores": int(restores),
           "bytes": int(cval("ckpt.bytes")),
           "verify_failures": int(cval("ckpt.verify_failures")),
           "fallbacks": int(cval("ckpt.fallbacks")),
           "quarantined": int(cval("ckpt.quarantined"))}
    if saves:
        out["bytes_per_save"] = int(out["bytes"] / saves)
    for timer, key in (("ckpt.save_ms", "save_ms"),
                       ("ckpt.restore_ms", "restore_ms")):
        t = timer_summary.get(timer)
        if t:
            out[key] = {"p50": t["p50"], "p99": t["p99"], "max": t["max"]}
    ps = cval("ps.checkpoints")
    if ps:
        out["ps_checkpoints"] = int(ps)
    return out


def _sharding_summary(counter_delta, counter_last, gauges):
    """Sharded-training accounting (parallel/axis_rules.py rule table +
    fleet ShardingOptimizer ZeRO): dp-collective payload per kind, the
    optimizer-state bytes the sharding keeps resident per device, rule
    resolutions, and reshard-on-load events."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    rs = cval("sharding.reduce_scatter_bytes")
    ag = cval("sharding.allgather_bytes")
    ar = cval("sharding.allreduce_bytes")
    params = cval("sharding.params_sharded")
    resolutions = cval("sharding.rule_resolutions")
    reshards = cval("sharding.resharding_events")
    stage = gauges.get("sharding.zero_stage")
    if not any((rs, ag, ar, params, resolutions, reshards)) \
            and stage is None:
        return None
    out = {"reduce_scatter_bytes": int(rs), "allgather_bytes": int(ag),
           "allreduce_bytes": int(ar), "params_sharded": int(params),
           "rule_resolutions": int(resolutions),
           "rules_skipped_indivisible":
               int(cval("sharding.rule_skipped_indivisible")),
           "resharding_events": int(reshards)}
    if stage is not None:
        out["zero_stage"] = int(stage)
    deg = gauges.get("sharding.degree")
    if deg is not None:
        out["degree"] = int(deg)
    state = gauges.get("sharding.optimizer_state_bytes")
    per_dev = gauges.get("sharding.optimizer_state_bytes_per_device")
    if state is not None:
        out["optimizer_state_bytes"] = int(state)
    if per_dev is not None:
        out["optimizer_state_bytes_per_device"] = int(per_dev)
        if state:
            out["state_shard_ratio"] = round(per_dev / state, 4)
    return out


def _memcost_summary(counter_delta, counter_last, gauges, cost_events,
                     oom_events):
    """Cost & memory observability accounting (core/costmodel.py): the
    HBM ledger gauges, per-compile capture health, dispatched flop
    volume and the live-MFU gauge — tools/mem_report.py renders the full
    per-program table and OOM forensics."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    captures = cval("cost.captures")
    unavailable = cval("costmodel.unavailable")
    if not captures and not unavailable and not cost_events \
            and not oom_events:
        return None
    out = {"captures": int(captures),
           "unavailable": int(unavailable),
           "programs": len({a.get("key") for a in cost_events}),
           "dispatch_flops": int(cval("cost.dispatch_flops")),
           "dispatch_bytes": int(cval("cost.dispatch_bytes")),
           "oom_events": int(cval("mem.oom_events") or oom_events)}
    for gname, key in (("mem.param_bytes", "param_bytes"),
                       ("mem.opt_state_bytes", "opt_state_bytes"),
                       ("mem.peak_temp_bytes", "peak_temp_bytes"),
                       ("mem.hbm_total_bytes", "hbm_total_bytes"),
                       ("cost.live_mfu", "live_mfu")):
        v = gauges.get(gname)
        if v is not None:
            out[key] = v
    verdicts = {}
    for a in cost_events:
        verdict = a.get("roofline")
        if verdict:
            verdicts[verdict] = verdicts.get(verdict, 0) + 1
    if verdicts:
        out["roofline"] = verdicts
    return out


def _verifier_summary(counter_delta, counter_last, timer_summary):
    """Static-verification accounting (core/verify.py): how many programs
    were checked, how many checks ran, what they found (violations /
    orphaned VarDescs pruned after passes), and what verification cost."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    programs = cval("verifier.programs")
    if not programs:
        return None
    out = {"programs": int(programs),
           "checks_run": int(cval("verifier.checks_run")),
           "violations": int(cval("verifier.violations")),
           "pruned_vars": int(cval("verifier.pruned_vars")),
           "shape_infer_skips": int(cval("verifier.shape_infer_skips"))}
    t = timer_summary.get("verifier.verify_ms")
    if t:
        out["verify_ms"] = {"p50": t["p50"], "p99": t["p99"],
                            "max": t["max"]}
        out["total_verify_ms"] = round(t["mean"] * t["count"], 1)
    return out


def _concurrency_summary(counter_delta, counter_last, timer_summary,
                         stall_events, thread_errors):
    """Lock-sanitizer accounting (core/analysis/lockdep.py,
    FLAGS_sanitize_locks): contention pressure, order violations, stall
    dumps, uncaught worker-thread exceptions and per-lock hold times.
    lock.acquires/contentions are quiet counters — their values ride the
    exit snapshot, so counter_last is the authoritative read."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    locks = {name: t for name, t in timer_summary.items()
             if name.startswith("lock.")}
    acquires = cval("lock.acquires")
    uncaught = cval("threads.uncaught_exceptions")
    if not (acquires or locks or stall_events or thread_errors
            or uncaught):
        return None
    out = {"acquires": int(acquires),
           "contentions": int(cval("lock.contentions")),
           "order_violations": int(cval("lock.order_violations")),
           "stalls": int(cval("lock.stalls")),
           "uncaught_thread_exceptions": int(uncaught)}
    by_lock = {}
    for name, t in sorted(locks.items()):
        # lock.<name>.held_ms / lock.<name>.wait_ms
        parts = name.split(".")
        if len(parts) < 3:
            continue
        lock_name = ".".join(parts[1:-1])
        metric = parts[-1]
        by_lock.setdefault(lock_name, {})[metric] = {
            "count": t["count"], "p50": t["p50"], "p99": t["p99"],
            "max": t["max"]}
    if by_lock:
        out["locks"] = by_lock
    if stall_events:
        out["stall_events"] = stall_events[:10]
    if thread_errors:
        out["thread_errors"] = thread_errors[:10]
    return out


def _incidents_summary(counter_delta, counter_last, gauges,
                       incident_events):
    """Flight recorder + SLO watchdog accounting (core/incidents.py):
    how many watchdog rules tripped, how many incident dumps landed vs
    were rate-limited, which rules are still firing (slo.<rule>_firing
    gauges), and the per-incident index — render the full postmortems
    with tools/incident_report.py."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    reported = cval("incidents.reported")
    rate_limited = cval("incidents.rate_limited")
    trips = cval("slo.trips")
    evaluations = cval("slo.evaluations")
    firing = {n[len("slo."):-len("_firing")]: v
              for n, v in gauges.items()
              if n.startswith("slo.") and n.endswith("_firing")}
    if not (reported or rate_limited or trips or evaluations
            or incident_events or firing):
        return None
    out = {"reported": int(reported),
           "rate_limited": int(rate_limited),
           "slo_trips": int(trips),
           "slo_evaluations": int(evaluations),
           "eval_errors": int(cval("slo.eval_errors")),
           "incidents": incident_events[:20]}
    if firing:
        out["rules_firing"] = {n: int(v or 0) for n, v in
                               sorted(firing.items())}
    if incident_events:
        out["last"] = incident_events[-1]
    return out


def _goodput_summary(counter_delta, counter_last, gauges):
    """Goodput ledger accounting (core/goodput.py): wall-clock
    attribution of the run into productive device compute vs the badput
    phases (goodput.productive_ms / goodput.wall_ms and the
    goodput.badput_<phase>_ms family — data_wait, host_dispatch,
    compile, checkpoint, collective, recovery, other — published via
    counter_set, so the LAST value wins), plus the live goodput.ratio
    gauge."""

    def cval(name):
        v = counter_last.get(name)
        if v is None:
            v = counter_delta.get(name)
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    wall = cval("goodput.wall_ms")
    productive = cval("goodput.productive_ms")
    ratio = gauges.get("goodput.ratio")
    badput_prefix = "goodput.badput_"   # truncated f-string emit name
    phases = {}
    for name in sorted(set(counter_delta) | set(counter_last)):
        if name.startswith(badput_prefix) and name.endswith("_ms"):
            phases[name[len(badput_prefix):-len("_ms")]] = cval(name)
    if not (wall or productive or phases or ratio is not None):
        return None
    out = {"wall_ms": round(wall, 3),
           "productive_ms": round(productive, 3),
           "badput_ms": round(sum(phases.values()), 3),
           "phases": {p: round(v, 3) for p, v in phases.items()}}
    if ratio is not None:
        out["ratio"] = ratio
    elif wall > 0:
        out["ratio"] = round(min(1.0, productive / wall), 4)
    return out


def _fleet_summary(counter_delta, counter_last, gauges):
    """Fleet observatory accounting (core/fleetobs.py): membership +
    scrape health (fleet.scrapes / fleet.scrape_failures /
    fleet.members_went_stale / fleet.members_registered /
    fleet.rule_eval_errors / fleet.scrape_pass_errors counters) and the
    last published fleet view (fleet.members, fleet.members_ok,
    fleet.members_stale, fleet.stragglers, fleet.qps,
    fleet.queue_depth, fleet.queue_frac, fleet.p99_ms gauges)."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    scrapes = cval("fleet.scrapes")
    failures = cval("fleet.scrape_failures")
    registered = cval("fleet.members_registered")
    went_stale = cval("fleet.members_went_stale")
    view = {k.split(".", 1)[1]: v for k, v in gauges.items()
            if k.startswith("fleet.") and isinstance(v, (int, float))}
    if not (scrapes or failures or registered or went_stale or view):
        return None
    return {
        "scrapes": int(scrapes),
        "scrape_failures": int(failures),
        "members_registered": int(registered),
        "members_went_stale": int(went_stale),
        "rule_eval_errors": int(cval("fleet.rule_eval_errors")),
        "scrape_pass_errors": int(cval("fleet.scrape_pass_errors")),
        "view": view,
    }


def _scaler_summary(counter_delta, counter_last, scale_events):
    """Elastic resize & autoscaling accounting (distributed/scaler.py
    policy engine + distributed/elastic.py runner + serving cluster
    scale_to): policy evaluations vs decisions (scaler.evaluations /
    scaler.decisions / scaler.scale_up / scaler.scale_down /
    scaler.suppressed_cooldown / scaler.clamped), executed transitions
    (elastic.scale_events, elastic.restarts,
    elastic.restart_budget_refunds, router.scale_events,
    router.scale_errors, incidents.scale_events), and the world-size-
    changing-resume machinery those transitions exercised
    (ps.barrier_regrown, ps.kv_rebalanced_rows, reader.cursor_resplits,
    sharding.zero_regroup_events) — plus the kind:"scale" event
    timeline the incident ring also captures."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    evaluations = cval("scaler.evaluations")
    decisions = cval("scaler.decisions")
    restarts = cval("elastic.restarts")
    transitions = cval("incidents.scale_events")
    regrown = cval("ps.barrier_regrown")
    if not (evaluations or decisions or restarts or transitions
            or regrown or scale_events):
        return None
    return {
        "evaluations": int(evaluations),
        "decisions": int(decisions),
        "scale_up": int(cval("scaler.scale_up")),
        "scale_down": int(cval("scaler.scale_down")),
        "suppressed_cooldown": int(cval("scaler.suppressed_cooldown")),
        "clamped": int(cval("scaler.clamped")),
        "restarts": int(restarts),
        "restart_budget_refunds":
            int(cval("elastic.restart_budget_refunds")),
        "elastic_scale_events": int(cval("elastic.scale_events")),
        "cluster_scale_events": int(cval("router.scale_events")),
        "cluster_scale_errors": int(cval("router.scale_errors")),
        "scale_incidents": int(transitions),
        "barrier_regrown": int(regrown),
        "kv_rebalanced_rows": int(cval("ps.kv_rebalanced_rows")),
        "reader_cursor_resplits": int(cval("reader.cursor_resplits")),
        "zero_regroup_events":
            int(cval("sharding.zero_regroup_events")),
        "events": scale_events[-20:],
    }


def _crash_survival_summary(counter_delta, counter_last):
    """Process-level fault tolerance accounting: the launch.py
    orchestrator's supervision plane (orch.spawns / orch.child_deaths /
    orch.respawns / orch.budget_exhausted / orch.restart_budget_refunds
    / orch.drains / orch.drain_kills / orch.scale_events), the training-
    side drain (elastic.drains / elastic.drain_timeouts), and the
    decode-session failover journal (session.journaled /
    session.evicted / session.resumed / session.resumed_tokens /
    session.journal_errors / session.failovers)."""

    def cval(name):
        v = counter_delta.get(name) or counter_last.get(name) or 0
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    spawns = cval("orch.spawns")
    deaths = cval("orch.child_deaths")
    journaled = cval("session.journaled")
    failovers = cval("session.failovers")
    drains = cval("elastic.drains") + cval("orch.drains")
    if not (spawns or deaths or journaled or failovers or drains):
        return None
    return {
        "spawns": int(spawns),
        "child_deaths": int(deaths),
        "respawns": int(cval("orch.respawns")),
        "budget_exhausted": int(cval("orch.budget_exhausted")),
        "budget_refunds": int(cval("orch.restart_budget_refunds")),
        "orch_drains": int(cval("orch.drains")),
        "drain_kills": int(cval("orch.drain_kills")),
        "orch_scale_events": int(cval("orch.scale_events")),
        "elastic_drains": int(cval("elastic.drains")),
        "elastic_drain_timeouts": int(cval("elastic.drain_timeouts")),
        "sessions_journaled": int(journaled),
        "sessions_evicted": int(cval("session.evicted")),
        "sessions_resumed": int(cval("session.resumed")),
        "resumed_tokens": int(cval("session.resumed_tokens")),
        "journal_errors": int(cval("session.journal_errors")),
        "failovers": int(failovers),
    }


def _fmt_num(v):
    if isinstance(v, float):
        return f"{v:,.3f}".rstrip("0").rstrip(".")
    if isinstance(v, int):
        return f"{v:,}"
    return str(v)


def render(s, out=sys.stdout):
    w = out.write
    w(f"== run log: {s['records']} records over {s['span_s']}s ==\n")
    if s.get("malformed_lines"):
        w(f"(skipped {s['malformed_lines']} malformed/torn line(s) — "
          f"crashed writer?)\n")

    if s["timers"]:
        w("\n-- step/latency timers (ms) --\n")
        w(f"{'timer':<28}{'count':>8}{'p50':>10}{'p90':>10}"
          f"{'p99':>10}{'max':>10}{'mean':>10}\n")
        for name, t in sorted(s["timers"].items()):
            w(f"{name[:27]:<28}{t['count']:>8}{t['p50']:>10}{t['p90']:>10}"
              f"{t['p99']:>10}{t['max']:>10}{t['mean']:>10}\n")

    w(f"\n-- compile events: {len(s['compiles'])} --\n")
    if s["compiles"]:
        t0 = s["compiles"][0].get("ts") or 0
        w(f"{'+s':>8}  {'ms':>10}  {'cache':>5}  cause\n")
        for c in s["compiles"]:
            off = (c.get("ts") or t0) - t0
            ms = c.get("ms")
            w(f"{off:>8.2f}  {ms if ms is not None else '?':>10}  "
              f"{c.get('cache_size') or '?':>5}  {c.get('cause')}\n")

    setups = [c["setup"] for c in s["compiles"] if c.get("setup")]
    if setups:
        w(f"\n-- set-up by program (s): {len(setups)} --\n")
        cols = SETUP_FIELDS[4:]
        w(f"{'program':<24}{'ops':>6}" + "".join(
            f"{c[:-2]:>12}" for c in cols) + "  cache\n")
        total = {c: sum(r.get(c) or 0.0 for r in setups) for c in cols}
        total.update(name="sum", ops=sum(r.get("ops") or 0 for r in setups))
        for r in setups + [total]:
            hit = {True: "hit", False: "miss"}.get(r.get("cache_hit"), "-")
            w(f"{str(r.get('name'))[:23]:<24}{r.get('ops') or 0:>6}"
              + "".join(f"{r.get(c) or 0.0:>12.3f}" for c in cols)
              + f"  {hit}\n")

    if s.get("fused"):
        f = s["fused"]
        w("\n-- fused dispatch (K-step pipelined execution) --\n")
        w(f"dispatches: {f['dispatches']}  fused steps: {f['fused_steps']}"
          f"  steps/dispatch: {f['steps_per_dispatch']}\n")
        if "dispatch_ms_p50" in f:
            w(f"p50 dispatch: {f['dispatch_ms_p50']} ms "
              f"({f['ms_per_fused_step_p50']} ms/fused step)\n")
        if "host_dispatch_ms_saved" in f:
            w(f"host-dispatch ms saved vs single-step: "
              f"~{_fmt_num(f['host_dispatch_ms_saved'])}\n")
        if "fallback_steps" in f:
            w(f"PS-IO fallback steps (ran unfused): {f['fallback_steps']}\n")

    if s.get("serving"):
        sv = s["serving"]
        w("\n-- serving (micro-batching engine) --\n")
        w(f"requests: {sv['requests']}  batches: {sv['batches']}")
        if "requests_per_batch" in sv:
            w(f"  req/batch: {sv['requests_per_batch']}"
              f"  rows/batch: {sv['rows_per_batch']}")
        w("\n")
        if "batch_fill" in sv:
            w(f"batch fill: {sv['batch_fill']:.1%} "
              f"(padding overhead {1 - sv['batch_fill']:.1%})\n")
        w(f"rejected: {sv['rejects']}  deadline-expired: "
          f"{sv['deadline_expired']}  handler errors: "
          f"{sv['handler_errors']}  warmup compiles: "
          f"{sv['warmup_compiles']}\n")
        for key, label in (("request_ms", "request latency"),
                           ("batch_ms", "batch dispatch")):
            if key in sv:
                t = sv[key]
                w(f"{label} ms: p50 {t['p50']}  p99 {t['p99']}"
                  f"  max {t['max']}\n")
        if "last_queue_depth" in sv:
            w(f"last queue depth: {_fmt_num(sv['last_queue_depth'])}\n")

    if s.get("decode"):
        dc = s["decode"]
        w("\n-- decode (continuous-batching generative engine) --\n")
        line = (f"requests: {dc['requests']}  prefills: {dc['prefills']} "
                f"({dc['prefill_tokens']} tokens)  steps: {dc['steps']}  "
                f"tokens: {dc['tokens']}")
        if "tokens_per_s" in dc:
            line += f"  ({dc['tokens_per_s']}/s over the log)"
        w(line + "\n")
        occ_line = []
        if "tokens_per_step" in dc:
            occ_line.append(f"tokens/step: {dc['tokens_per_step']}")
        if "batch_occupancy" in dc:
            occ_line.append(
                f"batch occupancy: {dc['batch_occupancy']['mean']:.1%} "
                f"mean (p50 {dc['batch_occupancy']['p50']:.1%})")
        if occ_line:
            w("  ".join(occ_line) + "\n")
        if "draft_accept_share" in dc:
            w(f"drafts accepted: {dc['draft_accept_share']}%  tokens a row "
              f"a step: {dc.get('tokens_per_row_step', '-')}  tokens thrown "
              f"away: {dc['tokens_discarded']}\n")
        if "state_rows" in dc or "conv_rows" in dc:
            w(f"per-slot state, rows x layers advanced: recurrent state "
              f"{dc.get('state_rows', 0)}  conv tail alone "
              f"{dc.get('conv_rows', 0)}\n")
        w(f"retired: {dc['retired']}  rejected: {dc['rejects']}  "
          f"kv refusals: {dc['kv_refusals']}  deadline-expired: "
          f"{dc['deadline_expired']}  errors: {dc['errors']}  "
          f"compiles: {dc['compiles']}\n")
        for key, label in (("prefill_ms", "prefill"),
                           ("step_ms", "decode step"),
                           ("request_ms", "request e2e")):
            if key in dc:
                t = dc[key]
                w(f"{label} ms: p50 {t['p50']}  p99 {t['p99']}"
                  f"  max {t['max']}\n")
        cost = [text.format(dc[key]) for key, text in (
            ("prefill_wait_share",
             "every slot waited for a prefill {}% of the loop's time"),
            ("engine_cpu_share", "the engine thread ran {}% of it"),
            ("prefill_padded_token_share",
             "{}% of the prefilled tokens were padding")) if key in dc]
        if cost:
            w("admissions: " + "; ".join(cost) + "\n")
        if "kv_pool_bytes" in dc:
            w(f"kv page pool: {_fmt_num(dc['kv_pool_bytes'])} B "
              f"(high water {_fmt_num(dc['kv_high_water_bytes'])} B, "
              f"in use {_fmt_num(dc['kv_used_bytes'])} B)\n")
        for klass, size in sorted(
                dc.get("kv_pool_bytes_by_class", {}).items()):
            w(f"  {klass} pages: {_fmt_num(size)} B\n")
        if "kv_pages_allocated" in dc:
            leak = dc["kv_pages_allocated"] - dc["kv_pages_freed"]
            w(f"kv pages: {dc['kv_pages_allocated']} allocated / "
              f"{dc['kv_pages_freed']} freed"
              + (f"  (LEAKED {leak})\n" if leak else "\n"))
        if "pallas_kernels" in dc:
            pk = dc["pallas_kernels"]
            w("pallas kernels (per lowering): " + ", ".join(
                f"{label} {pk.get(key + '_dispatches', 0)} dispatched / "
                f"{pk.get(key + '_fallbacks', 0)} stock-fallback"
                for label, key in (
                    ("int8 gemm", "int8_gemm"),
                    ("paged attn", "paged_attn"),
                    ("latent prefill attn", "mla_prefill"),
                    ("ssm state update", "ssm_state_update"),
                    ("gated delta state update", "gated_delta_state_update"),
                    ("gated delta chunk scan", "gated_delta_chunk_scan")))
              + "\n")
        if "prefix_store" in dc:
            ps = dc["prefix_store"]
            looks = ps.get("prefix_hits", 0) + ps.get("prefix_misses", 0)
            rate = ps.get("prefix_hits", 0) / looks if looks else 0.0
            w(f"prefix store: {ps.get('prefix_hits', 0)} hits / "
              f"{ps.get('prefix_misses', 0)} misses ({rate:.1%}), "
              f"{_fmt_num(ps.get('bytes_saved', 0))} B prefill skipped, "
              f"{ps.get('cow_forks', 0)} COW forks, "
              f"{ps.get('reclaims', 0)} reclaims"
              + (f", {ps['prefix_blocks']} blocks resident"
                 if "prefix_blocks" in ps else "")
              + (f"  (AUDIT FAILURES {ps['audit_failures']})"
                 if ps.get("audit_failures") else "") + "\n")
        if "disagg" in dc:
            dg = dc["disagg"]
            w(f"disagg prefill: {dg.get('ships', 0)} shipped "
              f"({_fmt_num(dg.get('ship_bytes', 0))} B), "
              f"{dg.get('installs', 0)} installed, "
              f"{dg.get('crc_rejects', 0)} CRC-rejected, "
              f"{dg.get('fallback_prefills', 0)} local fallbacks\n")

    if s.get("router"):
        rt = s["router"]
        w("\n-- router (cluster serving control plane) --\n")
        w(f"requests: {rt['requests']}  retries: {rt['retries']}  "
          f"failovers: {rt['failovers']}  rejects: {rt['rejects']}  "
          f"dedup hits: {rt['dedup_hits']}\n")
        w(f"dispatch errors: {rt['dispatch_errors']}  deadline exceeded: "
          f"{rt['deadline_exceeded']}\n")
        w(f"replica deaths: {rt['replica_deaths']}  respawns: "
          f"{rt['replica_restarts']}  model swaps: {rt['swaps']}  "
          f"swap errors: {rt['swap_errors']}\n")
        if "swapping_fallbacks" in rt:
            w(f"dispatches to a swapping replica (no READY peer): "
              f"{rt['swapping_fallbacks']}\n")
        for key, label in (("request_ms", "routed request"),
                           ("dispatch_ms", "replica dispatch")):
            if key in rt:
                t = rt[key]
                w(f"{label} ms: p50 {t['p50']}  p99 {t['p99']}"
                  f"  max {t['max']}\n")

    if s.get("checkpoint"):
        ck = s["checkpoint"]
        w("\n-- checkpointing (atomic commits + verification) --\n")
        w(f"saves: {ck['saves']}  restores: {ck['restores']}  bytes: "
          f"{_fmt_num(ck['bytes'])}")
        if "bytes_per_save" in ck:
            w(f"  ({_fmt_num(ck['bytes_per_save'])}/save)")
        w("\n")
        w(f"verify failures: {ck['verify_failures']}  fallbacks: "
          f"{ck['fallbacks']}  quarantined: {ck['quarantined']}\n")
        for key, label in (("save_ms", "save latency"),
                           ("restore_ms", "restore latency")):
            if key in ck:
                t = ck[key]
                w(f"{label} ms: p50 {t['p50']}  p99 {t['p99']}"
                  f"  max {t['max']}\n")
        if "ps_checkpoints" in ck:
            w(f"pserver snapshots: {ck['ps_checkpoints']}\n")

    if s.get("sharding"):
        sh = s["sharding"]
        w("\n-- sharding (rule-table partitioning + ZeRO) --\n")
        head = []
        if "zero_stage" in sh:
            head.append(f"zero stage: {sh['zero_stage']}")
        if "degree" in sh:
            head.append(f"degree: {sh['degree']}")
        head.append(f"params sharded: {sh['params_sharded']}")
        w("  ".join(head) + "\n")
        w(f"dp collectives: reduce-scatter {_fmt_num(sh['reduce_scatter_bytes'])} B"
          f"  allgather {_fmt_num(sh['allgather_bytes'])} B"
          f"  allreduce {_fmt_num(sh['allreduce_bytes'])} B\n")
        if "optimizer_state_bytes" in sh:
            line = (f"optimizer state: {_fmt_num(sh['optimizer_state_bytes'])} B"
                    f" global")
            if "optimizer_state_bytes_per_device" in sh:
                line += (f", {_fmt_num(sh['optimizer_state_bytes_per_device'])}"
                         f" B/device")
            if "state_shard_ratio" in sh:
                line += f" (ratio {sh['state_shard_ratio']})"
            w(line + "\n")
        w(f"rule resolutions: {sh['rule_resolutions']}  "
          f"indivisible skips: {sh['rules_skipped_indivisible']}  "
          f"reshard-on-load: {sh['resharding_events']}\n")

    if s.get("verifier"):
        vf = s["verifier"]
        w("\n-- verifier (static program checks) --\n")
        w(f"programs: {vf['programs']}  checks run: {vf['checks_run']}  "
          f"violations: {vf['violations']}  pruned vars: "
          f"{vf['pruned_vars']}\n")
        if vf.get("shape_infer_skips"):
            w(f"shape-inference skips (untraceable lowerings): "
              f"{vf['shape_infer_skips']}\n")
        if "verify_ms" in vf:
            t = vf["verify_ms"]
            w(f"verify ms: p50 {t['p50']}  p99 {t['p99']}  max {t['max']}"
              f"  (total ~{_fmt_num(vf['total_verify_ms'])})\n")

    if s.get("memcost"):
        mc = s["memcost"]
        w("\n-- memory & cost (XLA cost/memory capture) --\n")
        w(f"captures: {mc['captures']}  programs: {mc['programs']}  "
          f"unavailable probes: {mc['unavailable']}  "
          f"oom events: {mc['oom_events']}\n")
        if any(k in mc for k in ("param_bytes", "opt_state_bytes",
                                 "peak_temp_bytes", "hbm_total_bytes")):
            w(f"HBM ledger: params {_fmt_num(mc.get('param_bytes', 0))} B"
              f"  opt state {_fmt_num(mc.get('opt_state_bytes', 0))} B"
              f"  peak scratch {_fmt_num(mc.get('peak_temp_bytes', 0))} B"
              f"  total {_fmt_num(mc.get('hbm_total_bytes', 0))} B\n")
        if mc["dispatch_flops"]:
            w(f"dispatched: {_fmt_num(mc['dispatch_flops'])} FLOP, "
              f"{_fmt_num(mc['dispatch_bytes'])} B accessed\n")
        if "live_mfu" in mc:
            w(f"last live MFU: {mc['live_mfu']}\n")
        if "roofline" in mc:
            w(f"roofline verdicts: {mc['roofline']}  "
              f"(full table: tools/mem_report.py)\n")

    if s.get("concurrency"):
        cc = s["concurrency"]
        w("\n-- concurrency (lock sanitizer) --\n")
        w(f"acquires: {cc['acquires']}  contentions: "
          f"{cc['contentions']}  order violations: "
          f"{cc['order_violations']}  stalls: {cc['stalls']}  "
          f"uncaught thread exceptions: "
          f"{cc['uncaught_thread_exceptions']}\n")
        if cc.get("locks"):
            w(f"{'lock':<26}{'held p50':>10}{'held p99':>10}"
              f"{'held max':>10}{'wait p99':>10}{'holds':>8}\n")
            for name, m in cc["locks"].items():
                held = m.get("held_ms") or {}
                wait = m.get("wait_ms") or {}
                w(f"{name[:25]:<26}{held.get('p50', 0):>10}"
                  f"{held.get('p99', 0):>10}{held.get('max', 0):>10}"
                  f"{wait.get('p99', 0):>10}{held.get('count', 0):>8}\n")
        for ev in cc.get("stall_events", []):
            w(f"STALL: thread '{ev['thread']}' waited "
              f"{ev['waited_s']}s on '{ev['lock']}' "
              f"({ev['threads']} thread stacks in the run log)\n")
        for ev in cc.get("thread_errors", []):
            w(f"THREAD DIED: '{ev['thread']}' uncaught "
              f"{ev['exc']}\n")

    if s.get("incidents"):
        ic = s["incidents"]
        w("\n-- incidents & SLO (flight recorder + watchdog) --\n")
        w(f"incident dumps: {ic['reported']}  rate-limited: "
          f"{ic['rate_limited']}  slo rule trips: {ic['slo_trips']}  "
          f"evaluations: {ic['slo_evaluations']}")
        if ic.get("eval_errors"):
            w(f"  eval errors: {ic['eval_errors']}")
        w("\n")
        if ic.get("rules_firing"):
            still = [n for n, v in ic["rules_firing"].items() if v]
            w(f"rule firing states: "
              + "  ".join(f"{n}={'FIRING' if v else 'ok'}"
                          for n, v in ic["rules_firing"].items())
              + "\n")
            if still:
                w(f"STILL FIRING at end of log: {', '.join(still)}\n")
        for ev in ic.get("incidents", []):
            w(f"INCIDENT {ev.get('id') or '?'}: {ev['name']} "
              f"(source {ev['source']}"
              + (f", rule {ev['rule']}" if ev.get("rule") else "")
              + f", {ev['ring_records']} ring records — "
                f"tools/incident_report.py)\n")

    if s.get("goodput"):
        gp = s["goodput"]
        w("\n-- goodput (wall-clock attribution, core/goodput.py) --\n")
        line = (f"wall: {_fmt_num(gp['wall_ms'])} ms  productive: "
                f"{_fmt_num(gp['productive_ms'])} ms  badput: "
                f"{_fmt_num(gp['badput_ms'])} ms")
        if gp.get("ratio") is not None:
            line += f"  goodput ratio: {gp['ratio']:.1%}"
        w(line + "\n")
        if gp.get("phases"):
            wall = gp["wall_ms"] or 0.0
            for phase, ms in sorted(gp["phases"].items(),
                                    key=lambda kv: -kv[1]):
                frac = f" ({ms / wall:.1%} of wall)" if wall > 0 else ""
                w(f"  badput {phase:<14} {_fmt_num(ms):>12} ms{frac}\n")

    if s.get("fleet"):
        fl = s["fleet"]
        w("\n-- fleet (cross-process observatory, core/fleetobs.py) --\n")
        w(f"scrapes: {fl['scrapes']}  failures: {fl['scrape_failures']}  "
          f"registered: {fl['members_registered']}  went stale: "
          f"{fl['members_went_stale']}"
          + (f"  RULE EVAL ERRORS: {fl['rule_eval_errors']}"
             if fl.get("rule_eval_errors") else "")
          + (f"  SCRAPE PASS ERRORS: {fl['scrape_pass_errors']}"
             if fl.get("scrape_pass_errors") else "")
          + "\n")
        view = fl.get("view") or {}
        if view:
            w(f"members: {_fmt_num(view.get('members', 0))} "
              f"({_fmt_num(view.get('members_ok', 0))} ok / "
              f"{_fmt_num(view.get('members_stale', 0))} stale)  "
              f"stragglers: {_fmt_num(view.get('stragglers', 0))}\n")
            line = (f"fleet qps: {_fmt_num(view.get('qps', 0))}  "
                    f"queue depth: {_fmt_num(view.get('queue_depth', 0))} "
                    f"(saturation {view.get('queue_frac', 0.0):.1%})")
            if "p99_ms" in view:
                line += f"  merged p99: {_fmt_num(view['p99_ms'])} ms"
            w(line + "\n")

    if s.get("scaler"):
        sc = s["scaler"]
        w("\n-- elastic & autoscaling (distributed/scaler.py + "
          "elastic.py) --\n")
        w(f"policy evaluations: {sc['evaluations']}  decisions: "
          f"{sc['decisions']} (up {sc['scale_up']} / down "
          f"{sc['scale_down']})  cooldown-suppressed: "
          f"{sc['suppressed_cooldown']}  clamped: {sc['clamped']}\n")
        w(f"executed transitions: {sc['scale_incidents']} "
          f"(training {sc['elastic_scale_events']}, serving "
          f"{sc['cluster_scale_events']}"
          + (f", SCALE ERRORS {sc['cluster_scale_errors']}"
             if sc.get("cluster_scale_errors") else "")
          + f")  restarts: {sc['restarts']}"
          + (f" (budget refunds {sc['restart_budget_refunds']})"
             if sc.get("restart_budget_refunds") else "")
          + "\n")
        w(f"resume machinery: barrier regrown {sc['barrier_regrown']}  "
          f"kv rows rebalanced {_fmt_num(sc['kv_rebalanced_rows'])}  "
          f"reader cursor re-splits {sc['reader_cursor_resplits']}  "
          f"zero regroups {sc['zero_regroup_events']}\n")
        for ev in sc.get("events", []):
            w(f"  {ev.get('source') or '?'}.{ev.get('event') or '?'}: "
              f"world {ev.get('old_world')} -> {ev.get('new_world')}"
              + (f" ({ev['reason']})" if ev.get("reason") else "")
              + "\n")

    if s.get("crash_survival"):
        cs = s["crash_survival"]
        w("\n-- crash survival (launch.py orchestrator + session "
          "failover) --\n")
        w(f"child spawns: {cs['spawns']}  deaths: {cs['child_deaths']}  "
          f"respawns: {cs['respawns']}"
          + (f"  BUDGET EXHAUSTED: {cs['budget_exhausted']}"
             if cs.get("budget_exhausted") else "")
          + (f"  (budget refunds {cs['budget_refunds']})"
             if cs.get("budget_refunds") else "")
          + "\n")
        w(f"drains: orchestrator {cs['orch_drains']} (SIGKILL "
          f"escalations {cs['drain_kills']})  trainer "
          f"{cs['elastic_drains']} (writer-join timeouts "
          f"{cs['elastic_drain_timeouts']})  orchestrated resizes: "
          f"{cs['orch_scale_events']}\n")
        w(f"decode sessions: journaled {cs['sessions_journaled']}  "
          f"evicted {cs['sessions_evicted']}  failovers "
          f"{cs['failovers']}  resumed {cs['sessions_resumed']} "
          f"({_fmt_num(cs['resumed_tokens'])} tokens re-admitted)"
          + (f"  JOURNAL ERRORS {cs['journal_errors']}"
             if cs.get("journal_errors") else "")
          + "\n")

    if s.get("tracing"):
        tr = s["tracing"]
        w("\n-- tracing (distributed spans) --\n")
        w(f"spans: {tr['spans']}  traces: {tr['traces']}  "
          f"(merge multi-process logs with tools/trace_view.py)\n")
        w(f"{'span':<34}{'count':>8}{'p50 ms':>10}{'p99 ms':>10}"
          f"{'max ms':>10}\n")
        for name, row in tr["by_name"].items():
            w(f"{name[:33]:<34}{row['count']:>8}{row['p50_ms']:>10}"
              f"{row['p99_ms']:>10}{row['max_ms']:>10}\n")

    if s["counters"]:
        w("\n-- counters (delta over log / final) --\n")
        for name, c in s["counters"].items():
            w(f"{name[:40]:<42}{_fmt_num(c['delta']):>16}"
              f"{_fmt_num(c['last']) if c['last'] is not None else '?':>18}\n")

    if s["gauges"]:
        w("\n-- gauges --\n")
        for name, v in sorted(s["gauges"].items()):
            w(f"{name[:40]:<42}{_fmt_num(v):>16}\n")

    if s["metrics"]:
        w("\n-- bench metrics --\n")
        for m in s["metrics"]:
            extras = {k: v for k, v in m.items()
                      if k not in ("name", "value")}
            w(f"{m['name']}: {_fmt_num(m['value'])} {extras}\n")

    if s["steps"]:
        last = s["steps"][-1]
        w(f"\n-- train/eval steps: {len(s['steps'])} events "
          f"(last: {last.get('name')} value={last.get('value')}) --\n")

    if s["profiler"]:
        w("\n-- profiler host spans (profiler.summarize) --\n")
        w(f"{'event':<40}{'calls':>8}{'total_us':>14}{'avg_us':>12}"
          f"{'max_us':>12}\n")
        rows = sorted(s["profiler"],
                      key=lambda r: -(r.get("total_us") or 0))
        for r in rows:
            w(f"{r['name'][:39]:<40}{r.get('calls', '?'):>8}"
              f"{(r.get('total_us') or 0):>14.1f}"
              f"{(r.get('avg_us') or 0):>12.1f}"
              f"{(r.get('max_us') or 0):>12.1f}\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarize a paddle_tpu JSONL telemetry run log")
    ap.add_argument("log", help="path to the JSONL run log")
    ap.add_argument("--json", action="store_true",
                    help="print the computed summary as JSON")
    args = ap.parse_args(argv)
    recs, malformed = load_counted(args.log)
    summary = summarize_log(recs, malformed=malformed)
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        render(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
