#!/usr/bin/env python
"""mem_report — render the HBM ledger + per-program cost table from a
paddle_tpu JSONL telemetry run log.

The memory/cost twin of tools/perf_report.py, reading the records the
cost & memory observability plane (paddle_tpu/core/costmodel.py) writes:

* the **HBM ledger**: persistable param bytes, optimizer-state bytes
  (the ZeRO per-device figure from ``sharding.optimizer_state_bytes*``
  when present), worst-case compiled-program scratch
  (``mem.peak_temp_bytes``), per-serving-bucket footprints
  (``mem.serving.bucket<B>_peak_bytes``), the decode engine's
  preallocated KV page pool (``mem.serving.kv_pool_bytes`` /
  ``mem.serving.kv_used_bytes`` / ``mem.serving.kv_high_water_bytes``)
  and the composed total;
* the **per-program cost table**: one row per captured compile-cache
  entry (``kind:"cost"`` records) — flops, bytes accessed, argument/
  output/temp bytes, arithmetic intensity and the roofline verdict
  (compute- vs memory-bound);
* **OOM forensics**: every ``kind:"oom"`` record — where it happened,
  the offending program, the ledger at the time of death and the top
  cached programs by peak bytes;
* **capture health**: captures vs ``costmodel.unavailable`` probes (a
  backend without the analysis APIs degrades by counting), dispatch
  flop volume and the last live-MFU gauge.

Stdlib-only on purpose (like perf_report): a run log from a TPU worker
renders on any machine, no jax/framework import.

Usage:
    python tools/mem_report.py run.jsonl             # tables
    python tools/mem_report.py run.jsonl --json      # machine-readable
    python tools/mem_report.py --smoke               # self-check: render
        a synthetic log and exit nonzero if any section goes missing
"""

from __future__ import annotations

import argparse
import json
import sys

try:
    from tools.perf_report import load_counted
except ImportError:       # run as `python tools/mem_report.py`
    from perf_report import load_counted


def _num(v, default=0):
    try:
        return float(v)
    except (TypeError, ValueError):
        return default


def summarize_mem(recs, malformed=0):
    """Fold a record list into the mem_report summary dict."""
    gauges = {}
    counters = {}
    programs = {}          # key -> latest cost record attrs
    ooms = []
    for r in recs:
        kind, name = r.get("kind"), r.get("name")
        v, attrs = r.get("value"), r.get("attrs") or {}
        if kind == "gauge":
            gauges[name] = v
        elif kind == "counter":
            counters[name] = v
        elif kind == "cost":
            key = attrs.get("key") or name
            programs[key] = dict(attrs, ts=r.get("ts"))
        elif kind == "oom":
            ooms.append(dict(attrs, ts=r.get("ts")))
        elif kind == "snapshot":
            for n, cv in (attrs.get("counters") or {}).items():
                counters.setdefault(n, cv)
            for n, gv in (attrs.get("gauges") or {}).items():
                gauges.setdefault(n, gv)

    # -- ledger (composed exactly like costmodel.ledger) ---------------------
    param_b = int(_num(gauges.get("mem.param_bytes")))
    opt_per_dev = gauges.get("sharding.optimizer_state_bytes_per_device")
    opt_b = int(_num(opt_per_dev if opt_per_dev is not None
                     else gauges.get("mem.opt_state_bytes")))
    peak_temp = int(_num(gauges.get("mem.peak_temp_bytes")))
    buckets = {n[len("mem.serving.bucket"):-len("_peak_bytes")]:
               int(_num(v)) for n, v in gauges.items()
               if n.startswith("mem.serving.bucket")
               and n.endswith("_peak_bytes")}
    kv_pool = int(_num(gauges.get("mem.serving.kv_pool_bytes")))
    state_pool = int(_num(gauges.get("mem.serving.state_pool_bytes")))
    ledger = {"param_bytes": param_b, "opt_state_bytes": opt_b,
              "peak_temp_bytes": peak_temp,
              "total_bytes": int(_num(gauges.get("mem.hbm_total_bytes"),
                                      param_b + opt_b + peak_temp
                                      + kv_pool + state_pool))}
    if gauges.get("sharding.optimizer_state_bytes") is not None:
        ledger["opt_state_bytes_global"] = int(
            _num(gauges["sharding.optimizer_state_bytes"]))
    if buckets:
        ledger["serving_bucket_bytes"] = buckets
    if kv_pool:
        # the decode engine's paged KV cache (serving/kv_cache.py)
        ledger["serving_kv_pool_bytes"] = kv_pool
        ledger["serving_kv_used_bytes"] = int(
            _num(gauges.get("mem.serving.kv_used_bytes")))
        ledger["serving_kv_high_water_bytes"] = int(
            _num(gauges.get("mem.serving.kv_high_water_bytes")))
    if state_pool:
        # a state-space model's per-slot recurrent states and conv tails
        ledger["serving_state_pool_bytes"] = state_pool
        ledger["serving_state_used_bytes"] = int(
            _num(gauges.get("mem.serving.state_pool_bytes.used")))
    kv_saved = int(_num(gauges.get("mem.serving.kv_prefix_saved_bytes")))
    if kv_saved:
        # prefill bytes the content-addressed prefix store skipped
        # (serving/prefix_store.py) — a savings figure, not residency,
        # so it never joins total_bytes
        ledger["serving_kv_prefix_saved_bytes"] = kv_saved

    rows = sorted(programs.values(),
                  key=lambda a: -_num(a.get("peak_bytes"),
                                      _num(a.get("flops"))))
    capture = {
        "captures": int(_num(counters.get("cost.captures"))),
        "unavailable": int(_num(counters.get("costmodel.unavailable"))),
        "dispatch_flops": int(_num(counters.get("cost.dispatch_flops"))),
        "dispatch_bytes": int(_num(counters.get("cost.dispatch_bytes"))),
        "oom_events": int(_num(counters.get("mem.oom_events"))),
    }
    if gauges.get("cost.live_mfu") is not None:
        capture["last_live_mfu"] = _num(gauges["cost.live_mfu"])
    # which kernel variant the serving programs lowered to — pairs with
    # the per-program roofline verdicts above (the pallas fingerprint is
    # part of each program's capture key)
    pallas = {name.split(".", 1)[1]: int(_num(counters.get(name)))
              for name in ("pallas.int8_gemm_dispatches",
                           "pallas.int8_gemm_fallbacks",
                           "pallas.paged_attn_dispatches",
                           "pallas.paged_attn_fallbacks")
              if counters.get(name) is not None}
    if pallas:
        capture["pallas_kernels"] = pallas
    return {"ledger": ledger, "programs": rows, "ooms": ooms,
            "capture": capture, "malformed_lines": int(malformed),
            "records": len(recs)}


def _fmt_bytes(n):
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n):,} B"
        n /= 1024
    return f"{n:,.1f} TiB"


def _fmt_flops(n):
    n = float(n)
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(n) < 1000 or unit == "P":
            return f"{n:,.2f} {unit}FLOP".replace(" F", " F")
        n /= 1000
    return f"{n:,.2f} PFLOP"


def render(s, out=sys.stdout):
    w = out.write
    w(f"== mem report: {s['records']} records ==\n")
    if s.get("malformed_lines"):
        w(f"(skipped {s['malformed_lines']} malformed/torn line(s))\n")

    led = s["ledger"]
    w("\n-- HBM ledger --\n")
    w(f"{'params':<26}{_fmt_bytes(led['param_bytes']):>16}\n")
    line = f"{'optimizer state':<26}{_fmt_bytes(led['opt_state_bytes']):>16}"
    if "opt_state_bytes_global" in led:
        line += (f"   (global "
                 f"{_fmt_bytes(led['opt_state_bytes_global'])}, ZeRO "
                 f"per-device shown)")
    w(line + "\n")
    w(f"{'peak program scratch':<26}{_fmt_bytes(led['peak_temp_bytes']):>16}\n")
    w(f"{'ledger total':<26}{_fmt_bytes(led['total_bytes']):>16}\n")
    if led.get("serving_bucket_bytes"):
        w("serving bucket footprints:\n")
        for b, nb in sorted(led["serving_bucket_bytes"].items(),
                            key=lambda kv: int(kv[0])):
            w(f"  bucket {b:>6}: {_fmt_bytes(nb)}\n")
    if led.get("serving_kv_pool_bytes"):
        w(f"{'KV page pool (decode)':<26}"
          f"{_fmt_bytes(led['serving_kv_pool_bytes']):>16}"
          f"   (in use {_fmt_bytes(led['serving_kv_used_bytes'])}, "
          f"high water "
          f"{_fmt_bytes(led['serving_kv_high_water_bytes'])})\n")
    if led.get("serving_state_pool_bytes"):
        w(f"{'state pool (decode)':<26}"
          f"{_fmt_bytes(led['serving_state_pool_bytes']):>16}"
          f"   (seated {_fmt_bytes(led['serving_state_used_bytes'])})\n")
    if led.get("serving_kv_prefix_saved_bytes"):
        w(f"{'prefix cache savings':<26}"
          f"{_fmt_bytes(led['serving_kv_prefix_saved_bytes']):>16}"
          f"   (prefill skipped, not resident)\n")

    w(f"\n-- per-program cost table: {len(s['programs'])} captured --\n")
    if s["programs"]:
        w(f"{'kind':<10}{'key':<10}{'program':<16}{'flops':>14}"
          f"{'bytes':>12}{'temp':>12}{'AI':>8}  verdict\n")
        for a in s["programs"]:
            w(f"{str(a.get('kind'))[:9]:<10}"
              f"{str(a.get('key'))[:9]:<10}"
              f"{str(a.get('program'))[:15]:<16}"
              f"{_fmt_flops(_num(a.get('flops'))):>14}"
              f"{_fmt_bytes(_num(a.get('bytes_accessed'))):>12}"
              f"{_fmt_bytes(_num(a.get('temp_bytes'))):>12}"
              f"{_num(a.get('intensity')):>8.1f}  "
              f"{a.get('roofline')} [{a.get('source')}"
              f"{', k=%s' % a['steps_per_dispatch'] if _num(a.get('steps_per_dispatch'), 1) > 1 else ''}]\n")

    if s["ooms"]:
        w(f"\n-- OOM forensics: {len(s['ooms'])} event(s) --\n")
        for o in s["ooms"]:
            w(f"where: {o.get('where')}  program: {o.get('program')}\n")
            w(f"error: {str(o.get('error'))[:160]}\n")
            ol = o.get("ledger") or {}
            w(f"ledger at death: total {_fmt_bytes(_num(ol.get('total_bytes')))}"
              f"  params {_fmt_bytes(_num(ol.get('param_bytes')))}"
              f"  opt {_fmt_bytes(_num(ol.get('opt_state_bytes')))}"
              f"  scratch {_fmt_bytes(_num(ol.get('peak_temp_bytes')))}\n")
            top = o.get("top_programs") or []
            if top:
                w("top cached programs by peak bytes:\n")
                for t in top:
                    w(f"  {t.get('kind')}/{t.get('key')} "
                      f"{t.get('program')}: peak "
                      f"{_fmt_bytes(_num(t.get('peak_bytes')))} "
                      f"(temp {_fmt_bytes(_num(t.get('temp_bytes')))})\n")

    c = s["capture"]
    w("\n-- capture health --\n")
    w(f"captures: {c['captures']}  unavailable probes: {c['unavailable']}"
      f"  oom events: {c['oom_events']}\n")
    w(f"dispatched: {_fmt_flops(c['dispatch_flops'])}, "
      f"{_fmt_bytes(c['dispatch_bytes'])} accessed\n")
    if "last_live_mfu" in c:
        w(f"last live MFU gauge: {c['last_live_mfu']:.3g}\n")
    if "pallas_kernels" in c:
        pk = c["pallas_kernels"]
        w("pallas serving kernels: int8 gemm "
          f"{pk.get('int8_gemm_dispatches', 0)}/"
          f"{pk.get('int8_gemm_fallbacks', 0)} "
          "dispatched/stock, paged attn "
          f"{pk.get('paged_attn_dispatches', 0)}/"
          f"{pk.get('paged_attn_fallbacks', 0)} dispatched/stock\n")


REQUIRED_SECTIONS = ("-- HBM ledger --", "-- per-program cost table",
                     "-- capture health --")


def smoke() -> int:
    """Self-check: summarize + render a synthetic run log in memory and
    fail (exit 2) if any required section is missing — the tools-smoke
    guard that the renderer and the emitted schema stay in sync."""
    recs = [
        {"ts": 1.0, "kind": "gauge", "name": "mem.param_bytes",
         "value": 1 << 20, "attrs": {}},
        {"ts": 1.0, "kind": "gauge", "name": "mem.opt_state_bytes",
         "value": 2 << 20, "attrs": {}},
        {"ts": 1.1, "kind": "gauge", "name": "mem.peak_temp_bytes",
         "value": 3 << 20, "attrs": {}},
        {"ts": 1.1, "kind": "gauge", "name": "mem.hbm_total_bytes",
         "value": 6 << 20, "attrs": {}},
        {"ts": 1.2, "kind": "gauge",
         "name": "mem.serving.bucket8_peak_bytes", "value": 4096,
         "attrs": {}},
        {"ts": 1.2, "kind": "gauge", "name": "mem.serving.kv_pool_bytes",
         "value": 1 << 20, "attrs": {}},
        {"ts": 1.2, "kind": "gauge", "name": "mem.serving.kv_used_bytes",
         "value": 1 << 18, "attrs": {}},
        {"ts": 1.2, "kind": "gauge",
         "name": "mem.serving.kv_high_water_bytes", "value": 1 << 19,
         "attrs": {}},
        {"ts": 1.2, "kind": "gauge",
         "name": "mem.serving.kv_prefix_saved_bytes", "value": 1 << 19,
         "attrs": {}},
        {"ts": 1.2, "kind": "cost", "name": "costmodel.executor",
         "value": 2.0e9, "attrs": {
             "key": "deadbeef", "kind": "executor", "program": "1v0",
             "steps_per_dispatch": 1, "flops": 2.0e9,
             "bytes_accessed": 1.0e8, "temp_bytes": 3 << 20,
             "arg_bytes": 1 << 20, "out_bytes": 4096, "peak_bytes": 4 << 20,
             "source": "compiled", "intensity": 20.0,
             "roofline": "memory_bound"}},
        {"ts": 1.3, "kind": "counter", "name": "cost.captures",
         "value": 1, "attrs": {"delta": 1}},
        {"ts": 1.3, "kind": "counter", "name": "cost.dispatch_flops",
         "value": int(2.0e9), "attrs": {"delta": int(2.0e9)}},
        {"ts": 1.3, "kind": "counter", "name": "cost.dispatch_bytes",
         "value": int(1.0e8), "attrs": {"delta": int(1.0e8)}},
        {"ts": 1.3, "kind": "counter", "name": "costmodel.unavailable",
         "value": 1, "attrs": {"delta": 1, "stage": "memory_analysis"}},
        {"ts": 1.4, "kind": "gauge", "name": "cost.live_mfu",
         "value": 0.123, "attrs": {}},
        {"ts": 1.5, "kind": "counter", "name": "mem.oom_events",
         "value": 1, "attrs": {"delta": 1}},
        {"ts": 1.5, "kind": "oom", "name": "costmodel.oom", "value": None,
         "attrs": {"where": "executor.dispatch", "program": "1v0",
                   "error": "RESOURCE_EXHAUSTED: out of memory",
                   "ledger": {"param_bytes": 1 << 20,
                              "opt_state_bytes": 2 << 20,
                              "peak_temp_bytes": 3 << 20,
                              "total_bytes": 6 << 20},
                   "top_programs": [{"key": "deadbeef", "kind": "executor",
                                     "program": "1v0",
                                     "peak_bytes": 4 << 20,
                                     "temp_bytes": 3 << 20}]}},
    ]
    import io

    s = summarize_mem(recs)
    buf = io.StringIO()
    render(s, out=buf)
    text = buf.getvalue()
    missing = [sec for sec in REQUIRED_SECTIONS + ("-- OOM forensics",)
               if sec not in text]
    checks = [("param bytes", s["ledger"]["param_bytes"] == 1 << 20),
              ("kv pool", s["ledger"].get("serving_kv_pool_bytes")
               == 1 << 20),
              ("kv pool rendered", "KV page pool" in text),
              ("prefix savings", s["ledger"].get(
                  "serving_kv_prefix_saved_bytes") == 1 << 19),
              ("prefix savings rendered", "prefix cache savings" in text),
              ("program rows", len(s["programs"]) == 1),
              ("oom rows", len(s["ooms"]) == 1),
              ("captures", s["capture"]["captures"] == 1),
              ("unavailable", s["capture"]["unavailable"] == 1)]
    bad = [name for name, ok in checks if not ok]
    if missing or bad:
        print(f"mem_report --smoke FAILED: missing sections {missing}, "
              f"bad checks {bad}", file=sys.stderr)
        return 2
    print("mem_report --smoke ok")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Render the HBM ledger + per-program cost table "
                    "from a paddle_tpu JSONL run log")
    ap.add_argument("log", nargs="?", help="path to the JSONL run log")
    ap.add_argument("--json", action="store_true",
                    help="print the computed summary as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="self-check against a synthetic log (exit 2 on "
                         "missing sections)")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    if not args.log:
        ap.error("log path required (or --smoke)")
    recs, malformed = load_counted(args.log)
    summary = summarize_mem(recs, malformed=malformed)
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        render(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
