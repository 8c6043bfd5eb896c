#!/usr/bin/env python
"""slo_check — CI gate: compare a BENCH row against prior rows/baseline.

The offline leg of the SLO plane (paddle_tpu/core/incidents.py watches
the LIVE metrics; this tool watches the BENCH history): given one bench
result row — a ``bench.py`` / ``tools/bench_serving.py`` JSON line or a
committed ``BENCH_r*.json`` wrapper — it compares the row's metrics
against the best prior row of the same metric name (and BASELINE.json
when it publishes numbers) with per-metric thresholds:

* ``value``          — the headline throughput/latency figure; higher is
  better unless the unit spells ms ("ms", "ms/step", ...);
* ``extra.mfu``      — higher is better;
* ``extra.ms_per_step`` / ``extra.p99_ms`` / ``extra.ttft_ms`` /
  ``extra.itl_p99_ms`` — lower is better;
* ``extra.goodput.ratio`` — higher is better (the core/goodput.py
  productive-wall-clock fraction finalize_bench_result embeds).

A metric regresses when it is worse than the reference by more than its
tolerance (default 5% for throughput/MFU, 15% for tail latency).

``bench.py`` and ``bench_serving`` embed the verdict of every fresh row
into ``extra.slo`` via :func:`embed_verdict` (finalize_bench_result), so
committed BENCH rows are self-judging.

Usage:
    python tools/slo_check.py row.json                       # vs rows in the repo root (none committed: no_baseline)
    python tools/slo_check.py row.json --prior 'BENCH_r*.json'
    python tools/slo_check.py row.json --tol-throughput 0.1 --json

Exit status: 0 = pass (including "no comparable prior rows"), 1 = SLO
regression, 2 = unreadable/invalid input.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (key, where, direction, default tolerance) — where "" means the row
# top level, "extra" means row["extra"]
_METRICS = (
    ("value", "", None, 0.05),          # direction resolved from unit
    ("mfu", "extra", "higher", 0.05),
    ("ms_per_step", "extra", "lower", 0.10),
    ("p99_ms", "extra", "lower", 0.15),
    ("ttft_ms", "extra", "lower", 0.15),
    ("itl_p99_ms", "extra", "lower", 0.15),
    # goodput ratio (core/goodput.py, embedded as extra.goodput.ratio):
    # a run whose productive fraction collapsed is a regression even
    # when headline throughput survived (e.g. shorter timed windows
    # hiding data stalls) — dotted keys traverse nested extra dicts
    ("goodput.ratio", "extra", "higher", 0.10),
)


def load_row(path_or_doc):
    """One bench row from a raw result line or a BENCH_r*.json wrapper
    ({"parsed": {...}}). Raises ValueError when there is no row."""
    if isinstance(path_or_doc, dict):
        doc = path_or_doc
    else:
        with open(path_or_doc) as f:
            doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "metric" not in doc \
            or not isinstance(doc.get("value"), (int, float)):
        raise ValueError(f"not a bench row: {path_or_doc!r}")
    return doc


def load_prior_rows(patterns, skip_paths=()):
    """All readable rows matching the glob patterns (unreadable files
    and non-row wrappers are skipped — history may hold failed runs)."""
    rows = []
    skip = {os.path.abspath(p) for p in skip_paths}
    for pat in patterns:
        for path in sorted(_glob.glob(pat)):
            if os.path.abspath(path) in skip:
                continue
            try:
                rows.append(load_row(path))
            except (OSError, ValueError, json.JSONDecodeError):
                continue
    return rows


def _value_direction(row):
    unit = str(row.get("unit") or "").lower()
    return "lower" if "ms" in unit else "higher"


def _get(row, key, where):
    src = row.get("extra") or {} if where == "extra" else row
    # dotted keys traverse nested dicts ("goodput.ratio" ->
    # extra["goodput"]["ratio"])
    for part in key.split("."):
        if not isinstance(src, dict):
            return None
        src = src.get(part)
    return float(src) if isinstance(src, (int, float)) else None


def slo_verdict(row, prior_rows, tolerances=None):
    """Judge one row against the best prior rows of the SAME metric
    name. Returns
    {"verdict": "pass"|"regress"|"no_baseline", "checks": [...]}: a
    check regresses when the row is worse than the best prior value by
    more than its tolerance."""
    tolerances = tolerances or {}
    peers = [r for r in prior_rows
             if r.get("metric") == row.get("metric")]
    if not peers:
        return {"verdict": "no_baseline", "checks": [],
                "peers": 0}
    checks = []
    for key, where, direction, tol in _METRICS:
        tol = float(tolerances.get(key, tol))
        v = _get(row, key, where)
        if v is None:
            continue
        refs = [x for x in (_get(r, key, where) for r in peers)
                if x is not None]
        if not refs:
            continue
        if direction is None:
            direction = _value_direction(row)
        ref = max(refs) if direction == "higher" else min(refs)
        if direction == "higher":
            ok = v >= ref * (1.0 - tol)
        else:
            ok = v <= ref * (1.0 + tol)
        checks.append({"metric": key, "value": v, "reference": ref,
                       "direction": direction, "tolerance": tol,
                       "ok": bool(ok)})
    if not checks:
        return {"verdict": "no_baseline", "checks": [], "peers": len(peers)}
    verdict = "pass" if all(c["ok"] for c in checks) else "regress"
    return {"verdict": verdict, "checks": checks, "peers": len(peers)}


def embed_verdict(row, bench_dir=None):
    """The verdict finalize_bench_result embeds as ``extra.slo``:
    judged against the BENCH_r*.json / MULTICHIP_r*.json rows in
    ``bench_dir`` (PT_BENCH_DIR, else the repo root). A directory that
    holds no history gives ``no_baseline``."""
    root = bench_dir or os.environ.get("PT_BENCH_DIR") or REPO_ROOT
    prior = load_prior_rows([os.path.join(root, "BENCH_r*.json"),
                             os.path.join(root, "MULTICHIP_r*.json")])
    v = slo_verdict(row, prior)
    return {"verdict": v["verdict"], "peers": v["peers"],
            "failed": [c["metric"] for c in v["checks"] if not c["ok"]]}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="compare a BENCH row against prior rows with "
                    "per-metric SLO thresholds (exit 0 pass / 1 regress "
                    "/ 2 error)")
    ap.add_argument("row", help="bench row json (raw result line or "
                                "BENCH_r*.json wrapper)")
    ap.add_argument("--prior", action="append", default=[],
                    help="glob(s) of prior rows to judge against "
                         "(default: BENCH_r*.json + MULTICHIP_r*.json "
                         "in the repo root)")
    ap.add_argument("--tol-throughput", type=float, default=0.05,
                    help="relative tolerance on value/mfu (default 0.05)")
    ap.add_argument("--tol-latency", type=float, default=0.15,
                    help="relative tolerance on ms metrics "
                         "(default 0.15)")
    ap.add_argument("--tol-goodput", type=float, default=0.10,
                    help="relative tolerance on extra.goodput.ratio "
                         "(default 0.10)")
    ap.add_argument("--json", action="store_true",
                    help="print the verdict as JSON")
    args = ap.parse_args(argv)

    try:
        row = load_row(args.row)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"slo_check: cannot read row: {e}", file=sys.stderr)
        return 2
    patterns = args.prior or [os.path.join(REPO_ROOT, "BENCH_r*.json"),
                              os.path.join(REPO_ROOT, "MULTICHIP_r*.json")]
    prior = load_prior_rows(patterns, skip_paths=[args.row])
    tols = {"value": args.tol_throughput, "mfu": args.tol_throughput,
            "ms_per_step": args.tol_latency, "p99_ms": args.tol_latency,
            "ttft_ms": args.tol_latency, "itl_p99_ms": args.tol_latency,
            "goodput.ratio": args.tol_goodput}
    v = slo_verdict(row, prior, tolerances=tols)
    if args.json:
        print(json.dumps(dict(v, metric=row.get("metric")), indent=2))
    else:
        print(f"slo_check: {row.get('metric')} vs {v['peers']} prior "
              f"row(s): {v['verdict'].upper()}")
        for c in v["checks"]:
            mark = "ok  " if c["ok"] else "FAIL"
            print(f"  [{mark}] {c['metric']:<14} {c['value']:>14.4f}  vs "
                  f"{c['reference']:>14.4f} ({c['direction']}, "
                  f"tol {c['tolerance']:.0%})")
    return 1 if v["verdict"] == "regress" else 0


if __name__ == "__main__":
    sys.exit(main())
