"""Benchmark: ERNIE-large pretraining step throughput on the local chip.

The BASELINE north-star workload (ERNIE-large pretraining, seq 512,
data-parallel recipe measured per chip). Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline"}. vs_baseline = achieved MFU /
0.35 (the BASELINE.json target; the reference publishes no absolute
numbers — BASELINE.md).

Methodology (see tools/bench_models.py): warmup compile steps, then
timed windows of fetch-free steps closed by a single loss fetch. Best of
3 windows; the training state advances on-device between steps via buffer
donation, so every step does real optimizer work. Runs on a TPU: the MFU
figure needs the device's peak, and core/costmodel.py raises for a device
kind it has no peak for (the CPU included).

Pipelined mode: FLAGS_exec_steps_per_dispatch=k fuses k steps into one
lax.scan dispatch (Executor.run_steps); the BENCH row records the
configuration in extra.steps_per_dispatch and the dispatch-amortization
counters (telemetry_fused_dispatches / telemetry_fused_steps) merged by
finalize_bench_result.

Cost & memory: every row embeds extra.model_flops (the analytic
per-step flop count the MFU figure is derived from) and extra.live_mfu
(the runtime MFU gauge from core/costmodel.py — windowed captured-flop
rate / peak device flops), so BENCH rows are self-attributing; with
FLAGS_cost_capture=full the row also carries the composed HBM ledger
total (extra.mem_hbm_total_bytes).

Goodput: every row embeds ``extra.goodput`` — the core/goodput.py
wall-clock attribution (goodput ratio + per-phase badput ms: data
wait, host dispatch, compile, checkpoint, collective, recovery), so a
throughput regression in the row is attributable to the phase that ate
the wall time; tools/slo_check.py gates on the ratio vs history.

SLO gate: every row embeds ``extra.slo`` — the tools/slo_check.py
verdict of this run against the BENCH_r*.json rows beside it
(pass / regress / no_baseline + the failed metric list; the tree commits
no history, so a fresh checkout reads no_baseline), so a
throughput or MFU regression is visible in the row itself and
``python tools/slo_check.py <row>`` is the CI-able exit-code twin.

Sharded mode: when a mesh is active the row also records
extra.mesh_shape, extra.axis_rules_hash (the logical-axis-rule table
fingerprint, parallel/axis_rules.py) and extra.zero_stage (the fleet
ShardingOptimizer's ZeRO stage) — MULTICHIP rows stay attributable to
their exact partitioning config.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    import argparse

    argparse.ArgumentParser(description=__doc__.split("\n\n")[0]).parse_args()

    from tools.bench_models import bench_ernie_large, finalize_bench_result

    # finalize_bench_result merges telemetry.bench_extra() — compiles /
    # cache_hits / donation_copies — into `extra`, so every BENCH_r*.json
    # records the run's compile accounting alongside the throughput
    out = finalize_bench_result(bench_ernie_large(steps=20))
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
