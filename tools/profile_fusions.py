"""Top individual XLA fusions of the ERNIE step, with shapes.

profile_ernie.py aggregates by framework source line; this drills one
level down — per HLO op name — so fat fusions (e.g. a matmul whose
epilogue/prologue drags) are visible individually.

Usage: python tools/profile_fusions.py [--steps 4] [--top 40]
"""

from __future__ import annotations

import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--batch", type=int, default=34)
    args = ap.parse_args()

    import re
    import shutil
    import tempfile

    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import profiler
    from paddle_tpu.models import bert
    from tools.ablate_ernie import build

    cfg, mainp, startup, loss_v = build()
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    feed = {k: jnp.asarray(v) for k, v in bert.synthetic_pretraining_batch(
        cfg, args.batch, 512, seed=0,
        max_predictions_per_seq=80).items()}
    exe.run(mainp, feed=feed, fetch_list=[loss_v], scope=scope)
    exe.run(mainp, feed=feed, fetch_list=[], scope=scope)

    log_dir = tempfile.mkdtemp(prefix="pt_fusions_")
    try:
        with profiler.trace(log_dir):
            for _ in range(args.steps):
                exe.run(mainp, feed=feed, fetch_list=[], scope=scope)
        events = profiler._device_ops(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    excl = profiler._exclusive_times(events)

    by_name = collections.defaultdict(lambda: [0.0, 0, "", ""])
    total = 0.0
    for e in events:
        a = e.get("args") or {}
        name = e.get("name", "")
        long_name = a.get("long_name") or ""
        if name.startswith("jit_") or re.fullmatch(r"\d+", name):
            continue
        d = excl.get(id(e), e.get("dur", 0))
        row = by_name[name]
        row[0] += d
        row[1] += 1
        row[2] = long_name[:240]
        row[3] = (a.get("source") or "")[:60]
        total += d
    rows = sorted(by_name.items(), key=lambda kv: -kv[1][0])
    print(f"total exclusive {total/1e3/args.steps:.1f} ms/step")
    for name, (d, cnt, long_name, src) in rows[:args.top]:
        print(f"{d/1e3/args.steps:8.3f} ms x{cnt//args.steps:<4} {name:28s}"
              f" {src}\n          {long_name[:200]}")


if __name__ == "__main__":
    main()
