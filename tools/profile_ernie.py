"""Per-fusion device-time profile of the north-star ERNIE step.

Thin driver over paddle_tpu.profiler.device_profile (the jax profiler's
device trace): builds the bench-identical program,
runs a few steps under the trace, and prints exclusive device time per
operation (HLO instruction name with its number folded; a Mosaic kernel
under its `name=`). This is the tool that located the 183 ms attention
backward in the 480 ms round-4 step.

Usage: python tools/profile_ernie.py [--steps 4] [--top 25] [--batch 34]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--batch", type=int, default=34)
    args = ap.parse_args()

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
    # warm both cache entries (fetch / no-fetch)
    exe.run(mainp, feed=feed, fetch_list=[loss_v], scope=scope)
    exe.run(mainp, feed=feed, fetch_list=[], scope=scope)

    prof = profiler.device_profile(
        lambda: exe.run(mainp, feed=feed, fetch_list=[], scope=scope),
        steps=args.steps)
    print(f"exclusive device total {prof['ms_per_step']:.1f} ms/step "
          f"over {args.steps} steps")
    for src, ms in prof["rows"][:args.top]:
        print(f"{ms:8.2f} ms  {src[:100]}")


if __name__ == "__main__":
    main()
