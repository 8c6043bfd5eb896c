"""Time ops/pallas/grouped_swiglu.py against the three ragged_dots alone,
on the chip, at the routed layer's four shapes (PERF.md, PR 41).

    chiprun -- python tools/bench_grouped_swiglu.py [--tiles T,W,KB ...]

Sizes are drawn as seeded routing draws them: every live token keeps
top_k distinct experts of E uniformly, the pairs on the held experts sort
first. Each line of ``chiprun_out/grouped_swiglu_bench.jsonl`` is one
(shape, draw): ms a call of the stock path (``stock_ms``), of the kernel at
the module's tiles and at each ``--tiles tile,window,block_kib``
(``kernel[<tiles>]_ms``), and the bytes of the hit experts over each time
as a share of the chip's 819 GB/s (``..._bw_share``).
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import grouped_swiglu as gs

PEAK_BYTES_S = 819e9        # one v5e chip (benchmark/peaks.py has the table)
# name: rows routed, top_k, experts, held, H, F
SHAPES = {
    "trinity_step": (64, 4, 256, 32, 3072, 3072),
    "trinity_p4096": (4096, 4, 256, 32, 3072, 3072),
    "kimi_step": (64, 8, 384, 12, 7168, 2048),
    "kimi_p4096": (4096, 8, 384, 12, 7168, 2048),
}


def draw_sizes(rng, tokens, top_k, experts, held):
    picks = np.stack([rng.choice(experts, top_k, replace=False)
                      for _ in range(tokens)])
    return np.bincount(picks[picks < held], minlength=held).astype(np.int32)


def few_rows(tokens, top_k, experts, held):
    return -(-(2 * tokens * top_k * held // experts + 32) // 64) * 64


def ms_a_call(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="tile,window,block_kib variants beside the module's")
    ap.add_argument("--draws", type=int, default=3)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a chip measurement: found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/grouped_swiglu_bench.jsonl", "a")
    stock = jax.jit(gs.stock_grouped_swiglu)
    for name in args.shapes:
        tokens, top_k, experts, held, h, f = SHAPES[name]
        n = few_rows(tokens, top_k, experts, held)
        key = jax.random.PRNGKey(args.seed)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        bf = jnp.bfloat16
        w1 = (jax.random.normal(k1, (held, h, f), bf) * h ** -0.5)
        w3 = (jax.random.normal(k2, (held, h, f), bf) * h ** -0.5)
        w2 = (jax.random.normal(k3, (held, f, h), bf) * f ** -0.5)
        xs = jax.random.normal(k4, (n, h), bf)
        variants = {"module": gs._tiles(n, h, f, bf)}
        for spec in args.tiles:
            tile, window, kib = (int(v) for v in spec.split(","))
            tile = min(tile, n)
            variants[spec] = (tile, min(window, tile),
                              gs._lanes(h, f, bf, kib << 10))
        rng = np.random.RandomState(args.seed)
        for d in range(args.draws):
            sizes = draw_sizes(rng, tokens, top_k, experts, held)
            hit, pairs = int((sizes > 0).sum()), int(sizes.sum())
            gbytes = hit * 3 * h * f * 2 / 1e9
            sz = jnp.asarray(sizes)
            line = {"shape": name, "n": n, "draw": d, "hit": hit,
                    "pairs": pairs, "gbytes": round(gbytes, 4),
                    "device": dev.device_kind}

            def timed(key, fn):
                ms = ms_a_call(fn, (xs, w1, w3, w2, sz), args.reps)
                line[f"{key}_ms"] = round(ms, 4)
                line[f"{key}_bw_share"] = round(
                    100 * gbytes * 1e9 / (ms * 1e-3) / PEAK_BYTES_S, 1)

            ref = stock(xs, w1, w3, w2, sz)
            line["ref_max_abs"] = float(jnp.max(jnp.abs(ref[:pairs])))
            timed("stock", stock)
            for label, (tile, window, tn) in variants.items():
                fn = functools.partial(
                    gs._pallas_grouped_swiglu, tile=tile, window=window,
                    tn=tn, interpret=False)
                line[f"tiles[{label}]"] = [tile, window, tn]
                try:
                    got = fn(xs, w1, w3, w2, sz)
                    line[f"max_abs_diff[{label}]"] = float(
                        jnp.max(jnp.abs(got[:pairs] - ref[:pairs])))
                    timed(f"kernel[{label}]", fn)
                except Exception as e:     # a tile Mosaic refuses: say so
                    line[f"error[{label}]"] = str(e)[:300]
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()


if __name__ == "__main__":
    main()
