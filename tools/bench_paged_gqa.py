"""Time ops/pallas/paged_gqa_attention.py alone on the chip, at the shapes of
the four cells whose step runs it (PERF.md, PR 59).

    chiprun -- python tools/bench_paged_gqa.py [--piece P ...] [--chunk C ...]

A row's context is drawn from ``--seed`` (a lognormal about the cell's mean
live context, cut to the table) or fixed by ``--context``. Each line, also
written to ``chiprun_out/paged_gqa_bench.jsonl``, is one (shape, piece,
chunk): microseconds a call (``--reps`` calls chained inside one jitted
loop, so the host's dispatch is paid once), K and V of the keys attended
over that time as a share of the chip's 819 GB/s (``bw_share``: the cells'
roofline metrics count the same bytes), the bytes of the pages the kernel
copies (``copied_mb``: every held page once), the columns scored over the
keys attended (``scored_over_attended``) and the largest difference from the
stock lowering. ``--piece`` and ``--chunk`` set the module's two constants
for the run: they are how PIECE_TOKENS and CHUNK_TOKENS were chosen.
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import paged_gqa_attention as pg

PEAK_BYTES_S = 819e9        # one v5e chip (benchmark/peaks.py has the table)
PAGE = 64
# name: rows, query heads, K/V heads, head, table pages, window, ring,
# mean live context a row (PERF.md section 5)
SHAPES = {
    "lfm2_c10": (128, 32, 8, 64, 128, 0, False, 1915),
    "falcon_h1_c5": (64, 20, 4, 128, 32, 0, False, 450),
    "trinity_full_c3": (64, 6, 1, 128, 160, 0, False, 2946),
    "trinity_ring_c3": (64, 6, 1, 128, 65, 4096, True, 2946),
    "qwen3_next_c7": (64, 4, 1, 256, 288, 0, False, 5950),
}


def draw_positions(rng, rows, mean, cap, ring, context):
    if context:
        return np.full(rows, context - 1, np.int32)
    sigma = 0.6
    tokens = rng.lognormal(np.log(mean) - sigma ** 2 / 2, sigma, rows)
    top = 4 * cap if ring else cap
    return np.clip(tokens, 1, top).astype(np.int32) - 1


def us_a_call(fn, args, reps):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return best / reps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--piece", nargs="*", type=int,
                    default=[pg.PIECE_TOKENS])
    ap.add_argument("--chunk", nargs="*", type=int,
                    default=[pg.CHUNK_TOKENS])
    ap.add_argument("--context", type=int, default=0,
                    help="tokens every row holds (0: a seeded lognormal)")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a chip measurement: found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/paged_gqa_bench.jsonl", "a")
    for name in args.shapes:
        rows, n, nkv, hd, mp, window, ring, mean = SHAPES[name]
        rng = np.random.RandomState(args.seed % (1 << 32))
        cap = mp * PAGE
        pos = draw_positions(rng, rows, mean, cap, ring, args.context)
        held = np.minimum(pos + 1, cap)
        attended = np.minimum(pos + 1, window) if window else pos + 1
        pages = rows * mp + 1
        # each row's pages are its own, scattered over the pool
        table = jnp.asarray(1 + rng.permutation(rows * mp).reshape(rows, mp),
                            jnp.int32)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        bf = jnp.bfloat16
        pk = jax.random.normal(k1, (pages, PAGE, nkv * hd), bf)
        pv = jax.random.normal(k2, (pages, PAGE, nkv * hd), bf)
        q = jax.random.normal(k3, (rows, n * hd), jnp.float32)
        p = jnp.asarray(pos)
        scale = hd ** -0.5
        row_bytes = 2 * nkv * hd * 2          # K and V of one token
        kv_bytes = int(attended.sum()) * row_bytes
        # the pools go in as arguments: closed over they would be constants
        # of the program, a gigabyte each
        data = (pk, pv, table, p)
        want = jax.jit(lambda q, pk, pv, table, p:
                       pg.stock_paged_gqa_attention(
                           q, pk, pv, table, p, n, nkv, hd, scale, window,
                           ring))(q, *data)
        for piece, chunk in itertools.product(args.piece, args.chunk):
            pg.PIECE_TOKENS, pg.CHUNK_TOKENS = piece, chunk

            def one(q, *data):
                return pg._pallas_paged_gqa_attention(
                    q, *data, n, nkv, hd, scale, window, ring,
                    pg._tiling(PAGE, mp), interpret=False)

            @jax.jit
            def many(q, *data):
                # each call reads the one before, so none is dropped
                return jax.lax.fori_loop(
                    0, args.reps, lambda _, x: q + 1e-3 * one(x, *data), q)

            line = {"shape": name, "piece": piece, "chunk": chunk,
                    "rows": rows, "mean_context": float(pos.mean() + 1),
                    "seed": args.seed, "device": dev.device_kind,
                    "copied_mb": round(int((-(-held // PAGE)).sum())
                                       * PAGE * row_bytes / 1e6, 3),
                    "attended_mb": round(kv_bytes / 1e6, 3),
                    "scored_over_attended": round(
                        pg.tokens_scored(pos, PAGE, mp, window, ring)
                        / int(attended.sum()), 3)}
            try:
                line["max_abs_diff"] = float(
                    jnp.max(jnp.abs(jax.jit(one)(q, *data) - want)))
                us = us_a_call(many, (q, *data), args.reps)
                line["us_a_call"] = round(us, 2)
                line["bw_share"] = round(
                    100 * kv_bytes / (us * 1e-6) / PEAK_BYTES_S, 1)
            except Exception as e:     # a tiling Mosaic refuses: say so
                line["error"] = str(e)[:300]
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()


if __name__ == "__main__":
    main()
