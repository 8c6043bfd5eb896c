"""Per-shape roofline for the ERNIE dense matmuls (VERDICT r5 #1).

The round-4 profile attributes 199 ms of the 337 ms north-star step to
dense matmuls (fwd+bwd) at ~73-81% aggregate MXU. This tool times every
distinct dense matmul the step actually contains — forward, dX and dW
exactly as jax.vjp of jnp.matmul produces them (dot_general contractions,
no explicit transposes) — so the inefficiency can be pinned to shapes
instead of guessed at.

Method: device-side fori_loop slope timing (same as bench_conv.py).

Usage: python tools/bench_matmul_shapes.py [--batch 34]
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

PEAK_TFLOPS = 197.0


def slope_time(step, x0, n1=8, n2=40, repeats=3):
    @functools.lru_cache(maxsize=None)
    def runner(n):
        @jax.jit
        def run(x):
            return lax.fori_loop(0, n, lambda i, xx: step(xx), x)

        return run

    rng = np.random.RandomState(99)

    def window(n):
        x = x0 * (1.0 + 0.001 * float(rng.rand()))
        np.asarray(jnp.sum(x.astype(jnp.float32)))
        t0 = time.perf_counter()
        y = runner(n)(x)
        np.asarray(jnp.sum(y.astype(jnp.float32)))
        return time.perf_counter() - t0

    window(n1), window(n2)
    slopes = []
    for _ in range(max(repeats, 5)):
        t1, t2 = window(n1), window(n2)
        slopes.append((t2 - t1) / (n2 - n1))
    return float(np.median(slopes)) * 1e3


def bench(name, fn, x0, flops, count=1.0):
    ms = slope_time(fn, x0)
    tf = flops / (ms * 1e-3) / 1e12
    row = {"case": name, "count": count, "ms": round(ms, 4),
           "tflops": round(tf, 1),
           "pct_peak": round(100 * tf / PEAK_TFLOPS, 1)}
    print(json.dumps(row), flush=True)
    return ms, flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=34)
    args = ap.parse_args()
    B, S, H, I, V, KHEAD = args.batch, 512, 1024, 4096, 18000, 80
    dt = jnp.bfloat16
    key = jax.random.PRNGKey(0)

    total_ms = total_flops = 0.0

    def acc(ms, flops, count):
        nonlocal total_ms, total_flops
        total_ms += ms * count
        total_flops += flops * count

    # ---- per-layer dense blocks (24 layers) --------------------------------
    # fwd: [B,S,K] @ [K,N]   (3-D, as the program emits)
    # dX : einsum('bsn,kn->bsk')   dW: einsum('bsk,bsn->kn')
    def mk_fwd(Kd, Nd):
        w = jax.random.normal(key, (Kd, Nd), dt) * 0.02

        def f(x):
            y = jnp.matmul(x, w)
            return x * (1 + 1e-20 * jnp.mean(y).astype(x.dtype))

        return f, jax.random.normal(key, (B, S, Kd), dt)

    def mk_dx(Kd, Nd):
        w = jax.random.normal(key, (Kd, Nd), dt) * 0.02

        def f(g):
            dx = lax.dot_general(g, w, (((2,), (1,)), ((), ())))
            return g * (1 + 1e-20 * jnp.mean(dx).astype(g.dtype))

        return f, jax.random.normal(key, (B, S, Nd), dt)

    def mk_dw(Kd, Nd):
        xsaved = jax.random.normal(key, (B, S, Kd), dt)

        def f(g):
            dw = lax.dot_general(xsaved, g, (((0, 1), (0, 1)), ((), ())))
            return g * (1 + 1e-20 * jnp.mean(dw).astype(g.dtype))

        return f, jax.random.normal(key, (B, S, Nd), dt)

    M = B * S
    for tag, Kd, Nd, cnt in [("proj_1k_1k", H, H, 4 * 24),
                             ("ffn1_1k_4k", H, I, 24),
                             ("ffn2_4k_1k", I, H, 24)]:
        for kind, mk in [("fwd", mk_fwd), ("dx", mk_dx), ("dw", mk_dw)]:
            f, x0 = mk(Kd, Nd)
            ms, fl = bench(f"{tag}:{kind}", f, x0, 2.0 * M * Kd * Nd, cnt)
            acc(ms, fl, cnt)

    # ---- MLM head (k=80 gathered rows) -------------------------------------
    Mh = B * KHEAD
    wdec = jax.random.normal(key, (V, H), dt) * 0.02  # tied emb [V,H]
    xh = jax.random.normal(key, (B, KHEAD, H), dt)

    def dec_fwd(x):
        y = lax.dot_general(x, wdec, (((2,), (1,)), ((), ())))
        return x * (1 + 1e-20 * jnp.mean(y).astype(x.dtype))

    def dec_dx(g):
        dx = jnp.matmul(g, wdec)
        return g * (1 + 1e-20 * jnp.mean(dx).astype(g.dtype))

    def dec_dw(g):
        dw = lax.dot_general(g, xh, (((0, 1), (0, 1)), ((), ())))
        return g * (1 + 1e-20 * jnp.mean(dw).astype(g.dtype))

    ms, fl = bench("mlm_dec:fwd", dec_fwd, xh, 2.0 * Mh * H * V); acc(ms, fl, 1)
    g0 = jax.random.normal(key, (B, KHEAD, V), dt)
    ms, fl = bench("mlm_dec:dx", dec_dx, g0, 2.0 * Mh * H * V); acc(ms, fl, 1)
    ms, fl = bench("mlm_dec:dw", dec_dw, g0, 2.0 * Mh * H * V); acc(ms, fl, 1)
    ftrans, xt = mk_fwd(H, H)
    ms, fl = bench("mlm_trans:fwd", ftrans,
                   jax.random.normal(key, (B, KHEAD, H), dt),
                   2.0 * Mh * H * H)
    acc(ms, fl, 3)  # fwd + dx + dw approx equal

    print(json.dumps({
        "predicted_dense_ms": round(total_ms, 1),
        "agg_tflops": round(total_flops / (total_ms * 1e-3) / 1e12, 1),
        "agg_pct_peak": round(
            100 * total_flops / (total_ms * 1e-3) / 1e12 / PEAK_TFLOPS, 1),
        "profiled_dense_ms": 199.1}))


if __name__ == "__main__":
    main()
