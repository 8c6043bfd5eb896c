"""Per-conv roofline evidence for the ResNet-50 MFU floor (VERDICT r4 #1).

Times every distinct conv geometry in ResNet-50 (batch 256, bf16, NHWC)
individually on the chip, plus an equivalent-FLOP matmul for the heavy
shapes. If the per-conv achieved-TFLOPs ceiling explains the measured
step time (sum over op counts ~ step fwd time) while same-FLOP matmuls
run several times faster, the floor is a conv-lowering property of the
chip/compiler, not framework overhead.

Method: slope timing with data dependence (x <- x * (1 + 1e-20*mean(y)))
— the chained mean read costs one extra pass over y, small vs the conv.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

BATCH = 256

# (name, count, H_in, Cin, K, stride, Cout) — ResNet-50 unique convs.
# counts aggregate equal-geometry convs across blocks (c1 of block0 in a
# stage differs from later blocks only by Cin).
RESNET50_CONVS = [
    ("conv1_7x7s2", 1, 224, 3, 7, 2, 64),
    ("s1_c1_first", 1, 56, 64, 1, 1, 64),
    ("s1_c1", 2, 56, 256, 1, 1, 64),
    ("s1_c2", 3, 56, 64, 3, 1, 64),
    ("s1_c3", 3, 56, 64, 1, 1, 256),
    ("s1_sc", 1, 56, 64, 1, 1, 256),
    ("s2_c1_first", 1, 56, 256, 1, 1, 128),
    ("s2_c1", 3, 28, 512, 1, 1, 128),
    ("s2_c2_s2", 1, 56, 128, 3, 2, 128),
    ("s2_c2", 3, 28, 128, 3, 1, 128),
    ("s2_c3", 4, 28, 128, 1, 1, 512),
    ("s2_sc_s2", 1, 56, 256, 1, 2, 512),
    ("s3_c1_first", 1, 28, 512, 1, 1, 256),
    ("s3_c1", 5, 14, 1024, 1, 1, 256),
    ("s3_c2_s2", 1, 28, 256, 3, 2, 256),
    ("s3_c2", 5, 14, 256, 3, 1, 256),
    ("s3_c3", 6, 14, 256, 1, 1, 1024),
    ("s3_sc_s2", 1, 28, 512, 1, 2, 1024),
    ("s4_c1_first", 1, 14, 1024, 1, 1, 512),
    ("s4_c1", 2, 7, 2048, 1, 1, 512),
    ("s4_c2_s2", 1, 14, 512, 3, 2, 512),
    ("s4_c2", 2, 7, 512, 3, 1, 512),
    ("s4_c3", 3, 7, 512, 1, 1, 2048),
    ("s4_sc_s2", 1, 14, 1024, 1, 2, 2048),
]


def slope_time(step, x0, n1=8, n2=40, repeats=3):
    """Time step via lax.fori_loop INSIDE jit — per-dispatch host time
    swamps sub-ms kernels when looping in Python, so the loop lives on
    the device."""
    import functools

    @functools.lru_cache(maxsize=None)
    def runner(n):
        @jax.jit
        def run(x):
            return lax.fori_loop(0, n, lambda i, xx: step(xx), x)

        return run

    rng = np.random.RandomState(99)

    def window(n):
        # FRESH input per call
        x = x0 * (1.0 + 0.001 * float(rng.rand()))
        np.asarray(jnp.sum(x.astype(jnp.float32)))  # land it on device
        t0 = time.perf_counter()
        y = runner(n)(x)
        np.asarray(jnp.sum(y.astype(jnp.float32)))
        return time.perf_counter() - t0

    window(n1), window(n2)  # compile both
    slopes = []
    for _ in range(max(repeats, 5)):
        t1, t2 = window(n1), window(n2)
        slopes.append((t2 - t1) / (n2 - n1))
    return float(np.median(slopes)) * 1e3  # ms


def bench_conv(h, cin, k, stride, cout, dtype=jnp.bfloat16):
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (BATCH, h, h, cin), dtype)
    w = jax.random.normal(key, (k, k, cin, cout), dtype) * 0.01
    pad = (k - 1) // 2

    @jax.jit
    def step(x):
        y = lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return x * (1 + 1e-20 * jnp.mean(y).astype(x.dtype))

    ms = slope_time(step, x0)
    hout = -(-h // stride)
    flops = 2.0 * BATCH * hout * hout * cout * (k * k * cin)
    return ms, flops


def bench_matmul(m, kk, n, dtype=jnp.bfloat16):
    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (m, kk), dtype)
    w = jax.random.normal(key, (kk, n), dtype) * 0.01

    @jax.jit
    def step(x):
        y = x @ w
        return x * (1 + 1e-20 * jnp.mean(y).astype(x.dtype))

    ms = slope_time(step, x0)
    return ms, 2.0 * m * kk * n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--peak-tflops", type=float, default=197.0)
    args = ap.parse_args()
    rows, total_ms, total_flops = [], 0.0, 0.0
    for name, count, h, cin, k, stride, cout in RESNET50_CONVS:
        ms, flops = bench_conv(h, cin, k, stride, cout)
        tf = flops / (ms * 1e-3) / 1e12
        rows.append({"conv": name, "count": count, "ms": round(ms, 3),
                     "tflops": round(tf, 1),
                     "pct_peak": round(100 * tf / args.peak_tflops, 1)})
        total_ms += count * ms
        total_flops += count * flops
        print(json.dumps(rows[-1]), flush=True)
    # heavy-conv-equivalent matmuls: s2_c2 (3x3@28,128ch) and s3_c2
    for name, (m, kk, n) in {
        "mm_eq_s2_c2": (BATCH * 28 * 28, 9 * 128, 128),
        "mm_eq_s3_c2": (BATCH * 14 * 14, 9 * 256, 256),
        "mm_eq_s1_c3": (BATCH * 56 * 56, 64, 256),
        "mm_big_4k": (8192, 4096, 4096),
    }.items():
        ms, flops = bench_matmul(m, kk, n)
        tf = flops / (ms * 1e-3) / 1e12
        print(json.dumps({"matmul": name, "ms": round(ms, 3),
                          "tflops": round(tf, 1),
                          "pct_peak": round(100 * tf / args.peak_tflops, 1)}),
              flush=True)
    print(json.dumps({
        "predicted_fwd_ms": round(total_ms, 1),
        "fwd_tflops": round(total_flops / (total_ms * 1e-3) / 1e12, 1),
        "fwd_pct_peak": round(
            100 * total_flops / (total_ms * 1e-3) / 1e12 / args.peak_tflops,
            1)}))


if __name__ == "__main__":
    main()
