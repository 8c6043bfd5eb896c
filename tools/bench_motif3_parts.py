"""Time the kernels models/motif3.py brings, each beside its stock lowering,
on the chip at the cell's shapes, and hold each to that lowering there.

    chiprun -- python tools/bench_motif3_parts.py [--parts mhc band poly ring]

One JSON line a reading, also in ``chiprun_out/motif3_parts.jsonl``:

* ``mhc``: `mhc_pre` and `mhc_post` at 64 rows (a step) and at 4,096 and
  16,384 (prefill buckets): ms a call of kernel and stock, the least bytes
  over each time as a share of 819 GB/s, the largest difference.
* ``band``: `mla_prefill_attention` at 80 heads on 16 K/V heads, buckets
  4,096 and 16,384, with window 128 and as the triangle: a window layer's
  ms must grow with S, the triangle's with S^2.
* ``poly``: `grouped_polyglu` against three ragged products and the norm,
  at a step's rows (48 held experts of 384, top-8, 64 rows) and a 4,096
  bucket's.
* ``ring``: the paged latent kernel over a slot's ring of 3 pages (64 rows,
  80 heads) beside the stock gather over those 3 pages (the by-hand reading
  that decided which of the two a window layer takes), and over 4,096-token
  contexts on pages.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import grouped_swiglu as gs
from paddle_tpu.ops.pallas import mhc_mix
from paddle_tpu.ops.pallas import mla_prefill_attention as mpa
from paddle_tpu.ops.pallas import paged_mla_attention as pma

PEAK_BYTES_S, PEAK_FLOPS = 819e9, 197e12
OUT = os.path.join("chiprun_out", "motif3_parts.jsonl")


def ms_a_call(fn, args, reps=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def emit(**line):
    line["device"] = jax.devices()[0].device_kind
    text = json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in line.items()})
    print(text, flush=True)
    with open(OUT, "a") as f:
        f.write(text + "\n")


def diff(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def part_mhc(key):
    n, c = 4, 4096
    k = jax.random.split(key, 6)
    gamma = 1 + 0.1 * jax.random.normal(k[1], (n * c,))
    phi = (jax.random.normal(k[2], (n * c, 24)) * (n * c) ** -0.5
           ).astype(jnp.bfloat16)
    scale, bias = jnp.ones(3), jax.random.normal(k[3], (24,))
    kw = dict(n=n, iters=20, eps=1e-5)
    for rows in (64, 4096, 16384):
        x = jax.random.normal(k[0], (rows, n * c), jnp.float32)
        y = jax.random.normal(k[4], (rows, c), jnp.float32)
        pre = jax.jit(lambda x: mhc_mix._pallas_mhc_pre(
            x, gamma, phi, scale, bias, tile=mhc_mix.TILE, interpret=False,
            **kw))
        pre0 = jax.jit(lambda x: mhc_mix.stock_mhc_pre(
            x, gamma, phi, scale, bias, **kw))
        (u, maps), (u0, maps0) = pre(x), pre0(x)
        post = jax.jit(lambda x, y, m: mhc_mix._pallas_mhc_post(
            x, y, m, n=n, clamp=1e6, tile=mhc_mix.TILE, interpret=False))
        post0 = jax.jit(lambda x, y, m: mhc_mix.stock_mhc_post(
            x, y, m, n=n, clamp=1e6))
        for name, fn, fn0, args, moved, err in (
                ("mhc_pre", pre, pre0, (x,), rows * 4.0 * c * (n + 1),
                 max(diff(u, u0), diff(maps, maps0))),
                ("mhc_post", post, post0, (x, y, maps0),
                 rows * 4.0 * c * (2 * n + 1),
                 diff(post(x, y, maps0), post0(x, y, maps0)))):
            kernel_ms, stock_ms = ms_a_call(fn, args), ms_a_call(fn0, args)
            emit(part=name, rows=rows, kernel_ms=kernel_ms,
                 stock_ms=stock_ms, max_diff=err,
                 kernel_bw_share=moved / PEAK_BYTES_S / kernel_ms * 1e3,
                 stock_bw_share=moved / PEAK_BYTES_S / stock_ms * 1e3)


def part_band(key):
    n, nkv, nope, rope, dv = 80, 16, 128, 64, 128
    k = jax.random.split(key, 4)
    for s in (4096, 16384):
        dt = jnp.bfloat16
        qn = jax.random.normal(k[0], (s, n * nope), dt)
        qr = jax.random.normal(k[1], (s, n * rope), dt)
        kv = jax.random.normal(k[2], (s, nkv * (nope + dv)), dt)
        kr = jax.random.normal(k[3], (s, rope), dt)
        for window in (128, 0):
            fn = jax.jit(lambda *a, w=window: mpa.mla_prefill_attention(
                *a, 192 ** -0.5, num_heads=n, nope_dim=nope,
                num_kv_heads=nkv, window=w))
            ms = ms_a_call(fn, (qn, qr, kv, kr), reps=3)
            w = min(window, s) if window else 0
            pairs = s * w - w * (w - 1) / 2 if window else s * (s + 1) / 2
            line = dict(part="band", bucket=s, window=window, kernel_ms=ms,
                        flops_share=pairs * 2 * n * (nope + rope + dv)
                        / PEAK_FLOPS / ms * 1e3)
            if s == 4096:       # the stock lowering beside it, once
                head = slice(0, 10 * nope)
                out = fn(qn, qr, kv, kr)[:, :10 * dv]
                kvh = jnp.repeat(kv.reshape(s, nkv, nope + dv)[:, :2], 5, 1)
                ref = mpa.stock_mla_prefill_attention(
                    qn[:, head].reshape(s, 10, nope),
                    qr[:, :10 * rope].reshape(s, 10, rope), kvh[:, :, :nope],
                    kr, kvh[:, :, nope:], 192 ** -0.5, window=window)
                line["max_diff_10_heads"] = diff(out, ref.reshape(s, -1))
            emit(**line)


def part_poly(key):
    e, h, f, held, experts, top_k = 48, 4096, 1280, 48, 384, 8
    k = jax.random.split(key, 6)
    dt = jnp.bfloat16
    w1 = (jax.random.normal(k[1], (e, h, f)) * h ** -0.5).astype(dt)
    w3 = (jax.random.normal(k[2], (e, h, f)) * h ** -0.5).astype(dt)
    w2 = (jax.random.normal(k[3], (e, f, h)) * f ** -0.5).astype(dt)
    pn = 1 / 3 + 0.25 * jax.random.normal(k[4], (e, 4))
    kw = dict(eps=1e-5, out_scale=0.5, bias_clamp=0.5)
    rng = np.random.RandomState(0)
    for tokens in (64, 4096):
        picks = np.stack([rng.choice(experts, top_k, replace=False)
                          for _ in range(tokens)])
        sizes = jnp.asarray(np.bincount(picks[picks < held],
                                        minlength=held), jnp.int32)
        rows = -(-(2 * tokens * top_k * held // experts + 32) // 64) * 64
        xs = jax.random.normal(k[0], (rows, h)).astype(dt)
        fn = jax.jit(lambda xs, sizes: gs.grouped_polyglu(
            xs, w1, w3, w2, pn, sizes, **kw))
        fn0 = jax.jit(lambda xs, sizes: gs.stock_grouped_polyglu(
            xs, w1, w3, w2, pn, sizes, **kw))
        m = int(sizes.sum())
        hit = int((sizes > 0).sum())
        kernel_ms = ms_a_call(fn, (xs, sizes))
        stock_ms = ms_a_call(fn0, (xs, sizes))
        moved = hit * 3.0 * h * f * 2
        emit(part="poly", tokens=tokens, rows=rows, held_pairs=m,
             experts_hit=hit, kernel_ms=kernel_ms, stock_ms=stock_ms,
             max_diff=diff(fn(xs, sizes)[:m], fn0(xs, sizes)[:m]),
             kernel_bw_share=moved / PEAK_BYTES_S / kernel_ms * 1e3,
             stock_bw_share=moved / PEAK_BYTES_S / stock_ms * 1e3)


def part_ring(key):
    b, n, width, vdim, page = 64, 80, 640, 512, 64
    k = jax.random.split(key, 4)
    q = jax.random.normal(k[0], (b, n * width), jnp.float32)
    for name, mp, pages, window, ctx in (("ring", 3, 193, 128, 5000),
                                         ("pages", 288, 18433, 0, 4096)):
        pool = jax.random.normal(k[1], (pages, page, width), jnp.bfloat16)
        held = mp if window else -(-ctx // page)
        table = np.zeros((b, mp), np.int32)
        table[:, :held] = 1 + (np.arange(b * held).reshape(b, held)
                               % (pages - 1))
        table = jnp.asarray(table)
        pos = jnp.full((b,), ctx - 1, jnp.int32)
        kw = {"window": window} if window else {}
        fn = jax.jit(lambda q, pool: pma._pallas_paged_mla_attention(
            q, pool, table, pos, n, vdim, 192 ** -0.5, False, **kw))
        rows = b * (min(ctx, window) if window else ctx)
        line = dict(part="paged_mla", over=name, rows_attended=rows,
                    kernel_ms=ms_a_call(fn, (q, pool)))
        line["kernel_bw_share"] = rows * 1152 / PEAK_BYTES_S \
            / line["kernel_ms"] * 1e3
        if window:
            fn0 = jax.jit(lambda q, pool: pma.stock_paged_mla_attention(
                q, pool, table, pos, n, vdim, 192 ** -0.5, window))
            line["stock_ms"] = ms_a_call(fn0, (q, pool))
            line["max_diff"] = diff(fn(q, pool), fn0(q, pool))
        emit(**line)


PARTS = {"mhc": part_mhc, "band": part_band, "poly": part_poly,
         "ring": part_ring}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", nargs="*", default=sorted(PARTS),
                    choices=sorted(PARTS))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("tools/bench_motif3_parts.py times kernels on a "
                         "TPU; this is " + jax.default_backend())
    os.makedirs("chiprun_out", exist_ok=True)
    open(OUT, "w").close()
    for i, part in enumerate(args.parts):
        PARTS[part](jax.random.PRNGKey(i))


if __name__ == "__main__":
    main()
