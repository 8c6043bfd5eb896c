"""Microbenchmark: flash attention vs XLA attention at model geometries.

Uses tools/perf.py slope timing (each window's fixed cost cancels).

Usage: python tools/bench_attention.py [--geom ernie|bert|long] [--causal]
       [--sweep]
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tools.perf import time_chain

PEAK = 197e12
GEOMS = {
    "ernie": (32, 16, 512, 64),
    "ernie34": (34, 16, 512, 64),
    "bert": (384, 12, 128, 64),
    "long": (4, 16, 2048, 64),
    "xl": (8, 16, 4096, 64),
}


def bench_impl(name, attn_fn, q, k, v, causal, fwd_flops, bwd_flops):
    fwd = jax.jit(lambda x: attn_fn(x, k, v).astype(x.dtype))

    # differentiate wrt q AND k AND v: an x-only grad lets XLA DCE the
    # entire dk/dv computation (the accumulator scan in the chunked
    # path) — exactly the under-measurement that mis-calibrated the
    # round-3 dispatcher (bwd looked 2.7x cheaper than it runs
    # in-program). Chain the three cotangents into one output.
    def loss(x, kk, vv):
        return jnp.sum(attn_fn(x, kk, vv).astype(jnp.float32) ** 2) * 1e-6

    gf = jax.grad(loss, argnums=(0, 1, 2))

    def bwd_all(x):
        dq, dk, dv = gf(x, k, v)
        return (dq + dk + dv).astype(x.dtype)

    bwd = jax.jit(bwd_all)
    try:
        ms_f = time_chain(fwd, q)
        ms_b = time_chain(bwd, q)
        print(f"{name:10s} fwd {ms_f:7.3f} ms "
              f"({fwd_flops/ms_f*1e3/PEAK*100:5.1f}%)   "
              f"fwd+bwd {ms_b:7.3f} ms "
              f"({(fwd_flops+bwd_flops)/ms_b*1e3/PEAK*100:5.1f}%)",
              flush=True)
        return ms_f, ms_b
    except Exception as e:
        print(f"{name:10s} FAILED {type(e).__name__}: {str(e)[:160]}",
              flush=True)
        return None, None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--geom", default="ernie")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--no-bias", dest="bias", action="store_false",
                    default=True)
    ap.add_argument("--sweep", action="store_true",
                    help="sweep flash block sizes")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="attention-probs dropout rate (bench recipe: 0.1)")
    args = ap.parse_args()

    b, h, s, d = GEOMS[args.geom]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, s, d), jnp.bfloat16)
    bias = jnp.zeros((b, s), jnp.float32) if args.bias else None

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    causal = args.causal
    fwd_flops = 4.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    bwd_flops = fwd_flops * 2.5

    print(f"geom={args.geom} b={b} h={h} s={s} d={d} causal={causal} "
          f"bias={args.bias}")

    if args.sweep:
        os.environ["PT_FLASH_IMPL"] = "pallas"  # sweep the KERNEL, not
        for bq in (128, 256, 512):              # the auto-dispatched path
            for bk in (128, 256, 512):
                if bq > s or bk > s:
                    continue
                fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = bq, bk
                bench_impl(f"fl {bq}x{bk}",
                           lambda x, kk, vv: fa.flash_attention(
                               x, kk, vv, bias, causal=causal,
                               dropout_rate=args.dropout),
                           q, k, v, causal, fwd_flops, bwd_flops)
        os.environ["PT_FLASH_IMPL"] = "auto"
        return

    scale = 1.0 / d ** 0.5
    rate = args.dropout
    os.environ["PT_FLASH_IMPL"] = "pallas"
    bench_impl("pallas",
               lambda x, kk, vv: fa.flash_attention(x, kk, vv, bias,
                                                    causal=causal,
                                                    dropout_rate=rate),
               q, k, v, causal, fwd_flops, bwd_flops)
    os.environ["PT_FLASH_IMPL"] = "auto"
    bench_impl("xla-rcmp",
               lambda x, kk, vv: fa._xla_attention(
                   x, kk, vv, bias, jnp.uint32(0), causal, scale, rate),
               q, k, v, causal, fwd_flops, bwd_flops)
    bench_impl("xla-ref",
               lambda x, kk, vv: fa.reference_attention(
                   x, kk, vv, bias, causal=causal, dropout_rate=rate,
                   dropout_seed=jnp.uint32(0)),
               q, k, v, causal, fwd_flops, bwd_flops)

    def xla_bf16(x, kk, vv):
        sc = jnp.einsum("bhqd,bhkd->bhqk", x, kk,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
        if bias is not None:
            sc = sc + bias[:, None, None, :]
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(x.dtype), vv,
                          preferred_element_type=jnp.float32)

    bench_impl("xla-bf16", xla_bf16, q, k, v, causal, fwd_flops, bwd_flops)

    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock)

        bench_impl("jax-stock",
                   lambda x, kk, vv: stock(x, kk, vv, causal=causal,
                                           sm_scale=1.0 / d ** 0.5),
                   q, k, v, causal, fwd_flops, bwd_flops)
    except Exception as e:
        print(f"jax-stock unavailable: {e}")


if __name__ == "__main__":
    main()
