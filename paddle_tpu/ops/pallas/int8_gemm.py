"""Weight-only int8 GEMM as a Pallas MXU kernel (ROADMAP open item 1).

The serving-side counterpart of the reference's fused int8 GEMM CUDA
kernels (operators/fused/fused_fc_elementwise_layernorm, the int8
quant_conv2d/mul kernels): the weight stays **int8 in HBM** — half the
bytes of fp32 serving's dominant traffic — and the per-output-channel
dequant (one scale multiply) plus the optional bias/activation epilogue
fuse INTO the MXU matmul, so the fp32 weight tensor never exists in HBM
at all. The stock XLA lowering (`dequantize_weight` + matmul) reads the
int8 weight once, writes the fp32 dequant result, and reads it again in
the matmul — this kernel is the read-once form.

Dispatch discipline (the ops/pallas contract):
  * ``kernel_mode()`` 'off'  → the counted stock jnp lowering
    (``pallas.int8_gemm_fallbacks`` reason="mode_off") — bitwise-
    identical to what the op lowered to before the kernel existed;
  * 'interpret' → the Pallas kernel under the interpreter (CPU CI
    validates it against the stock path bit-for-bit in the single-block
    regime and against numpy oracles when tiled);
  * 'tpu' → the compiled Mosaic kernel.
  Shapes the kernel cannot tile (K beyond the VMEM budget, tpu-mode
  lane misalignment) take the counted fallback with a reason attr.

Epilogue order is pinned: ``acc * scale (+ bias) (relu)`` — the same
float ops in the same order as the stock path, which is what keeps
``PT_PALLAS=interpret`` decode output bitwise-identical to
``PT_PALLAS=off`` when one (block_m, block_n) tile covers the operand
(every repo-scale decode config; tiled shapes agree to the last ulp on
CPU XLA too, but only the single-block regime is *pinned* bitwise).

Dispatch/fallback counts land in telemetry as
``pallas.int8_gemm_dispatches`` / ``pallas.int8_gemm_fallbacks``
(rendered by tools/perf_report.py's Decode section); the tile geometry
is part of ``kernels_fingerprint()`` so the executor/decode compile
caches key on it (a tile-constant change recompiles instead of reusing
a stale kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import telemetry

# MXU-shaped output tiles; K is never split (f32 accumulation order must
# match the stock dot for the bitwise gates), so a VMEM budget caps it.
BLOCK_M = 128
BLOCK_N = 128
MAX_K = 8192            # x tile (128, K) f32 + w tile (K, 128) int8 ≲ 5 MiB


def int8_gemm_fingerprint() -> str:
    """Tile-geometry fingerprint — folded into the compile-cache keys so
    per-variant cost capture attributes flops/bytes correctly."""
    return f"i8g.m{BLOCK_M}n{BLOCK_N}k{MAX_K}"


def _epilogue(acc, scale, bias, act):
    """Pinned epilogue: dequant scale, then bias, then activation — ONE
    ordering shared by the kernel and the stock path (bitwise gates)."""
    out = acc * scale
    if bias is not None:
        out = out + bias
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    return out


def stock_int8_gemm(x2, w8, scale, bias, act):
    """The counted stock lowering (and the fallback/oracle reference):
    dequant folded as a post-matmul column scale. XLA fuses it, but the
    int8->fp32 weight cast still materialises on the stock path."""
    acc = jnp.dot(x2, w8.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return _epilogue(acc, scale, bias, act)


def _gemm_kernel(*refs, n_in, has_bias, act):
    ins, o_ref = refs[:n_in], refs[n_in]
    x_ref, w_ref, s_ref = ins[0], ins[1], ins[2]
    b_ref = ins[3] if has_bias else None
    # int8 tile -> f32 in VMEM: the dequant the stock path pays an HBM
    # round trip for happens here, inside the matmul's operand read
    acc = jnp.dot(x_ref[...], w_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = _epilogue(acc, s_ref[...],
                           b_ref[...] if has_bias else None, act)


def _pad_axis(a, axis, to):
    cur = a.shape[axis]
    if cur == to:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, to - cur)
    return jnp.pad(a, pad)


def _pallas_int8_gemm(x2, w8, scale, bias, act, interpret):
    from jax.experimental import pallas as pl

    m, k = x2.shape
    n = w8.shape[1]
    bm = min(BLOCK_M, m)
    bn = min(BLOCK_N, n)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    x2 = _pad_axis(x2, 0, mp)
    w8 = _pad_axis(w8, 1, np_)
    # scale/bias ride as (1, N) rows: Mosaic tiles a 1-D f32[N] operand
    # differently from XLA's HBM layout and refuses the call
    scale = _pad_axis(scale.reshape(1, -1), 1, np_)
    if bias is not None:
        bias = _pad_axis(bias.reshape(1, -1), 1, np_)
    grid = (mp // bm, np_ // bn)
    in_specs = [pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j))]
    args = [x2, w8, scale]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        args.append(bias)
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_in=len(args),
                          has_bias=bias is not None, act=act),
        grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * k * np_,
            bytes_accessed=mp * k * 4 + k * np_ + mp * np_ * 4 + np_ * 4,
            transcendentals=0),
        interpret=interpret, name="int8_gemm")(*args)
    return out[:m, :n]


def int8_weight_only_gemm(x, w8, scale, bias=None, act=None):
    """``act(x @ (w8 * scale[col]) + bias)`` with the weight kept int8.

    x fp [..., K]; w8 int8 [K, N]; scale fp32 [N] (per-output-channel,
    abs-max/127 layout of quantize_decoder_lm_params /
    contrib/slim.quantize_weights_int8); bias optional [N]; act None or
    'relu'. Leading axes of x are flattened for the kernel and restored
    on the way out. Routes per ``kernel_mode()`` with every stock
    fallback counted."""
    from . import kernel_mode

    lead = x.shape[:-1]
    k = x.shape[-1]
    n = int(w8.shape[-1])
    m = int(np.prod(lead)) if lead else 1
    x2 = jnp.asarray(x, jnp.float32).reshape(m, k)
    w8 = jnp.asarray(w8)
    scale = jnp.asarray(scale, jnp.float32).reshape(-1)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(-1)
    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif k > MAX_K:
        reason = "k_over_vmem_budget"
    elif mode == "tpu" and (k % 128 or n % 128 or m % 8):
        # Mosaic lane/sublane alignment: zero-padding K would change the
        # accumulation shape (and bits) vs the stock dot — fall back
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.int8_gemm_fallbacks", 1,
                              reason=reason)
        out2 = stock_int8_gemm(x2, w8, scale, bias, act)
    else:
        telemetry.counter_add("pallas.int8_gemm_dispatches", 1, mode=mode)
        out2 = _pallas_int8_gemm(x2, w8, scale, bias, act,
                                 interpret=mode == "interpret")
    return out2.reshape(tuple(lead) + (n,))
