"""The residual path of several streams (manifold-constrained
hyper-connections, arXiv:2512.24880; models/motif3.py) around one
sublayer, as two kernels over tiles of tokens.

A token carries ``n`` streams of width ``C`` (``X [T, n x C]`` float32,
stream i at columns ``[i C, (i + 1) C)``). Around a sublayer F:

  mhc_pre    xt = RMS_gamma(X) over all nC values;  l = xt Phi
             H_pre = sigmoid(a_pre l_pre + b_pre)            [n]
             H_post = 2 sigmoid(a_post l_post + b_post)      [n]
             H_res = Sinkhorn(exp(a_res l_res + B_res))      [n x n]
               (`res_clamp`: the logits clamped first, models/xing4.py)
             u = sum_i H_pre[i] X[i]                          -> F(RMS(u))
  mhc_post   X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, clamped

The three maps ride in ONE array ``maps [T, 128]`` float32, a lane tile a
token: ``[H_pre (n), H_post (n), H_res (n x n, row-major), zeros]`` in the
parameters' own order, so `mhc_post` reads one small block beside its
streams.

Why kernels: the path is bytes. A token moves ``nC`` floats in and ``C``
out in `mhc_pre`, ``nC + C`` in and ``nC`` out in `mhc_post`: 224 KB a
token a sublayer at 4 x 4096, where a plain residual moves 48. The stock
lowerings below (the kernels' oracles and counted fallbacks) leave XLA
several passes over X: the sum of squares, the normed and rounded copy
for the product, the weighted sums.

**mhc_pre** (``name="mhc_pre"``), grid over tiles of ``TILE`` tokens: X's
tile is read ONCE into VMEM; its rows' sum of squares, the normed tile
rounded to Phi's dtype, ONE product with Phi (padded to a lane tile of
columns) in float32, the maps on that [tile, 128] array with tokens on
sublanes, and ``u`` from the same resident tile. Sinkhorn's row and column
sums are butterflies of lane rotations (`pltpu.roll`) and selects over the
``n x n`` lanes (aligned groups of n for a row, stride n for a column), so
no sum leaves float32 and nothing is transposed; ``n`` a power of two.

**mhc_post** (``name="mhc_post"``), same grid: X's tile, y's and the maps'
read once, each output stream ``n + 1`` multiply-adds of [tile, C] by a
column of the maps broadcast over lanes, X' written once.

Dispatches and fallbacks: ``pallas.mhc_dispatches`` (``kernel=``) and
``pallas.mhc_fallbacks`` (``kernel=``, ``reason=``: ``mode_off``;
``shape``: rows no multiple of 8, C no multiple of 128, n no power of two
or maps wider than a lane tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

PRE_KERNEL, POST_KERNEL = "mhc_pre", "mhc_post"
MAPS_WIDTH = 128            # a lane tile: 2n + n^2 maps of a token, zeros
TILE = 64                   # tokens a grid step: 4 MiB of 4 x 4096 floats
VMEM_LIMIT = 100 << 20      # v5e has 128 MiB


def maps_layout(n: int):
    """(pre, post, res) column slices of the maps array."""
    return slice(0, n), slice(n, 2 * n), slice(2 * n, 2 * n + n * n)


def stock_mhc_pre(x, gamma, phi, scale, bias, *, n, iters, eps,
                  res_clamp=None, sinkhorn_eps=0.0):
    """x [T, nC] float32, gamma [nC], phi [nC, 2n + n^2] (the product
    rounds the normed row to its dtype, float32 accumulation), scale [3]
    (a_pre, a_post, a_res), bias [2n + n^2] -> (u [T, C], maps [T, 128]).
    `res_clamp` (lo, hi) clamps the residual map's logits before the
    exponential and `sinkhorn_eps` is added to every Sinkhorn denominator
    (models/xing4.py)."""
    t = x.shape[0]
    c = x.shape[1] // n
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    xt = x * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
    logits = jnp.dot(xt.astype(phi.dtype), phi,
                     preferred_element_type=jnp.float32)
    pre, post, res = maps_layout(n)
    scale = scale.astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(scale[0] * logits[:, pre] + bias[pre])
    h_post = 2.0 * jax.nn.sigmoid(scale[1] * logits[:, post] + bias[post])
    z_res = scale[2] * logits[:, res] + bias[res]
    if res_clamp is not None:
        z_res = jnp.clip(z_res, res_clamp[0], res_clamp[1])
    m = jnp.exp(z_res).reshape(t, n, n)
    for _ in range(iters):
        for axis in (2, 1):
            total = jnp.sum(m, axis=axis, keepdims=True)
            m = m / (total + sinkhorn_eps if sinkhorn_eps else total)
    # weighted sums stream by stream: float32 multiply-adds on any backend
    # (an einsum is a product, which a TPU rounds to bfloat16 by default)
    u = sum(h_pre[:, i:i + 1] * x[:, i * c:(i + 1) * c] for i in range(n))
    maps = jnp.concatenate([h_pre, h_post, m.reshape(t, n * n)], axis=1)
    return u, jnp.pad(maps, ((0, 0), (0, MAPS_WIDTH - maps.shape[1])))


def stock_mhc_post(x, y, maps, *, n, clamp):
    """x [T, nC], y [T, C], maps [T, 128] -> X' [T, nC] float32."""
    c = x.shape[1] // n
    _, post, res = maps_layout(n)
    h_res, h_post = maps[:, res], maps[:, post]
    out = [h_post[:, i:i + 1] * y + sum(
        h_res[:, i * n + j:i * n + j + 1] * x[:, j * c:(j + 1) * c]
        for j in range(n)) for i in range(n)]
    return jnp.clip(jnp.concatenate(out, axis=1), -clamp, clamp)


def _butterfly(v, rel, span):
    """v + its partner `span` lanes away within the aligned block of
    2 x span lanes (`rel`: the lane counted from the first of the n x n
    lanes, mod 128). Spans 1 .. n/2 in turn give every lane the sum of its
    aligned group of n (a row of the n x n map); spans n .. n^2/2 the sum
    of its stride-n class (a column)."""
    from jax.experimental.pallas import tpu as pltpu

    lower = (rel % (2 * span)) < span
    return v + jnp.where(lower, pltpu.roll(v, MAPS_WIDTH - span, 1),
                         pltpu.roll(v, span, 1))


def _pre_kernel(x_ref, gamma_ref, phi_ref, sb_ref, u_ref, maps_ref, *, n,
                iters, eps, res_clamp, sinkhorn_eps):
    x = x_ref[...]                                         # [tile, nC]
    c = x.shape[1] // n
    ms = jnp.sum(x * x, axis=1, keepdims=True) * (1.0 / x.shape[1])
    xt = x * jax.lax.rsqrt(ms + eps) * gamma_ref[...]
    logits = jnp.dot(xt.astype(phi_ref.dtype), phi_ref[...],
                     preferred_element_type=jnp.float32)   # [tile, 128]
    z = logits * sb_ref[0:1, :] + sb_ref[1:2, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    is_res = (lane >= 2 * n) & (lane < 2 * n + n * n)
    rel = lane + (MAPS_WIDTH - 2 * n)
    # lanes outside the n x n block hold 1: their sums stay finite and
    # never reach the block (a butterfly stays inside aligned groups)
    z_res = z if res_clamp is None else jnp.clip(z, res_clamp[0],
                                                 res_clamp[1])
    m = jnp.where(is_res, jnp.exp(z_res), 1.0)

    def sinkhorn(_, m):
        for first in (1, n):            # rows, then columns, to sum 1
            total, span = m, first
            while span < first * n:
                total = _butterfly(total, rel, span)
                span *= 2
            m = m / (total + sinkhorn_eps if sinkhorn_eps else total)
        return m

    m = jax.lax.fori_loop(0, iters, sinkhorn, m)
    gate = jax.nn.sigmoid(z)
    maps = jnp.where(is_res, m,
                     jnp.where(lane < n, gate,
                               jnp.where(lane < 2 * n, 2.0 * gate, 0.0)))
    maps_ref[...] = maps
    u = maps[:, 0:1] * x[:, :c]
    for i in range(1, n):
        u += maps[:, i:i + 1] * x[:, i * c:(i + 1) * c]
    u_ref[...] = u


def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n, clamp):
    c = y_ref.shape[1]
    maps = maps_ref[...]
    y = y_ref[...]
    for i in range(n):
        acc = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            at = 2 * n + i * n + j
            acc += maps[:, at:at + 1] * x_ref[:, j * c:(j + 1) * c]
        o_ref[:, i * c:(i + 1) * c] = jnp.clip(acc, -clamp, clamp)


def _padded_phi(phi, scale, bias, n):
    """Phi with its columns padded to a lane tile, and [8, 128] float32
    whose row 0 is each lane's scale (a_pre, a_post or a_res) and row 1 its
    bias."""
    width = phi.shape[1]
    phi = jnp.pad(phi, ((0, 0), (0, MAPS_WIDTH - width)))
    lanes = jnp.concatenate([
        jnp.broadcast_to(scale[k].astype(jnp.float32), (lanes_of,))
        for k, lanes_of in enumerate((n, n, n * n))])
    sb = jnp.zeros((8, MAPS_WIDTH), jnp.float32)
    sb = sb.at[0, :width].set(lanes).at[1, :width].set(
        bias.astype(jnp.float32))
    return phi, sb


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "tile",
                                             "interpret", "res_clamp",
                                             "sinkhorn_eps"))
def _pallas_mhc_pre(x, gamma, phi, scale, bias, *, n, iters, eps, tile,
                    interpret, res_clamp=None, sinkhorn_eps=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, nc = x.shape
    c = nc // n
    phi, sb = _padded_phi(phi, scale, bias, n)
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, iters=iters, eps=eps,
                          res_clamp=res_clamp, sinkhorn_eps=sinkhorn_eps),
        grid=(t // tile,),
        in_specs=[pl.BlockSpec((tile, nc), lambda i: (i, 0)),
                  pl.BlockSpec((1, nc), lambda i: (0, 0)),
                  pl.BlockSpec((nc, MAPS_WIDTH), lambda i: (0, 0)),
                  pl.BlockSpec((8, MAPS_WIDTH), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0)),
                   pl.BlockSpec((tile, MAPS_WIDTH), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, c), jnp.float32),
                   jax.ShapeDtypeStruct((t, MAPS_WIDTH), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=PRE_KERNEL)(
            x, gamma.astype(jnp.float32).reshape(1, nc), phi, sb)


@functools.partial(jax.jit, static_argnames=("n", "clamp", "tile",
                                             "interpret"))
def _pallas_mhc_post(x, y, maps, *, n, clamp, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, nc = x.shape
    c = nc // n
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, clamp=clamp),
        grid=(t // tile,),
        in_specs=[pl.BlockSpec((tile, nc), lambda i: (i, 0)),
                  pl.BlockSpec((tile, c), lambda i: (i, 0)),
                  pl.BlockSpec((tile, MAPS_WIDTH), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, nc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=POST_KERNEL)(x, y, maps)


def _route(kernel, t, c, n):
    """(mode, tile) for the kernel, or (None, 0) with the fallback
    counted."""
    from . import kernel_mode

    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif (t % 8 or c % 128 or n < 2 or n & (n - 1)
          or 2 * n + n * n > MAPS_WIDTH):
        reason = "shape"
    if reason is not None:
        telemetry.counter_add("pallas.mhc_fallbacks", 1, kernel=kernel,
                              reason=reason)
        return None, 0
    telemetry.counter_add("pallas.mhc_dispatches", 1, kernel=kernel,
                          mode=mode)
    tile = TILE
    while t % tile:
        tile //= 2
    return mode, tile


def mhc_pre(x, gamma, phi, scale, bias, *, n, iters, eps, res_clamp=None,
            sinkhorn_eps=0.0):
    """(u [T, C], maps [T, 128]) of x [T, nC] float32 (module docstring).
    Routed per ``kernel_mode()``; a stock fallback is counted. `res_clamp`
    (lo, hi): the residual map's logits clamped before the exponential;
    `sinkhorn_eps` joins every Sinkhorn denominator."""
    mode, tile = _route(PRE_KERNEL, x.shape[0], x.shape[1] // n, n)
    if mode is None:
        return stock_mhc_pre(x, gamma, phi, scale, bias, n=n, iters=iters,
                             eps=eps, res_clamp=res_clamp,
                             sinkhorn_eps=sinkhorn_eps)
    return _pallas_mhc_pre(x, gamma, phi, scale, bias, n=n, iters=iters,
                           eps=float(eps), tile=tile,
                           interpret=mode == "interpret",
                           res_clamp=res_clamp,
                           sinkhorn_eps=float(sinkhorn_eps))


def mhc_post(x, y, maps, *, n, clamp):
    """X' [T, nC] of x [T, nC], y [T, C] and `mhc_pre`'s maps."""
    mode, tile = _route(POST_KERNEL, x.shape[0], y.shape[1], n)
    if mode is None:
        return stock_mhc_post(x, y, maps, n=n, clamp=clamp)
    return _pallas_mhc_post(x, y, maps, n=n, clamp=float(clamp), tile=tile,
                            interpret=mode == "interpret")
