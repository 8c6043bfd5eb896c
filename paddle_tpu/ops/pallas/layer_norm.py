"""Fused layer_norm forward as a Pallas TPU kernel.

Mirrors the reference's fused LN CUDA kernel (operators/layer_norm_op.cu)
for the normalise-last-dim case transformers use: one VMEM-resident pass
computes mean/var/normalise/affine per row block in fp32. Backward uses the
saved statistics with a jnp formula (XLA fuses it into two kernels — the
bandwidth win is in the forward's single pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 256


def _ln_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # (rows, h)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if scale_ref is not None:
        y = y * scale_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        y = y + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_pallas(x2, scale, bias, eps, interpret):
    from jax.experimental import pallas as pl

    n, h = x2.shape
    rows = BLOCK_ROWS
    while n % rows:
        rows //= 2
    if rows < 16:
        # a block must be sublane-aligned (8 rows f32, 16 bf16) or span
        # the whole axis; a row count with no such divisor runs as one
        rows = n
    grid = (n // rows,)
    # statistics and affine vectors ride as 2-D columns/rows: Mosaic tiles
    # a 1-D f32[n] operand differently from XLA's layout and refuses it
    in_specs = [pl.BlockSpec((rows, h), lambda i: (i, 0))]
    args = [x2]
    n_in = 1
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, h), lambda i: (0, 0)))
        args.append(scale.reshape(1, h))
        n_in += 1
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, h), lambda i: (0, 0)))
        args.append(bias.reshape(1, h))
        n_in += 1

    def kernel(*refs, eps):
        ins, outs = refs[:n_in], refs[n_in:]
        x_ref = ins[0]
        idx = 1
        s_ref = b_ref = None
        if scale is not None:
            s_ref = ins[idx]
            idx += 1
        if bias is not None:
            b_ref = ins[idx]
        _ln_kernel(x_ref, s_ref, b_ref, *outs, eps=eps)

    y, mean, rstd = pl.pallas_call(
        functools.partial(kernel, eps=eps),
        grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((rows, h), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret, name="layer_norm")(*args)
    return y, mean[:, 0], rstd[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x2, scale, bias, eps, interpret):
    return _ln_pallas(x2, scale, bias, eps, interpret)


def _fused_ln_fwd(x2, scale, bias, eps, interpret):
    y, mean, rstd = _ln_pallas(x2, scale, bias, eps, interpret)
    return (y, mean, rstd), (x2, scale, bias, mean, rstd)


def _fused_ln_bwd(eps, interpret, res, cts):
    # cotangents through the mean/rstd outputs are not propagated — they are
    # statistics outputs (the reference's LN Mean/Variance are intermediates
    # for the backward, never training signals)
    dy = cts[0]
    x2, scale, bias, mean, rstd = res
    h = x2.shape[-1]
    xf = x2.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * rstd[:, None]
    dscale = jnp.sum(dyf * xhat, axis=0) if scale is not None else None
    dbias = jnp.sum(dyf, axis=0) if bias is not None else None
    g = dyf * (scale.astype(jnp.float32) if scale is not None else 1.0)
    # dx = rstd * (g - mean(g) - xhat * mean(g * xhat))
    gm = jnp.mean(g, axis=-1, keepdims=True)
    gxm = jnp.mean(g * xhat, axis=-1, keepdims=True)
    dx = (rstd[:, None] * (g - gm - xhat * gxm)).astype(x2.dtype)
    return (dx,
            dscale.astype(scale.dtype) if scale is not None else None,
            dbias.astype(bias.dtype) if bias is not None else None)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, scale=None, bias=None, eps=1e-5):
    """LayerNorm over the last axis. Returns (y, mean, rstd) with mean/rstd
    shaped like x without the last axis. Pallas forward when available."""
    from . import kernel_mode

    lead = x.shape[:-1]
    h = x.shape[-1]
    n = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(n, h)
    mode = kernel_mode()
    if mode == "off" or h % 128 != 0:
        xf = x2.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1)
        xc = xf - mean[:, None]
        var = jnp.mean(xc * xc, axis=-1)
        rstd = 1.0 / jnp.sqrt(var + eps)
        y = xc * rstd[:, None]
        if scale is not None:
            y = y * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        y = y.astype(x.dtype)
    else:
        y, mean, rstd = _fused_ln(x2, scale, bias, eps, mode == "interpret")
    return (y.reshape(x.shape), mean.reshape(lead), rstd.reshape(lead))
