"""Fused AdamW parameter update as a single Pallas elementwise kernel.

TPU analog of the reference's fused optimizer passes
(ir/fuse_optimizer_ops_pass/fuse_adam_op_pass.cc): one kernel reads
param/grad/moments and writes param/moments back, instead of a chain of
elementwise HLOs. XLA usually fuses the chain anyway; the kernel guarantees
it and pins fp32 moment math for bf16 params.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_LANES = 128
_BLOCK = 1024  # rows per grid step (x 128 lanes)


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  po_ref, mo_ref, vo_ref, *, beta1, beta2, eps, wd):
    lr = sc_ref[0, 0]
    bp1 = sc_ref[0, 1]   # 1 - beta1^t
    bp2 = sc_ref[0, 2]   # 1 - beta2^t
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    # paddle AdamFunctor form (operators/optimizers/adam_op.h): matches the
    # unfused `adam` op lowering exactly so backends agree bitwise
    lr_t = lr * jnp.sqrt(bp2) / bp1
    upd = lr_t * m_new / (jnp.sqrt(v_new) + eps) + lr * wd * p
    po_ref[...] = (p - upd).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def fused_adamw(param, grad, m, v, lr, beta1, beta2, eps, weight_decay,
                beta1_pow, beta2_pow):
    """One fused AdamW step. Returns (param', m', v').

    lr may be a traced scalar; beta1_pow/beta2_pow are beta^t scalars
    (traced). Falls back to jnp when no TPU/interpreter backend.
    """
    from . import kernel_mode

    mode = kernel_mode()
    lr = jnp.asarray(lr, jnp.float32).reshape(())
    bp1 = 1.0 - jnp.asarray(beta1_pow, jnp.float32).reshape(())
    bp2 = 1.0 - jnp.asarray(beta2_pow, jnp.float32).reshape(())

    size = int(np.prod(param.shape)) if param.shape else 1
    if mode == "off" or size < _LANES:
        pf = param.astype(jnp.float32)
        gf = grad.astype(jnp.float32)
        m_new = beta1 * m.astype(jnp.float32) + (1.0 - beta1) * gf
        v_new = beta2 * v.astype(jnp.float32) + (1.0 - beta2) * gf * gf
        lr_t = lr * jnp.sqrt(bp2) / bp1
        upd = lr_t * m_new / (jnp.sqrt(v_new) + eps) + lr * weight_decay * pf
        return ((pf - upd).astype(param.dtype),
                m_new.astype(m.dtype), v_new.astype(v.dtype))

    from jax.experimental import pallas as pl

    # flatten + pad to (rows, 128)
    rows = int(np.ceil(size / _LANES))
    block = min(_BLOCK, rows)
    rows_pad = int(np.ceil(rows / block) * block)
    pad = rows_pad * _LANES - size

    def flat(t):
        f = t.reshape(-1)
        if pad:
            f = jnp.pad(f, (0, pad))
        return f.reshape(rows_pad, _LANES)

    scalars = jnp.stack([lr, bp1, bp2]).reshape(1, 3)
    grid = (rows_pad // block,)
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    sc_spec = pl.BlockSpec((1, 3), lambda i: (0, 0))
    p2, m2, v2 = pl.pallas_call(
        functools.partial(_adamw_kernel, beta1=float(beta1),
                          beta2=float(beta2), eps=float(eps),
                          wd=float(weight_decay)),
        grid=grid,
        in_specs=[spec, spec, spec, spec, sc_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, _LANES), param.dtype),
                   jax.ShapeDtypeStruct((rows_pad, _LANES), m.dtype),
                   jax.ShapeDtypeStruct((rows_pad, _LANES), v.dtype)],
        interpret=mode == "interpret", name="fused_adam",
    )(flat(param), flat(grad), flat(m), flat(v), scalars)

    def unflat(t2, like):
        return t2.reshape(-1)[:size].reshape(like.shape)

    return unflat(p2, param), unflat(m2, m), unflat(v2, v)
