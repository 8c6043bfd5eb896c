"""Causal flash attention with a window and grouped K/V heads (fwd + bwd).

What flash_attention.py's blockwise kernels do not know: a query at t
reads the keys at ``t - window < s <= t`` (all ``s <= t`` without a
window), and query head j reads K/V head ``j // group``. Three kernels,
under names of their own: ``flash_fwd_window``, ``flash_bwd_window_dkv``,
``flash_bwd_window_dq``.

**Layout.** The layer's own arrays, no head-major copy: q, out and their
cotangents [B, S, H*hd], k and v [B, S, Hkv*hd]; a head is a column block
of a BlockSpec (hd a multiple of the 128 lanes). lse [B, H, S] float32.

**Grid** (batch, K/V head, block, reach). One step holds one K/V block and
the ``group`` query heads that read it, so a K/V block is fetched once for
all of them, and dK/dV of a K/V head are summed over its query heads in
VMEM. ``reach`` counts only the blocks a block can reach: a query block i
visits K blocks ``i - reach + 1 .. i`` (``reach`` = ceil((window - 1) /
block) + 1 with a window: 3 of 16 at window 1,024, block 512, s 8,192;
every block up to the diagonal without), and a K block j the query blocks
``j .. j + reach - 1``. A visit that falls off the sequence repeats the
last block's index, so nothing moves, and computes nothing.

Where ``kernel_mode()`` is off or the shape cannot be tiled, the plain
masked form (``masked_attention``) stands in, counted
(``pallas.flash_window_fallbacks``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

BLOCK = 512
NEG_INF = -1e30
VMEM_LIMIT = 64 << 20       # group x (block, 128) statistics; v5e: 128 MiB
FWD_NAME = "flash_fwd_window"
DKV_NAME = "flash_bwd_window_dkv"
DQ_NAME = "flash_bwd_window_dq"


def masked_attention(q, k, v, *, num_heads, num_kv_heads, window=0,
                     scale=None, out_dtype=None):
    """The plain form: q [B, S, H*hd], k, v [B, S, Hkv*hd] -> [B, S, H*hd]
    in `out_dtype` (q's by default); scores and softmax in float32."""
    b, s, _ = q.shape
    hd = q.shape[-1] // num_heads
    g = num_heads // num_kv_heads
    scale = hd ** -0.5 if scale is None else scale
    qh = q.reshape(b, s, num_kv_heads, g, hd)
    kh = k.reshape(b, s, num_kv_heads, hd)
    vh = v.reshape(b, s, num_kv_heads, hd)
    sc = jnp.einsum("bqkgh,bskh->bkgqs", qh, kh,
                    preferred_element_type=jnp.float32) * scale
    t = jnp.arange(s, dtype=jnp.int32)
    ok = t[None, :] <= t[:, None]
    if window:
        ok &= t[None, :] > t[:, None] - window
    p = jax.nn.softmax(jnp.where(ok, sc, NEG_INF), axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(out_dtype or q.dtype)


def reach_of(s, block, window):
    """K blocks a query block visits (and query blocks a K block)."""
    n = s // block
    return n if not window else min(n, -(-(window - 1) // block) + 1)


def _allowed(qb, kb, block, window):
    rows = qb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ok = cols <= rows
    if window:
        ok &= cols > rows - window
    return ok


def _scores(q, k, ok, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(ok, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, window, block, g, hd, reach):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    kb = i - (reach - 1) + j

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kb >= 0)
    def _():
        k, v = k_ref[0], v_ref[0]
        ok = _allowed(i, kb, block, window)
        for h in range(g):
            cols = slice(h * hd, (h + 1) * hd)
            s = _scores(q_ref[0, :, cols], k, ok, scale)
            # a row with no key in this block yet (a window's first block)
            # adds exp(0) here; the first block that holds one of its keys
            # scales that away (alpha = 0), and the diagonal always does
            m_prev, l_prev = m_scr[h, :, :1], l_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:, cols] = acc_scr[:, cols] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == reach - 1)
    def _():
        for h in range(g):
            cols = slice(h * hd, (h + 1) * hd)
            l = l_scr[h, :, :1]
            o_ref[0, :, cols] = (acc_scr[:, cols] / l).astype(o_ref.dtype)
            lse_ref[0, 0, h] = (m_scr[h, :, :1] + jnp.log(l))[:, 0]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, window, block, g, hd,
                reach, nq):
    from jax.experimental import pallas as pl

    jk, t = pl.program_id(2), pl.program_id(3)
    qb = jk + t

    @pl.when(t == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qb < nq)
    def _():
        k, v = k_ref[0], v_ref[0]
        ok = _allowed(qb, jk, block, window)
        for h in range(g):
            cols = slice(h * hd, (h + 1) * hd)
            q, do = q_ref[0, :, cols], do_ref[0, :, cols]
            p = jnp.exp(_scores(q, k, ok, scale)
                        - lse_ref[0, 0, h][:, None])
            dv_scr[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0, h][:, None]) * scale
            dk_scr[...] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(t == reach - 1)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, window, block, g, hd, reach):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    kb = i - (reach - 1) + j

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(kb >= 0)
    def _():
        k, v = k_ref[0], v_ref[0]
        ok = _allowed(i, kb, block, window)
        for h in range(g):
            cols = slice(h * hd, (h + 1) * hd)
            do = do_ref[0, :, cols]
            p = jnp.exp(_scores(q_ref[0, :, cols], k, ok, scale)
                        - lse_ref[0, 0, h][:, None])
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0, h][:, None]) * scale
            dq_scr[:, cols] += jax.lax.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(j == reach - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _geometry(q, k, num_heads, num_kv_heads, block):
    b, s, _ = q.shape
    hd = q.shape[-1] // num_heads
    return b, s, hd, num_heads // num_kv_heads, s // block


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _specs(block, g, hd, reach):
    """BlockSpecs of a (batch, K/V head, query block, reach) grid: the
    query side, the K/V side it visits, and the row statistics."""
    from jax.experimental import pallas as pl

    def kv_block(i, j):
        return jnp.maximum(i - (reach - 1) + j, 0)

    q_spec = pl.BlockSpec((1, block, g * hd), lambda b, n, i, j: (b, i, n))
    kv_spec = pl.BlockSpec((1, block, hd),
                           lambda b, n, i, j: (b, kv_block(i, j), n))
    stat_spec = pl.BlockSpec((1, 1, g, block),
                             lambda b, n, i, j: (b, n, 0, i))
    return q_spec, kv_spec, stat_spec


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "scale", "block", "interpret",
    "out_dtype"))
def _fwd_pallas(q, k, v, *, num_heads, num_kv_heads, window, scale, block,
                interpret, out_dtype=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, hd, g, nq = _geometry(q, k, num_heads, num_kv_heads, block)
    reach = reach_of(s, block, window)
    q_spec, kv_spec, stat_spec = _specs(block, g, hd, reach)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, window=window,
                          block=block, g=g, hd=hd, reach=reach),
        grid=(b, num_kv_heads, nq, reach),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((b, num_kv_heads, g, s),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, block, 128), jnp.float32),
                        pltpu.VMEM((g, block, 128), jnp.float32),
                        pltpu.VMEM((block, g * hd), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name=FWD_NAME)(
            q, k, v)
    return out, lse.reshape(b, num_heads, s)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "scale", "block", "interpret"))
def _bwd_pallas(q, k, v, out, lse, dout, *, num_heads, num_kv_heads, window,
                scale, block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, hd, g, nq = _geometry(q, k, num_heads, num_kv_heads, block)
    reach = reach_of(s, block, window)
    dout = dout.astype(q.dtype)
    delta = jnp.sum((dout.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, s, num_heads, hd), axis=-1)
    delta = jnp.moveaxis(delta, 1, 2).reshape(b, num_kv_heads, g, s)
    lse = lse.reshape(b, num_kv_heads, g, s)
    kw = dict(scale=scale, window=window, block=block, g=g, hd=hd,
              reach=reach)

    # dK, dV: a K block over the query blocks that reach it
    def q_block(jk, t):
        return jnp.minimum(jk + t, nq - 1)

    q_of_k = pl.BlockSpec((1, block, g * hd),
                          lambda b_, n, jk, t: (b_, q_block(jk, t), n))
    stat_of_k = pl.BlockSpec((1, 1, g, block),
                             lambda b_, n, jk, t: (b_, n, 0, q_block(jk, t)))
    k_own = pl.BlockSpec((1, block, hd), lambda b_, n, jk, t: (b_, jk, n))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **kw),
        grid=(b, num_kv_heads, nq, reach),
        in_specs=[q_of_k, k_own, k_own, q_of_k, stat_of_k, stat_of_k],
        out_specs=[k_own, k_own],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32),
                        pltpu.VMEM((block, hd), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name=DKV_NAME)(
            q, k, v, dout, lse, delta)

    q_spec, kv_spec, stat_spec = _specs(block, g, hd, reach)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(b, num_kv_heads, nq, reach),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, g * hd), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name=DQ_NAME)(
            q, k, v, dout, lse, delta)
    return dq, dk, dv


def _pick_block(s, block):
    for c in (block, 256, 128):
        if c <= block and s % c == 0:
            return c
    return None


def window_route(q, k, num_heads, num_kv_heads, block=None):
    """-> (route, block): 'pallas' / 'pallas_interpret' with the block the
    kernels tile the sequence by, or ('reference', None) for the plain
    masked form. A pure function of shapes, dtypes and kernel_mode(): the
    grad op asks what its forward asked."""
    from . import kernel_mode

    mode = kernel_mode()
    if mode == "off":
        return "reference", None
    hd = q.shape[-1] // num_heads
    blk = block or _pick_block(q.shape[1], BLOCK)
    ok = (q.ndim == 3 and q.shape[1] == k.shape[1] and blk
          and q.shape[1] % blk == 0 and num_heads % num_kv_heads == 0
          and q.dtype == k.dtype)
    if mode == "tpu":
        ok = ok and hd % 128 == 0 and blk % 128 == 0
    if not ok:
        return "reference", None
    return ("pallas_interpret" if mode == "interpret" else "pallas"), blk


def _scale(q, num_heads, scale):
    return float(scale) if scale is not None \
        else float((q.shape[-1] // num_heads) ** -0.5)


def flash_window_fwd_lse(q, k, v, *, num_heads, num_kv_heads, window=0,
                         scale=None, block=None, out_dtype=None):
    """(out [B, S, H*hd], lse [B, H, S] float32). On the reference route
    lse is zeros: its backward differentiates the masked form. `out_dtype`
    (q's by default) is what the float32 accumulator is written as: a
    caller that wants it unrounded asks for float32; the saved forward of
    the backward kernels is q's."""
    route, blk = window_route(q, k, num_heads, num_kv_heads, block)
    if route == "reference":
        telemetry.counter_add("pallas.flash_window_fallbacks", 1)
        out = masked_attention(q, k, v, num_heads=num_heads,
                               num_kv_heads=num_kv_heads, window=window,
                               scale=scale, out_dtype=out_dtype)
        return out, jnp.zeros((q.shape[0], num_heads, q.shape[1]),
                              jnp.float32)
    telemetry.counter_add("pallas.flash_window_dispatches", 1)
    return _fwd_pallas(q, k, v, num_heads=num_heads,
                       num_kv_heads=num_kv_heads, window=int(window),
                       scale=_scale(q, num_heads, scale), block=blk,
                       interpret=route == "pallas_interpret",
                       out_dtype=out_dtype)


def flash_window_bwd(q, k, v, out, lse, dout, *, num_heads, num_kv_heads,
                     window=0, scale=None, block=None):
    """(dq, dk, dv) from the saved forward (out, lse); on the reference
    route the masked form's own vjp."""
    route, blk = window_route(q, k, num_heads, num_kv_heads, block)
    if route == "reference":
        _, vjp = jax.vjp(functools.partial(
            masked_attention, num_heads=num_heads,
            num_kv_heads=num_kv_heads, window=window, scale=scale), q, k, v)
        return vjp(dout.astype(out.dtype).reshape(out.shape))
    return _bwd_pallas(q, k, v, out, lse, dout, num_heads=num_heads,
                       num_kv_heads=num_kv_heads, window=int(window),
                       scale=_scale(q, num_heads, scale), block=blk,
                       interpret=route == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_window_attention(q, k, v, num_heads, num_kv_heads, window=0,
                           scale=None, block=None):
    """Causal attention over ``t - window < s <= t`` for grouped heads,
    differentiable: the kernels' saved (out, lse) feed their backward."""
    return flash_window_fwd_lse(q, k, v, num_heads=num_heads,
                                num_kv_heads=num_kv_heads, window=window,
                                scale=scale, block=block)[0]


def _vjp_fwd(q, k, v, num_heads, num_kv_heads, window, scale, block):
    out, lse = flash_window_fwd_lse(q, k, v, num_heads=num_heads,
                                    num_kv_heads=num_kv_heads, window=window,
                                    scale=scale, block=block)
    return out, (q, k, v, out, lse)


def _vjp_bwd(num_heads, num_kv_heads, window, scale, block, res, dout):
    return flash_window_bwd(*res, dout, num_heads=num_heads,
                            num_kv_heads=num_kv_heads, window=window,
                            scale=scale, block=block)


flash_window_attention.defvjp(_vjp_fwd, _vjp_bwd)
