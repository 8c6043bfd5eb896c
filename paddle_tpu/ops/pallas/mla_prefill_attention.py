"""Whole-prompt causal attention in the EXPANDED form of multi-head latent
attention (the DeepSeek-V3 block's prefill), blockwise with an online
softmax: the scores never leave VMEM.

Head h's key is ``[k_n (nope), k_r (rope)]`` with ``k_r`` ONE rotated key
for all heads, its query ``[q_n, q_r]``, its value ``v`` of another width
than its key's. The two parts of a score are two dots, so the shared key
is never broadcast over heads and neither part is padded to the other's
width; the rotary part rides in whole lane tiles (64 values in 128, the
tail zero in query and key alike).

Why a kernel: the stock lowering (ops/llm_ops.py) writes a block's float32
scores to HBM and reads them back for the maximum, the exponentials, the
sum and the weighted sum: 16 bytes a score, 43 GB for a 4096-token prompt
over 64 heads and 5 layers, which took ~100 of the prefill's 166 ms on the
chip (PR 33) where the arithmetic is 1.7 TFLOP.

Grid (heads, query blocks, key blocks), the key blocks innermost and in
order: block (h, i, j) adds keys ``j x block`` to the running maximum, sum
and accumulator of queries ``i x block``; blocks above the diagonal are
neither computed nor copied (their index maps repeat the diagonal's
block, which is already there). Only the diagonal block is masked. A
padded prompt's tail lies after every real token, so causality alone
keeps it out of real rows. Products take the inputs' dtype and accumulate
in float32; maximum, exponentials and sums are float32.
``name="mla_prefill_attention"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "mla_prefill_attention"
BLOCK = 512        # queries and keys a block; a prompt bucket's divisor
_LANES = 128


def stock_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                                block_q=256):
    """The stock lowering, and the kernel's oracle. q_nope [S, n, nope],
    q_rope [S, n, rope], k_nope [S, n, nope], k_rope [S, rope], v
    [S, n, dv] -> float32 [S, n, dv]. Queries go in blocks of `block_q`,
    each over the keys at or before its last query (static slices)."""
    s, n, _ = q_nope.shape
    bq = min(block_q, s)
    if s % bq:
        raise ValueError(f"prompt length {s} is no multiple of block_q {bq}")
    outs = []
    for q0 in range(0, s, bq):
        end = q0 + bq
        sc = (jnp.einsum("qhd,shd->hqs", q_nope[q0:end], k_nope[:end],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("qhr,sr->hqs", q_rope[q0:end], k_rope[:end],
                           preferred_element_type=jnp.float32)) * scale
        ok = jnp.arange(end, dtype=jnp.int32)[None, :] \
            <= q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        p = jax.nn.softmax(jnp.where(ok, sc, -1e9), axis=-1)
        outs.append(jnp.einsum("hqs,shv->qhv", p.astype(v.dtype), v[:end],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=0)


def _kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale, block):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        nt = (((1,), (1,)), ((), ()))       # q @ k^T
        s = (jax.lax.dot_general(qn_ref[...], kn_ref[...], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[...], kr_ref[...], nt,
                                   preferred_element_type=jnp.float32)
             ) * scale                                   # (block, block)
        row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        # below the diagonal block every key is at or before every query
        s = jnp.where((j < i) | (col <= row), s, -1e30)
        m_old = m_ref[...]                               # (block, 128)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr[:, :acc_ref.shape[1]] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...][:, :acc_ref.shape[1]]


def _pallas_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                                  block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n, nope = q_nope.shape
    dv = v.shape[2]
    rope = -(-q_rope.shape[2] // _LANES) * _LANES
    pad = rope - q_rope.shape[2]
    # head-major, the rotary parts in whole lane tiles
    qn = jnp.swapaxes(q_nope, 0, 1)                      # [n, S, nope]
    kn = jnp.swapaxes(k_nope, 0, 1)
    vh = jnp.swapaxes(v, 0, 1)                           # [n, S, dv]
    qr = jnp.pad(jnp.swapaxes(q_rope, 0, 1), ((0, 0), (0, 0), (0, pad)))
    kr = jnp.pad(k_rope, ((0, 0), (0, pad)))             # [S, rope]
    blocks = s // block

    def of_query(h, i, j):
        return (h, i, 0)

    def of_key(h, i, j):
        return (h, jnp.minimum(j, i), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block),
        grid=(n, blocks, blocks),
        in_specs=[pl.BlockSpec((None, block, nope), of_query),
                  pl.BlockSpec((None, block, rope), of_query),
                  pl.BlockSpec((None, block, nope), of_key),
                  pl.BlockSpec((block, rope),
                               lambda h, i, j: (jnp.minimum(j, i), 0)),
                  pl.BlockSpec((None, block, dv), of_key)],
        out_specs=pl.BlockSpec((None, block, dv), of_query),
        out_shape=jax.ShapeDtypeStruct((n, s, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAME)(qn, qr, kn, kr, vh)
    return jnp.swapaxes(out, 0, 1)                       # [S, n, dv]


def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal attention of one prompt. q_nope, k_nope [S, n, nope]; q_rope
    [S, n, rope]; k_rope [S, rope] (one key for all heads); v [S, n, dv].
    Returns float32 [S, n, dv]. Routed per ``kernel_mode()``; a stock
    fallback is counted in ``pallas.mla_prefill_fallbacks``."""
    from . import kernel_mode

    s, _n, nope = q_nope.shape
    dv = v.shape[2]
    block = min(BLOCK, s)
    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif s % block:
        reason = "length"
    elif mode == "tpu" and (nope % _LANES or dv % _LANES or block % 128):
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.mla_prefill_fallbacks", 1,
                              reason=reason)
        return stock_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope,
                                           v, scale)
    telemetry.counter_add("pallas.mla_prefill_dispatches", 1, mode=mode)
    return _pallas_mla_prefill_attention(
        q_nope, q_rope, k_nope, k_rope, v, float(scale), block,
        interpret=mode == "interpret")
