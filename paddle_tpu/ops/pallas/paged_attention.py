"""Paged cached-KV decode attention as a Pallas TPU kernel.

One autoregressive decode step of attention against the paged KV pool
(serving/kv_cache.py) — the kernel form of the ``cached_kv_attention``
op's attend phase. The stock lowering gathers every row's pages into a
dense [B, MP*P, kvdim] context in HBM (``pool[table]``) and runs stock
einsum attention over it: two full passes over the row's KV through HBM
plus the gathered copy itself — memory-bound on TPU. This kernel walks
the page table directly: per batch row, each owned page is DMA'd
HBM→VMEM exactly once (block-gather per page, no dense gathered tensor
in HBM), scores/softmax/weighted-sum run in VMEM, and stale positions
(the pool recycles pages across requests) are masked so their
contribution is exactly zero.

Softmax discipline, pinned for the bitwise gates:
  * when the row's whole context fits one KV chunk
    (FLAGS_pallas_kv_chunk_tokens, default 1024 ≥ every repo-scale
    decode config; narrowed by the VMEM budget at serving widths, see
    ``_chunk_pages``) the kernel runs the exact single-pass softmax with
    the SAME op sequence as the stock lowering — ``PT_PALLAS=interpret``
    decode output is bitwise-identical to ``PT_PALLAS=off``;
  * longer contexts stream KV chunks through online-softmax
    accumulation (running max/sum rescaling, flash-attention style) —
    mathematically identical, last-ulp different, and exercised by the
    numpy-oracle OpTests with the chunk flag forced small.

Dispatch/fallback counts land as ``pallas.paged_attn_dispatches`` /
``pallas.paged_attn_fallbacks``; the chunk geometry is part of
``kernels_fingerprint()`` so compile caches key on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry
from ...core.flags import flag as _flag


def paged_attn_fingerprint() -> str:
    """Chunk-geometry fingerprint for the compile-cache keys (the chunk
    flag changes the lowering, so it must recompile, not reuse)."""
    return (f"pa.c{int(_flag('pallas_kv_chunk_tokens'))}"
            f"v{_KV_SCRATCH_BYTES >> 20}")


def stock_paged_attention(q, pool_k, pool_v, table, pos, n, hd, scale):
    """The counted stock lowering (and the fallback/oracle reference):
    dense page gather + stock einsum attention, positions past the row's
    own masked to -1e9 BEFORE the softmax — byte-identical to what
    ops/attention_ops.cached_kv_attention lowered to before the kernel
    existed."""
    b = q.shape[0]
    page = int(pool_k.shape[1])
    mp = int(table.shape[1])
    ctx_k = pool_k[table].reshape(b, mp * page, -1)
    ctx_v = pool_v[table].reshape(b, mp * page, -1)
    qh = q.reshape(b, n, hd)
    kh = ctx_k.reshape(b, mp * page, n, hd)
    vh = ctx_v.reshape(b, mp * page, n, hd)
    scores = jnp.einsum("bnh,bsnh->bns", qh, kh) * scale
    mask = jnp.arange(mp * page, dtype=jnp.int32)[None, None, :] \
        <= pos[:, None, None]
    scores = jnp.where(mask, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bns,bsnh->bnh", probs, vh).reshape(b, n * hd)


def _chunk_starts(mp: int, chunk_pages: int):
    return list(range(0, mp, chunk_pages))


# Both KV scratches (K and V chunk, f32) must sit inside Mosaic's scoped
# VMEM next to the kernel's temporaries: the chunk shrinks with the KV
# width so 2 * chunk_tokens * kvdim * 4 B stays under this.
_KV_SCRATCH_BYTES = 4 << 20


def _chunk_pages(page: int, mp: int, kvdim: int) -> int:
    """Pages per KV chunk: the flag is the cap, the VMEM budget the
    bound (d_model 2048 -> 256 tokens; repo-scale widths keep the
    flag's value, so the single-chunk bitwise regime is unchanged)."""
    fit = _KV_SCRATCH_BYTES // (2 * 4 * kvdim)
    chunk_tokens = max(min(int(_flag("pallas_kv_chunk_tokens")), fit),
                       page)
    return max(1, min(chunk_tokens // page, mp))


def _pa_kernel(table_ref, pos_ref, q_ref, pk_ref, pv_ref, o_ref, *,
               n, hd, page, mp, chunk_pages, scale):
    """Grid (B,): row i gathers its pages chunk by chunk into token-major
    (tokens, n*hd) VMEM scratch via async DMA and attends the row's
    query over them. Heads are lane slices of the scratch — Mosaic has
    no lowering for the (tokens, n, hd) reshape. Scores are one 2-D
    q_h @ k_h^T dot per head and the weighted sum one head-batched dot:
    on CPU XLA exactly these two forms accumulate in the stock einsums'
    order (the bitwise gates), and Mosaic lowers both."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pos = pos_ref[i]
    starts = _chunk_starts(mp, chunk_pages)
    nt = (((1,), (1,)), ((), ()))       # q_h @ k_h^T

    def body(ks_ref, vs_ref, sem):
        def head(ref, h, s_tok):
            # one head's (s_tok, hd) lane slice of the token-major scratch
            return ref[:s_tok, h * hd:(h + 1) * hd]

        def gather(base, count):
            # block-gather: each owned page moves HBM->VMEM exactly once
            copies = []
            for j in range(count):
                pid = table_ref[i, base + j]
                rows = pl.ds(j * page, page)
                copies.append(pltpu.make_async_copy(
                    pk_ref.at[pid], ks_ref.at[rows], sem))
                copies.append(pltpu.make_async_copy(
                    pv_ref.at[pid], vs_ref.at[rows], sem))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            s_tok = count * page
            s = jnp.stack([jax.lax.dot_general(
                q_ref[h], head(ks_ref, h, s_tok), nt,
                preferred_element_type=jnp.float32)
                for h in range(n)]) * scale          # (n, 1, s_tok)
            # stale-position mask (pool pages are recycled across
            # requests): iota of rank >= 2 — TPU rejects 1-D
            idx = jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, s_tok), 2) + base * page
            valid = idx <= pos
            vh = jnp.stack([head(vs_ref, h, s_tok) for h in range(n)])
            return jnp.where(valid, s, -1e9), valid, vh

        if len(starts) == 1:
            # exact single-pass softmax, same op sequence as the stock
            # lowering: normalize-then-dot (bitwise with PT_PALLAS=off)
            s, _valid, vh = gather(0, mp)
            p = jax.nn.softmax(s, axis=-1)
            o_ref[...] = jnp.einsum("nqs,nsh->nqh", p, vh,
                                    preferred_element_type=jnp.float32)
            return
        # online-softmax accumulation across KV chunks (running max
        # rescale); masked weights multiplied to exact zero
        m_run = jnp.full((n, 1, 1), -jnp.inf, jnp.float32)
        l_run = jnp.zeros((n, 1, 1), jnp.float32)
        acc = jnp.zeros((n, 1, hd), jnp.float32)
        for base in starts:
            s, valid, vh = gather(base, min(chunk_pages, mp - base))
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_run - m_new)
            w = jnp.exp(s - m_new) * valid.astype(jnp.float32)
            l_run = l_run * corr + jnp.sum(w, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "nqs,nsh->nqh", w, vh, preferred_element_type=jnp.float32)
            m_run = m_new
        o_ref[...] = acc / l_run

    chunk_tokens = min(chunk_pages, mp) * page
    pl.run_scoped(
        body,
        ks_ref=pltpu.VMEM((chunk_tokens, n * hd), jnp.float32),
        vs_ref=pltpu.VMEM((chunk_tokens, n * hd), jnp.float32),
        sem=pltpu.SemaphoreType.DMA(()))


def _pallas_paged_attention(q, pool_k, pool_v, table, pos, n, hd, scale,
                            chunk_pages, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = q.shape[0]
    page = int(pool_k.shape[1])
    mp = int(table.shape[1])
    # q/out ride head-major (B, n, hd) with the row squeezed: a (1, n*hd)
    # block breaks Mosaic's (8, 128) rule, a whole (n, hd) slab does not
    row = pl.BlockSpec((None, n, 1, hd), lambda i, t, p: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page table + positions
        grid=(b,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row)
    s_tok = mp * page
    out = pl.pallas_call(
        functools.partial(_pa_kernel, n=n, hd=hd, page=page, mp=mp,
                          chunk_pages=chunk_pages, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, 1, hd), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n * s_tok * hd,
            bytes_accessed=2 * b * s_tok * n * hd * 4 + 2 * b * n * hd * 4,
            transcendentals=b * n * s_tok),
        interpret=interpret, name="paged_attention")(
            table, pos, q.reshape(b, n, 1, hd), pool_k, pool_v)
    return out.reshape(b, n * hd)


def paged_decode_attention(q, pool_k, pool_v, table, positions,
                           num_heads, head_dim, scale):
    """Attend each row's query over its own paged KV context.

    q [B, nh*hd] fp32 (the step's projected query); PoolK/PoolV
    [N, P, kvdim] (already holding the step's K/V — the write phase is
    the op layer's, shared by every route); table [B, MP] int32 physical
    page ids; positions [B] int32 (context = 0..pos). Returns
    [B, nh*hd]. Routes per ``kernel_mode()`` with every stock fallback
    counted."""
    from . import kernel_mode

    n, hd = int(num_heads), int(head_dim)
    q = jnp.asarray(q, jnp.float32)
    pos = jnp.asarray(positions).reshape(-1)
    page = int(pool_k.shape[1])
    mp = int(table.shape[1])
    kvdim = int(pool_k.shape[2])
    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif kvdim != n * hd:
        reason = "kvdim_mismatch"
    elif mode == "tpu" and (kvdim % 128 or page % 8):
        # Mosaic lane/sublane alignment on the per-page VMEM blocks
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.paged_attn_fallbacks", 1,
                              reason=reason)
        return stock_paged_attention(q, pool_k, pool_v, table, pos,
                                     n, hd, scale)
    chunk_pages = _chunk_pages(page, mp, kvdim)
    telemetry.counter_add("pallas.paged_attn_dispatches", 1, mode=mode,
                          chunks=-(-mp // chunk_pages))
    return _pallas_paged_attention(q, pool_k, pool_v, table, pos, n, hd,
                                   float(scale), chunk_pages,
                                   interpret=mode == "interpret")
