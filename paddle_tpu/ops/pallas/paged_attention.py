"""Paged cached-KV decode attention as a Pallas TPU kernel.

One autoregressive decode step of attention against the paged KV pool
(serving/kv_cache.py) — the kernel form of the ``cached_kv_attention``
op's attend phase. The stock lowering gathers every row's pages into a
dense [B, MP*P, kvdim] context in HBM (``pool[table]``) and runs stock
einsum attention over it: two full passes over the row's KV through HBM
plus the gathered copy itself — memory-bound on TPU. This kernel walks
the page table directly: per batch row pages are DMA'd HBM→VMEM
(block-gather per page, no dense gathered tensor in HBM),
scores/softmax/weighted-sum run in VMEM, and stale positions (the pool
recycles pages across requests) are masked so their contribution is
exactly zero.

Two paths, chosen by the table's width against the chunk
(``_chunk_pages``: FLAGS_pallas_kv_chunk_tokens is the cap, default
1024 ≥ every repo-scale decode config; the VMEM budget is the bound at
serving widths), each with its softmax discipline:
  * the whole table fits one KV chunk: every page of the table is
    copied once and the kernel runs the exact single-pass softmax with
    the SAME op sequence as the stock lowering — ``PT_PALLAS=interpret``
    decode output is bitwise-identical to ``PT_PALLAS=off`` (the pinned
    gates);
  * wider tables stream KV chunks: a row walks only the chunks, and of
    its last chunk only the pages, that hold a token at or before its
    ``pos`` — each page the row holds is DMA'd exactly once and no
    other page is read (an empty slot reads one page) — and the next
    chunk's copies (the row's next, or the next row's first) run while
    this chunk is attended, two K and two V buffers in turn. Chunks
    join by online-softmax accumulation (running max/sum rescaling,
    flash-attention style) — mathematically identical, last-ulp
    different, and exercised by the numpy-oracle OpTests with the chunk
    flag forced small.

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
            f"v{_KV_SCRATCH_BYTES >> 20}s{STREAM_CHUNK_TOKENS}")


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


# The K and V scratch of either path must sit inside Mosaic's scoped VMEM
# next to the kernel's temporaries: a table is "one chunk" only while
# 2 * tokens * kvdim * 4 B stays under this (d_model 2048 -> 256 tokens;
# repo-scale widths keep the flag's value, so the single-chunk bitwise
# regime is unchanged), and the streamed path's four buffers share it.
_KV_SCRATCH_BYTES = 4 << 20
# Tokens of K and of V one chunk of the streamed path holds (two such
# buffers each: one attended while the other fills). On the chip (PR 29:
# 8 rows of 41-731 cached tokens, mean 337, 16 heads x 128, pages of 16)
# a call took 65.2 us at 64, 64.9 at 128, 67.8 at 256 and 71.9 at 512,
# where a kernel that only copies took 63.0: a longer chunk attends more
# of what an earlier chunk left behind the row's last page
STREAM_CHUNK_TOKENS = 128


def _chunk_pages(page: int, mp: int, kvdim: int) -> int:
    """Pages per KV chunk. ``mp`` (the whole table) where the table fits
    one chunk: the flag is the cap, the VMEM budget the bound. Otherwise
    the streamed path's chunk, under the same cap and budget."""
    cap = int(_flag("pallas_kv_chunk_tokens"))
    fit = _KV_SCRATCH_BYTES // (4 * kvdim)      # rows of K + V scratch
    if max(min(cap, fit // 2), page) // page >= mp:
        return mp
    chunk_tokens = max(min(cap, STREAM_CHUNK_TOKENS, fit // 4), page)
    return max(1, chunk_tokens // page)


def _pa_kernel(table_ref, pos_ref, q_ref, pk_ref, pv_ref, o_ref, *,
               n, hd, page, mp, scale):
    """Grid (B,), the whole table in one chunk: row i gathers its pages
    into token-major (tokens, n*hd) VMEM scratch via async DMA and
    attends the row's query over them with the exact single-pass
    softmax. Heads are lane slices of the scratch — Mosaic has no
    lowering for the (tokens, n, hd) reshape. Scores are one 2-D
    q_h @ k_h^T dot per head and the weighted sum one head-batched dot:
    on CPU XLA exactly these two forms accumulate in the stock einsums'
    order (the bitwise gates), and Mosaic lowers both."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pos = pos_ref[i]
    s_tok = mp * page
    nt = (((1,), (1,)), ((), ()))       # q_h @ k_h^T

    def body(ks_ref, vs_ref, sem):
        def head(ref, h):
            # one head's (s_tok, hd) lane slice of the token-major scratch
            return ref[:, h * hd:(h + 1) * hd]

        copies = []
        for j in range(mp):
            pid = table_ref[i, j]
            rows = pl.ds(j * page, page)
            copies.append(pltpu.make_async_copy(
                pk_ref.at[pid], ks_ref.at[rows], sem))
            copies.append(pltpu.make_async_copy(
                pv_ref.at[pid], vs_ref.at[rows], sem))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        s = jnp.stack([jax.lax.dot_general(
            q_ref[h], head(ks_ref, h), nt,
            preferred_element_type=jnp.float32)
            for h in range(n)]) * scale          # (n, 1, s_tok)
        # stale-position mask (pool pages are recycled across
        # requests): iota of rank >= 2 — TPU rejects 1-D
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s_tok), 2)
        vh = jnp.stack([head(vs_ref, h) for h in range(n)])
        # same op sequence as the stock lowering: normalize-then-dot
        # (bitwise with PT_PALLAS=off)
        p = jax.nn.softmax(jnp.where(idx <= pos, s, -1e9), axis=-1)
        o_ref[...] = jnp.einsum("nqs,nsh->nqh", p, vh,
                                preferred_element_type=jnp.float32)

    pl.run_scoped(
        body,
        ks_ref=pltpu.VMEM((s_tok, n * hd), jnp.float32),
        vs_ref=pltpu.VMEM((s_tok, n * hd), jnp.float32),
        sem=pltpu.SemaphoreType.DMA(()))


def _pa_stream_kernel(table_ref, pos_ref, q_ref, pk_ref, pv_ref, o_ref,
                      ks_ref, vs_ref, sem, slot_ref, *, n, hd, page, mp,
                      chunk_pages, scale):
    """Grid (B,), sequential; the table is wider than a chunk. Row i
    walks ceil(held pages / chunk_pages) chunks, where the held pages
    are those with a token at or before ``pos``, and copies of a chunk
    only its held pages. ``ks_ref`` / ``vs_ref`` are (2, chunk tokens,
    n*hd) and persist across rows: while one half is attended the next
    chunk's pages (the row's next, or the next row's first) fill the
    other. What an earlier chunk or row left behind a chunk's held pages
    is masked by position before the softmax and multiplied to exact
    zero after it; the scratch is zeroed before the first row so that
    what is multiplied by zero is finite. Chunks join by online-softmax
    accumulation (running max and sum)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    rows = pl.num_programs(0)
    ct = chunk_pages * page

    def held_pages(r):
        return jnp.minimum(pos_ref[r] // page + 1, mp)

    def each_copy(r, c, slot, act):
        """``act`` on the K and V copy of every held page of row r's
        chunk c, into half ``slot`` of the scratch."""
        base = c * chunk_pages
        count = jnp.minimum(chunk_pages, held_pages(r) - base)

        def one(j, _):
            pid = table_ref[r, base + j]
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            act(pltpu.make_async_copy(
                pk_ref.at[pid], ks_ref.at[slot, dst], sem.at[slot]))
            act(pltpu.make_async_copy(
                pv_ref.at[pid], vs_ref.at[slot, dst], sem.at[slot]))
            return 0

        jax.lax.fori_loop(0, count, one, 0)

    def start(r, c, slot):
        each_copy(r, c, slot, lambda cp: cp.start())

    @pl.when(i == 0)
    def _():
        ks_ref[...] = jnp.zeros(ks_ref.shape, ks_ref.dtype)
        vs_ref[...] = jnp.zeros(vs_ref.shape, vs_ref.dtype)
        slot_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[i]
    n_chunks = (held_pages(i) + chunk_pages - 1) // chunk_pages
    slot0 = slot_ref[0]                  # where this row's chunk 0 lands

    def chunk(c, carry):
        m_run, l_run, acc = carry
        slot = jax.lax.rem(slot0 + c, 2)
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_not(last))
        def _():
            start(i, c + 1, 1 - slot)

        @pl.when(last & (i + 1 < rows))
        def _():
            start(i + 1, 0, 1 - slot)

        each_copy(i, c, slot, lambda cp: cp.wait())
        # tokens on sublanes throughout: a head's scores are a lane
        # reduction of k_h * q_h and come out as a (ct, 1) column, which is
        # the layout the weighted sum over v_h's tokens wants
        idx = jax.lax.broadcasted_iota(jnp.int32, (ct, 1), 0) + c * ct
        valid = idx <= pos
        live = valid.astype(jnp.float32)
        m_new, l_new, a_new = [], [], []
        for h in range(n):
            # a head is one lane tile of the token-major scratch
            k_h = ks_ref[slot, :, h * hd:(h + 1) * hd]          # (ct, hd)
            v_h = vs_ref[slot, :, h * hd:(h + 1) * hd]
            s = jnp.sum(k_h * q_ref[h], axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, -1e9)
            m_h = jnp.maximum(m_run[h], jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m_run[h] - m_h)
            w = jnp.exp(s - m_h) * live                         # (ct, 1)
            l_new.append(l_run[h] * corr
                         + jnp.sum(w, axis=0, keepdims=True))
            a_new.append(acc[h] * corr
                         + jnp.sum(w * v_h, axis=0, keepdims=True))
            m_new.append(m_h)
        return tuple(m_new), tuple(l_new), tuple(a_new)

    init = (tuple(jnp.full((1, 1), -jnp.inf, jnp.float32)
                  for _ in range(n)),
            tuple(jnp.zeros((1, 1), jnp.float32) for _ in range(n)),
            tuple(jnp.zeros((1, hd), jnp.float32) for _ in range(n)))
    _m, l_run, acc = jax.lax.fori_loop(0, n_chunks, chunk, init)
    slot_ref[0] = jax.lax.rem(slot0 + n_chunks, 2)
    for h in range(n):
        o_ref[h] = acc[h] / l_run[h]


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
    if chunk_pages >= mp:
        kernel = functools.partial(_pa_kernel, n=n, hd=hd, page=page,
                                   mp=mp, scale=scale)
        scratch, params = [], None
    else:
        kernel = functools.partial(_pa_stream_kernel, n=n, hd=hd, page=page,
                                   mp=mp, chunk_pages=chunk_pages,
                                   scale=scale)
        half = (2, chunk_pages * page, n * hd)
        scratch = [pltpu.VMEM(half, jnp.float32),
                   pltpu.VMEM(half, jnp.float32),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((1,), jnp.int32)]
        # rows run in order: the scratch, the half in turn and the copies
        # in flight carry over from one row to the next
        params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page table + positions
        grid=(b,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=scratch)
    s_tok = mp * page
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, 1, hd), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n * s_tok * hd,
            bytes_accessed=2 * b * s_tok * n * hd * 4 + 2 * b * n * hd * 4,
            transcendentals=b * n * s_tok),
        compiler_params=params,
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
