"""Paged decode attention for grouped heads, windows and rings of pages.

The decode step of a model whose query heads share K/V heads
(``num_heads = groups x num_kv_heads``), whose cache may be held in a
lower precision than the query (bfloat16 pages, float32 scores, softmax
and accumulation), and whose window layers keep a fixed RING of pages a
slot (serving/kv_cache.py): a token at position ``t`` lives at ring index
``t mod (ring_pages x page)``, and a key is attended iff its TRUE position
lies in ``(pos - window, pos]``. ``paged_attention.py`` beside this file
is the float32 multi-head kernel (``kvdim == n x hd``, one class of pages)
under its own name; this one is a kernel of its own,
``name="paged_gqa_attention"``.

Per batch row the kernel walks the row's page table chunk by chunk and
reads only the chunks that hold attended keys (a dynamic trip count: a
row with 700 cached tokens reads two chunks of 512, not the table's
whole width), and of a chunk only the pages that hold something of the
row: each such page is DMA'd HBM->VMEM once, the scores of a K/V head's
group of query heads are one (group x chunk) dot, and chunks are joined by
online-softmax accumulation. Stale ring entries and what an earlier row
left in the rest of the chunk's scratch are masked by position before the
softmax and multiplied to exact zero after it (the scratch is zeroed
before the first row, so what is multiplied by zero is always finite).

A head narrower than a lane tile (64: models/lfm2.py) is scored under the
kernel's own rules by PACKING: ``128 / hd`` neighbouring K/V heads share the
128 lanes of a cached row as they lie in the pool, and the kernel is handed
them as ONE K/V head of 128 whose group is the packed heads' query heads,
each query laid into its own head's lanes with zeros in the others'
(`_pack_queries`). A packed head's scores are then exactly its own (the
zeros add nothing), every load and slice stays on a lane-tile boundary, the
pool is read as it is and once, and of the (group, 128) output each row's
own lanes are kept (`_unpack_output`). The kernel's body is the same
instructions at ``nkv / pack`` heads of 128; heads of 128 and more do not
pass through the packing at all. Its bytes are counted the same way (K and
V of the keys attended, once), so it keeps its ``name=``.

Dispatch and fallback counts land in the same counters as the float32
kernel's (``pallas.paged_attn_dispatches`` / ``pallas.paged_attn_fallbacks``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "paged_gqa_attention"
# tokens of K and of V a chunk holds in VMEM; a multiple of every page size.
# 2048 tokens of one K/V head of 128 in bfloat16 are 512 KB each. On the
# chip (PR 28: 64 rows, mean context 2,946, pages of 64 tokens) a step's
# five layers took 2.23 ms at 512 and 1.60 ms at 2048
CHUNK_TOKENS = 2048
_SUBLANES = 8      # a group of query heads is padded to whole sublanes
_LANES = 128       # a K/V head narrower than this shares its lane tile


def true_positions(slot, pos, cap, ring):
    """True position of cache index ``slot`` for a row whose newest token
    is at ``pos``: the index itself in a context's pages; in a ring of
    ``cap`` tokens the newest position <= pos that lands on it (negative
    where the ring has not been filled that far)."""
    if not ring:
        return slot
    return pos - jnp.mod(pos - slot, cap)


def stock_paged_gqa_attention(q, pool_k, pool_v, table, pos, n, nkv, hd,
                              scale, window, ring):
    """The counted stock lowering, and the kernel's oracle: dense page
    gather, scores in float32, keys outside the row's own positions (and
    outside the window) masked before the softmax."""
    b = q.shape[0]
    page = int(pool_k.shape[1])
    cap = int(table.shape[1]) * page
    g = n // nkv
    kh = pool_k[table].reshape(b, cap, nkv, hd)
    vh = pool_v[table].reshape(b, cap, nkv, hd)
    qh = q.reshape(b, nkv, g, hd).astype(kh.dtype)
    scores = jnp.einsum("bkgh,bskh->bkgs", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    true = true_positions(slot, pos[:, None], cap, ring)
    valid = (true >= 0) & (true <= pos[:, None])
    if window:
        valid &= true > pos[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, n * hd)


def _kernel(table_ref, pos_ref, q_ref, pk_ref, pv_ref, o_ref, ks_ref,
            vs_ref, sem, *, nkv, hd, page, mp, chunk_pages, scale, window,
            ring):
    """Grid (B,): row i attends its (nkv, group, hd) queries over its own
    pages, a chunk of ``chunk_pages`` pages at a time. ``ks_ref`` /
    ``vs_ref`` are the chunk's K and V in VMEM, kept across rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    pos = pos_ref[i]
    cap = mp * page
    ct = chunk_pages * page
    g8 = q_ref.shape[1]

    @pl.when(i == 0)
    def _():
        # rows past a chunk's pages are masked, and multiplied by zero:
        # what lies there has to be finite from the first row on
        ks_ref[...] = jnp.zeros(ks_ref.shape, ks_ref.dtype)
        vs_ref[...] = jnp.zeros(vs_ref.shape, vs_ref.dtype)

    # cache indices 0 .. held-1 hold something of this row
    held = jnp.minimum(pos + 1, cap) if ring else pos + 1
    held_pages = (held + page - 1) // page
    n_chunks = (held_pages + chunk_pages - 1) // chunk_pages
    first = 0
    if window and not ring:
        first = jnp.maximum(pos - window + 1, 0) // ct
    nt = (((1,), (1,)), ((), ()))       # q_h @ k_h^T

    def copies(base, j):
        pid = table_ref[i, base + j]
        rows = pl.ds(pl.multiple_of(j * page, page), page)
        return (pltpu.make_async_copy(pk_ref.at[pid], ks_ref.at[rows], sem),
                pltpu.make_async_copy(pv_ref.at[pid], vs_ref.at[rows], sem))

    def chunk(c, carry):
        m_run, l_run, acc = carry
        base = c * chunk_pages
        # of this chunk, the pages that hold something of the row
        count = jnp.minimum(chunk_pages, held_pages - base)

        def start(j, _):
            for cp in copies(base, j):
                cp.start()
            return 0

        def wait(j, _):
            for cp in copies(base, j):
                cp.wait()
            return 0

        jax.lax.fori_loop(0, count, start, 0)
        jax.lax.fori_loop(0, count, wait, 0)
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, ct), 1) + base * page
        if ring:
            # the ring wraps at w: indices up to w hold the newest lap,
            # those after it the lap before
            w = jax.lax.rem(pos, cap)
            true = jnp.where(slot <= w, slot, slot - cap) + (pos - w)
        else:
            true = slot
        valid = (true >= 0) & (true <= pos) & (slot < held)
        if window:
            valid &= true > pos - window
        m_new, l_new, a_new = [], [], []
        for h in range(nkv):
            k_h = ks_ref[:, h * hd:(h + 1) * hd]                 # (ct, hd)
            v_h = vs_ref[:, h * hd:(h + 1) * hd]
            q_h = q_ref[h].astype(k_h.dtype)                     # (g8, hd)
            s = jax.lax.dot_general(
                q_h, k_h, nt,
                preferred_element_type=jnp.float32) * scale      # (g8, ct)
            s = jnp.where(valid, s, -1e9)
            m_h = jnp.maximum(m_run[h], jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_run[h] - m_h)
            p = jnp.exp(s - m_h) * valid.astype(jnp.float32)
            l_new.append(l_run[h] * corr
                         + jnp.sum(p, axis=-1, keepdims=True))
            a_new.append(acc[h] * corr + jnp.dot(
                p.astype(v_h.dtype), v_h,
                preferred_element_type=jnp.float32))
            m_new.append(m_h)
        return jnp.stack(m_new), jnp.stack(l_new), jnp.stack(a_new)

    init = (jnp.full((nkv, g8, 1), -1e30, jnp.float32),
            jnp.zeros((nkv, g8, 1), jnp.float32),
            jnp.zeros((nkv, g8, hd), jnp.float32))
    _m, l_run, acc = jax.lax.fori_loop(first, n_chunks, chunk, init)
    o_ref[...] = acc / l_run


def lane_pack(nkv: int, hd: int) -> int:
    """K/V heads that share a lane tile: 1 for a head of a whole tile or
    more; ``_LANES / hd`` for a narrower one that divides it, where the K/V
    heads come in whole packs; 0 where neither holds (no kernel form)."""
    if hd % _LANES == 0:
        return 1
    pack = _LANES // hd if _LANES % hd == 0 else 0
    return pack if pack and nkv % pack == 0 else 0


def _pack_queries(qh, pack):
    """[B, nkv, g, hd] -> [B, nkv / pack, pack * g, pack * hd]: the query of
    packed head j in lanes j * hd .. (j + 1) * hd of its row, zeros in the
    lanes of the heads it shares the tile with."""
    b, nkv, g, hd = qh.shape
    own = jnp.eye(pack, dtype=qh.dtype)
    qp = qh.reshape(b, nkv // pack, pack, g, 1, hd) \
        * own[None, None, :, None, :, None]
    return qp.reshape(b, nkv // pack, pack * g, pack * hd)


def _unpack_output(out, pack, g, hd):
    """[B, nkv / pack, pack * g, pack * hd] -> [B, nkv, g, hd]: of each row
    the lanes of its own head."""
    b, tiles = out.shape[:2]
    own = jnp.einsum("btjgjh->btjgh",
                     out.reshape(b, tiles, pack, g, pack, hd))
    return own.reshape(b, tiles * pack, g, hd)


def _pallas_paged_gqa_attention(q, pool_k, pool_v, table, pos, n, nkv, hd,
                                scale, window, ring, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = q.shape[0]
    page = int(pool_k.shape[1])
    mp = int(table.shape[1])
    g = n // nkv
    qh = q.reshape(b, nkv, g, hd)
    pack = lane_pack(nkv, hd)
    if pack > 1:
        # `pack` K/V heads a lane tile: one head of `pack * hd` to the kernel
        out = _pallas_paged_gqa_attention(
            _pack_queries(qh, pack).reshape(b, -1), pool_k, pool_v, table,
            pos, n, nkv // pack, pack * hd, scale, window, ring,
            interpret)
        return _unpack_output(
            out.reshape(b, nkv // pack, pack * g, pack * hd), pack, g,
            hd).reshape(b, n * hd)
    g8 = -(-g // _SUBLANES) * _SUBLANES
    chunk_pages = max(1, min(CHUNK_TOKENS // page, mp))
    if g8 != g:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, g8 - g), (0, 0)))
    row = pl.BlockSpec((None, nkv, g8, hd), lambda i, t, p: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page table + positions
        grid=(b,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((chunk_pages * page, nkv * hd), pool_k.dtype),
            pltpu.VMEM((chunk_pages * page, nkv * hd), pool_v.dtype),
            pltpu.SemaphoreType.DMA(())])
    out = pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, hd=hd, page=page, mp=mp,
                          chunk_pages=chunk_pages, scale=scale,
                          window=window, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g8, hd), jnp.float32),
        interpret=interpret, name=KERNEL_NAME)(
            table, pos, qh.astype(jnp.float32), pool_k, pool_v)
    return out[:, :, :g, :].reshape(b, n * hd)


def paged_gqa_decode_attention(q, pool_k, pool_v, table, positions,
                               num_heads, num_kv_heads, head_dim, scale,
                               window=0, ring=False):
    """Attend each row's query over its own pages.

    q [B, n*hd]; PoolK/PoolV [N, P, nkv*hd] (already holding the step's
    K/V); table [B, MP] int32: a context's pages in order, or the row's
    ring; positions [B] int32, the newest token's TRUE position. Returns
    float32 [B, n*hd]. Routed per ``kernel_mode()``; every stock fallback
    is counted."""
    from . import kernel_mode

    n, nkv, hd = int(num_heads), int(num_kv_heads), int(head_dim)
    pos = jnp.asarray(positions).reshape(-1).astype(jnp.int32)
    page = int(pool_k.shape[1])
    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif n % nkv or int(pool_k.shape[2]) != nkv * hd:
        reason = "kvdim_mismatch"
    elif mode == "tpu" and (
            not lane_pack(nkv, hd) or page % (32 // pool_k.dtype.itemsize)
            or CHUNK_TOKENS % page):
        # Mosaic lane / sublane alignment of a page's VMEM block (a head
        # under a lane tile goes packed, `lane_pack`)
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.paged_attn_fallbacks", 1,
                              reason=reason)
        return stock_paged_gqa_attention(q, pool_k, pool_v, table, pos, n,
                                         nkv, hd, scale, int(window),
                                         bool(ring))
    telemetry.counter_add("pallas.paged_attn_dispatches", 1, mode=mode,
                          kernel=KERNEL_NAME)
    return _pallas_paged_gqa_attention(
        q, pool_k, pool_v, jnp.asarray(table, jnp.int32), pos, n, nkv, hd,
        float(scale), int(window), bool(ring), interpret=mode == "interpret")
