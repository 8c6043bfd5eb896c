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
row with 2,500 cached tokens reads two chunks of 2,048, not the table's
whole width), and of a chunk only the pages that hold something of the
row: each such page is DMA'd HBM->VMEM once. The kernel STREAMS, as the
float32 kernel beside it does: the scratch is two halves taken in turn,
and before a chunk is waited for the copies of what comes next are started
into the other half, the row's next chunk or, at its last chunk, the next
row's first (rows run in order; the half a row begins in is carried in
SMEM), so a row costs the larger of its copy and its score and not their
sum. A chunk's copies are waited for by the binary digits of their count,
not one by one. And a row is scored over what it holds: of a chunk the
scores of a K/V head's group of query heads (one (group x width) dot), the
softmax and ``p @ v`` run over its first ``ceil(held / PIECE_TOKENS)``
pieces, a width chosen by a switch among the few a chunk can have (one
straight run of instructions each: a loop over pieces waits out each
piece's chain of dependent steps), and chunks are joined by online-softmax
accumulation; `tokens_scored` counts those columns. Stale ring entries and
what an earlier row left in the rest of a piece are masked by position
before the softmax and multiplied to exact zero after it (both halves are
zeroed before the first row, so what is multiplied by zero is always
finite).

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
import numpy as np

from ...core import telemetry

KERNEL_NAME = "paged_gqa_attention"
# tokens of K and of V a HALF of the scratch holds in VMEM (a chunk: what is
# copied and waited for at once), and the grain a row's share of a chunk is
# scored in (a piece). Both a multiple of every page size. On the chip
# (tools/bench_paged_gqa.py, PR 59; microseconds a call at the shapes of cells
# 10 / 5 / 3 full / 3 ring / 7; the one-scratch kernel before: 1,172 / 261 /
# 280 / 256 / 830): this kernel 741 / 119 / 193 / 182 / 559 at pieces of 256,
# 512 and 1,024 alike within 1% but for cell 5 at 1,024 (128), so the piece
# that scores the fewest columns stays (cell 5: 1.29 x the keys attended; 1.61
# at 512). Chunks of 1,024 read 3-12% slower at every shape but cell 5's.
# Pieces in a LOOP read 832 / 132 / 281 / 260 / 609 at 512 and 1,169 / 173 /
# 397 / 362 / 795 at 256: a trip is a chain the next cannot begin under, ~0.25
# us a head whatever its width, which is why the widths are a switch.
CHUNK_TOKENS = 2048
PIECE_TOKENS = 256
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


def _tiling(page, table_pages):
    """(tokens a piece, pages a chunk) at a page size and a table's width:
    a piece is whole pages, a chunk whole pieces, and neither is wider than
    the table asks for."""
    cap = page * table_pages
    piece = min(page * max(1, PIECE_TOKENS // page), cap)
    pieces = max(1, min(CHUNK_TOKENS // piece, -(-cap // piece)))
    return piece, pieces * piece // page


def _walk(pos, page, mp, chunk_pages, window, ring):
    """(first chunk, end chunk, held pages, held tokens) of a row whose
    newest token is at ``pos``: cache indices 0 .. held-1 hold something of
    the row, a window without a ring skips the chunks wholly before it, and
    a row always walks one chunk at least (a row in flight is waited for)."""
    cap = mp * page
    held = jnp.minimum(pos + 1, cap) if ring else pos + 1
    held_pages = (held + page - 1) // page
    first = 0
    if window and not ring:
        first = jnp.maximum(pos - window + 1, 0) // (chunk_pages * page)
    end = jnp.maximum((held_pages + chunk_pages - 1) // chunk_pages,
                      first + 1)
    return first, end, held_pages, held


def tokens_scored(positions, page, table_pages, window=0, ring=False):
    """Columns the kernel scores for rows whose newest tokens are at
    ``positions``, over tables ``table_pages`` wide: of every chunk a row
    walks, whole pieces as far as the row holds something there. Over the
    keys attended it is the kernel's masked share."""
    pos = np.asarray(positions, np.int64).reshape(-1, 1)
    piece, chunk_pages = _tiling(page, table_pages)
    ct = chunk_pages * page
    first, end, _pages, held = (np.asarray(x) for x in _walk(
        pos, page, table_pages, chunk_pages, window, ring))
    c = np.arange(-(-table_pages // chunk_pages))[None, :]
    tokens = np.clip(held - c * ct, 0, ct) * ((c >= first) & (c < end))
    return int((-(-tokens // piece)).sum()) * piece


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
            vs_ref, sem, half_ref, *, nkv, hd, page, mp, chunk_pages, piece,
            scale, window, ring):
    """Grid (B,), rows in order: row i attends its (nkv, group, hd) queries
    over its own pages, a chunk of ``chunk_pages`` pages at a time and of a
    chunk ``piece`` tokens at a time, as far as the row holds. ``ks_ref`` /
    ``vs_ref`` are two halves of a chunk's K and V in VMEM, kept across
    rows: while one half is scored, the copies of what comes next (the
    row's next chunk, or the next row's first) fill the other.
    ``half_ref[0]`` is the half this row's first chunk was sent to."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    rows = pl.num_programs(0)
    cap = mp * page
    ct = chunk_pages * page
    g8 = q_ref.shape[1]

    def walk(r):
        return _walk(pos_ref[r], page, mp, chunk_pages, window, ring)

    def held_in(r, c):
        """Pages of row r's chunk c that hold something of the row."""
        return jnp.minimum(chunk_pages, walk(r)[2] - c * chunk_pages)

    def start(r, c, half):
        """Start the K and the V copy of every held page of row r's chunk c
        into ``half`` of the scratch."""
        base = c * chunk_pages

        def one(j, _):
            pid = table_ref[r, base + j]
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            pltpu.make_async_copy(
                pk_ref.at[pid], ks_ref.at[half, dst], sem.at[half]).start()
            pltpu.make_async_copy(
                pv_ref.at[pid], vs_ref.at[half, dst], sem.at[half]).start()
            return 0

        jax.lax.fori_loop(0, held_in(r, c), one, 0)

    def wait(r, c, half):
        """Wait for what `start` sent there. A half's copies signal one
        semaphore by their bytes, so the count of pages is waited for by its
        binary digits, a power of two pages of K and of V at a time (the
        descriptor only measures: as many rows of the half as those pages),
        not page by page."""
        count = held_in(r, c)
        k = 1
        while k <= chunk_pages:
            @pl.when((count & k) != 0)
            def _(k=k):
                for ref in (ks_ref, vs_ref):
                    rows_k = ref.at[half, pl.ds(0, k * page)]
                    pltpu.make_async_copy(rows_k, rows_k,
                                          sem.at[half]).wait()
            k *= 2

    @pl.when(i == 0)
    def _():
        # what lies past a chunk's held pages is masked, and multiplied by
        # zero: it has to be finite from the first row on, in both halves
        ks_ref[...] = jnp.zeros(ks_ref.shape, ks_ref.dtype)
        vs_ref[...] = jnp.zeros(vs_ref.shape, vs_ref.dtype)
        half_ref[0] = 0
        start(0, walk(0)[0], 0)

    pos = pos_ref[i]
    first, end, _pages, held = walk(i)
    half0 = half_ref[0]
    nt = (((1,), (1,)), ((), ()))       # q_h @ k_h^T
    heads = range(nkv)

    def chunk(c, carry):
        half = jax.lax.rem(half0 + c - first, 2)
        last = c + 1 == end

        @pl.when(jnp.logical_not(last))
        def _():
            start(i, c + 1, 1 - half)

        @pl.when(last & (i + 1 < rows))
        def _():
            # (the grid's last row starts nothing: no row comes to wait)
            start(i + 1, walk(i + 1)[0], 1 - half)

        wait(i, c, half)

        def score(width, carry):
            """The online-softmax step over the half's first ``width``
            columns."""
            m_run, l_run, acc = carry
            slot = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) \
                + c * ct
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
            live = valid.astype(jnp.float32)
            m_new, l_new, a_new = [], [], []
            for h in heads:
                lanes = slice(h * hd, (h + 1) * hd)
                k_h = ks_ref[half, pl.ds(0, width), lanes]       # (width, hd)
                v_h = vs_ref[half, pl.ds(0, width), lanes]
                q_h = q_ref[h].astype(k_h.dtype)                 # (g8, hd)
                s = jax.lax.dot_general(
                    q_h, k_h, nt,
                    preferred_element_type=jnp.float32) * scale  # (g8, width)
                s = jnp.where(valid, s, -1e9)
                m_h = jnp.maximum(m_run[h],
                                  jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_run[h] - m_h)
                p = jnp.exp(s - m_h) * live
                l_new.append(l_run[h] * corr
                             + jnp.sum(p, axis=-1, keepdims=True))
                a_new.append(acc[h] * corr + jnp.dot(
                    p.astype(v_h.dtype), v_h,
                    preferred_element_type=jnp.float32))
                m_new.append(m_h)
            return tuple(m_new), tuple(l_new), tuple(a_new)

        # of this chunk, the pieces that hold something of the row, scored
        # as ONE run of instructions a count of pieces: a loop over pieces
        # waits out a piece's whole chain (scores, maximum, exponentials,
        # p @ v) before the next piece's scores begin
        pieces = (jnp.minimum(ct, held - c * ct) + piece - 1) // piece
        widths = [functools.partial(score, (k + 1) * piece)
                  for k in range(ct // piece)]
        return jax.lax.switch(pieces - 1, widths, carry)

    init = (tuple(jnp.full((g8, 1), -1e30, jnp.float32) for _ in heads),
            tuple(jnp.zeros((g8, 1), jnp.float32) for _ in heads),
            tuple(jnp.zeros((g8, hd), jnp.float32) for _ in heads))
    _m, l_run, acc = jax.lax.fori_loop(first, end, chunk, init)
    half_ref[0] = jax.lax.rem(half0 + end - first, 2)
    for h in heads:
        o_ref[h] = acc[h] / l_run[h]


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


# jitted: a model's attention layers call it with the same shapes, and one
# trace and one lowering of the kernel then serve them all (the widths of
# `score` make a kernel ~0.2-0.3 s to trace and lower: 6 layers a program and
# every program of a set-up would pay it)
@functools.partial(jax.jit, static_argnames=(
    "n", "nkv", "hd", "scale", "window", "ring", "tiling", "interpret"))
def _pallas_paged_gqa_attention(q, pool_k, pool_v, table, pos, n, nkv, hd,
                                scale, window, ring, tiling, interpret):
    """``tiling``: `_tiling` of the page size and the table's width."""
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
            pos, n, nkv // pack, pack * hd, scale, window, ring, tiling,
            interpret)
        return _unpack_output(
            out.reshape(b, nkv // pack, pack * g, pack * hd), pack, g,
            hd).reshape(b, n * hd)
    g8 = -(-g // _SUBLANES) * _SUBLANES
    piece, chunk_pages = tiling
    if g8 != g:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, g8 - g), (0, 0)))
    row = pl.BlockSpec((None, nkv, g8, hd), lambda i, t, p: (i, 0, 0, 0))
    halves = (2, chunk_pages * page, nkv * hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page table + positions
        grid=(b,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM(halves, pool_k.dtype),
            pltpu.VMEM(halves, pool_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_kernel, nkv=nkv, hd=hd, page=page, mp=mp,
                          chunk_pages=chunk_pages, piece=piece, scale=scale,
                          window=window, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g8, hd), jnp.float32),
        # rows run in order: the scratch, the half in turn and the copies
        # in flight carry over from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
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
            or PIECE_TOKENS % page):
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
                          kernel=KERNEL_NAME, piece=PIECE_TOKENS)
    return _pallas_paged_gqa_attention(
        q, pool_k, pool_v, jnp.asarray(table, jnp.int32), pos, n, nkv, hd,
        float(scale), int(window), bool(ring),
        _tiling(page, int(table.shape[1])), interpret=mode == "interpret")
