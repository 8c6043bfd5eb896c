"""Paged decode attention over latent pages (multi-head latent attention,
the DeepSeek-V3 block's, in its ABSORBED form).

A latent layer caches ONE row a token (serving/kv_cache.py `LayerCache`
with ``latent``): ``[c (value_dim), k_r (rope_dim)]``, the normed
compressed latent and the rotated key that all heads share, carried in
whole lane tiles (576 values in 640, the tail zero in the pages and in the
query alike: Mosaic copies no page whose row is not whole tiles, and the
chip lays a 576-wide bfloat16 array out in 640 lanes anyway). The decode
step's query arrives absorbed, ``[q_n W_uk (value_dim), q_r (rope_dim)]`` a
head, so that every head scores against the cached row itself and its
value is the row's first ``value_dim`` entries:

    score_h[s] = (q_h . row[s]) x scale,   o_h = sum_s p_h[s] row[s, :value_dim]

(``o_h W_uv`` is the caller's). All ``num_heads`` heads read the same row,
so a chunk of the cache is read once and attended by one (heads x chunk)
dot: ~2 x heads x (width + value_dim) FLOP against ``width`` x 2 bytes a
token, 121 FLOP a byte at 64 heads of 576 / 512 in bfloat16 (109 against
the 640 lanes read). This is why
the kernel is one of its own beside ``paged_gqa_attention.py``
(``name="paged_mla_attention"``): that one reads a K pool and a V pool,
and taught "values are a slice of the keys" it would read every page
twice or grow a second code path through its ring and window handling.

Per batch row the kernel walks the row's page table chunk by chunk, only
as far as the row's position, and copies of a chunk only the pages that
hold something of the row. The scratch is two halves: while one is
attended, the next chunk's pages (the row's next, or the next row's
first) fill the other, as in ``paged_attention.py``'s streamed kernel.
What an earlier chunk or row left behind a chunk's held pages is masked by
position before the softmax and multiplied to exact zero after it (the
scratch is zeroed before the first row, so it is finite). Chunks join by
online-softmax accumulation. Scores, softmax and accumulation are float32;
the two dots take the pages' dtype.

**A latent ring** (``window`` > 0; a window layer of models/motif3.py):
the table is a slot's ring of ``cap = MP x P`` rows, the row of position s
lies at index ``s mod cap``, and a head attends the rows whose TRUE
position is in ``pos - window < s <= pos``. The walk is the same and never
longer than the ring (3 pages of 64 at window 128); only the mask differs:
index i of the ring holds position ``pos - ((pos - i) mod cap)``.

**Several query positions a row** (``queries`` > 1; a step that verifies a
draft, models/xing4.py): a row's query holds ``queries x heads`` heads, the
heads of its position ``pos + j`` at rows ``[j x heads, (j + 1) x heads)``,
and the pages already hold the rows of all its positions. The walk reaches
``pos + queries - 1`` and reads every chunk ONCE for all of them; a head of
position j attends ``s <= pos + j``: only the mask knows of j. No ring.

Dispatch and fallback counts land in the same counters as the other two
paged kernels' (``pallas.paged_attn_dispatches`` / ``_fallbacks``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "paged_mla_attention"
# tokens of latent rows one half of the scratch holds; a multiple of every
# page size. 1024 rows of 640 lanes in bfloat16 are 1.3 MB a half
CHUNK_TOKENS = 1024


def _ring_valid(idx, pos, cap, window):
    """Which ring indices `idx` hold a key row `pos` attends: the true
    position of index i is ``pos - ((pos - i) mod cap)``."""
    true = pos - jnp.remainder(pos - idx, cap)
    return (true >= 0) & (pos - true < window)


def stock_paged_mla_attention(q, pool, table, pos, n, value_dim, scale,
                              window=0, queries=1):
    """The counted stock lowering, and the kernel's oracle: dense page
    gather, scores in float32, positions past the row's own (outside its
    window, in a ring) masked before the softmax. `n` counts every head of
    a row, `queries` positions of ``n // queries`` heads each."""
    b = q.shape[0]
    page, width = int(pool.shape[1]), int(pool.shape[2])
    cap = int(table.shape[1]) * page
    rows = pool[table].reshape(b, cap, width)
    qh = q.reshape(b, n, width).astype(rows.dtype)
    scores = jnp.einsum("bhw,bsw->bhs", qh, rows,
                        preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(cap, dtype=jnp.int32)[None, :]
    if queries > 1:
        ahead = jnp.arange(n, dtype=jnp.int32) // (n // queries)
        valid = idx[:, None, :] <= (pos[:, None] + ahead[None, :])[..., None]
    else:
        valid = (_ring_valid(idx, pos[:, None], cap, window) if window
                 else idx <= pos[:, None])[:, None, :]
    probs = jax.nn.softmax(jnp.where(valid, scores, -1e9), axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", probs.astype(rows.dtype),
                     rows[..., :value_dim],
                     preferred_element_type=jnp.float32)
    return out.reshape(b, n * value_dim)


def _kernel(table_ref, pos_ref, q_ref, pool_ref, o_ref, cs_ref, sem,
            slot_ref, *, value_dim, page, mp, chunk_pages, scale, window,
            queries):
    """Grid (B,), sequential: row i attends its (heads, width) queries over
    its own pages, ``chunk_pages`` pages at a time. ``cs_ref`` is (2, chunk
    tokens, width) and persists across rows, as do the half in turn
    (``slot_ref``) and the copies in flight."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    rows = pl.num_programs(0)
    ct = chunk_pages * page
    n, width = q_ref.shape

    def held_pages(r):
        return jnp.minimum((pos_ref[r] + queries - 1) // page + 1, mp)

    def each_copy(r, c, slot, act):
        """``act`` on the copy of every held page of row r's chunk c, into
        half ``slot`` of the scratch."""
        base = c * chunk_pages
        count = jnp.minimum(chunk_pages, held_pages(r) - base)

        def one(j, _):
            pid = table_ref[r, base + j]
            dst = pl.ds(pl.multiple_of(j * page, page), page)
            act(pltpu.make_async_copy(
                pool_ref.at[pid], cs_ref.at[slot, dst], sem.at[slot]))
            return 0

        jax.lax.fori_loop(0, count, one, 0)

    def start(r, c, slot):
        each_copy(r, c, slot, lambda cp: cp.start())

    @pl.when(i == 0)
    def _():
        cs_ref[...] = jnp.zeros(cs_ref.shape, cs_ref.dtype)
        slot_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[i]
    n_chunks = (held_pages(i) + chunk_pages - 1) // chunk_pages
    slot0 = slot_ref[0]                  # where this row's chunk 0 lands
    nt = (((1,), (1,)), ((), ()))        # q @ rows^T
    q = q_ref[...].astype(cs_ref.dtype)
    # the two parts of a row are whole lane tiles each: the latent (and
    # value) first, the shared rotated key behind it
    q_c, q_r = q[:, :value_dim], q[:, value_dim:]

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
        lat = cs_ref[slot, :, :value_dim]                       # (ct, v)
        s = jax.lax.dot_general(q_c, lat, nt,
                                preferred_element_type=jnp.float32)
        if width > value_dim:
            s += jax.lax.dot_general(q_r, cs_ref[slot, :, value_dim:], nt,
                                     preferred_element_type=jnp.float32)
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, ct), 1) + c * ct
        if queries > 1:     # heads of position pos + j see one row more
            ahead = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) \
                // (n // queries)
            valid = idx <= pos + ahead                          # (n, ct)
        else:
            valid = _ring_valid(idx, pos, mp * page, window) if window \
                else idx <= pos
        s = jnp.where(valid, s * scale, -1e9)                   # (n, ct)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        l_new = l_run * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(lat.dtype), lat,
                                   preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((n, 1), -1e30, jnp.float32),
            jnp.zeros((n, 1), jnp.float32),
            jnp.zeros((n, value_dim), jnp.float32))
    _m, l_run, acc = jax.lax.fori_loop(0, n_chunks, chunk, init)
    slot_ref[0] = jax.lax.rem(slot0 + n_chunks, 2)
    o_ref[...] = acc / l_run


def _pallas_paged_mla_attention(q, pool, table, pos, n, value_dim, scale,
                                interpret, window=0, queries=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = q.shape[0]
    page, width = int(pool.shape[1]), int(pool.shape[2])
    mp = int(table.shape[1])
    chunk_pages = max(1, min(CHUNK_TOKENS // page, mp))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # page table + positions
        grid=(b,),
        in_specs=[pl.BlockSpec((None, n, width), lambda i, t, p: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, n, value_dim),
                               lambda i, t, p: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages * page, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_kernel, value_dim=value_dim, page=page, mp=mp,
                          chunk_pages=chunk_pages, scale=scale,
                          window=window, queries=queries),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, value_dim), jnp.float32),
        # rows run in order: the scratch, the half in turn and the copies
        # in flight carry over from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=KERNEL_NAME)(
            table, pos, q.reshape(b, n, width).astype(jnp.float32), pool)
    return out.reshape(b, n * value_dim)


def paged_mla_decode_attention(q, pool, table, positions, num_heads,
                               value_dim, scale, window=0, queries=1):
    """Attend each row's absorbed queries over its own latent pages.

    q [B, n*width] (a head: the absorbed latent query, then the rotated
    part); pool [N, P, width] (already holding the step's row); table
    [B, MP] int32, a context's pages in order; positions [B] int32.
    Returns float32 [B, n*value_dim]: a head's probabilities over the
    rows' first ``value_dim`` entries. With ``window`` the table is a
    slot's latent ring (module docstring). With ``queries`` > 1 a row's q
    holds that many positions' heads, position ``positions[b] + j`` at
    heads ``[j x num_heads, (j + 1) x num_heads)``, and the result likewise.
    Routed per ``kernel_mode()``; every stock fallback is counted."""
    from . import kernel_mode

    queries = int(queries)
    if queries > 1 and window:
        raise ValueError("several query positions a row over a latent ring "
                         "are not built")
    n, value_dim = int(num_heads) * queries, int(value_dim)
    pos = jnp.asarray(positions).reshape(-1).astype(jnp.int32)
    page, width = int(pool.shape[1]), int(pool.shape[2])
    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif int(q.shape[1]) != n * width or value_dim > width:
        reason = "width_mismatch"
    elif mode == "tpu" and (
            value_dim % 128 or width % 128 or n % 8
            or page % (32 // pool.dtype.itemsize) or CHUNK_TOKENS % page):
        # Mosaic lane / sublane alignment: a row and its value part are
        # whole lane tiles (a 576-wide row rides in 640, the tail zero in
        # the pages and in the query alike: XLA's own layout of a
        # 576-wide array is 640 lanes too), a page whole sublane tiles
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.paged_attn_fallbacks", 1,
                              reason=reason)
        return stock_paged_mla_attention(q, pool, table, pos, n, value_dim,
                                         scale, window, queries)
    telemetry.counter_add("pallas.paged_attn_dispatches", 1, mode=mode,
                          kernel=KERNEL_NAME)
    return _pallas_paged_mla_attention(
        q, pool, jnp.asarray(table, jnp.int32), pos, n, value_dim,
        float(scale), interpret=mode == "interpret", window=int(window),
        queries=queries)
