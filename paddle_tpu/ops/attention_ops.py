"""Fused attention ops backed by the Pallas flash-attention kernel.

Capability mirror of the reference's fused inference attention
(operators/fused/multihead_matmul_op.cu) generalised to training: one IR op
`flash_attention` replaces the matmul/softmax/dropout/matmul chain, with a
custom-VJP Pallas backward. The inference fuse pass
(inference/passes) rewrites the unfused pattern into this op; models can
also emit it directly (models/bert.py with use_flash_attention=True).
"""

from __future__ import annotations

from ..core.ir import OpDesc
from ..core.registry import register_grad_maker, register_op


def _attn_dropout(attrs):
    """(rate, seed) for attention-probs dropout. seed is a uint32 scalar
    folding the build-time op seed, the runtime step (fresh mask per step
    without retrace) and the dp rank (dp shards see different global
    batches). sp/mp ranks are deliberately NOT folded: the mask is keyed
    on GLOBAL (b, h, q, k) positions, so sequence/model shards of one
    logical batch must agree on it."""
    rate = float(attrs.get("dropout_prob", 0.0) or 0.0)
    if rate <= 0.0 or bool(attrs.get("is_test", False)):
        return 0.0, None
    import jax
    import jax.numpy as jnp

    from .tensor_ops import _rng_key

    key = _rng_key(attrs, axes=("dp",))
    kd = jnp.asarray(jax.random.key_data(key)).reshape(-1).astype(jnp.uint32)
    return rate, kd[0] ^ kd[-1]


@register_op("flash_attention", non_diff_inputs=("Bias",))
def flash_attention_op(ins, attrs):
    """Out = softmax(Q K^T * scale + Bias) V.

    Q [B,H,Sq,D]; K,V [B,H,Sk,D]; Bias optional, broadcastable to
    [B,1,1,Sk] (key padding mask). Attrs: causal (bool), scale (float,
    default 1/sqrt(D)), dropout_prob/is_test/seed (attention-probs
    dropout, reference attention_probs_dropout_prob semantics); window
    (a query at t reads keys at t - window < s <= t) and num_kv_heads
    (K, V hold fewer heads; query head j reads K/V head j // group): with
    either, causal self-attention with no Bias and no dropout through
    ops/pallas/flash_window.py.

    Second output Lse ([B,H,Sq] f32 log-sum-exp) feeds the saved-residual
    flash_attention_grad op so the backward never re-runs the forward
    kernel (pallas custom-calls are not CSE'd by XLA; the re-trace cost
    ~0.8 ms/layer on ERNIE-large). Program descs built without an Lse
    output still work — the extra lowering output is dropped and the
    grad falls back to the generic vjp.
    """
    from .pallas.flash_attention import flash_attention_fwd_lse

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = None
    if ins.get("Bias") and ins["Bias"][0] is not None:
        bias = ins["Bias"][0]
    rate, seed = _attn_dropout(attrs)
    out, lse = flash_attention_fwd_lse(
        q, k, v, bias=bias, causal=bool(attrs.get("causal", False)),
        scale=attrs.get("scale", None),
        dropout_rate=rate, dropout_seed=seed,
        num_heads=_local_heads(q, attrs), **_window_attrs(attrs))
    return {"Out": out, "Lse": lse}


def _window_attrs(attrs):
    """The window and K/V head count of a windowed / grouped call, for
    the forward and the grad op alike; {} on descs without them."""
    kw = {}
    if attrs.get("window"):
        kw["window"] = int(attrs["window"])
    if attrs.get("num_kv_heads"):
        kw["num_kv_heads"] = int(attrs["num_kv_heads"])
    return kw


def _local_heads(q, attrs):
    """Packed-layout head count for THIS shard: prefer the
    sharding-invariant head_dim attr (q's columns may be a
    tensor-parallel shard of the global width), fall back to the
    num_heads attr for descs without it."""
    if q.ndim != 3:
        return None
    hd = attrs.get("head_dim")
    if hd:
        return int(q.shape[-1]) // int(hd)
    return attrs.get("num_heads", None)


@register_grad_maker("flash_attention")
def _flash_attention_grad_maker(op, out_grads, in_grads):
    """Emit flash_attention_grad consuming the SAVED forward Out/Lse
    instead of the generic __vjp_grad__ (which re-traces the forward —
    a duplicate pallas fwd kernel XLA cannot CSE). Falls back to the
    generic maker for descs without the Lse output (e.g. programs
    serialised before round 5)."""
    from ..core import registry as _registry

    og = (out_grads.get("Out") or [None])[0]
    if og is None or not op.outputs.get("Lse"):
        return _registry.default_grad_maker(op, out_grads, in_grads)
    grads = {s: (in_grads.get(s) or [None])[0]
             for s in ("Q", "K", "V", "Bias")}
    if all(g is None for g in grads.values()):
        return []
    inputs = {"Q": list(op.inputs["Q"]), "K": list(op.inputs["K"]),
              "V": list(op.inputs["V"]), "Out": list(op.outputs["Out"]),
              "Lse": list(op.outputs["Lse"]), "OutGrad": [og]}
    if op.inputs.get("Bias"):
        inputs["Bias"] = list(op.inputs["Bias"])
    outputs = {s + "Grad": [g] for s, g in grads.items() if g is not None}
    attrs = dict(op.attrs)
    # drop the forward's role tags so append_backward's setdefault tags
    # this op Backward — else clone(for_test=True) would keep it while
    # stripping the producer of its OutGrad input
    attrs.pop("op_role", None)
    attrs.pop("op_role_var", None)
    return [OpDesc("flash_attention_grad", inputs, outputs, attrs)]


@register_op("flash_attention_grad",
             non_diff_inputs=("Bias", "Out", "Lse", "OutGrad"),
             skip_infer_shape=True)
def flash_attention_grad_op(ins, attrs):
    """d(Q,K,V,Bias) of flash_attention from the saved (Out, Lse).

    Asks attention_route() what its forward asked (a pure function of
    shapes, layout, bias form and kernel_mode()): on the 'packed' and
    'pallas*' routes it calls the bwd kernels directly — zero forward
    re-execution; on the xla/reference routes it runs the generic vjp of
    the forward lowering, whose re-traced standard-HLO forward XLA CSEs
    with the forward op's."""
    import jax

    from .pallas.flash_attention import (attention_route, flash_attention,
                                         flash_attention_bwd)

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    out, lse, do = ins["Out"][0], ins["Lse"][0], ins["OutGrad"][0]
    rate, seed = _attn_dropout(attrs)
    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale", None)
    num_heads = _local_heads(q, attrs)
    windowed = _window_attrs(attrs)
    # these routes saved (out, lse); the others recompute
    direct = bool(windowed) or attention_route(q, k, bias, num_heads)[0] in (
        "packed", "pallas", "pallas_interpret")
    if direct:
        dq, dk, dv, dbias_kv = flash_attention_bwd(
            q, k, v, bias, out, lse, do, causal=causal, scale=scale,
            dropout_rate=rate, dropout_seed=seed, num_heads=num_heads,
            **windowed)
    else:
        args = (q, k, v) + ((bias,) if bias is not None else ())

        def f(*a):
            b_ = a[3] if len(a) > 3 else None
            return flash_attention(a[0], a[1], a[2], bias=b_, causal=causal,
                                   scale=scale, dropout_rate=rate,
                                   dropout_seed=seed, num_heads=num_heads)

        _, vjp = jax.vjp(f, *args)
        got = vjp(do.astype(out.dtype).reshape(out.shape))
        dq, dk, dv = got[0], got[1], got[2]
        dbias_kv = got[3] if len(got) > 3 else None
    outs = {"QGrad": dq, "KGrad": dk, "VGrad": dv}
    if dbias_kv is not None and bias is not None:
        outs["BiasGrad"] = dbias_kv.reshape(bias.shape) \
            if dbias_kv.size == bias.size else dbias_kv
    return outs


@register_op("kv_cache_write",
             non_diff_inputs=("K", "V", "PoolK", "PoolV", "PageTable",
                              "Lengths"))
def kv_cache_write_op(ins, attrs):
    """Bulk-write a prompt's keys/values into the paged KV pool — the
    PREFILL half of the decode engine's cache discipline
    (serving/kv_cache.py; vLLM's PagedAttention cache layout in dense
    jax form).

    K, V [B, S, kvdim]; PoolK, PoolV [N, P, kvdim] (N pages of P tokens);
    PageTable [B, MP] int32 physical page ids owned by each row;
    Lengths [B] int32 true prompt lengths. Token s of row b lands at
    page PageTable[b, s // P], offset s % P. Positions at or past the
    row's length are routed to page 0 — the pool's reserved scratch page
    (never allocated to a request) — so padded prompt tail writes can
    never corrupt another request's pages.

    Attr ``ring`` (present: the pool is of a page class, any dtype): when
    true the table is a slot's ring and token s lands at index
    ``s mod (MP x P)``; see ``_kv_cache_write_classed``."""
    import jax.numpy as jnp

    k, v = ins["K"][0], ins["V"][0]
    # .at[] updates need jax arrays (a direct OpTest call feeds numpy)
    pool_k = jnp.asarray(ins["PoolK"][0])
    pool_v = jnp.asarray(ins["PoolV"][0])
    table = jnp.asarray(ins["PageTable"][0])
    lengths = jnp.asarray(ins["Lengths"][0]).reshape(-1)
    b, s, _ = k.shape
    page = int(pool_k.shape[1])
    pos = jnp.arange(s, dtype=jnp.int32)                       # [S]
    if "ring" in attrs:
        return _kv_cache_write_classed(k, v, pool_k, pool_v, table, lengths,
                                       bool(attrs["ring"]))
    logical = pos // page                                      # [S]
    phys = jnp.take_along_axis(
        table, jnp.broadcast_to(logical[None, :], (b, s)), axis=1)
    valid = pos[None, :] < lengths[:, None]                    # [B, S]
    phys = jnp.where(valid, phys, 0).reshape(-1)
    off = jnp.broadcast_to((pos % page)[None, :], (b, s)).reshape(-1)
    pool_k = pool_k.at[phys, off].set(k.reshape(b * s, -1))
    pool_v = pool_v.at[phys, off].set(v.reshape(b * s, -1))
    return {"PoolKOut": pool_k, "PoolVOut": pool_v}


def _kv_cache_write_classed(k, v, pool_k, pool_v, table, lengths, ring):
    """`kv_cache_write` for a pool of either class and any dtype (attr
    `ring` present). A ring's table holds `cap = MP x P` tokens: token s
    lands at index s mod cap, and of a prompt longer than the ring only
    the last `cap` tokens are written (the earlier ones would land on the
    same indices; they go to the scratch page)."""
    import jax.numpy as jnp

    b, s, _ = k.shape
    page = int(pool_k.shape[1])
    cap = int(table.shape[1]) * page
    pos = jnp.arange(s, dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]                    # [B, S]
    idx = pos
    if ring:
        valid &= pos[None, :] >= lengths[:, None] - cap
        idx = pos % cap
    phys = jnp.take_along_axis(
        table, jnp.broadcast_to((idx // page)[None, :], (b, s)), axis=1)
    phys = jnp.where(valid, phys, 0).reshape(-1)
    off = jnp.broadcast_to((idx % page)[None, :], (b, s)).reshape(-1)
    pool_k = pool_k.at[phys, off].set(
        k.reshape(b * s, -1).astype(pool_k.dtype))
    pool_v = pool_v.at[phys, off].set(
        v.reshape(b * s, -1).astype(pool_v.dtype))
    return {"PoolKOut": pool_k, "PoolVOut": pool_v}


@register_op("cached_kv_attention",
             required_attrs=("num_heads", "head_dim"),
             non_diff_inputs=("K", "V", "PoolK", "PoolV", "PageTable",
                              "Positions"))
def cached_kv_attention_op(ins, attrs):
    """One autoregressive DECODE step of attention against the paged KV
    cache — the cached-KV twin of flash_attention for the generative
    serving engine (serving/decode.py).

    Q, K, V [B, nh*hd] — the new token's projections; PoolK/PoolV
    [N, P, kvdim]; PageTable [B, MP]; Positions [B] int32 — the new
    token's 0-based position (context length = pos + 1). The op first
    writes the new K/V at (PageTable[b, pos//P], pos%P), then attends
    the query over the row's pages with positions > pos masked out
    BEFORE the softmax, so stale page contents (the pool recycles pages
    across requests) contribute exactly zero — per-row outputs are a
    pure function of the row's own tokens, which is what keeps
    continuous-batched decode bitwise-identical to sequential decode.
    Empty slots carry an all-zero page table and write to the pool's
    reserved scratch page 0.

    The attend phase routes through the Pallas paged-attention kernel
    (ops/pallas/paged_attention.py: per-page HBM→VMEM block-gather, no
    dense gathered context in HBM; a table wider than one KV chunk is
    walked only as far as the row's ``pos``, each held page read once,
    the next chunk copied while this one is attended; a table of one
    chunk is read whole) under the PT_PALLAS dispatch; the
    'off' mode and untileable shapes take the counted stock
    gather+einsum lowering (``pallas.paged_attn_fallbacks``). The write
    phase is shared by every route.

    Outputs: Out [B, nh*hd], PoolKOut, PoolVOut (the engine threads the
    pools through the step program and donates them to the jit so XLA
    can update in place).

    Attr ``num_kv_heads`` (present: grouped heads, K/V [B, nkv*hd], pages
    of any dtype) routes to ops/pallas/paged_gqa_attention.py; with it
    ``window`` (keys at pos - window < s <= pos only) and ``ring`` (the
    table is a slot's ring: the step's K/V land at ``pos mod (MP x P)``
    and keys are masked by their TRUE position). Without the attr this is
    the float32 multi-head op it always was."""
    import jax.numpy as jnp

    from .pallas.paged_attention import paged_decode_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    # .at[] updates need jax arrays (a direct OpTest call feeds numpy)
    pool_k = jnp.asarray(ins["PoolK"][0])
    pool_v = jnp.asarray(ins["PoolV"][0])
    table = jnp.asarray(ins["PageTable"][0])
    pos = jnp.asarray(ins["Positions"][0]).reshape(-1)
    n = int(attrs["num_heads"])
    hd = int(attrs["head_dim"])
    scale = float(attrs.get("scale") or hd ** -0.5)
    page = int(pool_k.shape[1])
    if "num_kv_heads" in attrs:
        # grouped heads, a window, a ring of pages, pages of another dtype
        # (ops/pallas/paged_gqa_attention.py): the step's K/V land at the
        # position's index in the row's pages, a ring's modulo its size
        from .pallas.paged_gqa_attention import paged_gqa_decode_attention

        ring = bool(attrs.get("ring", False))
        idx = pos % (int(table.shape[1]) * page) if ring else pos
        phys = jnp.take_along_axis(table, (idx // page)[:, None],
                                   axis=1)[:, 0]
        pool_k = pool_k.at[phys, idx % page].set(k.astype(pool_k.dtype))
        pool_v = pool_v.at[phys, idx % page].set(v.astype(pool_v.dtype))
        out = paged_gqa_decode_attention(
            q, pool_k, pool_v, table, pos, num_heads=n,
            num_kv_heads=int(attrs["num_kv_heads"]), head_dim=hd,
            scale=scale, window=int(attrs.get("window", 0)), ring=ring)
        return {"Out": out, "PoolKOut": pool_k, "PoolVOut": pool_v}
    # write the step's K/V into each row's current page
    phys = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0]
    pool_k = pool_k.at[phys, pos % page].set(k)
    pool_v = pool_v.at[phys, pos % page].set(v)
    out = paged_decode_attention(q, pool_k, pool_v, table, pos,
                                 num_heads=n, head_dim=hd, scale=scale)
    return {"Out": out, "PoolKOut": pool_k, "PoolVOut": pool_v}


def _lane_padded(x, width):
    """`x` with its last axis zero-padded to `width`."""
    import jax.numpy as jnp

    short = width - x.shape[-1]
    if short < 0:
        raise ValueError(f"a row of {x.shape[-1]} values in pages of "
                         f"{width}")
    return x if not short else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


@register_op("latent_cache_write",
             non_diff_inputs=("Latent", "Pool", "PageTable", "Lengths"))
def latent_cache_write_op(ins, attrs):
    """Bulk-write a prompt's latent rows into a latent layer's pages: the
    PREFILL half of the cache discipline for a layer that keeps ONE array
    a token (serving/kv_cache.py `LayerCache(latent=True)`).

    Latent [B, S, w] (the normed compressed latent, then the rotated
    shared key); Pool [N, P, W] with W >= w (a row rides in whole lane
    tiles, its tail zero); PageTable [B, MP]; Lengths [B]. Token s of row b
    lands at page PageTable[b, s // P], offset s % P; positions at or past
    the row's length go to the scratch page 0. Attr ``ring``: the table is
    a slot's latent ring of ``cap = MP x P`` rows, token s lands at index
    ``s mod cap`` and of a longer prompt only the last `cap` tokens are
    written (`_kv_cache_write_classed`)."""
    import jax.numpy as jnp

    pool = jnp.asarray(ins["Pool"][0])
    table = jnp.asarray(ins["PageTable"][0])
    lengths = jnp.asarray(ins["Lengths"][0]).reshape(-1)
    lat = _lane_padded(ins["Latent"][0], pool.shape[2])
    b, s, _ = lat.shape
    page = int(pool.shape[1])
    at = pos = jnp.arange(s, dtype=jnp.int32)
    cap = int(table.shape[1]) * page
    if attrs.get("ring"):
        pos = at % cap
    phys = jnp.take_along_axis(
        table, jnp.broadcast_to((pos // page)[None, :], (b, s)), axis=1)
    valid = at[None, :] < lengths[:, None]
    if attrs.get("ring"):
        valid &= at[None, :] >= lengths[:, None] - cap
    phys = jnp.where(valid, phys, 0).reshape(-1)
    off = jnp.broadcast_to((pos % page)[None, :], (b, s)).reshape(-1)
    return {"PoolOut": pool.at[phys, off].set(
        lat.reshape(b * s, -1).astype(pool.dtype))}


@register_op("cached_latent_attention",
             required_attrs=("num_heads", "value_dim"),
             non_diff_inputs=("Latent", "Pool", "PageTable", "Positions"))
def cached_latent_attention_op(ins, attrs):
    """One DECODE step of latent attention in its absorbed form against
    latent pages: the twin of `cached_kv_attention` for a layer that
    caches one row a token.

    Q [B, n*w] (`mla_absorb_query`: a head's query in the latent's space,
    then its rotary part), Latent [B, w] (the new token's row), Pool
    [N, P, W >= w], PageTable [B, MP], Positions [B]. The op writes the
    row at (PageTable[b, pos // P], pos % P), then every head attends the
    row's pages, positions > pos masked before the softmax, its values the
    rows' first `value_dim` entries. Out [B, n*value_dim] float32 (still
    in the latent's space: `mla_expand_output` follows), PoolOut.

    The attend phase is ops/pallas/paged_mla_attention.py under the
    PT_PALLAS dispatch; mode 'off' and untileable shapes take the counted
    stock gather (``pallas.paged_attn_fallbacks``).

    Attr ``window`` (a window layer's LATENT RING): the table is the slot's
    ring of ``cap = MP x P`` rows, the row lands at index ``pos mod cap``,
    and every head attends the rows whose TRUE position lies in
    ``pos - window < s <= pos``: the ring alone is read, whatever the
    context's length.

    Attr ``queries`` (a step that verifies a draft, models/xing4.py): the B
    rows are ``queries`` consecutive positions of B / queries slots (row
    ``queries x s + j`` is slot s at position ``pos_s + j``, with slot s's
    table). Every row's latent is written first; then a slot's latents are
    read ONCE for all its positions, position j attending ``<= pos_s + j``
    (so it sees the fresh rows before it)."""
    import jax.numpy as jnp

    from .pallas.paged_mla_attention import paged_mla_decode_attention

    pool = jnp.asarray(ins["Pool"][0])
    table = jnp.asarray(ins["PageTable"][0])
    pos = jnp.asarray(ins["Positions"][0]).reshape(-1)
    n = int(attrs["num_heads"])
    q, lat = ins["Q"][0], ins["Latent"][0]
    b, w = lat.shape
    width, page = int(pool.shape[2]), int(pool.shape[1])
    scale = float(attrs.get("scale") or w ** -0.5)
    window = int(attrs.get("window", 0))
    queries = int(attrs.get("queries", 1))
    idx = pos % (int(table.shape[1]) * page) if window else pos
    phys = jnp.take_along_axis(table, (idx // page)[:, None], axis=1)[:, 0]
    pool = pool.at[phys, idx % page].set(
        _lane_padded(lat, width).astype(pool.dtype))
    q = _lane_padded(q.reshape(b, n, w), width)
    if queries > 1:
        out = paged_mla_decode_attention(
            q.reshape(b // queries, queries * n * width), pool,
            table[::queries], pos[::queries], num_heads=n,
            value_dim=int(attrs["value_dim"]), scale=scale,
            queries=queries)
        return {"Out": out.reshape(b, -1), "PoolOut": pool}
    out = paged_mla_decode_attention(
        q.reshape(b, n * width), pool, table, pos, num_heads=n,
        value_dim=int(attrs["value_dim"]), scale=scale, window=window)
    return {"Out": out, "PoolOut": pool}


@register_op("chunk_cached_attention",
             required_attrs=("num_heads", "head_dim"),
             non_diff_inputs=("K", "V", "PoolK", "PoolV", "PageTable",
                              "ChunkStart", "Lengths"))
def chunk_cached_attention_op(ins, attrs):
    """One page-aligned PROMPT CHUNK of prefill against the paged KV
    pool — the building block of the prefix-sharing chunked prefill
    (serving/prefix_store.py). Where ``kv_cache_write`` +
    ``flash_attention`` prefill the whole prompt in one pass, this op
    processes ``C`` tokens starting at global position ``ChunkStart``:
    it writes the chunk's K/V into the row's pages and attends each
    chunk query over (a) the POOL positions 0..ChunkStart-1 — the
    already-prefilled (possibly SHARED, cache-hit) prefix — and (b) the
    in-program chunk keys causally (s' <= s). Because a chunk's output
    depends only on the chunk tokens and the prior positions' pool
    BYTES (invalid positions are masked to -1e9 before the softmax, so
    recycled-page garbage and physical page ids contribute exactly
    zero), replaying only the uncached suffix chunks over bit-identical
    cached prefix pages reproduces the cold prefill bit for bit — the
    prefix-hit bitwise gate of tests/test_prefix_store.py.

    Q, K, V [B, C, kvdim] — the chunk's projections; PoolK/PoolV
    [N, P, kvdim]; PageTable [B, MP]; ChunkStart [B] int32 (page-aligned
    global position of chunk token 0); Lengths [B] int32 (valid tokens
    in this chunk, 1..C). Writes route invalid positions to the pool's
    reserved scratch page 0; a SHARED page is protected by pointing the
    chunk's own page-table entry at 0 (attention never reads the
    current chunk through the pool, so absorbing its write into scratch
    is free). Outputs: Out [B, C, kvdim], PoolKOut, PoolVOut.

    Attr ``num_kv_heads`` (present) selects grouped heads over context
    pages of any dtype: ``_chunk_cached_attention_classed``. A ring of
    pages is refused: the prefix store shares a context's pages, and
    nothing chunk-prefills a window layer yet."""
    import jax
    import jax.numpy as jnp

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    # .at[] updates need jax arrays (a direct OpTest call feeds numpy)
    pool_k = jnp.asarray(ins["PoolK"][0])
    pool_v = jnp.asarray(ins["PoolV"][0])
    table = jnp.asarray(ins["PageTable"][0])
    start = jnp.asarray(ins["ChunkStart"][0]).reshape(-1)
    lengths = jnp.asarray(ins["Lengths"][0]).reshape(-1)
    b, c, _ = k.shape
    n = int(attrs["num_heads"])
    hd = int(attrs["head_dim"])
    scale = float(attrs.get("scale") or hd ** -0.5)
    page = int(pool_k.shape[1])
    mp = int(table.shape[1])
    if "num_kv_heads" in attrs:
        if attrs.get("ring") or attrs.get("window"):
            raise NotImplementedError(
                "chunk_cached_attention over a window layer's ring of "
                "pages: the prefix store handles context pages only")
        return _chunk_cached_attention_classed(
            q, k, v, pool_k, pool_v, table, start, lengths, n,
            int(attrs["num_kv_heads"]), hd, scale)
    # prior context is gathered from the PRE-write pools: positions
    # < ChunkStart are untouched by this chunk's writes by construction
    s_ctx = mp * page
    ctx_k = pool_k[table].reshape(b, s_ctx, n, hd)
    ctx_v = pool_v[table].reshape(b, s_ctx, n, hd)
    # -- write phase (kv_cache_write with a start offset) --------------------
    pos = jnp.arange(c, dtype=jnp.int32)                       # [C]
    g = start[:, None] + pos[None, :]                          # [B, C]
    phys = jnp.take_along_axis(table, g // page, axis=1)
    valid = pos[None, :] < lengths[:, None]                    # [B, C]
    phys = jnp.where(valid, phys, 0).reshape(-1)
    off = (g % page).reshape(-1)
    pool_k_out = pool_k.at[phys, off].set(k.reshape(b * c, -1))
    pool_v_out = pool_v.at[phys, off].set(v.reshape(b * c, -1))
    # -- attend phase: prior pool context + causal in-chunk ------------------
    qh = q.reshape(b, c, n, hd)
    sc_ctx = jnp.einsum("bqnh,bsnh->bnqs", qh, ctx_k) * scale  # [B,n,C,S]
    ctx_pos = jnp.arange(s_ctx, dtype=jnp.int32)
    m_ctx = ctx_pos[None, None, None, :] < start[:, None, None, None]
    sc_ctx = jnp.where(m_ctx, sc_ctx, -1e9)
    kh = k.reshape(b, c, n, hd)
    vh = v.reshape(b, c, n, hd)
    sc_chk = jnp.einsum("bqnh,bsnh->bnqs", qh, kh) * scale     # [B,n,C,C]
    causal = pos[None, :] <= pos[:, None]                      # [C_q, C_k]
    sc_chk = jnp.where(causal[None, None, :, :], sc_chk, -1e9)
    probs = jax.nn.softmax(jnp.concatenate([sc_ctx, sc_chk], -1), axis=-1)
    out = jnp.einsum("bnqs,bsnh->bqnh", probs[..., :s_ctx], ctx_v) \
        + jnp.einsum("bnqs,bsnh->bqnh", probs[..., s_ctx:], vh)
    return {"Out": out.reshape(b, c, n * hd),
            "PoolKOut": pool_k_out, "PoolVOut": pool_v_out}


def _chunk_cached_attention_classed(q, k, v, pool_k, pool_v, table, start,
                                    lengths, n, nkv, hd, scale):
    """`chunk_cached_attention` for grouped heads over context pages of
    the pool's dtype (attr `num_kv_heads` present). The prior context is
    read from the PRE-write pools, float32 accumulation throughout."""
    import jax
    import jax.numpy as jnp

    b, c, _ = k.shape
    page = int(pool_k.shape[1])
    cap = int(table.shape[1]) * page
    g = n // nkv
    dt = pool_k.dtype
    ctx_k = pool_k[table].reshape(b, cap, nkv, hd)
    ctx_v = pool_v[table].reshape(b, cap, nkv, hd)
    pos = jnp.arange(c, dtype=jnp.int32)
    gpos = start[:, None] + pos[None, :]                       # [B, C]
    phys = jnp.take_along_axis(table, gpos // page, axis=1)
    valid = pos[None, :] < lengths[:, None]
    phys = jnp.where(valid, phys, 0).reshape(-1)
    off = (gpos % page).reshape(-1)
    pool_k_out = pool_k.at[phys, off].set(k.reshape(b * c, -1).astype(dt))
    pool_v_out = pool_v.at[phys, off].set(v.reshape(b * c, -1).astype(dt))
    m_ctx = (jnp.arange(cap, dtype=jnp.int32)[None, :]
             < start[:, None])[:, None, :]                     # [B,1,cap]
    causal = (pos[None, :] <= pos[:, None])[None]              # [1,Cq,Ck]
    qh = q.reshape(b, c, nkv, g, hd).astype(dt)
    kh = k.reshape(b, c, nkv, hd).astype(dt)
    vh = v.reshape(b, c, nkv, hd).astype(dt)
    sc_ctx = jnp.einsum("bqkgh,bskh->bkgqs", qh, ctx_k,
                        preferred_element_type=jnp.float32) * scale
    sc_chk = jnp.einsum("bqkgh,bskh->bkgqs", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    sc_ctx = jnp.where(m_ctx[:, None, None], sc_ctx, -1e9)
    sc_chk = jnp.where(causal[:, None, None], sc_chk, -1e9)
    probs = jax.nn.softmax(jnp.concatenate([sc_ctx, sc_chk], -1), axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs[..., :cap].astype(dt),
                     ctx_v, preferred_element_type=jnp.float32) \
        + jnp.einsum("bkgqs,bskh->bqkgh", probs[..., cap:].astype(dt), vh,
                     preferred_element_type=jnp.float32)
    return {"Out": out.reshape(b, c, n * hd),
            "PoolKOut": pool_k_out, "PoolVOut": pool_v_out}


@register_op("ring_attention", non_diff_inputs=("Bias",), is_collective=True)
def ring_attention_op(ins, attrs):
    """Sequence-parallel attention over the `sp` mesh axis
    (parallel/ring_attention.py). Q/K/V are the local sequence shards
    [B,H,S_local,D]; Bias the local key-bias shard [B,S_local]. Degrades to
    single-device flash attention outside an SPMD region (nranks==1)."""
    from ..parallel.ring_attention import ring_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = None
    if ins.get("Bias") and ins["Bias"][0] is not None:
        bias = ins["Bias"][0]
    rate, seed = _attn_dropout(attrs)
    out = ring_attention(q, k, v, bias_kv=bias,
                         causal=bool(attrs.get("causal", False)),
                         scale=attrs.get("scale", None),
                         axis_name=attrs.get("axis_name", "sp"),
                         dropout_rate=rate, dropout_seed=seed)
    return {"Out": out}


@register_op("fused_bn_add_act", non_diff_inputs=("Mean", "Variance"))
def fused_bn_add_act_op(ins, attrs):
    """Training-time BatchNorm(+residual)+ReLU as ONE op with the
    pinned-residual custom_vjp backward (ops/pallas/bn_act.py; reference
    fused_bn_add_activation_op.cu). Same contract as batch_norm plus the
    optional Z side input added before the activation."""
    from .pallas.bn_act import fused_batch_norm_act

    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    z = ins.get("Z", [None])[0]
    layout = attrs.get("data_layout", "NCHW")
    y, mo, vo, sm, sv = fused_batch_norm_act(
        x, scale, bias, mean, var, z,
        eps=float(attrs.get("epsilon", 1e-5)),
        momentum=float(attrs.get("momentum", 0.9)),
        c_axis=1 if layout == "NCHW" else -1,
        act=attrs.get("act", "relu"),
        is_test=bool(attrs.get("is_test", False)))
    return {"Y": y, "MeanOut": mo, "VarianceOut": vo,
            "SavedMean": sm, "SavedVariance": sv}


@register_op("fused_layer_norm")
def fused_layer_norm_op(ins, attrs):
    """layer_norm over the last axis via the Pallas kernel (nn_ops.layer_norm
    stays the general begin_norm_axis implementation)."""
    from .pallas import fused_layer_norm

    x = ins["X"][0]
    scale = ins["Scale"][0] if ins.get("Scale") and ins["Scale"][0] is not None else None
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None else None
    eps = attrs.get("epsilon", 1e-5)
    y, mean, rstd = fused_layer_norm(x, scale, bias, eps=eps)
    # match nn_ops.layer_norm's contract: Variance is the variance, not rstd
    return {"Y": y, "Mean": mean, "Variance": 1.0 / (rstd * rstd) - eps}
