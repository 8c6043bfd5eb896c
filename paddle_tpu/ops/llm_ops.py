"""Ops of today's decoder blocks that the post-LN block of
models/decoder_lm.py does not have: RMS norm, a matmul over low-precision
weights that accumulates in float32, SwiGLU, per-head QK-norm with rotary
positions, an output gate, and whole-prompt attention for grouped heads
with a window; and the latent attention of models/kimi_k2.py around its
cache (YaRN rotary on the rotary part, the absorbed query, the expanded
output, whole-prompt attention in the expanded form). models/afmoe.py and
models/kimi_k2.py build their programs from them; the cached attention ops
are in attention_ops.py and the routed expert layer in moe_ops.py.

Number format: weights and K/V pages may be bfloat16; a row's activations
stay float32 between matmuls and are rounded to the weight's dtype where
they enter one, every product accumulates in float32, and norms, softmax
and router scores are float32 throughout.
"""

from __future__ import annotations

from ..core.registry import register_grad_maker, register_op


@register_op("rms_norm")
def rms_norm_op(ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * (scale_offset +
    Scale), in float32. Attr `scale_offset` (0 by default) is 1 for a gain
    stored around zero."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0].astype(jnp.float32)
    scale = ins["Scale"][0].astype(jnp.float32)
    if attrs.get("scale_offset"):
        scale = scale + float(attrs["scale_offset"])
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return {"Y": x * jax.lax.rsqrt(ms + float(attrs.get("epsilon", 1e-5)))
            * scale}


@register_op("linear_acc32")
def linear_acc32_op(ins, attrs):
    """Out = X @ W with X rounded to W's dtype and the products
    accumulated in float32: the MXU's native bf16 x bf16 -> f32. Out is
    float32. `transpose_Y` reads W as [out, in]."""
    import jax
    import jax.numpy as jnp

    x, w = ins["X"][0], ins["W"][0]
    dims = (((x.ndim - 1,), (1 if attrs.get("transpose_Y") else 0,)),
            ((), ()))
    return {"Out": jax.lax.dot_general(x.astype(w.dtype), w, dims,
                                       preferred_element_type=jnp.float32)}


@register_op("swiglu")
def swiglu_op(ins, attrs):
    """Out = silu(Gate) * Up."""
    import jax

    return {"Out": jax.nn.silu(ins["Gate"][0]) * ins["Up"][0]}


@register_op("sigmoid_gate")
def sigmoid_gate_op(ins, attrs):
    """Out = X * sigmoid(Gate): the gate on the attention output."""
    import jax

    return {"Out": ins["X"][0] * jax.nn.sigmoid(ins["Gate"][0])}


@register_op("embed_scaled")
def embed_scaled_op(ins, attrs):
    """Out = W[Ids] * scale in float32 (muP's embedding multiplier)."""
    import jax.numpy as jnp

    return {"Out": ins["W"][0][ins["Ids"][0]].astype(jnp.float32)
            * float(attrs.get("scale", 1.0))}


@register_op("last_token_rows")
def last_token_rows_op(ins, attrs):
    """Out [B, D] = X[b, Lengths[b] - 1]: the last real position of each
    padded row."""
    import jax.numpy as jnp

    x = ins["X"][0]
    last = jnp.clip(ins["Lengths"][0].reshape(-1) - 1, 0, x.shape[1] - 1)
    return {"Out": jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]}


@register_op("rows_live")
def rows_live_op(ins, attrs):
    """Live [B] bool: the rows of a decode step's slot array that carry a
    request. An empty slot's page table is all zeros (the pool's scratch
    page); a live row owns a first page."""
    return {"Live": ins["PageTable"][0][:, 0] > 0}


@register_op("prompt_rows_live")
def prompt_rows_live_op(ins, attrs):
    """Live [B, S] bool: the positions of a padded prompt (or chunk) that
    hold a real token, the first Lengths[b] of row b."""
    import jax.numpy as jnp

    s = ins["Tokens"][0].shape[1]
    return {"Live": jnp.arange(s, dtype=jnp.int32)[None, :]
            < ins["Lengths"][0].reshape(-1, 1)}


@register_op("qk_norm_rope", non_diff_inputs=("Positions",),
             required_attrs=("head_dim",))
def qk_norm_rope_op(ins, attrs):
    """Per-head RMS norm of Q and K over `head_dim` with learned gains,
    then (attr `rope`) rotary position embedding over the whole head in
    the half-split convention: (x1, x2) -> (x1 cos - x2 sin, x2 cos +
    x1 sin) with angle pos * theta^(-2i/head_dim). With attr
    `yarn_factor` over 1 the frequencies are YaRN's (`yarn_inv_freq` from
    `theta`, `yarn_original_max`, `yarn_beta_fast`, `yarn_beta_slow`), and
    cos and sin are multiplied by attr `attention_factor`. Attr
    `rotary_dim` (the whole head by default) turns the FIRST that many
    dimensions of a head alone, pairs (i, i + rotary_dim / 2) at
    theta^(-2i/rotary_dim), and leaves the rest as the norm gave them;
    attr `scale_offset` (0 by default) is added to both gains (1: gains
    stored around zero).

    Q [..., nq*hd], K [..., nkv*hd] float32; QScale, KScale [hd];
    Positions int32, shaped like Q without its last axis; left out, a
    row's position is its index along Q's second-to-last axis (a whole
    unpadded sequence a row, as a trainer feeds them)."""
    import jax
    import jax.numpy as jnp

    hd = int(attrs["head_dim"])
    eps = float(attrs.get("epsilon", 1e-5))
    if ins.get("Positions"):
        pos = ins["Positions"][0]
    else:
        q = ins["Q"][0]
        pos = jnp.broadcast_to(jnp.arange(q.shape[-2], dtype=jnp.int32),
                               q.shape[:-1])
    yarn = float(attrs.get("yarn_factor", 1.0))
    mscale = float(attrs.get("attention_factor", 1.0))
    rd = int(attrs.get("rotary_dim", hd))
    offset = float(attrs.get("scale_offset", 0.0))

    def one(x, scale):
        lead = x.shape[:-1]
        xh = x.astype(jnp.float32).reshape(lead + (-1, hd))
        ms = jnp.mean(jnp.square(xh), axis=-1, keepdims=True)
        gain = scale.astype(jnp.float32)
        xh = xh * jax.lax.rsqrt(ms + eps) * (gain + offset if offset
                                             else gain)
        if attrs.get("rope"):
            half = rd // 2
            theta = float(attrs.get("theta", 10000.0))
            if yarn > 1.0:
                inv = jnp.asarray(yarn_inv_freq(
                    rd, theta, yarn, int(attrs["yarn_original_max"]),
                    float(attrs.get("yarn_beta_fast", 32.0)),
                    float(attrs.get("yarn_beta_slow", 1.0))))
            else:
                inv = theta ** (-jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / rd)
            ang = pos.astype(jnp.float32)[..., None, None] * inv
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            if mscale != 1.0:
                cos, sin = cos * mscale, sin * mscale
            x1, x2 = xh[..., :half], xh[..., half:rd]
            xh = jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
                + ([xh[..., rd:]] if rd < hd else []), axis=-1)
        return xh.reshape(x.shape)

    return {"QOut": one(ins["Q"][0], ins["QScale"][0]),
            "KOut": one(ins["K"][0], ins["KScale"][0])}


# `gqa_prefill_attention`'s shape rule: the float32 scores ONE query block
# holds in the XLA form (B x n x block_q x the keys it reads) from which the
# prompt goes to the flash forward kernel instead: 64 MiB of them. Both
# forms on the chip, ms a layer in bfloat16 products (v5e, my chip run, PR
# 51: `tools/bench_qwen3_next_prefill.py --attention`), kernel / XLA form,
# with the scores a block holds in Mi:
#   4 + 1 heads of 256, no window (Qwen3-Next's share):
#     2,048: 0.229 / 0.225 (4)    4,096: 0.428 / 0.557 (8)
#     8,192: 1.290 / 2.174 (16)  16,384: 4.595 / 20.205 (32)
#   6 + 1 heads of 128 (Trinity's share), window 4,096 | no window:
#     2,048: 0.255 / 0.239 (6)   | 0.247 / 0.227 (6)
#     4,096: 0.531 / 0.486 (12)  | 0.536 / 0.491 (12)
#     8,192: 1.434 / 1.009 (13.5) | 1.779 / 2.030 (24)
# The kernel takes ~2.2 us a (head, 512 x 512 block) it visits at either
# head size, 8.4 ps a score; the XLA form 5 ps a score at head 128 and 8 at
# 256, over every key a block can reach, and 19 once a block's scores pass
# 128 MiB. So the kernel wins where it skips half the keys AND the scores
# are large: at 16 Mi it is ahead at both head sizes, under 14 Mi it loses
# at head 128, and what it wins under 16 Mi at head 256 is 0.13 ms a layer.
GQA_PREFILL_KERNEL_FROM = 1 << 24


@register_op("gqa_prefill_attention",
             required_attrs=("num_heads", "num_kv_heads", "head_dim"))
def gqa_prefill_attention_op(ins, attrs):
    """Causal attention of a whole (padded) prompt over its own keys, for
    grouped heads and an optional window: query head j reads K/V head
    j // (num_heads / num_kv_heads), and with `window` a query at t reads
    keys at t - window < s <= t only. No pool is read: the prefill writes
    its K/V with `kv_cache_write` beside this op.

    Q [B, S, n*hd], K, V [B, S, nkv*hd]. Inputs are rounded to
    `compute_dtype` for the two products, which accumulate in float32;
    the softmax is float32. Out float32 [B, S, n*hd].

    Two forms of the one statement, chosen by the scores a query block
    of the first would hold (GQA_PREFILL_KERNEL_FROM, with the chip's
    readings that set it). Under it, XLA products: queries go in blocks
    of `block_q`, a block reads the keys its window can reach (all of
    them without a window) and keeps its float32 scores, block_q x
    (block_q + window) a head, in HBM. From it on, where the kernel can
    tile the shape (`flash_window.window_route`), the trainer's forward
    kernel (`flash_fwd_window`), which visits only the blocks under the
    diagonal and inside the window and keeps every score in VMEM; it
    has no backward here (a trainer calls `flash_attention`). Counted at
    trace time: `pallas.gqa_prefill_dispatches`, and
    `pallas.gqa_prefill_fallbacks` with `reason=` `mode` (kernels off),
    `short` (under the rule), `shape` (nothing the kernel can tile)."""
    import jax
    import jax.numpy as jnp

    from ..core import telemetry
    from .pallas import flash_window, kernel_mode

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    n, nkv = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    hd = int(attrs["head_dim"])
    g = n // nkv
    window = int(attrs.get("window", 0))
    scale = float(attrs.get("scale") or hd ** -0.5)
    dt = jnp.dtype(attrs.get("compute_dtype", "float32"))
    b, s, _ = q.shape
    bq = min(int(attrs.get("block_q", 512)), s)
    if s % bq:
        raise ValueError(f"prompt length {s} is no multiple of block_q {bq}")
    # keys a block of queries can reach
    kw = s if not window else min(s, bq + -(-window // bq) * bq)
    if kernel_mode() == "off":
        reason = "mode"
    elif b * n * bq * kw < GQA_PREFILL_KERNEL_FROM:
        reason = "short"
    else:
        qc, kc, vc = q.astype(dt), k.astype(dt), v.astype(dt)
        if flash_window.window_route(qc, kc, n, nkv)[0] != "reference":
            telemetry.counter_add("pallas.gqa_prefill_dispatches", 1)
            return {"Out": flash_window.flash_window_fwd_lse(
                qc, kc, vc, num_heads=n, num_kv_heads=nkv, window=window,
                scale=scale, out_dtype=jnp.float32)[0]}
        reason = "shape"
    telemetry.counter_add("pallas.gqa_prefill_fallbacks", 1, reason=reason)
    qh = q.reshape(b, s, nkv, g, hd).astype(dt)
    kh = k.reshape(b, s, nkv, hd).astype(dt)
    vh = v.reshape(b, s, nkv, hd).astype(dt)

    def block(q0):
        k0 = jnp.clip(q0 + bq - kw, 0, s - kw)
        qb = jax.lax.dynamic_slice_in_dim(qh, q0, bq, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(kh, k0, kw, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vh, k0, kw, axis=1)
        sc = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb,
                        preferred_element_type=jnp.float32) * scale
        tq = q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        ts = k0 + jnp.arange(kw, dtype=jnp.int32)[None, :]
        ok = ts <= tq
        if window:
            ok &= ts > tq - window
        p = jax.nn.softmax(jnp.where(ok, sc, -1e9), axis=-1)
        return jnp.einsum("bkgqs,bskh->bqkgh", p.astype(dt), vb,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(block, jnp.arange(0, s, bq, dtype=jnp.int32))
    # [blocks, B, bq, nkv, g, hd] -> [B, S, n*hd]
    return {"Out": jnp.moveaxis(out, 0, 1).reshape(b, s, n * hd)}


def _chunked_head_loss(x, w, labels, chunk):
    """-> (mean cross-entropy of softmax(x @ w) against labels, its
    gradients in x [T, H] and w [H, V]), a chunk of rows at a time."""
    import jax
    import jax.numpy as jnp

    t, h = x.shape
    n = t // chunk
    xs = x.astype(w.dtype).reshape(n, chunk, h)

    def some(carry, row):
        loss, dw = carry
        xc, lab = row
        logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        ex = jnp.exp(logits - top)
        total = jnp.sum(ex, axis=-1, keepdims=True)
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
            == lab[:, None]
        loss = loss + jnp.sum(jnp.log(total) + top
                              - jnp.sum(jnp.where(hit, logits, 0.0),
                                        axis=-1, keepdims=True))
        dl = ((ex / total - hit.astype(jnp.float32)) / t).astype(w.dtype)
        dx = jax.lax.dot_general(dl, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(xc, dl, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return (loss, dw), dx.astype(w.dtype)

    (loss, dw), dx = jax.lax.scan(
        some, (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32)),
        (xs, labels.reshape(n, chunk).astype(jnp.int32)))
    return loss / t, dx.reshape(t, h), dw.astype(w.dtype)


@register_op("head_cross_entropy", non_diff_inputs=("Label",))
def head_cross_entropy_op(ins, attrs):
    """Loss [1] = mean over rows of the cross-entropy of softmax(X @ W)
    against Label: the output head and its loss as one op that never holds
    the logits whole. X [..., H] float32 (rounded to W's dtype where it
    enters the product, float32 accumulation), W [H, V], Label int [...].
    Rows go `chunk` at a time (attr; the row count where it does not
    divide): a chunk's [chunk, V] float32 logits, their softmax and the
    chunk's part of both gradients, so that a step's peak holds one
    chunk's logits and not [tokens, V] twice over (3.2 GB at 16,384 x
    24,576). XGrad [..., H] and WGrad [H, V] (W's dtype) are the
    gradients of Loss itself: `head_cross_entropy_grad` scales them by
    Loss's cotangent, and the backward computes no product again."""
    import jax.numpy as jnp

    x, w, labels = ins["X"][0], ins["W"][0], ins["Label"][0]
    flat = x.reshape(-1, x.shape[-1])
    t = flat.shape[0]
    chunk = int(attrs.get("chunk", 2048))
    if chunk <= 0 or t % chunk:
        chunk = t
    loss, dx, dw = _chunked_head_loss(flat, w, labels.reshape(-1), chunk)
    return {"Loss": loss.reshape(1).astype(jnp.float32),
            "XGrad": dx.reshape(x.shape), "WGrad": dw}


@register_grad_maker("head_cross_entropy")
def _head_cross_entropy_grad_maker(op, out_grads, in_grads):
    from ..core.ir import OpDesc

    og = (out_grads.get("Loss") or [None])[0]
    grads = {s: (in_grads.get(s) or [None])[0] for s in ("X", "W")}
    if og is None or all(g is None for g in grads.values()):
        return []
    return [OpDesc(
        "head_cross_entropy_grad",
        {"XGrad": list(op.outputs["XGrad"]),
         "WGrad": list(op.outputs["WGrad"]), "LossGrad": [og]},
        {s + "Grad": [g] for s, g in grads.items() if g is not None}, {})]


@register_op("head_cross_entropy_grad",
             non_diff_inputs=("XGrad", "WGrad", "LossGrad"),
             skip_infer_shape=True)
def head_cross_entropy_grad_op(ins, attrs):
    """d(X, W) of head_cross_entropy: its saved unit gradients times the
    cotangent of Loss. XGrad in float32 (X's dtype), WGrad in W's."""
    import jax.numpy as jnp

    g = ins["LossGrad"][0].reshape(()).astype(jnp.float32)
    dx, dw = ins["XGrad"][0], ins["WGrad"][0]
    return {"XGrad": dx.astype(jnp.float32) * g,
            "WGrad": (dw.astype(jnp.float32) * g).astype(dw.dtype)}


# ---------------------------------------------------------------------------
# multi-head latent attention (the DeepSeek-V3 block; models/kimi_k2.py)

def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """The `dim // 2` rotary frequencies under YaRN: pair i turns by
    ``theta^(-2i/dim)`` a position where it turns more than `beta_fast`
    times over the original `original_max` positions, by that over `factor`
    where it turns fewer than `beta_slow` times, and by a linear blend of
    the two between (the ramp runs over whole pair indices, floor of the
    one bound to ceil of the other)."""
    import math

    import numpy as np

    half = dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    if factor <= 1.0:
        return plain.astype(np.float32)

    def turns_at(turns):       # the pair index that makes `turns` turns
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: the softmax scale is multiplied by its
    square (``mscale_all_dim`` in the published config)."""
    import math

    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_pairs(x, positions, inv_freq):
    """Rotate the interleaved pairs (x[2i], x[2i+1]) of the last axis by
    ``positions x inv_freq[i]``; `positions` is shaped like x without its
    last axes (heads broadcast)."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    while ang.ndim < x.ndim:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@register_op("mla_rope_split",
             required_attrs=("num_heads", "nope_dim", "rope_dim"))
def mla_rope_split_op(ins, attrs):
    """The position-dependent middle of a latent attention layer, between
    its down- and up-projections.

    Q [..., n*(nope+rope)] (a head: its position-free part, then its
    rotary part), KVA [..., rank+rope] (the compressed latent, then the
    one rotary key all heads share), KVScale [rank], Positions int32
    shaped like Q without its last axis. The latent is RMS-normed with
    its gain; both rotary parts are rotated (YaRN frequencies from attrs
    `theta`, `yarn_factor`, `yarn_original_max`, `yarn_beta_fast`,
    `yarn_beta_slow`; interleaved pairs). Outputs, float32: QNope
    [..., n*nope], QRope [..., n*rope], C [..., rank] (the normed latent)
    and Latent [..., rank+rope] = [C, rotated key]: the row a latent page
    holds."""
    import jax
    import jax.numpy as jnp

    n, nope = int(attrs["num_heads"]), int(attrs["nope_dim"])
    rope = int(attrs["rope_dim"])
    inv = yarn_inv_freq(rope, float(attrs.get("theta", 10000.0)),
                        float(attrs.get("yarn_factor", 1.0)),
                        int(attrs.get("yarn_original_max", 4096)),
                        float(attrs.get("yarn_beta_fast", 32.0)),
                        float(attrs.get("yarn_beta_slow", 1.0)))
    pos = ins["Positions"][0]
    q = ins["Q"][0].astype(jnp.float32)
    kva = ins["KVA"][0].astype(jnp.float32)
    lead = q.shape[:-1]
    qh = q.reshape(lead + (n, nope + rope))
    q_rope = _rope_pairs(qh[..., nope:], pos, inv)
    c, k_rope = kva[..., :-rope], kva[..., -rope:]
    ms = jnp.mean(jnp.square(c), axis=-1, keepdims=True)
    c = c * jax.lax.rsqrt(ms + float(attrs.get("epsilon", 1e-5))) \
        * ins["KVScale"][0].astype(jnp.float32)
    k_rope = _rope_pairs(k_rope, pos, inv)
    return {"QNope": qh[..., :nope].reshape(lead + (n * nope,)),
            "QRope": q_rope.reshape(lead + (n * rope,)),
            "C": c, "Latent": jnp.concatenate([c, k_rope], axis=-1)}


def _kvb_heads(w, n):
    """W_kvb [rank, n*(nope+v)] as [rank, n, nope+v]."""
    return w.reshape(w.shape[0], n, w.shape[1] // n)


@register_op("mla_absorb_query", required_attrs=("num_heads", "nope_dim"))
def mla_absorb_query_op(ins, attrs):
    """The decode step's query in the latent's own space: per head
    ``[q_n W_uk (rank), q_r (rope)]`` with ``W_uk`` head h's key half of W
    (the up-projection of latents to keys and values, [rank, n*(nope+v)]),
    so that ``q . [c, k_r]`` is ``q_n . k_n + q_r . k_r`` without any
    key being expanded. QNope [B, n*nope], QRope [B, n*rope] -> Q
    [B, n*(rank+rope)] float32; the product rounds QNope to W's dtype and
    accumulates in float32. Attr `num_kv_heads` (grouped heads: W holds
    that many K/V heads, query head h reads head h // (n / num_kv_heads))."""
    import jax.numpy as jnp

    n, nope = int(attrs["num_heads"]), int(attrs["nope_dim"])
    nkv = int(attrs.get("num_kv_heads", n))
    w = _kvb_heads(ins["W"][0], nkv)[:, :, :nope]        # [rank, nkv, nope]
    qn, qr = ins["QNope"][0], ins["QRope"][0]
    b = qn.shape[0]
    if nkv != n:
        qc = jnp.einsum(
            "bkgd,ckd->bkgc",
            qn.reshape(b, nkv, n // nkv, nope).astype(w.dtype), w,
            preferred_element_type=jnp.float32).reshape(b, n, -1)
    else:
        qc = jnp.einsum("bhd,chd->bhc",
                        qn.reshape(b, n, nope).astype(w.dtype), w,
                        preferred_element_type=jnp.float32)
    q = jnp.concatenate([qc, qr.reshape(b, n, -1).astype(jnp.float32)],
                        axis=-1)
    return {"Q": q.reshape(b, -1)}


@register_op("mla_expand_output", required_attrs=("num_heads", "nope_dim"))
def mla_expand_output_op(ins, attrs):
    """Out [B, n*v] = per head ``o_c W_uv``: the attended latents X
    [B, n*rank] through head h's value half of W [rank, n*(nope+v)].
    float32 out, W's dtype in, float32 accumulation. Attr `num_kv_heads`
    as `mla_absorb_query`'s: the n rows of X read the value half of K/V
    head h // (n / num_kv_heads)."""
    import jax.numpy as jnp

    n, nope = int(attrs["num_heads"]), int(attrs["nope_dim"])
    nkv = int(attrs.get("num_kv_heads", n))
    w = _kvb_heads(ins["W"][0], nkv)[:, :, nope:]          # [rank, nkv, v]
    x = ins["X"][0]
    b = x.shape[0]
    if nkv != n:
        out = jnp.einsum(
            "bkgc,ckv->bkgv",
            x.reshape(b, nkv, n // nkv, -1).astype(w.dtype), w,
            preferred_element_type=jnp.float32)
        return {"Out": out.reshape(b, -1)}
    out = jnp.einsum("bhc,chv->bhv", x.reshape(b, n, -1).astype(w.dtype), w,
                     preferred_element_type=jnp.float32)
    return {"Out": out.reshape(b, -1)}


@register_op("mla_prefill_attention",
             required_attrs=("num_heads", "nope_dim", "rope_dim"))
def mla_prefill_attention_op(ins, attrs):
    """Causal attention of a whole (padded) prompt over its own keys in the
    EXPANDED form of latent attention: head h's key is ``[k_n (nope), k_r
    (rope)]`` with k_n and its value v from KV = C W_kvb ([B, S,
    n*(nope+v)], a head: key part, then value) and k_r the one rotated key
    of Latent's last `rope` entries; its query ``[q_n, q_r]``. No pool is
    read: the prefill writes Latent with `latent_cache_write` beside this.

    The attend phase is ops/pallas/mla_prefill_attention.py (blockwise,
    online softmax, the scores stay in VMEM) under the PT_PALLAS
    dispatch, on these arrays as they stand: a prompt's QNope, QRope and
    KV go in as [S, n*width] and Out comes back [S, n*v], no head-major
    copy of anything (a head is a column block of the kernel's
    BlockSpecs). Mode 'off' and untileable shapes take the counted stock
    lowering (``pallas.mla_prefill_fallbacks``). Inputs are rounded to
    `compute_dtype` for the products, which accumulate in float32; the
    softmax is float32. Out [B, S, n*v] is the float32 result rounded
    once to `compute_dtype`: the rounding the output projection's
    `linear_acc32` gave it (to its weight's dtype, the same) before.

    Attr `num_kv_heads` (grouped heads: KV is [B, S, nkv*(nope+v)] and
    query head h reads K/V head h // (n / nkv)) and attr `window` (a query
    at t reads keys at t - window < s <= t only, and the kernel visits
    only the block pairs that band touches)."""
    import jax.numpy as jnp

    from .pallas.mla_prefill_attention import mla_prefill_attention

    n, nope = int(attrs["num_heads"]), int(attrs["nope_dim"])
    rope = int(attrs["rope_dim"])
    dt = jnp.dtype(attrs.get("compute_dtype", "float32"))
    qn, qr = ins["QNope"][0].astype(dt), ins["QRope"][0].astype(dt)
    kv = ins["KV"][0].astype(dt)
    k_rope = ins["Latent"][0][..., -rope:].astype(dt)
    scale = float(attrs.get("scale") or (nope + rope) ** -0.5)
    out = [mla_prefill_attention(qn[i], qr[i], kv[i], k_rope[i], scale,
                                 num_heads=n, nope_dim=nope,
                                 num_kv_heads=int(attrs.get("num_kv_heads",
                                                            n)),
                                 window=int(attrs.get("window", 0)))
           for i in range(qn.shape[0])]
    return {"Out": jnp.stack(out)}


# ---------------------------------------------------------------------------
# the Motif-3 block (models/motif3.py): differential heads, a residual of
# several streams, PolyNorm

@register_op("diff_head_combine", required_attrs=("num_groups", "width"))
def diff_head_combine_op(ins, attrs):
    """Grouped differential attention's subtraction, in float32: X
    [..., G*(s+1)*w] holds, a group, its s signal heads' outputs and then
    its noise head's, each `width` wide; LambdaLogits [..., G*s] a token
    and signal head. Out [..., G*s*w]: ``x_{g,j} - sigmoid(l_{g,j})
    x_{g,s}``. The width is the value's in the expanded prefill and the
    LATENT's in the absorbed decode step, where the one up-projection of
    the group follows the subtraction (`mla_expand_output`)."""
    import jax
    import jax.numpy as jnp

    g, w = int(attrs["num_groups"]), int(attrs["width"])
    x = ins["X"][0].astype(jnp.float32)
    lead = x.shape[:-1]
    xh = x.reshape(lead + (g, -1, w))                    # [..., G, s+1, w]
    lam = jax.nn.sigmoid(ins["LambdaLogits"][0].astype(jnp.float32)
                         ).reshape(lead + (g, -1, 1))
    out = xh[..., :-1, :] - lam * xh[..., -1:, :]
    return {"Out": out.reshape(lead + (-1,))}


@register_op("embed_streams", required_attrs=("n_streams",))
def embed_streams_op(ins, attrs):
    """Out [..., n*C] float32: the embedding row W[Ids] copied into each
    of `n_streams` streams (stream i at columns [iC, (i+1)C))."""
    import jax.numpy as jnp

    row = ins["W"][0][ins["Ids"][0]].astype(jnp.float32)
    return {"Out": jnp.tile(row, (1,) * (row.ndim - 1)
                            + (int(attrs["n_streams"]),))}


@register_op("tile_streams", required_attrs=("n_streams",))
def tile_streams_op(ins, attrs):
    """Out [..., n*C] float32: X [..., C] copied into each of `n_streams`
    streams, as `embed_streams` copies an embedding row (the input of a
    draft module, models/xing4.py)."""
    import jax.numpy as jnp

    x = ins["X"][0].astype(jnp.float32)
    return {"Out": jnp.tile(x, (1,) * (x.ndim - 1)
                            + (int(attrs["n_streams"]),))}


@register_op("sum_streams", required_attrs=("n_streams",))
def sum_streams_op(ins, attrs):
    """Out [..., C] = the sum of X's `n_streams` streams [..., n*C]."""
    x = ins["X"][0]
    n = int(attrs["n_streams"])
    return {"Out": x.reshape(x.shape[:-1] + (n, -1)).sum(axis=-2)}


@register_op("polyglu")
def polyglu_op(ins, attrs):
    """Out = PN(Gate) * Up in float32: PolyNorm over Gate's whole last
    axis (ops/pallas/grouped_swiglu.py `poly_norm`; PN [4]: three weights
    and a bias; attrs `epsilon`, `out_scale`, `bias_clamp`)."""
    import jax.numpy as jnp

    from .pallas.grouped_swiglu import poly_norm

    gate = ins["Gate"][0].astype(jnp.float32)
    return {"Out": poly_norm(
        gate, ins["PN"][0].astype(jnp.float32),
        eps=float(attrs.get("epsilon", 1e-5)),
        out_scale=float(attrs.get("out_scale", 1.0)),
        bias_clamp=float(attrs.get("bias_clamp", 0.5)))
        * ins["Up"][0].astype(jnp.float32)}


@register_op("mhc_pre", required_attrs=("n_streams", "sinkhorn_iters"))
def mhc_pre_op(ins, attrs):
    """The residual path of `n_streams` streams BEFORE a sublayer
    (ops/pallas/mhc_mix.py): X [..., n*C] float32 (stream i at columns
    [iC, (i+1)C)), Gamma [n*C], Phi [n*C, 2n+n^2], Scale [3], Bias
    [2n+n^2] -> U [..., C] (the sublayer's input before its norm) and Maps
    [..., 128] (H_pre, H_post and the doubly stochastic H_res of each
    token, a lane tile; `mhc_post` reads it). The kernel ``mhc_pre``
    under the PT_PALLAS dispatch, its stock lowering where the mode is
    off or the shape cannot be tiled (``pallas.mhc_fallbacks``). Attrs
    `res_clamp_min` / `res_clamp_max` (both or neither) clamp H_res's
    logits before the exponential; `sinkhorn_eps` joins the Sinkhorn
    denominators."""
    from .pallas.mhc_mix import mhc_pre

    x = ins["X"][0]
    lead = x.shape[:-1]
    clamp = {}
    if "res_clamp_min" in attrs:
        clamp["res_clamp"] = (float(attrs["res_clamp_min"]),
                              float(attrs["res_clamp_max"]))
    if attrs.get("sinkhorn_eps"):
        clamp["sinkhorn_eps"] = float(attrs["sinkhorn_eps"])
    u, maps = mhc_pre(
        x.reshape(-1, x.shape[-1]), ins["Gamma"][0], ins["Phi"][0],
        ins["Scale"][0], ins["Bias"][0], n=int(attrs["n_streams"]),
        iters=int(attrs["sinkhorn_iters"]),
        eps=float(attrs.get("epsilon", 1e-5)), **clamp)
    return {"U": u.reshape(lead + (-1,)), "Maps": maps.reshape(lead + (-1,))}


@register_op("mhc_post", required_attrs=("n_streams",))
def mhc_post_op(ins, attrs):
    """The residual path AFTER a sublayer: Out[i] = sum_j H_res[i, j] X[j]
    + H_post[i] Y, clamped to +-`clamp`; X [..., n*C], Y [..., C], Maps
    as `mhc_pre` wrote them. Kernel ``mhc_post``, or its counted stock
    lowering."""
    from .pallas.mhc_mix import mhc_post

    x, y, maps = ins["X"][0], ins["Y"][0], ins["Maps"][0]
    out = mhc_post(x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]),
                   maps.reshape(-1, maps.shape[-1]),
                   n=int(attrs["n_streams"]),
                   clamp=float(attrs.get("clamp", 3e38)))
    return {"Out": out.reshape(x.shape)}
