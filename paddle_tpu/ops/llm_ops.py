"""Ops of today's decoder blocks that the post-LN block of
models/decoder_lm.py does not have: RMS norm, a matmul over low-precision
weights that accumulates in float32, SwiGLU, per-head QK-norm with rotary
positions, an output gate, and whole-prompt attention for grouped heads
with a window. models/afmoe.py builds its programs from them; the cached
attention ops are in attention_ops.py and the routed expert layer in
moe_ops.py.

Number format: weights and K/V pages may be bfloat16; a row's activations
stay float32 between matmuls and are rounded to the weight's dtype where
they enter one, every product accumulates in float32, and norms, softmax
and router scores are float32 throughout.
"""

from __future__ import annotations

from ..core.registry import register_op


@register_op("rms_norm")
def rms_norm_op(ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale, in float32."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0].astype(jnp.float32)
    scale = ins["Scale"][0].astype(jnp.float32)
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


@register_op("qk_norm_rope", required_attrs=("head_dim",))
def qk_norm_rope_op(ins, attrs):
    """Per-head RMS norm of Q and K over `head_dim` with learned gains,
    then (attr `rope`) rotary position embedding over the whole head in
    the half-split convention: (x1, x2) -> (x1 cos - x2 sin, x2 cos +
    x1 sin) with angle pos * theta^(-2i/head_dim).

    Q [..., nq*hd], K [..., nkv*hd] float32; QScale, KScale [hd];
    Positions int32, shaped like Q without its last axis."""
    import jax
    import jax.numpy as jnp

    hd = int(attrs["head_dim"])
    eps = float(attrs.get("epsilon", 1e-5))
    pos = ins["Positions"][0]

    def one(x, scale):
        lead = x.shape[:-1]
        xh = x.astype(jnp.float32).reshape(lead + (-1, hd))
        ms = jnp.mean(jnp.square(xh), axis=-1, keepdims=True)
        xh = xh * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
        if attrs.get("rope"):
            half = hd // 2
            inv = float(attrs.get("theta", 10000.0)) ** (
                -jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
            ang = pos.astype(jnp.float32)[..., None, None] * inv
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            x1, x2 = xh[..., :half], xh[..., half:]
            xh = jnp.concatenate([x1 * cos - x2 * sin,
                                  x2 * cos + x1 * sin], axis=-1)
        return xh.reshape(x.shape)

    return {"QOut": one(ins["Q"][0], ins["QScale"][0]),
            "KOut": one(ins["K"][0], ins["KScale"][0])}


@register_op("gqa_prefill_attention",
             required_attrs=("num_heads", "num_kv_heads", "head_dim"))
def gqa_prefill_attention_op(ins, attrs):
    """Causal attention of a whole (padded) prompt over its own keys, for
    grouped heads and an optional window: query head j reads K/V head
    j // (num_heads / num_kv_heads), and with `window` a query at t reads
    keys at t - window < s <= t only. No pool is read: the prefill writes
    its K/V with `kv_cache_write` beside this op.

    Q [B, S, n*hd], K, V [B, S, nkv*hd]. Queries go in blocks of
    `block_q`; a block reads the keys its window can reach (all of them
    without a window), so the scores never hold more than
    block_q x (block_q + window) entries a head. Inputs are rounded to
    `compute_dtype` for the two products, which accumulate in float32;
    the softmax is float32. Out float32 [B, S, n*hd]."""
    import jax
    import jax.numpy as jnp

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
