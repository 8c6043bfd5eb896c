"""Flash attention as Pallas TPU kernels (fwd + bwd), with custom_vjp.

The TPU answer to the reference's fused attention CUDA kernels
(operators/fused/multihead_matmul_op.cu, math/bert_encoder_functor.cu):
blockwise online-softmax attention that never materialises the [S, S]
probability matrix in HBM — O(S) memory, MXU-sized tiles, fp32 accumulation
over bf16 inputs.

Layout: q [B, H, Sq, D], k/v [B, H, Sk, D]; optional additive bias over
keys ([B, Sk], or any shape broadcastable to [B, 1, 1, Sk] — the padding
mask form BERT/ERNIE use); optional causal masking.

Falls back to a pure-jnp reference when shapes don't meet TPU tiling
constraints or no TPU/interpreter backend is selected (kernel_mode()).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# 512x512 blocks measured ~2x faster than 128x128 on v5e (fewer grid
# steps -> less per-step VPU softmax bookkeeping; VMEM use stays < 4 MB)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention-probs dropout
#
# The reference recipe applies dropout to the softmax probabilities
# (attention_probs_dropout_prob — e.g. tests/unittests/dist_transformer.py
# attention dropout). A fused/recompute attention cannot save the mask, so
# the mask is a STATELESS position-keyed hash: keep(b, h, q, k) =
# splitmix32(lattice_index ^ seed·φ) — recomputable bit-exactly in the
# backward, and identical across the dense, q-chunked, Pallas and ring
# paths because it depends only on GLOBAL coordinates. Sequence/model
# sharding therefore never changes the mask (parity tests stay exact);
# data-parallel decorrelation comes from folding the dp rank into `seed`
# at the op layer (ops/attention_ops.py).
# ---------------------------------------------------------------------------

def _splitmix(x):
    """splitmix32 finalizer over a uint32 array."""
    U = jnp.uint32
    return _splitmix_tail(x ^ (x >> U(16)))


def _splitmix_tail(x):
    """_splitmix after its first xor-shift. That shift is linear over xor
    ((a ^ b) >> 16 == (a >> 16) ^ (b >> 16)), so a kernel that hashes
    `lattice ^ seed word` for many seeds shifts the lattice ONCE and the
    seed word as a scalar, and starts here: the same bits for two
    operations a position less."""
    U = jnp.uint32
    x = x * U(0x85EBCA6B)
    x = (x ^ (x >> U(13))) * U(0xC2B2AE35)
    return x ^ (x >> U(16))


def _bh_seed(seed, bh):
    """Per-(batch*heads + head) derived seed: hashing (b, h) into the seed
    keeps the (q, k) lattice below 2^32 (wrap-free up to 64k sequence
    length) instead of one flat index over b*h*q*k that would alias."""
    U = jnp.uint32
    return _splitmix(jnp.asarray(bh, U) ^ (jnp.asarray(seed, U)
                                           * U(0x9E3779B9)))


def _keep_from_lin(lin, seed2, rate):
    """The dropout mask's bits: True where position `lin` (a q*Sk+k
    lattice index) of the (b,h) whose derived seed is `seed2` is KEPT.
    Threshold compare in uint space: drop iff hash < rate * 2^32."""
    U = jnp.uint32
    x = _splitmix(lin ^ (jnp.asarray(seed2, U) * U(0x9E3779B9)))
    return x >= _drop_below(rate)


def _drop_below(rate):
    """The hash value under which a position is dropped."""
    return jnp.uint32(min(int(float(rate) * 4294967296.0), 4294967295))


def _keep_scale_from_lin(lin, seed2, rate):
    """f32 keep/(1-rate)-or-0 multiplier of the same bits (shared by the
    XLA, Pallas and ring paths)."""
    return jnp.where(_keep_from_lin(lin, seed2, rate),
                     jnp.float32(1.0 / (1.0 - rate)), jnp.float32(0.0))


def _warn_lattice_wrap(sq_g, sk_g):
    """The (q, k) lattice is uint32: above 64k global sequence length
    q*Sk+k wraps and mask bits alias across q rows. Warn once — dropout
    still runs, but with correlated (non-i.i.d.) positions."""
    if float(sq_g) * float(sk_g) >= 4294967296.0 and \
            not getattr(_warn_lattice_wrap, "_done", False):
        import warnings

        _warn_lattice_wrap._done = True
        warnings.warn(
            f"attention dropout lattice {sq_g}x{sk_g} exceeds 2^32: mask "
            f"bits alias across query rows (correlated dropout). Global "
            f"sequence lengths above 64k need a 64-bit lattice.",
            stacklevel=3)


def _attn_keep_scale(seed, rate, shape, q_off, k_off, n_heads, sq_g, sk_g):
    """f32 multiplier tensor over `shape` = (b, h, cq, ck): keep/(1-rate)
    or 0. seed uint32 scalar (may be traced); q_off/k_off global offsets
    of this tile; sq_g/sk_g the GLOBAL sequence extents (lattice strides —
    they must agree across shards for mask coherence)."""
    _warn_lattice_wrap(sq_g, sk_g)
    U = jnp.uint32
    b, h = shape[0], shape[1]
    bh = (jax.lax.broadcasted_iota(U, (b, h, 1, 1), 0) * U(n_heads)
          + jax.lax.broadcasted_iota(U, (b, h, 1, 1), 1))
    seed2 = _bh_seed(seed, bh)                       # (b, h, 1, 1)
    qi = jax.lax.broadcasted_iota(U, (1, 1, shape[2], shape[3]), 2) \
        + jnp.asarray(q_off, U)
    ki = jax.lax.broadcasted_iota(U, (1, 1, shape[2], shape[3]), 3) \
        + jnp.asarray(k_off, U)
    lin = qi * jnp.asarray(sk_g, U) + ki             # (1, 1, cq, ck)
    return _keep_scale_from_lin(jnp.broadcast_to(lin, shape),
                                jnp.broadcast_to(seed2, shape), rate)


def _keep_scale_tile(seed, rate, bidx, n_heads, q0, k0, bq, bk, sq_g, sk_g):
    """Kernel-side tile of the same mask: (bq, bk) multiplier for batch*head
    index `bidx` (already b*n_heads + h in the flattened grid) at tile
    origin (q0, k0) — bit-identical to _attn_keep_scale at the same
    global coordinates."""
    U = jnp.uint32
    seed2 = _bh_seed(seed, jnp.asarray(bidx, U))
    qi = jnp.asarray(q0, U) + jax.lax.broadcasted_iota(U, (bq, bk), 0)
    ki = jnp.asarray(k0, U) + jax.lax.broadcasted_iota(U, (bq, bk), 1)
    lin = qi * U(sk_g) + ki
    return _keep_scale_from_lin(lin, seed2, rate)


# ---------------------------------------------------------------------------
# jnp reference (used for fallback and as the test oracle)
# ---------------------------------------------------------------------------

def reference_attention(q, k, v, bias_kv=None, causal=False, scale=None,
                        dropout_rate=0.0, dropout_seed=None):
    """Plain XLA attention: softmax(q k^T * scale + bias) v, fp32 softmax.
    bias_kv may be [B, Sk] (key-padding form) or any [B,H,Sq,Sk]-broadcastable
    4-D bias. dropout_rate>0 applies the position-keyed mask to the probs
    (upscale_in_train semantics, identical to every fused path)."""
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias_kv is not None:
        b = bias_kv.astype(jnp.float32)
        s = s + (b[:, None, None, :] if b.ndim == 2 else b)
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        seed = jnp.uint32(0) if dropout_seed is None else dropout_seed
        p = p * _attn_keep_scale(seed, float(dropout_rate), p.shape, 0, 0,
                                 q.shape[1], q.shape[2], k.shape[2])
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# XLA path with recompute backward
#
# Measured on v5e (BASELINE.md, slope timing): at d=64,
# s<=512 plain XLA attention with bf16 MXU dots runs ~7x faster than the
# Pallas flash kernels (ours AND jax's stock one — both are VPU/overhead
# bound at small head_dim). Flash's real win at those sizes is MEMORY:
# jax.vjp of plain attention saves the [B,H,S,S] probs for backward, which
# is what made unfused ERNIE-large uncompilable. This custom_vjp keeps the
# XLA forward but RECOMPUTES scores/probs in the backward (flash-style
# recompute at the XLA level), so nothing O(S^2) is saved between fwd and
# bwd. Only q, k, v, bias are residuals.
# ---------------------------------------------------------------------------

# Bound the per-chunk [B,H,chunk,Sk] f32 scores transient; without
# chunking XLA's scheduler keeps several layers' full scores temps alive
# at once and ERNIE-large (24 x 512 MB) OOMs at batch 32.
XLA_ATTN_CHUNK_TARGET_BYTES = 256 << 20


def _q_chunk(q, k):
    sq = q.shape[2]
    chunk = sq
    bytes_per = 4.0 * q.shape[0] * q.shape[1] * k.shape[2]
    while chunk > 128 and chunk % 2 == 0 and \
            bytes_per * chunk > XLA_ATTN_CHUNK_TARGET_BYTES:
        chunk //= 2
    return chunk


def _xla_scores(q, k, bias_kv, causal, scale, q_offset=0, full_sq=None):
    """f32 logits for a q chunk starting at q_offset of a full_sq query
    sequence (causal masking is bottom-right aligned, reference
    semantics)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias_kv is not None:
        s = s + bias_kv.astype(jnp.float32)[:, None, None, :]
    if causal:
        cq, sk = q.shape[2], k.shape[2]
        full_sq = full_sq if full_sq is not None else cq
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (cq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (cq, sk), 1)
        s = jnp.where(rows + (sk - full_sq) >= cols, s, NEG_INF)
    return s


def _xla_attn_chunk(qc, k, v, bias_kv, causal, scale, off, full_sq,
                    seed=None, rate=0.0):
    p = jax.nn.softmax(
        _xla_scores(qc, k, bias_kv, causal, scale, off, full_sq), axis=-1)
    if rate > 0.0:
        p = p * _attn_keep_scale(seed, rate, p.shape, off, 0,
                                 qc.shape[1], full_sq, k.shape[2])
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(qc.dtype), v,
                      preferred_element_type=jnp.float32).astype(qc.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _xla_attention(q, k, v, bias_kv, seed, causal, scale, rate=0.0):
    b, h, sq, d = q.shape
    chunk = _q_chunk(q, k)
    if chunk == sq:
        return _xla_attn_chunk(q, k, v, bias_kv, causal, scale, 0, sq,
                               seed, rate)
    n = sq // chunk
    qs = jnp.moveaxis(q.reshape(b, h, n, chunk, d), 2, 0)
    offs = jnp.arange(n, dtype=jnp.int32) * chunk

    def body(args):
        qc, off = args
        return _xla_attn_chunk(qc, k, v, bias_kv, causal, scale, off, sq,
                               seed, rate)

    out = jax.lax.map(body, (qs, offs))            # [n,b,h,chunk,d]
    return jnp.moveaxis(out, 0, 2).reshape(b, h, sq, d)


def _xla_attention_fwd(q, k, v, bias_kv, seed, causal, scale, rate):
    return (_xla_attention(q, k, v, bias_kv, seed, causal, scale, rate),
            (q, k, v, bias_kv, seed))


def _xla_chunk_grads(qc, k, v, bias_kv, causal, scale, doc, off, full_sq,
                     seed=None, rate=0.0):
    """Per-q-chunk cotangents: dq chunk + f32 partials of dk/dv/dbias.
    Recomputes the (identical, position-keyed) dropout mask: with
    pd = m*p the vjp is dv = pd^T do, dp = m*(do v^T),
    ds = p*(dp - <p,dp>)."""
    p = jax.nn.softmax(
        _xla_scores(qc, k, bias_kv, causal, scale, off, full_sq), axis=-1)
    if rate > 0.0:
        m = _attn_keep_scale(seed, rate, p.shape, off, 0,
                             qc.shape[1], full_sq, k.shape[2])
        pd = p * m
    else:
        m, pd = None, p
    pb = pd.astype(qc.dtype)
    dof = doc.astype(qc.dtype)
    dv_p = jnp.einsum("bhqk,bhqd->bhkd", pb, dof,
                      preferred_element_type=jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v,
                    preferred_element_type=jnp.float32)
    if m is not None:
        dp = dp * m
    ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))  # f32
    dsb = ds.astype(qc.dtype)
    dq = (jnp.einsum("bhqk,bhkd->bhqd", dsb, k,
                     preferred_element_type=jnp.float32)
          * scale).astype(qc.dtype)
    dk_p = jnp.einsum("bhqk,bhqd->bhkd", dsb, qc,
                      preferred_element_type=jnp.float32) * scale
    db_p = jnp.sum(ds, axis=(1, 2)) if bias_kv is not None else None
    return dq, dk_p, dv_p, db_p


def _xla_attention_bwd(causal, scale, rate, res, do):
    q, k, v, bias_kv, seed = res
    b, h, sq, d = q.shape
    chunk = _q_chunk(q, k)
    if chunk == sq:
        dq, dk_p, dv_p, db_p = _xla_chunk_grads(
            q, k, v, bias_kv, causal, scale, do, 0, sq, seed, rate)
        dbias = None if db_p is None else db_p.astype(bias_kv.dtype)
        return dq, dk_p.astype(k.dtype), dv_p.astype(v.dtype), dbias, None

    n = sq // chunk
    qs = jnp.moveaxis(q.reshape(b, h, n, chunk, d), 2, 0)
    dos = jnp.moveaxis(do.reshape(b, h, n, chunk, d), 2, 0)
    offs = jnp.arange(n, dtype=jnp.int32) * chunk
    sk = k.shape[2]
    acc0 = (jnp.zeros((b, h, sk, d), jnp.float32),
            jnp.zeros((b, h, sk, d), jnp.float32),
            jnp.zeros((b, sk), jnp.float32) if bias_kv is not None else 0.0)

    def step(acc, args):
        qc, doc, off = args
        dk_a, dv_a, db_a = acc
        dq, dk_p, dv_p, db_p = _xla_chunk_grads(
            qc, k, v, bias_kv, causal, scale, doc, off, sq, seed, rate)
        db_a = db_a + db_p if bias_kv is not None else db_a
        return (dk_a + dk_p, dv_a + dv_p, db_a), dq

    (dk_a, dv_a, db_a), dqs = jax.lax.scan(step, acc0, (qs, dos, offs))
    dq = jnp.moveaxis(dqs, 0, 2).reshape(b, h, sq, d)
    dbias = None if bias_kv is None else db_a.astype(bias_kv.dtype)
    return dq, dk_a.astype(k.dtype), dv_a.astype(v.dtype), dbias, None


_xla_attention.defvjp(_xla_attention_fwd, _xla_attention_bwd)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                causal_offset=0, rate=0.0, n_heads=1, sq_g=1, sk_g=1):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (bq, d) native dtype
    k = k_ref[0]                                   # (bk, d)
    v = v_ref[0]
    # native-dtype (bf16) MXU dots, fp32 accumulation
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        i = pl.program_id(1)
        rows = causal_offset + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_scr[:, :1]                          # (bq, 1)
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)
    # dropout multiplies the NORMALISED probs, so l accumulates the
    # unmasked p while only the acc contribution is masked:
    # out = sum(m*p~, v) / sum(p~)
    if rate > 0.0:
        mt = _keep_scale_tile(seed_ref[0], rate, pl.program_id(0), n_heads,
                              pl.program_id(1) * block_q, j * block_k,
                              block_q, block_k, sq_g, sk_g)
        pa = p * mt
    else:
        pa = p
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
        pa.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows → 0 out
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, :1]
                         + jnp.log(jnp.maximum(l_scr[:, :1], 1e-30)))[:, 0]


def _seed_spec(pl, pltpu):
    """SMEM spec for the (1,) uint32 dropout seed."""
    return pl.BlockSpec((1,), lambda *_: (0,), memory_space=pltpu.SMEM)


def _fused_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                      lse_ref, *, scale, causal, rate=0.0, n_heads=1,
                      sq_g=1, sk_g=1):
    """Single-block forward: whole (Sq, Sk) row in VMEM → direct softmax,
    no online-softmax scratch/bookkeeping (measured 2.85 ms/layer of pure
    overhead vs this kernel on the ERNIE geometry — the m/l/acc scratch
    machinery is dead weight when one k block covers the row)."""
    from jax.experimental import pallas as pl

    q = q_ref[0]                               # (sq, d) native dtype
    k = k_ref[0]                               # (sk, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    sq_n, sk_n = s.shape
    if causal:
        rows = (sk_n - sq_n) + jax.lax.broadcasted_iota(
            jnp.int32, (sq_n, sk_n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq_n, sk_n), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)                         # (sq, sk) f32
    l = jnp.sum(p, axis=-1, keepdims=True)
    if rate > 0.0:
        p = p * _keep_scale_tile(seed_ref[0], rate, pl.program_id(0),
                                 n_heads, 0, 0, sq_n, sk_n, sq_g, sk_g)
    ln = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows → 0 out
    acc = jax.lax.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
    o_ref[0] = (acc / ln).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


def _fwd_pallas_fused(q, k, v, bias_kv, causal, scale, interpret,
                      seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)
    in_specs = [
        pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0)),
    ]
    args = [q3, k3, v3]
    kw = dict(scale=scale, causal=causal, rate=rate, n_heads=h,
              sq_g=sq, sk_g=sk)
    if bias_kv is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, sk), lambda bi, _h=h: (bi // _h, 0, 0)))
        args.append(bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1]))
        kernel = functools.partial(_fused_fwd_kernel, **kw)
    else:
        def kernel(q, k, v, seed, o, lse):
            _fused_fwd_kernel(q, k, v, None, seed, o, lse, **kw)
    in_specs.append(_seed_spec(pl, pltpu))
    args.append(seed_arr)
    o3, lse = pl.pallas_call(
        kernel, grid=(bh,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
                   pl.BlockSpec((1, 1, sq), lambda bi: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)],
        interpret=interpret, name="flash_fwd_fused")(*args)
    return o3.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _fwd_pallas_fused_g(q, k, v, bias_kv, causal, scale, interpret, g,
                        seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3, k3, v3 = (t.reshape(bh, t.shape[2], d) for t in (q, k, v))
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)
    in_specs = [
        pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0)),
    ]
    args = [q3, k3, v3]
    kw = dict(scale=scale, causal=causal, g=g, rate=rate, n_heads=h,
              sq_g=sq, sk_g=sk)
    if bias_kv is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, sk), lambda bi, _h=h, _g=g: ((bi * _g) // _h, 0, 0)))
        args.append(bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1]))
        kernel = functools.partial(_fused_fwd_kernel_g, **kw)
    else:
        def kernel(q, k, v, seed, o, lse):
            _fused_fwd_kernel_g(q, k, v, None, seed, o, lse, **kw)
    in_specs.append(_seed_spec(pl, pltpu))
    args.append(seed_arr)
    o3, lse = pl.pallas_call(
        kernel, grid=(bh // g,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
                   pl.BlockSpec((g, 1, sq), lambda bi: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)],
        interpret=interpret, name="flash_fwd_fused_g")(*args)
    return o3.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _bwd_pallas_fused_g(q, k, v, bias_kv, causal, scale, interpret, g,
                        o, lse, do, seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3, k3, v3 = (t.reshape(bh, t.shape[2], d) for t in (q, k, v))
    do3 = do.reshape(bh, sq, d)
    o3 = o.reshape(bh, sq, d)
    lse3 = lse.reshape(bh, 1, sq)
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)
    has_bias = bias_kv is not None
    in_specs = [
        pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((g, 1, sq), lambda bi: (bi, 0, 0)),
    ]
    args = [q3, k3, v3, do3, o3, lse3]
    kw = dict(scale=scale, causal=causal, g=g, rate=rate, n_heads=h,
              sq_g=sq, sk_g=sk)
    out_specs = [pl.BlockSpec((g, sq, d), lambda bi: (bi, 0, 0)),
                 pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0)),
                 pl.BlockSpec((g, sk, d), lambda bi: (bi, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), v.dtype)]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, sk), lambda bi, _h=h, _g=g: ((bi * _g) // _h, 0, 0)))
        args.append(bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1]))
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)
        out_specs.append(pl.BlockSpec((1, 1, sk), lambda bi: (bi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh // g, 1, sk),
                                              jnp.float32))
        kernel = functools.partial(_fused_bwd_kernel_g, **kw)
    else:
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)

        def kernel(q, k, v, do, o, lse, seed, dq, dk, dv):
            _fused_bwd_kernel_g(q, k, v, do, o, lse, None, seed,
                                dq, dk, dv, None, **kw)
    outs = pl.pallas_call(
        kernel, grid=(bh // g,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name="flash_bwd_fused_g")(*args)
    if has_bias:
        dq3, dk3, dv3, dbias3 = outs
        dbias = jnp.sum(dbias3.reshape(b, h // g, sk), axis=1)
    else:
        dq3, dk3, dv3 = outs
        dbias = None
    return (dq3.reshape(q.shape), dk3.reshape(k.shape),
            dv3.reshape(v.shape), dbias)


def _fwd_pallas(q, k, v, bias_kv, causal, scale, interpret,
                seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    g = _fused_g(sq, sk, h)
    if not g and sq == sk and _fused_bwd_applies(sq, sk):
        # FORWARD-only head-blocking in the single-block regime: with
        # one (b,h) slice per cell the fwd (2 matmuls) is grid-overhead
        # bound — bigger cells fixed it (ERNIE step 336.8 -> 325.3 ms at
        # g=2/S=512, 324.7 at g=4; bwd measured neutral at g=2 and keeps
        # g=1, its 5-matmul cells are already compute-filled). sq == sk
        # keeps the per-cell k/v tiles bounded by the same row target;
        # 4 x (S,S) f32 scores = 4 MB VMEM at S=512.
        g = _largest_divisor_leq(h, max(1, 2048 // sq))
    if g:
        return _fwd_pallas_fused_g(q, k, v, bias_kv, causal, scale,
                                   interpret, g, seed, rate)
    if _fused_bwd_applies(sq, sk):
        return _fwd_pallas_fused(q, k, v, bias_kv, causal, scale,
                                 interpret, seed, rate)
    bq = _pick_block(sq, DEFAULT_BLOCK_Q)
    bk = _pick_block(sk, DEFAULT_BLOCK_K)
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    grid = (bh, sq // bq, sk // bk)
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, bk, d), lambda bi, i, j: (bi, j, 0)),
        pl.BlockSpec((1, bk, d), lambda bi, i, j: (bi, j, 0)),
    ]
    args = [q3, k3, v3]
    if bias_kv is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda bi, i, j, _h=h: (bi // _h, 0, j)))
        args.append(bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1]))
        kernel = _fwd_kernel
    else:
        kernel = functools.partial(_bias_none_wrap, _fwd_kernel, n_in=3)
    in_specs.append(_seed_spec(pl, pltpu))
    args.append(seed_arr)

    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, d), lambda bi, i, j: (bi, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda bi, i, j: (bi, 0, i)),
    ]
    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    o3, lse = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, causal_offset=sk - sq,
                          rate=rate, n_heads=h, sq_g=sq, sk_g=sk),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, name="flash_fwd_2pass")(*args)
    return o3.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _bias_none_wrap(kernel, *refs, n_in, **kw):
    """Adapt a kernel expecting a bias ref to the no-bias call signature."""
    ins, rest = refs[:n_in], refs[n_in:]
    kernel(*ins, None, *rest, **kw)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
                seed_ref, dk_ref, dv_ref, dbias_ref, dk_scr, dv_scr, db_scr,
                *, scale, causal, block_q, block_k, causal_offset=0,
                rate=0.0, n_heads=1, sq_g=1, sk_g=1):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)                      # q block (innermost)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if db_scr is not None:
            db_scr[:] = jnp.zeros_like(db_scr)

    q = q_ref[0]                              # (bq, d) native dtype
    k = k_ref[0]                              # (bk, d)
    v = v_ref[0]
    do = do_ref[0]                            # (bq, d)
    lse = lse_ref[0, 0][:, None]              # (bq, 1)
    delta = delta_ref[0, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        j = pl.program_id(1)
        rows = causal_offset + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)                      # (bq, bk) fp32
    # recomputed dropout: pd = m*p feeds dv; dp is masked before the
    # softmax vjp (delta = sum_k pd*dp already carries the mask)
    if rate > 0.0:
        mt = _keep_scale_tile(seed_ref[0], rate, pl.program_id(0), n_heads,
                              i * block_q, pl.program_id(1) * block_k,
                              block_q, block_k, sq_g, sk_g)
        pd_ = p * mt
    else:
        mt, pd_ = None, p
    dv_scr[:] += jax.lax.dot_general(pd_.astype(do.dtype), do,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if mt is not None:
        dp = dp * mt
    ds_nos = p * (dp - delta)                 # cotangent of post-bias logits
    ds = ds_nos * scale                       # (bq, bk)
    if db_scr is not None:
        db_scr[:] += jnp.sum(ds_nos, axis=0, keepdims=True)
    dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
        if dbias_ref is not None:
            dbias_ref[0, 0] = db_scr[0, :]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
               seed_ref, dq_ref, dq_scr, *, scale, causal, block_q, block_k,
               causal_offset=0, rate=0.0, n_heads=1, sq_g=1, sk_g=1):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)                      # kv block (innermost)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        i = pl.program_id(1)
        rows = causal_offset + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if rate > 0.0:
        dp = dp * _keep_scale_tile(
            seed_ref[0], rate, pl.program_id(0), n_heads,
            pl.program_id(1) * block_q, j * block_k,
            block_q, block_k, sq_g, sk_g)
    ds = p * (dp - delta) * scale
    dq_scr[:] += jax.lax.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, bias_ref,
                      seed_ref, dq_ref, dk_ref, dv_ref, dbias_ref, *,
                      scale, causal, rate=0.0, n_heads=1, sq_g=1, sk_g=1):
    """Single-block backward: the whole (Sq, Sk) tile of one (b, h) pair
    lives in VMEM, so dq/dk/dv come out of ONE kernel with ONE scores
    recompute — no lse two-pass, no f32 HBM accumulators, no O(S^2)
    HBM traffic. This is the profile-driven fix for the north-star step:
    the XLA chunked-recompute backward's scan carried full-size f32
    dk/dv accumulators through HBM every chunk (~7.5 ms/layer measured;
    BASELINE.md); at S<=512 everything fits on-chip."""
    from jax.experimental import pallas as pl

    q = q_ref[0]                              # (sq, d) native dtype
    k = k_ref[0]                              # (sk, d)
    v = v_ref[0]
    do = do_ref[0]                            # (sq, d)
    o = o_ref[0]
    lse = lse_ref[0, 0][:, None]              # (sq, 1) f32
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)   # (sq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    sq_n, sk_n = s.shape
    if causal:
        rows = (sk_n - sq_n) + jax.lax.broadcasted_iota(
            jnp.int32, (sq_n, sk_n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq_n, sk_n), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)                      # (sq, sk) f32
    if rate > 0.0:
        mt = _keep_scale_tile(seed_ref[0], rate, pl.program_id(0), n_heads,
                              0, 0, sq_n, sk_n, sq_g, sk_g)
        pd_ = p * mt
    else:
        mt, pd_ = None, p
    dv_ref[0] = jax.lax.dot_general(
        pd_.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if mt is not None:
        dp = dp * mt
    ds_nos = p * (dp - delta)                 # cotangent of post-bias logits
    if dbias_ref is not None:
        dbias_ref[0, 0] = jnp.sum(ds_nos, axis=0)
    ds = (ds_nos * scale).astype(q.dtype)     # (sq, sk) bf16
    dq_ref[0] = jax.lax.dot(ds, k,
                            preferred_element_type=jnp.float32
                            ).astype(dq_ref.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _keep_scale_tile_g(seed, rate, bidx0, g, n_heads, q0, k0, bq, bk,
                       sq_g, sk_g):
    """(g, bq, bk) dropout multiplier for g CONSECUTIVE flattened
    batch*head indices starting at bidx0 — row i bit-identical to
    _keep_scale_tile(seed, rate, bidx0+i, ...)."""
    U = jnp.uint32
    bids = jnp.asarray(bidx0, U) + jax.lax.broadcasted_iota(
        U, (g, 1, 1), 0)
    seed2 = _bh_seed(seed, bids)                       # (g, 1, 1)
    qi = jnp.asarray(q0, U) + jax.lax.broadcasted_iota(U, (1, bq, bk), 1)
    ki = jnp.asarray(k0, U) + jax.lax.broadcasted_iota(U, (1, bq, bk), 2)
    lin = qi * U(sk_g) + ki                            # (1, bq, bk)
    shape = (g, bq, bk)
    return _keep_scale_from_lin(jnp.broadcast_to(lin, shape),
                                jnp.broadcast_to(seed2, shape), rate)


def _fused_fwd_kernel_g(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                        lse_ref, *, scale, causal, g, rate=0.0, n_heads=1,
                        sq_g=1, sk_g=1):
    """Head-blocked single-block forward: g consecutive (b,h) slices per
    grid cell, batched MXU dots — amortises per-cell overhead at small
    sequence lengths (S=128 tiles individually under-fill a cell; 4608
    one-slice cells measured 1.8x SLOWER than XLA at the BERT geometry)."""
    from jax.experimental import pallas as pl

    q = q_ref[...]                                 # (g, sq, d)
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, None, :]
    gg, sq_n, sk_n = s.shape
    if causal:
        rows = (sk_n - sq_n) + jax.lax.broadcasted_iota(
            jnp.int32, (1, sq_n, sk_n), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, sq_n, sk_n), 2)
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if rate > 0.0:
        p = p * _keep_scale_tile_g(seed_ref[0], rate,
                                   pl.program_id(0) * g, g, n_heads,
                                   0, 0, sq_n, sk_n, sq_g, sk_g)
    ln = jnp.where(l == 0.0, 1.0, l)
    acc = jax.lax.dot_general(p.astype(v.dtype), v,
                              (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc / ln).astype(o_ref.dtype)
    lse_ref[...] = jnp.transpose(
        m + jnp.log(jnp.maximum(l, 1e-30)), (0, 2, 1))


def _fused_bwd_kernel_g(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                        bias_ref, seed_ref, dq_ref, dk_ref, dv_ref,
                        dbias_ref, *, scale, causal, g, rate=0.0,
                        n_heads=1, sq_g=1, sk_g=1):
    """Head-blocked single-block backward — the g-sliced analog of
    _fused_bwd_kernel (one scores recompute, batched dots, all grads in
    one kernel)."""
    from jax.experimental import pallas as pl

    q = q_ref[...]                                 # (g, sq, d)
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    o = o_ref[...]
    lse = jnp.transpose(lse_ref[...], (0, 2, 1))   # (g, sq, 1)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, None, :]
    gg, sq_n, sk_n = s.shape
    if causal:
        rows = (sk_n - sq_n) + jax.lax.broadcasted_iota(
            jnp.int32, (1, sq_n, sk_n), 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, sq_n, sk_n), 2)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)
    if rate > 0.0:
        mt = _keep_scale_tile_g(seed_ref[0], rate, pl.program_id(0) * g,
                                g, n_heads, 0, 0, sq_n, sk_n, sq_g, sk_g)
        pd_ = p * mt
    else:
        mt, pd_ = None, p
    dv_ref[...] = jax.lax.dot_general(
        pd_.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    if mt is not None:
        dp = dp * mt
    ds_nos = p * (dp - delta)
    if dbias_ref is not None:
        dbias_ref[0, 0] = jnp.sum(ds_nos, axis=(0, 1))
    ds = (ds_nos * scale).astype(q.dtype)
    dq_ref[...] = jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[...] = jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


# ---------------------------------------------------------------------------
# packed-layout fused kernels: q/k/v in the projection's native [B,S,n*hd]
# ---------------------------------------------------------------------------
#
# The model's 4 head transposes per layer ([B,S,n,hd]<->[B,n,S,hd] around
# q/k/v and ctx) cost ~13.9 ms of the ERNIE step. These kernels read the
# projection outputs DIRECTLY: the grid cell is (batch, block of g heads),
# the block a [sq, g*hd] column slice.
#
# **A step** goes through its block in UNITS, unrolled: a unit is one
# 128-lane tile and the `128 // hd` heads whose columns share it (2 at hd
# 64), or one head's own columns where hd does not divide 128. The heads
# of a tile are MASKED apart, never sliced: head j's scores are the
# product of the q tile with the other heads' lanes zeroed on the WHOLE k
# tile (a 128-deep contraction costs the MXU the passes of a half-filled
# 64-deep one), `P_j V_tile` puts out 128 lanes of which head j's are
# kept by one select, and the tile is stored whole. No load or store is
# lane-shifted or masked.
#
# **The forward's statistics** (row maximum, row sum, lse) are float32 and
# lane-replicated `[sq, 128]`, widened over the score block by whole-tile
# reuse (`pltpu.repeat`), as `mla_prefill_attention.py` keeps them. `Lse`
# stays `[B, n, S]` in HBM (positions on lanes); the turn from the
# kernel's columns to that row is ONE `[sq, 128]` transpose a step (a
# step's heads side by side on the lanes), not one relayout a head: the
# head-a-time store `lse_ref[0, i, :] = col[:, 0]` was a quarter of the old
# forward.
#
# **The backward is KEY-MAJOR:** its score blocks are `[keys, queries]`
# (`K_j Q^T`, `V_j dO^T`; the head masks go on k and v). A query's lse and
# delta are then ROWS, as `Lse` lies in HBM: lse is a static row of the
# `[g, sq]` block broadcast over sublanes, delta a sublane sum of ONE
# transpose of the tile's `dO * O` for the tile's heads. `dv = P^T dO` and
# `dk = dS^T Q` are plain products of the blocks as computed; only `dq`
# turns one (the query-major form turned two, and picked lse and delta
# columns out by lane sums: 1.44 against 1.38 ms a layer).
#
# **Off the score block:** `scale` goes into the q tile where it is a power
# of two (exact in any float; otherwise it multiplies the scores as
# before); dropout's `1 / (1 - rate)` multiplies the `[sq, 128]` products,
# the mask itself is a select on the hash's compare; the bias, the
# causal mask and the hash's position lattice with its first xor-shift
# (`_splitmix_tail`) are built once a step.
#
# Precisions are the bnsd kernels': the inputs' dtype into every product,
# float32 scores, statistics and accumulators, probabilities rounded to
# the inputs' dtype for the second product (BEFORE dropout's scale, which
# is where a result can differ from the bnsd route's in its last bit).
#
# Alone on a v5e at ERNIE-large's shape (b40 s512 16 x 64 bfloat16, key
# bias, dropout 0.1; chip runs of PR 36, ms a layer): forward 0.75,
# backward 1.38 (the sliced, head-a-time form before: 1.11 and 1.57).
# With hd 64 every product half-fills the 128 x 128 MXU, so its own floor
# is 0.44 and 1.09. What knock-outs read on the masked form: the dropout
# hash 0.17 forward / 0.23 backward (its bits are shared with the XLA,
# bnsd and ring routes and stay), the row maximum 0.11 and the row sum
# 0.13 (cross-lane reductions), the backward's dbias sum 0.07.

_LANES = 128
# A step's [sq, g*hd] block in ELEMENTS (cols x sq): 1 MiB in bfloat16,
# g 16 at ERNIE-large's shape. The backward holds 8 such blocks (two
# buffers each, 16 MiB) beside ~8 MiB of score temporaries, the forward
# 4; the backward at g 16 against g 8 read 1.437 against 1.454 ms, the
# forward 1.114 against 1.123 (chip runs, PR 36): a step's overhead is
# not what bounds them.
PACKED_ELEMS = 1024 * 512
PACKED_VMEM_LIMIT = 64 << 20    # of v5e's 128 MiB; the default is 16


def _packed_unit(hd):
    """(heads, columns) of a unit: the heads that share a 128-lane tile
    where hd divides 128, else one head and its own columns."""
    heads = _LANES // hd if _LANES % hd == 0 else 1
    return heads, heads * hd


def _packed_g(h, hd, sq):
    """Largest g dividing h whose [sq, g*hd] block is Mosaic-legal
    ((g*hd) % 128 == 0 or whole-width; lse block needs g % 8 == 0 or
    whole-h), is whole units, keeps a head's statistic a lane of one
    tile and fits PACKED_ELEMS; 0 if none."""
    unit_heads = _packed_unit(hd)[0]
    for g in range(min(h, _LANES), 0, -1):
        if h % g or g % unit_heads:
            continue
        if (g * hd) % 128 and g != h:
            continue
        if g % 8 and g != h:
            continue
        if g * hd * sq <= PACKED_ELEMS:
            return g
    return 0


def _wide(x, cols):
    """[rows, 128], every lane alike -> [rows, cols], whole tiles reused."""
    from jax.experimental.pallas import tpu as pltpu

    if cols <= _LANES:
        return x[:, :cols]
    if cols % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], cols))
    return pltpu.repeat(x, cols // _LANES, 1)


def _row_stat(x):
    """A row statistic (rows, 1) lane-replicated [rows, 128]."""
    return jnp.broadcast_to(x, (x.shape[0], _LANES))


class _PackedStep:
    """What the heads of a grid step share: the step's first (b,h) index,
    the bias, the causal mask, the dropout lattice (all three [keys,
    queries] where `key_major`) and the lane ids of a unit."""

    def __init__(self, bias_ref, seed_ref, q_ref, *, scale, causal, g, npg,
                 hd, rate, n_heads, key_major=False):
        from jax.experimental import pallas as pl

        c = pl.program_id(0)
        self.bidx0 = (c // npg) * n_heads + (c % npg) * g
        self.scale, self.rate, self.g = scale, rate, g
        # a power of two: multiplying q by it rounds nothing, so the
        # [sq, sk] scores need no multiply
        self.fold = scale > 0 and math.frexp(scale)[0] == 0.5
        self.heads, self.cols = _packed_unit(hd)
        self.units = g // self.heads
        self.sq = sq = q_ref.shape[1]
        self.bias = None if bias_ref is None \
            else bias_ref[0].astype(jnp.float32)              # (1, sk)
        qd, kd = (1, 0) if key_major else (0, 1)     # the score block's axes
        if key_major and self.bias is not None:
            # a key's bias down the sublanes: the row turned once
            self.bias = _wide(jnp.broadcast_to(self.bias, (_LANES, sq)).T,
                              sq)
        self.ok = None
        if causal:
            self.ok = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), qd) \
                >= jax.lax.broadcasted_iota(jnp.int32, (sq, sq), kd)
        self.lin = self.seed = None
        if rate > 0.0:
            U = jnp.uint32
            lin = jax.lax.broadcasted_iota(U, (sq, sq), qd) * U(sq) \
                + jax.lax.broadcasted_iota(U, (sq, sq), kd)
            self.lin = lin ^ (lin >> U(16))     # see _splitmix_tail
            self.seed = seed_ref[0]
        self.unit_lane = jax.lax.broadcasted_iota(
            jnp.int32, (1, self.cols), 1) // hd

    def lanes(self, t):
        """Unit t's columns of a block."""
        return slice(t * self.cols, (t + 1) * self.cols)

    def only(self, xf, j, dtype):
        """Head j's own copy of a unit's float32 values [rows, cols]: the
        other heads' lanes zeroed, rounded to `dtype` (a product's
        operand)."""
        if self.heads > 1:
            xf = jnp.where(self.unit_lane == j, xf, 0.0)
        return xf.astype(dtype)

    def merge(self, out, x, j):
        """Head j's lanes of x into the unit's result."""
        return x if j == 0 else jnp.where(self.unit_lane == j, x, out)

    def scaled_q(self, q):
        """The q tile in float32 with the scale in it, where that rounds
        nothing."""
        qf = q.astype(jnp.float32)
        return qf * self.scale if self.fold and self.scale != 1.0 else qf

    def scores(self, xf, y, j):
        """Head j's float32 scores x_j y^T, scale, bias and causal mask
        applied: [queries, keys] of (q float32, k), or key-major
        [keys, queries] of (k float32, q)."""
        s = jax.lax.dot_general(self.only(xf, j, y.dtype), y,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if not self.fold:
            s = s * self.scale
        if self.bias is not None:
            s = s + self.bias
        if self.ok is not None:
            s = jnp.where(self.ok, s, NEG_INF)
        return s

    def keep(self, head):
        """The dropout mask's bits for head `head` of the step (bool
        [sq, sk]; bit-identical to _attn_keep_scale at these positions)."""
        U = jnp.uint32
        word = _bh_seed(self.seed, jnp.asarray(self.bidx0 + head, U)) \
            * U(0x9E3779B9)
        return _splitmix_tail(self.lin ^ (word ^ (word >> U(16)))) \
            >= _drop_below(self.rate)


def _packed_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref,
                       lse_ref, **kw):
    st = _PackedStep(bias_ref, seed_ref, q_ref, **kw)
    sq, inv_keep = st.sq, 1.0 / (1.0 - st.rate)
    lses = jnp.zeros((sq, _LANES), jnp.float32)        # a head a lane
    lse_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    for t in range(st.units):
        cols = st.lanes(t)
        qf = st.scaled_q(q_ref[0, :, cols])
        k = k_ref[0, :, cols]
        v = v_ref[0, :, cols]
        out = None
        for j in range(st.heads):
            head = t * st.heads + j
            s = st.scores(qf, k, j)
            m = _row_stat(jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _wide(m, sq))
            l = _row_stat(jnp.sum(p, axis=-1, keepdims=True))
            if st.rate > 0.0:
                p = jnp.where(st.keep(head), p, 0.0)
            acc = jax.lax.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)
            r = inv_keep / jnp.where(l == 0.0, 1.0, l)
            out = st.merge(out, acc * _wide(r, st.cols), j)
            lses = jnp.where(lse_lane == head,
                             m + jnp.log(jnp.maximum(l, 1e-30)), lses)
        o_ref[0, :, cols] = out.astype(o_ref.dtype)
    lse_ref[0] = lses.T[:st.g]


def _packed_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                       bias_ref, seed_ref, dq_ref, dk_ref, dv_ref,
                       dbias_ref, **kw):
    # KEY-MAJOR: every score block here is [keys, queries] (see above)
    st = _PackedStep(bias_ref, seed_ref, q_ref, key_major=True, **kw)
    sq, keep_prob = st.sq, 1.0 - st.rate
    inv_keep = 1.0 / keep_prob
    hd = st.cols // st.heads
    db = 0.0                                    # dS^T summed over the heads
    for t in range(st.units):
        cols = st.lanes(t)
        k = k_ref[0, :, cols]
        v = v_ref[0, :, cols]
        do = do_ref[0, :, cols]
        q = st.scaled_q(q_ref[0, :, cols]).astype(k.dtype)
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        # dO * O turned once a unit: a head's delta is a sublane sum
        do_o_t = (do.astype(jnp.float32)
                  * o_ref[0, :, cols].astype(jnp.float32)).T   # (cols, sq)
        dq = dk = dv = None
        for j in range(st.heads):
            head = t * st.heads + j
            p = jnp.exp(st.scores(kf, q, j) - lse_ref[0, head:head + 1, :])
            dp = jax.lax.dot_general(st.only(vf, j, v.dtype), do,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            pd_ = p
            if st.rate > 0.0:
                keep = st.keep(head)
                pd_ = jnp.where(keep, p, 0.0)
                dp = jnp.where(keep, dp, 0.0)
            delta = jnp.sum(do_o_t[j * hd:(j + 1) * hd], axis=0,
                            keepdims=True) * keep_prob         # (1, sq)
            ds = p * (dp - delta)
            if dbias_ref is not None:
                db = db + ds
            ds = ds.astype(k.dtype)
            dv = st.merge(dv, jax.lax.dot(
                pd_.astype(do.dtype), do,
                preferred_element_type=jnp.float32), j)
            dk = st.merge(dk, jax.lax.dot(
                ds, q, preferred_element_type=jnp.float32), j)
            dq = st.merge(dq, jax.lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), j)
        for ref, x, c in ((dq_ref, dq, st.scale * inv_keep),
                          (dk_ref, dk,
                           (1.0 if st.fold else st.scale) * inv_keep),
                          (dv_ref, dv, inv_keep)):
            ref[0, :, cols] = (x if c == 1.0 else x * c).astype(ref.dtype)
    if dbias_ref is not None:
        dbias_ref[0] = _row_stat(
            jnp.sum(db, axis=-1, keepdims=True) * inv_keep).T[:1]


def _packed_call(kernel, name, q3, bias_kv, operands, n_outs, causal, scale,
                 interpret, seed, rate, n_heads):
    """`kernel` over (batch, blocks of g heads): `operands` are [B,S,n*hd]
    arrays, or [B,n,S] (a statistic); the key bias and the seed follow
    them. Outputs: `n_outs` [B,S,n*hd] arrays, then lse [B,n,S] from the
    forward, or from the backward with a bias its dbias partials
    [B*npg,1,S]. Without a bias the kernel's `bias_ref` is None and so is
    the backward's `dbias_ref`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, htot = q3.shape
    hd = htot // n_heads
    g = _packed_g(n_heads, hd, sq)
    npg = n_heads // g
    cspec = pl.BlockSpec((1, sq, g * hd),
                         lambda c: (c // npg, 0, c % npg))
    lspec = pl.BlockSpec((1, g, sq), lambda c: (c // npg, c % npg, 0))
    fwd = kernel is _packed_fwd_kernel
    in_specs = [cspec if x.shape == q3.shape else lspec for x in operands]
    args = list(operands)
    out_specs = [cspec] * n_outs
    out_shape = [jax.ShapeDtypeStruct(q3.shape, q3.dtype)] * n_outs
    if fwd:
        out_specs.append(lspec)
        out_shape.append(jax.ShapeDtypeStruct((b, n_heads, sq), jnp.float32))
    if bias_kv is not None:
        in_specs.append(pl.BlockSpec((1, 1, sq),
                                     lambda c: (c // npg, 0, 0)))
        args.append(bias_kv.reshape(b, 1, sq))
        if not fwd:
            out_specs.append(pl.BlockSpec((1, 1, sq), lambda c: (c, 0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((b * npg, 1, sq),
                                                  jnp.float32))
    in_specs.append(_seed_spec(pl, pltpu))
    args.append(jnp.asarray(0 if seed is None else seed,
                            jnp.uint32).reshape(1))
    kw = dict(scale=scale, causal=causal, g=g, npg=npg, hd=hd, rate=rate,
              n_heads=n_heads)

    def body(*refs):
        refs = list(refs)
        if bias_kv is None:
            refs.insert(len(operands), None)            # bias_ref
            if not fwd:
                refs.append(None)                       # dbias_ref
        return kernel(*refs, **kw)

    return pl.pallas_call(
        body, grid=(b * npg,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PACKED_VMEM_LIMIT),
        interpret=interpret, name=name)(*args)


# both jitted so that a program's layers share ONE trace and lowering of
# their kernel: the IR traces an op when it is appended and again when its
# program lowers, 48 times a step program of 24 layers
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 8, 9))
def _fwd_pallas_packed(q3, k3, v3, bias_kv, causal, scale, interpret,
                       seed, rate, n_heads):
    return _packed_call(_packed_fwd_kernel, "flash_fwd_packed", q3, bias_kv,
                        (q3, k3, v3), 1, causal, scale, interpret, seed,
                        rate, n_heads)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 11, 12))
def _bwd_pallas_packed(q3, k3, v3, bias_kv, causal, scale, interpret,
                       o3, lse, do3, seed, rate, n_heads):
    outs = _packed_call(_packed_bwd_kernel, "flash_bwd_packed", q3, bias_kv,
                        (q3, k3, v3, do3, o3, lse), 3, causal, scale,
                        interpret, seed, rate, n_heads)
    if bias_kv is None:
        return (*outs, None)
    b, sq, _ = q3.shape
    return (*outs[:3], jnp.sum(outs[3].reshape(b, -1, sq), axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_packed(q, k, v, bias_kv, seed, causal, scale, interpret, rate,
                  n_heads):
    """Packed-layout twin of _flash: (out, lse) over [B,S,n*hd] inputs.
    lse's cotangent is discarded (auxiliary output)."""
    return _fwd_pallas_packed(q, k, v, bias_kv, causal, scale, interpret,
                              seed, rate, n_heads)


def _flash_packed_fwd(q, k, v, bias_kv, seed, causal, scale, interpret,
                      rate, n_heads):
    o, lse = _fwd_pallas_packed(q, k, v, bias_kv, causal, scale,
                                interpret, seed, rate, n_heads)
    return (o, lse), (q, k, v, bias_kv, seed, o, lse)


def _flash_packed_bwd(causal, scale, interpret, rate, n_heads, res, cts):
    do, _dlse = cts
    q, k, v, bias_kv, seed, o, lse = res
    dq, dk, dv, dbias = _bwd_pallas_packed(q, k, v, bias_kv, causal,
                                           scale, interpret, o, lse, do,
                                           seed, rate, n_heads)
    if dbias is not None:
        dbias = dbias.astype(bias_kv.dtype)
    return dq, dk, dv, dbias, None


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def _largest_divisor_leq(h, want):
    """Largest g in (1, want] dividing h (0 if none) — the head-block
    size search shared by _fused_g and the fwd-only blocking."""
    for g in range(min(want, h), 1, -1):
        if h % g == 0:
            return g
    return 0


def _fused_g(sq, sk, h):
    """Head-block size for the g-sliced fused kernels: pack g consecutive
    (b,h) slices so g*sq ~ 512 rows per cell. g must divide h so a cell
    never spans two batch rows (the bias/dbias blocks are per-batch).
    Returns 0 when blocking is not applicable/beneficial."""
    if sq != sk or sq >= FUSED_MIN_SEQ or sq < 8:
        return 0
    return _largest_divisor_leq(h, max(1, 512 // sq))


# Fused single-block backward applies when one (Sq, Sk) f32 tile fits
# comfortably in VMEM next to its ~4 same-size f32/bf16 intermediates
# (v5e ~16 MB/core; 512x512 f32 = 1 MB).
FUSED_BWD_MAX_SCORES_BYTES = 1 << 20


def _fused_bwd_applies(sq, sk):
    return (_pick_block(sq, DEFAULT_BLOCK_Q) == sq
            and _pick_block(sk, DEFAULT_BLOCK_K) == sk
            and 4 * sq * sk <= FUSED_BWD_MAX_SCORES_BYTES)


def _bwd_pallas_fused(q, k, v, bias_kv, causal, scale, interpret, o, lse,
                      do, seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3, k3, v3 = (t.reshape(bh, t.shape[2], d) for t in (q, k, v))
    do3 = do.reshape(bh, sq, d)
    o3 = o.reshape(bh, sq, d)
    lse3 = lse.reshape(bh, 1, sq)
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)
    has_bias = bias_kv is not None

    in_specs = [
        pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
        pl.BlockSpec((1, 1, sq), lambda bi: (bi, 0, 0)),
    ]
    args = [q3, k3, v3, do3, o3, lse3]
    kw = dict(scale=scale, causal=causal, rate=rate, n_heads=h,
              sq_g=sq, sk_g=sk)
    out_specs = [pl.BlockSpec((1, sq, d), lambda bi: (bi, 0, 0)),
                 pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0)),
                 pl.BlockSpec((1, sk, d), lambda bi: (bi, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), v.dtype)]
    if has_bias:
        bias3 = bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1])
        in_specs.append(pl.BlockSpec((1, 1, sk),
                                     lambda bi, _h=h: (bi // _h, 0, 0)))
        args.append(bias3)
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)
        out_specs.append(pl.BlockSpec((1, 1, sk), lambda bi: (bi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, sk), jnp.float32))
        kernel = functools.partial(_fused_bwd_kernel, **kw)
    else:
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)

        def kernel(q, k, v, do, o, lse, seed, dq, dk, dv):
            _fused_bwd_kernel(q, k, v, do, o, lse, None, seed,
                              dq, dk, dv, None, **kw)
    outs = pl.pallas_call(
        kernel, grid=(bh,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name="flash_bwd_fused")(*args)
    if has_bias:
        dq3, dk3, dv3, dbias3 = outs
        dbias = jnp.sum(dbias3.reshape(b, h, sk), axis=1)
    else:
        dq3, dk3, dv3 = outs
        dbias = None
    return (dq3.reshape(q.shape), dk3.reshape(k.shape),
            dv3.reshape(v.shape), dbias)


def _bwd_pallas(q, k, v, bias_kv, causal, scale, interpret, o, lse, do,
                seed=None, rate=0.0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    g = _fused_g(sq, sk, h)
    if g:
        return _bwd_pallas_fused_g(q, k, v, bias_kv, causal, scale,
                                   interpret, g, o, lse, do, seed, rate)
    if _fused_bwd_applies(sq, sk):
        return _bwd_pallas_fused(q, k, v, bias_kv, causal, scale,
                                 interpret, o, lse, do, seed, rate)
    bq = _pick_block(sq, DEFAULT_BLOCK_Q)
    bk = _pick_block(sk, DEFAULT_BLOCK_K)
    bh = b * h
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, sq)
    q3, k3, v3 = (t.reshape(bh, t.shape[2], d) for t in (q, k, v))
    do3 = do.reshape(bh, sq, d)
    lse3 = lse.reshape(bh, 1, sq)
    bias3 = (None if bias_kv is None
             else bias_kv.reshape(bias_kv.shape[0], 1, bias_kv.shape[1]))
    seed_arr = jnp.asarray([0 if seed is None else seed], jnp.uint32)

    def specs(maps):
        return [pl.BlockSpec(shape, m) for shape, m in maps]

    common_args = [q3, k3, v3, do3, lse3, delta]
    has_bias = bias_kv is not None

    # --- dk/dv: grid (bh, kv blocks, q blocks) ---
    in_specs = specs([
        ((1, bq, d), lambda bi, j, i: (bi, i, 0)),
        ((1, bk, d), lambda bi, j, i: (bi, j, 0)),
        ((1, bk, d), lambda bi, j, i: (bi, j, 0)),
        ((1, bq, d), lambda bi, j, i: (bi, i, 0)),
        ((1, 1, bq), lambda bi, j, i: (bi, 0, i)),
        ((1, 1, bq), lambda bi, j, i: (bi, 0, i)),
    ])
    args = list(common_args)
    kw = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
              causal_offset=sk - sq, rate=rate, n_heads=h, sq_g=sq, sk_g=sk)
    out_specs = [pl.BlockSpec((1, bk, d), lambda bi, j, i: (bi, j, 0)),
                 pl.BlockSpec((1, bk, d), lambda bi, j, i: (bi, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), v.dtype)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bk),
                                     lambda bi, j, i, _h=h: (bi // _h, 0, j)))
        args.append(bias3)
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)
        # per-(b,h) dbias accumulates over q blocks; summed over h outside
        out_specs.append(pl.BlockSpec((1, 1, bk),
                                      lambda bi, j, i: (bi, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, sk), jnp.float32))
        scratch.append(pltpu.VMEM((1, bk), jnp.float32))
        kernel = functools.partial(_dkv_kernel, **kw)
    else:
        in_specs.append(_seed_spec(pl, pltpu))
        args.append(seed_arr)

        def kernel(q, k, v, do, lse, delta, seed, dk, dv, dks, dvs):
            _dkv_kernel(q, k, v, do, lse, delta, None, seed, dk, dv, None,
                        dks, dvs, None, **kw)
    outs = pl.pallas_call(
        kernel,
        grid=(bh, sk // bk, sq // bq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret, name="flash_bwd_dkv")(*args)
    if has_bias:
        dk3, dv3, dbias3 = outs
        dbias = jnp.sum(dbias3.reshape(b, h, sk), axis=1)
    else:
        dk3, dv3 = outs
        dbias = None

    # --- dq: grid (bh, q blocks, kv blocks) ---
    in_specs = specs([
        ((1, bq, d), lambda bi, i, j: (bi, i, 0)),
        ((1, bk, d), lambda bi, i, j: (bi, j, 0)),
        ((1, bk, d), lambda bi, i, j: (bi, j, 0)),
        ((1, bq, d), lambda bi, i, j: (bi, i, 0)),
        ((1, 1, bq), lambda bi, i, j: (bi, 0, i)),
        ((1, 1, bq), lambda bi, i, j: (bi, 0, i)),
    ])
    args = list(common_args)
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bk),
                                     lambda bi, i, j, _h=h: (bi // _h, 0, j)))
        args.append(bias3)
        kernel = _dq_kernel
    else:
        kernel = functools.partial(_bias_none_wrap, _dq_kernel, n_in=6)
    in_specs.append(_seed_spec(pl, pltpu))
    args.append(seed_arr)
    dq3 = pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, causal_offset=sk - sq,
                          rate=rate, n_heads=h, sq_g=sq, sk_g=sk),
        grid=(bh, sq // bq, sk // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda bi, i, j: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, name="flash_bwd_dq")(*args)

    return (dq3.reshape(q.shape), dk3.reshape(k.shape), dv3.reshape(v.shape),
            dbias)


# ---------------------------------------------------------------------------
# custom_vjp wrapper + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias_kv, seed, causal, scale, interpret, rate=0.0):
    """(out, lse). lse is an auxiliary output for the program-level saved-
    residual backward (flash_attention_grad op); its cotangent is
    DISCARDED by the custom vjp — do not build losses on lse."""
    return _fwd_pallas(q, k, v, bias_kv, causal, scale, interpret,
                       seed, rate)


def _flash_fwd(q, k, v, bias_kv, seed, causal, scale, interpret, rate):
    o, lse = _fwd_pallas(q, k, v, bias_kv, causal, scale, interpret,
                         seed, rate)
    return (o, lse), (q, k, v, bias_kv, seed, o, lse)


def _flash_bwd(causal, scale, interpret, rate, res, cts):
    do, _dlse = cts          # lse is auxiliary; its cotangent is discarded
    q, k, v, bias_kv, seed, o, lse = res
    dq, dk, dv, dbias = _bwd_pallas(q, k, v, bias_kv, causal, scale,
                                    interpret, o, lse, do, seed, rate)
    if dbias is not None:
        dbias = dbias.astype(bias_kv.dtype)
    return dq, dk, dv, dbias, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pick_block(s, prefer):
    """Largest block <= prefer that divides s (multiples of 128 first, so
    long sequences like 640 or 1920 keep kernel coverage); whole-s block
    for short sequences; None if s is long but has no usable divisor."""
    for c in (512, 384, 256, 128):
        if c <= prefer and s % c == 0:
            return c
    if s <= prefer:
        return s
    return None


def _supported(b, sq, sk, d, bias_kv):
    if d > 256:
        return False
    if _pick_block(sq, DEFAULT_BLOCK_Q) is None or \
            _pick_block(sk, DEFAULT_BLOCK_K) is None:
        return False
    if min(sq, sk) < 8:
        return False
    if bias_kv is not None and bias_kv.shape != (b, sk):
        return False
    return True


def _pad_head_dim(x, target):
    d = x.shape[-1]
    if d == target:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, target - d)]
    return jnp.pad(x, pad)


# v5e measurements (BASELINE.md, slope timing, d=64, dropout
# 0.1, grads taken wrt q AND k AND v — an earlier q-only grad let XLA DCE
# the chunked path's dk/dv accumulator scan and under-measured its
# backward 2.7x, mis-routing the ERNIE geometry until round 4):
#   s=512  b34:  pallas(fused 1-block bwd) 2.95 ms f+b vs xla-rcmp 8.87
#                -> pallas wins 3.0x (the xla scan drags f32 [B,H,S,D]
#                   dk/dv accumulators through HBM every chunk)
#   s=256  b48:  pallas 2.33 vs xla 2.59            -> pallas wins 1.1x
#   s=128  b384: pallas 8.61 vs xla 4.85            -> XLA wins 1.8x
#                (4608 tiny grid cells; per-cell overhead dominates)
#   s=2048 b4:   pallas(2-pass online-softmax) 6.64 vs xla-rcmp 14.74
#                -> pallas wins 2.2x (the old "xla wins 1.6x" was the
#                   same q-only-grad DCE artifact)
#   s=4096: xla FAILS TO COMPILE (the [B,H,S,S] f32 transient = 8.6 GB);
#           pallas runs — its O(S) HBM footprint is the only option.
# Dispatch: pallas kernels (fused single-block where one tile covers
# the row, 2-pass online-softmax above) for sq >= FUSED_MIN_SEQ; XLA
# recompute only below it, where tiny grid cells lose. The scores-bytes
# threshold still forces pallas where XLA cannot even compile.
PALLAS_MIN_SCORES_BYTES = 2 << 30
FUSED_MIN_SEQ = 256


def attention_route(q, k, bias=None, num_heads=None):
    """The one place that decides which implementation attention takes,
    from what it can observe: the shapes, the layout (q [B,H,Sq,D], or
    packed [B,S,n*hd] with ``num_heads``), the bias form and
    kernel_mode(). Returns (route, bias_kv):

      'packed'            the packed fused kernels, no head transposes
      'pallas'            the bnsd kernels (fused single-block where one
                          tile covers the row, 2-pass online-softmax above)
      'pallas_interpret'  the same through the Pallas interpreter
      'xla'               plain XLA attention, recompute backward
      'reference'         the probs-saving jnp reference
      'reference_general' the reference on a bias that is not a key bias

    bias_kv is the [B,Sk] key-bias normal form (None when bias is None,
    or on 'reference_general', which keeps the raw bias). A packed input
    on any route but 'packed' is transposed to bnsd by its caller. The
    forward, the flash_attention_grad lowering (ops/attention_ops.py)
    and flash_attention_bwd all ask here, so a grad op's route is its
    forward's. On the 'packed' and 'pallas*' routes the forward's
    (out, lse) are saved and the backward runs the bwd kernels alone."""
    from . import kernel_mode, mosaic_withheld

    packed = len(q.shape) == 3
    if packed:
        b, sq, htot = q.shape
        sk, n = k.shape[1], int(num_heads)
        if htot % n:
            raise ValueError(
                f"packed width {htot} is not a multiple of num_heads={n}")
        hd = htot // n
    else:
        b, n, sq, hd = q.shape
        sk = k.shape[2]
    bias_kv = None
    if bias is not None:
        bias_kv = jnp.broadcast_to(bias, (b, 1, 1, sk)).reshape(b, sk) \
            if bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1 \
            else (bias if bias.ndim == 2 else None)
        if bias_kv is None:
            return "reference_general", None
    mode = kernel_mode()
    if mode == "off":
        # a step XLA partitions itself cannot hold Mosaic kernels: the
        # O(S)-residual XLA recompute route stands in, not the
        # probs-saving reference
        return ("xla" if mosaic_withheld() else "reference"), bias_kv
    if not _supported(b, sq, sk, hd, bias_kv):
        # pallas tiling unsupported: prefer the O(S)-residual XLA
        # recompute path on TPU over the probs-saving reference path
        return ("xla" if mode == "tpu" else "reference"), bias_kv
    # the packed kernels come BEFORE the bnsd FUSED_MIN_SEQ=256 routing:
    # without head transposes the round-4 "XLA wins below 256"
    # measurement flips — BERT-base (s=128 b384) measured 219.3 ms/step
    # on the packed kernels vs 250.7 on the XLA route (62.1% vs 54.3%
    # MFU). They need a fused-single-block geometry with lane-aligned
    # head blocks.
    if (packed and sq == sk and hd % 8 == 0 and (n * hd) % 128 == 0
            and _fused_bwd_applies(sq, sk) and _packed_g(n, hd, sq)):
        return "packed", bias_kv
    if mode == "interpret":
        return "pallas_interpret", bias_kv
    # Below FUSED_MIN_SEQ the head-blocked fused kernels (_fused_g)
    # microbenchmark well in isolation (s=128 b384: fwd 0.14 ms vs 1.65
    # XLA, f+b 3.14 vs 3.66) — but IN-PROGRAM the BERT-base step measured
    # 283 ms on them vs 251 ms on the XLA path (the kernel boundary
    # defeats XLA's fusion of attention with the surrounding
    # bias/dropout/projection ops), so bnsd inputs stay on XLA here unless
    # XLA cannot hold the scores at all. Step-level measurements win.
    if sq < FUSED_MIN_SEQ and 4.0 * b * n * sq * sk < PALLAS_MIN_SCORES_BYTES:
        return "xla", bias_kv
    return "pallas", bias_kv


def _packed_to_bnsd(x, n_heads):
    b, s, htot = x.shape
    return jnp.swapaxes(x.reshape(b, s, n_heads, htot // n_heads), 1, 2)


def _bnsd_to_packed(x4):
    b, n, s, hd = x4.shape
    return jnp.swapaxes(x4, 1, 2).reshape(b, s, n * hd)


def _windowed(window, num_kv_heads):
    """A window or a K/V head count of its own asks for flash_window.py's
    kernels; the kernels here know neither."""
    return bool(window) or num_kv_heads is not None


def _window_call(q, k, v, bias, causal, dropout_rate, num_heads,
                 num_kv_heads, window, scale):
    """A windowed / grouped call, which is causal self-attention with no
    bias and no dropout, as flash_window.py takes it -> ((q, k, v) packed,
    its keyword arguments, `back`: an array of `heads` heads returned to
    the caller's layout)."""
    if not causal or bias is not None or float(dropout_rate or 0.0):
        raise ValueError("window / num_kv_heads attention is causal, with "
                         "no bias and no dropout")
    if q.ndim == 4:
        n, nkv = q.shape[1], k.shape[1]
        q, k, v = (_bnsd_to_packed(x) for x in (q, k, v))
        back = _packed_to_bnsd
    elif not num_heads:
        raise ValueError("packed flash attention needs num_heads")
    else:
        n, nkv = int(num_heads), int(num_kv_heads or num_heads)

        def back(x, heads):
            return x
    return (q, k, v), dict(num_heads=n, num_kv_heads=nkv,
                           window=int(window or 0), scale=scale), back


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    dropout_rate=0.0, dropout_seed=None, num_heads=None,
                    window=None, num_kv_heads=None):
    """softmax(q k^T * scale + bias) v, O(S)-memory in the backward.

    With ``window`` (a query at t reads keys at t - window < s <= t) or
    ``num_kv_heads`` (query head j reads K/V head j // group; k, v
    [B,Hkv,S,D] or packed [B,S,Hkv*hd]) the call is causal
    self-attention through ops/pallas/flash_window.py, differentiable.

    q [B,H,Sq,D]; k,v [B,H,Sk,D] — or packed [B,S,n*hd] with num_heads
    (see flash_attention_fwd_lse); bias None or broadcastable to
    [B,1,1,Sk] (key padding mask) or exactly [B,Sk].
    dropout_rate>0 applies attention-probs dropout (reference recipe's
    attention_probs_dropout_prob, upscale_in_train) via the position-keyed
    stateless mask — recomputed bit-identically in every backward, no mask
    storage. dropout_seed: uint32 scalar (vary per step for fresh masks).

    Two fused implementations (both save only q/k/v/bias for backward):
      * 'xla' — plain XLA attention + recompute-backward custom_vjp;
        fastest below FUSED_MIN_SEQ=256 where tiny grid cells lose.
      * 'pallas' — fused single-block / blockwise online-softmax kernels;
        never materialises the [S,S] scores in HBM. Auto-routed for all
        sq >= FUSED_MIN_SEQ; the scores-bytes threshold
        (PALLAS_MIN_SCORES_BYTES) additionally forces pallas where XLA
        cannot even compile (e.g. s=4096).
    attention_route() decides between them.
    """
    if _windowed(window, num_kv_heads):
        from .flash_window import flash_window_attention

        qkv, kw, back = _window_call(q, k, v, bias, causal, dropout_rate,
                                     num_heads, num_kv_heads, window, scale)
        return back(flash_window_attention(
            *qkv, kw["num_heads"], kw["num_kv_heads"], kw["window"], scale),
            kw["num_heads"])
    out, _ = flash_attention_fwd_lse(q, k, v, bias, causal, scale,
                                     dropout_rate, dropout_seed,
                                     num_heads=num_heads)
    return out


def flash_attention_fwd_lse(q, k, v, bias=None, causal=False, scale=None,
                            dropout_rate=0.0, dropout_seed=None,
                            num_heads=None, window=None, num_kv_heads=None):
    """flash_attention returning (out, lse).

    lse [B,H,Sq] f32 is the log-sum-exp residual the saved-residual
    program backward (flash_attention_grad op) needs; it is only
    meaningful on the pallas routes — the xla/reference recompute paths
    return zeros (their program backward re-traces the forward, whose
    standard-HLO duplicate XLA CSEs away; only pallas custom-calls are
    never CSE'd, which is why the saved-lse path exists).

    3-D q/k/v [B,S,n*hd] (num_heads required) select the PACKED layout:
    the projection outputs feed the kernels directly and ctx comes back
    [B,S,n*hd] — no head transposes in the program (~13.9 ms/step of
    the round-4 ERNIE profile). Shapes outside the packed fused regime
    transpose internally and take the standard dispatch."""
    if _windowed(window, num_kv_heads):
        from .flash_window import flash_window_fwd_lse

        qkv, kw, back = _window_call(q, k, v, bias, causal, dropout_rate,
                                     num_heads, num_kv_heads, window, scale)
        out, lse = flash_window_fwd_lse(*qkv, **kw)
        return back(out, kw["num_heads"]), lse
    if q.ndim == 3:
        if not num_heads:
            raise ValueError("packed flash attention needs num_heads")
        return _packed_fwd_lse(q, k, v, bias, causal, scale,
                               dropout_rate, dropout_seed, int(num_heads))
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    rate = float(dropout_rate or 0.0)
    seed = jnp.asarray(0 if dropout_seed is None else dropout_seed,
                       jnp.uint32)
    route, bias_kv = attention_route(q, k, bias)
    if route == "reference_general":
        out = reference_attention(q, k, v, bias, causal, scale, rate, seed)
    elif route == "reference":
        out = reference_attention(q, k, v, bias_kv, causal, scale, rate,
                                  seed)
    elif route == "xla":
        out = _xla_attention(q, k, v, bias_kv, seed, causal, scale, rate)
    else:
        # pad head dim only when it breaks sublane tiling (block covers
        # the whole d, so any multiple of 8 is legal; zero pads don't
        # change scores and padded v columns are sliced off)
        dpad = d if d % 8 == 0 else int(np.ceil(d / 8) * 8)
        qp, kp, vp = (_pad_head_dim(t, dpad) for t in (q, k, v))
        if rate > 0.0:
            _warn_lattice_wrap(q.shape[2], k.shape[2])
        out, lse = _flash(qp, kp, vp, bias_kv, seed, causal, scale,
                          route == "pallas_interpret", rate)
        return out[..., :d], lse
    b, h, sq = q.shape[0], q.shape[1], q.shape[2]
    return out, jnp.zeros((b, h, sq), jnp.float32)


def _packed_fwd_lse(q, k, v, bias, causal, scale, dropout_rate,
                    dropout_seed, n_heads):
    b, sq, htot = q.shape
    hd = htot // n_heads
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(hd))
    rate = float(dropout_rate or 0.0)
    seed = jnp.asarray(0 if dropout_seed is None else dropout_seed,
                       jnp.uint32)
    route, bias_kv = attention_route(q, k, bias, n_heads)
    if route == "packed":
        from . import interpret_mode

        if rate > 0.0:
            _warn_lattice_wrap(sq, sq)
        return _flash_packed(q, k, v, bias_kv, seed, causal, scale,
                             interpret_mode(), rate, n_heads)
    out4, lse = flash_attention_fwd_lse(
        _packed_to_bnsd(q, n_heads), _packed_to_bnsd(k, n_heads),
        _packed_to_bnsd(v, n_heads), bias, causal, scale, dropout_rate,
        dropout_seed)
    return _bnsd_to_packed(out4), lse


def flash_attention_bwd(q, k, v, bias, out, lse, dout, causal=False,
                        scale=None, dropout_rate=0.0, dropout_seed=None,
                        num_heads=None, window=None, num_kv_heads=None):
    """Backward from the SAVED forward (out, lse): runs only the bwd
    kernels — no forward re-execution (the vjp path re-runs the fwd
    pallas custom-call, which XLA cannot CSE with the forward op's;
    measured ~0.8 ms/layer of pure duplicate work on ERNIE-large).

    Only valid where attention_route() says 'packed' or 'pallas*' —
    callers check first.
    Returns (dq, dk, dv, dbias_kv); dbias_kv is [B,Sk] (the key-bias
    normal form) or None when bias is None. A windowed / grouped call
    (flash_window.py) is valid on every route: its reference route
    differentiates the masked form."""
    if _windowed(window, num_kv_heads):
        from .flash_window import flash_window_bwd

        packed = q.ndim == 3
        qkv, kw, back = _window_call(q, k, v, bias, causal, dropout_rate,
                                     num_heads, num_kv_heads, window, scale)
        if not packed:
            out, dout = _bnsd_to_packed(out), _bnsd_to_packed(dout)
        grads = flash_window_bwd(*qkv, out, lse, dout, **kw)
        return tuple(back(g_, kw[h_]) for g_, h_ in zip(grads, (
            "num_heads", "num_kv_heads", "num_kv_heads"))) + (None,)
    if q.ndim == 3:
        from . import interpret_mode

        n = int(num_heads)
        route, bias_kv = attention_route(q, k, bias, n)
        if route.startswith("pallas"):
            # packed model at a non-packed geometry (e.g. long context
            # s >= 2048, or cross-attention sq != sk): the forward
            # transposed internally to the bnsd pallas path and its
            # (out, lse) ARE saved — transpose and run the
            # saved-residual bnsd backward (the vjp fallback would
            # re-run the non-CSE-able fwd kernel)
            dq4, dk4, dv4, dbias = flash_attention_bwd(
                _packed_to_bnsd(q, n), _packed_to_bnsd(k, n),
                _packed_to_bnsd(v, n), bias, _packed_to_bnsd(out, n),
                lse, _packed_to_bnsd(dout, n), causal=causal,
                scale=scale, dropout_rate=dropout_rate,
                dropout_seed=dropout_seed)
            return (_bnsd_to_packed(dq4), _bnsd_to_packed(dk4),
                    _bnsd_to_packed(dv4), dbias)
        if route != "packed":
            raise ValueError(
                f"flash_attention_bwd(packed) on the '{route}' route "
                f"— the grad op should have taken the vjp fallback")
        hd = q.shape[-1] // n
        scale = float(scale) if scale is not None \
            else 1.0 / float(np.sqrt(hd))
        seed = jnp.asarray(0 if dropout_seed is None else dropout_seed,
                           jnp.uint32)
        dq, dk, dv, dbias = _bwd_pallas_packed(
            q, k, v, bias_kv, causal, scale, interpret_mode(), out, lse,
            dout, seed, float(dropout_rate or 0.0), n)
        if dbias is not None and bias_kv is not None:
            dbias = dbias.astype(bias_kv.dtype)
        return dq, dk, dv, dbias
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    rate = float(dropout_rate or 0.0)
    seed = jnp.asarray(0 if dropout_seed is None else dropout_seed,
                       jnp.uint32)
    route, bias_kv = attention_route(q, k, bias)
    if not route.startswith("pallas"):
        raise ValueError(
            f"flash_attention_bwd called on the '{route}' route — the "
            f"saved-lse backward only exists for the pallas kernels")
    dpad = d if d % 8 == 0 else int(np.ceil(d / 8) * 8)
    qp, kp, vp, op_, dop = (_pad_head_dim(t, dpad)
                            for t in (q, k, v, out, dout))
    dq, dk, dv, dbias = _bwd_pallas(qp, kp, vp, bias_kv, causal, scale,
                                    route == "pallas_interpret", op_, lse,
                                    dop, seed, rate)
    if dbias is not None and bias_kv is not None:
        dbias = dbias.astype(bias_kv.dtype)
    return dq[..., :d], dk[..., :d], dv[..., :d], dbias

