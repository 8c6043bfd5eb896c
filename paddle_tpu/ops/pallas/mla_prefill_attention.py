"""Whole-prompt causal attention in the EXPANDED form of multi-head latent
attention (the DeepSeek-V3 block's prefill), blockwise with an online
softmax: the scores never leave VMEM.

Head h's key is ``[k_n (nope), k_r (rope)]`` with ``k_r`` ONE rotated key
for all heads, its query ``[q_n, q_r]``, its value ``v`` of another width
than its key's.

Why a kernel: the stock lowering (ops/llm_ops.py) writes a block's float32
scores to HBM and reads them back for the maximum, the exponentials, the
sum and the weighted sum: 16 bytes a score, 43 GB for a 4096-token prompt
over 64 heads and 5 layers, which took ~100 of the prefill's 166 ms on the
chip (PR 33) where the arithmetic is 1.7 TFLOP.

**Layout.** The operands are the layer's own arrays, rows = positions and
a head's values side by side on the lane axis: ``q_nope [S, n x nope]``,
``q_rope [S, n x rope]``, ``kv [S, n x (nope + dv)]`` (a head: its key
part, then its value, as ``c W_kvb`` leaves them) and ``k_rope [S, rope]``;
the output is ``[S, n x dv]``, what ``W_o`` takes. ``BlockSpec``s pick a
step's heads as columns: nothing is transposed, padded or split in HBM
(PR 34; until then head-major copies of every operand and of the output
stood around the call, ~2 ms a layer at 4096 on the chip).

**Grid** (head groups, steps): a step is one (query block, key block) pair
of the causal triangle for `heads` heads (`_heads_a_step`: as many as 16
MiB of VMEM blocks and scratch hold, 8 at the Kimi widths in bfloat16).
The pairs at or below the diagonal are enumerated on the host and reach
the index maps through scalar prefetch, so no step is empty; a query
block's pairs are consecutive and end on its diagonal, where the output
block is written. Blocks are ``BLOCK`` = 512 positions square (a shorter
prompt is one block).

**A step** loops over its heads' rotary lane tiles (`lax.fori_loop`, the
heads of a tile unrolled in its body, a head's columns sliced at traced
multiples of 128: unrolling all 8 heads read 3-5% faster alone, 2.61
against 2.75 ms a layer at 4096, and cost every process 8 s of set-up to
trace and lower its 24 bodies, PR 34). **For each head:** ONE product
``[q_n | q_r tile] x [k_n | k_r tile]^T`` over ``nope + 128`` lanes,
float32 out: both sides are concatenations of whole lane tiles in
registers, so the two parts of a score are summed inside the MXU. A rotary part narrower than a lane
tile is not padded per head: ``128 / rope`` heads' rotary queries share a
tile as the layer wrote them, and the key comes in as many variants
(``_rope_key_tiles``: the key at head h's lanes of the tile, zeros
elsewhere), so the other heads' lanes multiply zeros. Below the diagonal
the body has no mask. On the diagonal the block goes in sub-blocks of
``SUB`` = 256 query rows, each over the keys up to its own last (the
upper-right sub-blocks are neither multiplied nor exponentiated) and
masked ``key <= query``. A padded prompt's tail lies after every real
token, so causality alone keeps it out of real rows.

The running maximum (of the RAW scores), sum and correction are float32
and lane-replicated ``[block, 128]``, widened over a score block with
``pltpu.repeat`` (whole-tile reuse; broadcasting their lane-0 column was
what the MXU waited for until PR 34: 4.8 against 2.9 ms a layer at 4096).
The scale is applied once, inside the exponent: ``p = exp2((s - m) x
scale x log2 e)``, so no product's input is scaled or re-rounded.
Products take the inputs' dtype and accumulate in float32; the output is
``acc / l`` rounded once to the inputs' dtype (where ``W_o``'s product
rounded the float32 output before). ``name="mla_prefill_attention"``.

**Grouped heads** (``num_kv_heads`` < ``num_heads``; models/motif3.py:
80 query heads on 16 K/V heads). ``kv`` is ``[S, nkv x (nope + dv)]`` and
query head h reads K/V head ``h // group``. A step's heads are a multiple
of the group as well, its K/V block the ``heads / group`` K/V heads under
them, and a rotary tile's operand is put together from its heads' own K/V
columns (lane-tile slices at traced offsets).

**A window** (``window`` > 0: a query at t reads keys at ``t - window < s
<= t``; whole lane tiles, a divisor of the block). Only the block pairs the
band touches are enumerated: a query block's own diagonal pair and, past
the first, the pair with the block before it. Both go in sub-blocks of
``window`` query rows: on the diagonal each over the ``2 x window`` keys up
to its own last, masked on both edges of the band; of the block before,
the first ``window`` rows alone over its last ``window`` keys (the other
rows' running statistics pass through). So a window layer computes
``2 x window`` keys a query at any length, where the triangle computes
S / 2. With ``window=0`` and ``group=1`` the lowered kernel is the one it
always was.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core import telemetry
from .flash_attention import _wide

KERNEL_NAME = "mla_prefill_attention"
BLOCK = 512        # queries and keys a block; a prompt bucket's divisor
SUB = 256          # query rows of a diagonal block attended at once
_LANES = 128
_NEG = -1e30
VMEM_BLOCKS = 16 << 20      # a step's blocks (two buffers each) + scratch
VMEM_LIMIT = 64 << 20       # those + the score temporaries; v5e has 128 MiB


def stock_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                                block_q=256, window=0):
    """The stock lowering, and the kernel's oracle. q_nope [S, n, nope],
    q_rope [S, n, rope], k_nope [S, n, nope], k_rope [S, rope], v
    [S, n, dv] -> float32 [S, n, dv]. Queries go in blocks of `block_q`,
    each over the keys at or before its last query and, with `window`,
    within the band (static slices)."""
    s, n, _ = q_nope.shape
    bq = min(block_q, s)
    if s % bq:
        raise ValueError(f"prompt length {s} is no multiple of block_q {bq}")
    outs = []
    for q0 in range(0, s, bq):
        end = q0 + bq
        k0 = max(0, q0 - window + 1) if window else 0
        sc = (jnp.einsum("qhd,shd->hqs", q_nope[q0:end], k_nope[k0:end],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("qhr,sr->hqs", q_rope[q0:end], k_rope[k0:end],
                           preferred_element_type=jnp.float32)) * scale
        if window:
            gap = q0 + jnp.arange(bq, dtype=jnp.int32)[:, None] \
                - jnp.arange(k0, end, dtype=jnp.int32)[None, :]
            ok = (gap >= 0) & (gap < window)
        else:
            ok = jnp.arange(end, dtype=jnp.int32)[None, :] \
                <= q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        p = jax.nn.softmax(jnp.where(ok, sc, -1e9), axis=-1)
        outs.append(jnp.einsum("hqs,shv->qhv", p.astype(v.dtype),
                               v[k0:end],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=0)


def _rope_key_tiles(k_rope):
    """[S, rope] -> [S, t x 128], t = 128 / rope: tile i holds the key at
    lanes [i x rope, (i+1) x rope) and zeros elsewhere, so that the lane
    tile t heads' rotary queries share gives head i's part against tile i.
    A rope of whole lane tiles is its own one tile."""
    rope = k_rope.shape[1]
    if rope % _LANES == 0:
        return k_rope
    return jnp.concatenate(
        [jnp.pad(k_rope, ((0, 0), (i * rope, _LANES - (i + 1) * rope)))
         for i in range(_LANES // rope)], axis=1)


def _heads_a_step(n, nope, rope, dv, block, itemsize, group=1):
    """The most heads (a divisor of n whose rotary queries fill whole lane
    tiles, and whole groups of `group` query heads a K/V head) whose
    blocks, double-buffered, and float32 scratch fit VMEM_BLOCKS; 0 if not
    even the least does."""
    share = max(1, _LANES // rope)
    best = 0
    for g in range(share, n + 1, share):
        if n % g or g % group:
            continue
        blocks = 2 * itemsize * block * (
            g * (nope + rope) + g // group * (nope + dv)
            + share * max(rope, _LANES) + g * dv)
        scratch = 4 * g * block * (2 * _LANES + dv)
        if blocks + scratch <= VMEM_BLOCKS:
            best = g
    return best


def _spans(rows, keys, sub, window, before):
    """The (first row, last row, first key, last key, mask) spans one
    block pair is attended in: the whole pair below the diagonal; on the
    diagonal sub-blocks of `sub` rows over the keys up to their own last,
    masked key <= query; with `window` sub-blocks of `window` rows over
    the band's keys (mask "band"), and of the block `before` the first
    `window` rows over its last `window` keys alone (mask "before": key i
    of those reaches rows < i)."""
    if window and before:
        return [(0, window, keys - window, keys, "before")]
    if window:
        return [(r0, r0 + window, max(0, r0 - window), r0 + window, "band")
                for r0 in range(0, rows, window)]
    if sub is None:
        return [(0, rows, 0, keys, None)]
    return [(r0, r0 + sub, 0, r0 + sub, "causal")
            for r0 in range(0, rows, sub)]


@functools.partial(jax.jit, static_argnames=("c", "nope", "dv", "sub",
                                             "window", "before"))
def _attend_tile(qn, qr, kv, kr, m, l, acc, *, c, nope, dv, sub, window=0,
                 before=False):
    """The heads of ONE rotary lane tile on one (query block, key block)
    pair, on values the kernel has loaded: qn [rows, t x nope], qr
    [rows, 128 or rope] (the tile the t heads' rotary queries share), kv
    [keys, t x (nope + dv)], kr [keys, t x 128] (`_rope_key_tiles`), and
    the heads' running m, l [t, rows, 128] and acc [t, rows, dv]. Below
    the diagonal `sub` is None: every key counts. On the diagonal the
    block goes in sub-blocks of `sub` query rows, each on the keys up to
    its own last and masked key <= query, and the heads' finished output
    [rows, t x dv] float32 comes back as well. With `window` the pair is
    the diagonal's band, or (`before`) the corner of the block before it
    (`_spans`); rows no span covers keep their statistics.

    A jitted function of values so that its trace is made once a process
    and shape, not once a program: the kernel's own trace is a few loads,
    this call and a few stores (tracing every head's arithmetic in every
    prefill program cost a process 6-9 s of set-up, PR 34)."""
    nt = (((1,), (1,)), ((), ()))           # q @ k^T
    heads, rows = m.shape[0], qn.shape[0]
    rw, kw = qr.shape[1], nope + dv
    ms, ls, accs = [], [], []
    spans = _spans(rows, kv.shape[0], sub, window, before)
    for j in range(heads):
        parts = []
        for r0, r1, k0, keys, mask in spans:
            q = jnp.concatenate([qn[r0:r1, j * nope:(j + 1) * nope],
                                 qr[r0:r1]], axis=1)
            k = jnp.concatenate([kv[k0:keys, j * kw:j * kw + nope],
                                 kr[k0:keys, j * rw:(j + 1) * rw]], axis=1)
            s = jax.lax.dot_general(q, k, nt,
                                    preferred_element_type=jnp.float32)
            if mask is not None:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + r0
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                if mask == "causal":
                    ok = col <= row
                elif mask == "band":
                    ok = (col + k0 <= row) & (col + k0 > row - window)
                else:       # the block before: key i lies window - i back
                    ok = col > row
                s = jnp.where(ok, s, _NEG)
            m_old = m[j, r0:r1]                              # [rows, 128]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp2((m_old - m_new) * c)
            e = jnp.exp2((s - _wide(m_new, s.shape[1])) * c)
            v = kv[k0:keys, j * kw + nope:(j + 1) * kw]
            parts.append((
                m_new,
                l[j, r0:r1] * corr + jnp.sum(e, axis=-1, keepdims=True),
                acc[j, r0:r1] * _wide(corr, dv) + jnp.dot(
                    e.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)))
        if spans[-1][1] < rows:         # rows the spans leave as they are
            done = spans[-1][1]
            parts.append((m[j, done:], l[j, done:], acc[j, done:]))
        for out, i in ((ms, 0), (ls, 1), (accs, 2)):
            out.append(jnp.concatenate([part[i] for part in parts]))
    stats = jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)
    if before or (sub is None and not window):
        return stats
    return stats + (jnp.concatenate(
        [a / _wide(d, dv) for a, d in zip(accs, ls)], axis=1),)


def _kernel(qi_ref, kj_ref, qn_ref, qr_ref, kv_ref, kr_ref, o_ref, m_ref,
            l_ref, acc_ref, *, c, heads, nope, dv, rope, sub, group=1,
            window=0):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)
    qi, kj = qi_ref[t], kj_ref[t]
    share = max(1, _LANES // rope)          # heads to a rotary lane tile
    rw = max(rope, _LANES)                  # lanes of a rotary operand

    # a query block's first pair: key block 0, or with a window the block
    # before its own
    @pl.when(kj == (jnp.maximum(qi - 1, 0) if window else 0))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(i, sub, **band):
        """Rotary tile i of the step (traced inside the loop): load,
        attend, store."""
        def lanes(width):
            if isinstance(i, int):
                return slice(i * width, (i + 1) * width)
            return pl.ds(pl.multiple_of(i * width, _LANES), width)

        if group == 1:
            kv = kv_ref[:, lanes(share * (nope + dv))]
        else:       # each head's own K/V head's columns, side by side
            kv = jnp.concatenate(
                [kv_ref[:, pl.ds(pl.multiple_of(
                    (i * share + j) // group * (nope + dv), _LANES),
                    nope + dv)] for j in range(share)], axis=1)
        mine = pl.ds(i * share, share)
        m, l, acc, *out = _attend_tile(
            qn_ref[:, lanes(share * nope)], qr_ref[:, lanes(rw)],
            kv, kr_ref[...], m_ref[mine],
            l_ref[mine], acc_ref[mine], c=c, nope=nope, dv=dv, sub=sub,
            **band)
        m_ref[mine], l_ref[mine], acc_ref[mine] = m, l, acc
        if out:
            o_ref[:, lanes(share * dv)] = out[0].astype(o_ref.dtype)

    def every_tile(sub, **band):
        if heads == share:
            tile(0, sub, **band)
        else:
            jax.lax.fori_loop(
                0, heads // share,
                lambda i, _: (tile(i, sub, **band), 0)[1], 0)

    @pl.when(kj < qi)
    def _():
        if window:
            every_tile(None, window=window, before=True)
        else:
            every_tile(None)

    @pl.when(kj == qi)
    def _():
        if window:
            every_tile(None, window=window)
        else:
            every_tile(sub)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _pallas_mla_prefill_attention(q_nope, q_rope, kv, k_rope, scale, n,
                                  nope, block, heads, interpret, group=1,
                                  window=0):
    # jitted so that a program's layers share ONE trace and lowering of
    # the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = q_nope.shape[0]
    rope = q_rope.shape[1] // n
    dv = kv.shape[1] // (n // group) - nope
    kr = _rope_key_tiles(k_rope)
    # the causal triangle's (query block, key block) pairs, a query
    # block's in key order and its diagonal last; with a window (no wider
    # than a block) the diagonal and the block before it
    pairs = np.array([(i, j) for i in range(s // block)
                      for j in range(max(0, i - 1) if window else 0,
                                     i + 1)], np.int32)

    def of_query(h, t, qi, kj):
        return (qi[t], h)

    def of_key(h, t, qi, kj):
        return (kj[t], h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // heads, len(pairs)),
        in_specs=[pl.BlockSpec((block, heads * nope), of_query),
                  pl.BlockSpec((block, heads * rope), of_query),
                  pl.BlockSpec((block, heads // group * (nope + dv)),
                               of_key),
                  pl.BlockSpec((block, kr.shape[1]),
                               lambda h, t, qi, kj: (kj[t], 0))],
        out_specs=pl.BlockSpec((block, heads * dv), of_query),
        scratch_shapes=[pltpu.VMEM((heads, block, _LANES), jnp.float32),
                        pltpu.VMEM((heads, block, _LANES), jnp.float32),
                        pltpu.VMEM((heads, block, dv), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, c=scale * math.log2(math.e), heads=heads,
                          nope=nope, dv=dv, rope=rope,
                          sub=SUB if block % SUB == 0 else block,
                          group=group, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, n * dv), kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME)(
            pairs[:, 0], pairs[:, 1], q_nope, q_rope, kv, kr)


def mla_prefill_attention(q_nope, q_rope, kv, k_rope, scale, *, num_heads,
                          nope_dim, num_kv_heads=None, window=0):
    """Causal attention of one prompt in the layer's own layout. q_nope
    [S, n x nope]; q_rope [S, n x rope]; kv [S, n x (nope + dv)] (a head:
    key part, then value); k_rope [S, rope] (one key for all heads).
    Returns [S, n x dv] in kv's dtype. With `num_kv_heads` kv is
    [S, nkv x (nope + dv)] and query head h reads K/V head h // (n / nkv);
    with `window` a query reads its last `window` keys only (module
    docstring). Routed per ``kernel_mode()``; a
    stock fallback is counted in ``pallas.mla_prefill_fallbacks`` by its
    reason: ``mode_off``; ``length`` (S no multiple of its block);
    ``window`` (a window that is no whole lane tiles or no divisor of
    the block);
    ``tpu_tiling`` (a head width or the block no whole lane tiles, a rope
    that neither divides a lane tile nor is a multiple of one, or heads
    that do not fill their shared rotary tiles); ``vmem`` (one step's
    blocks over VMEM_BLOCKS)."""
    from . import kernel_mode

    if not scale > 0:
        raise ValueError(f"softmax scale {scale} is not positive: the "
                         f"running maximum is taken of the raw scores")
    s, n, nope = q_nope.shape[0], num_heads, nope_dim
    nkv = num_kv_heads or n
    group = n // nkv
    if group * nkv != n:
        raise ValueError(f"{n} query heads on {nkv} K/V heads")
    rope = q_rope.shape[1] // n
    dv = kv.shape[1] // nkv - nope
    block = min(BLOCK, s)
    if s % block:
        # a bucket halfway between two powers of two (768 = 2 x 384): the
        # largest whole-lane-tile block under BLOCK that divides it
        block = next((b for b in range(BLOCK - _LANES, 0, -_LANES)
                      if s % b == 0), block)
    mode = kernel_mode()
    heads = 0
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif s % block:
        reason = "length"
    elif window and (window % _LANES or block % window):
        reason = "window"
    elif (nope % _LANES or dv % _LANES or block % _LANES
          or (rope % _LANES and _LANES % rope)
          or n % max(1, _LANES // rope)):
        reason = "tpu_tiling"
    else:
        heads = _heads_a_step(n, nope, rope, dv, block, kv.dtype.itemsize,
                              group)
        if not heads:
            reason = "vmem"
    if reason is not None:
        telemetry.counter_add("pallas.mla_prefill_fallbacks", 1,
                              reason=reason)
        kvh = kv.reshape(s, nkv, nope + dv)
        if group > 1:
            kvh = jnp.repeat(kvh, group, axis=1)
        out = stock_mla_prefill_attention(
            q_nope.reshape(s, n, nope), q_rope.reshape(s, n, rope),
            kvh[:, :, :nope], k_rope, kvh[:, :, nope:], scale,
            block_q=math.gcd(s, SUB), window=window)
        return out.reshape(s, n * dv).astype(kv.dtype)
    telemetry.counter_add("pallas.mla_prefill_dispatches", 1, mode=mode)
    return _pallas_mla_prefill_attention(
        q_nope, q_rope, kv, k_rope, float(scale), n, nope, block, heads,
        mode == "interpret", group, window)
