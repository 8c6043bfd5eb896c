"""The held experts' SwiGLU over rows sorted by expert, as one kernel.

``xs [n, H]`` holds the routed layer's pairs sorted by held expert
(parallel/moe.py ``routed_experts_share``): the first ``sizes[0]`` rows
are expert 0's, the next ``sizes[1]`` expert 1's, and the rows past
``sum(sizes)`` are nobody's. A row of expert e gives

    ys = (silu(x @ w1[e]) * (x @ w3[e])).astype(w2.dtype) @ w2[e]

with every product accumulated in float32: what three
``jax.lax.ragged_dot`` compute (``stock_grouped_swiglu``, the oracle and
the counted fallback), in one pass over the weights.

The weights are the whole cost: 3 x H x F values an expert hit (56.6 MB at
3072 x 3072 bfloat16, 88 MB at 7168 x 2048) against a few rows of
activations, in a decode step and in a prefill alike (a 4,096-token
prompt leaves an expert ~60 rows): bound by bytes, so the kernel is a
stream of weight blocks with a small product under each.

**Grid** (visits, F blocks). A *visit* is one expert over one row tile of
``tile`` rows (all ``n`` rows in a step; 512 or 1,024 in a prefill, as
many as VMEM holds, so that few groups straddle two tiles and are read
twice). Which expert and which tile a visit serves come by scalar
prefetch, so the pipeline fetches the next visit's first blocks under
the last product of this one and an expert with no row is never read. The visits past the last real one repeat its
block indices (nothing moves) and compute nothing. A step of the grid
holds ``w1[e][:, j]``, ``w3[e][:, j]`` ([H, tn]) and ``w2[e][j]``
([tn, H]): the gate and up products over the whole of H, ``silu(g) * u``
rounded to ``w2.dtype``, and its part of the down product added to the
output tile, which stays in VMEM for as long as the visits stay on its
row tile.

**Rows.** Within a tile the products run over windows of ``window`` rows
(128, the MXU's height) that start at the group's first row rounded down
to a sublane tile: a group of 60 rows costs one window wherever it lies,
and a window's rows outside the group are selected away (`where`, so that
whatever lies past ``n`` in the last tile stays out).

Rows that belong to no expert come back zero within a visited tile and
unwritten in a tile no visit touched: the caller selects them away, as it
does with ``ragged_dot``'s. ``name="grouped_swiglu"``.

**PolyNorm experts** (``grouped_polyglu``, ``name="grouped_polyglu"``;
models/motif3.py). Their activation is not elementwise: a row of expert e
gives ``(PN_e(x @ w1[e]) * (x @ w3[e])) @ w2[e]`` with

    PN(g) = out_scale (p0 g / r(g) + p1 g^2 / r(g^2) + p2 g^3 / r(g^3)
            + clip(p3, +-bias_clamp)),   r(a) = sqrt(mean(a^2) + eps)

over the expert's WHOLE width F and ``p = pn[e]``, so no F block's part of
the down product can start before the gate's last block is in. The kernel
shares this module's visits, tiles and scalar prefetch and doubles the
second grid axis: steps ``j < nj`` stream ``w1[e][:, j]`` and
``w3[e][:, j]`` and leave a window's gate and up products in two float32
scratch arrays [tile, F]; steps ``j >= nj`` stream ``w2[e][j - nj]`` and,
for each window, take the three row statistics from the whole gate row,
form ``PN(g) * u`` of their F block and add its part of the down product.
A step's idle operand stays on the block it has (the down block on the
expert's first while gate and up stream, so it is fetched under them), and
an expert's three matrices are still read once. ``pn [E, 4]`` float32 rides
in SMEM whole. ``stock_grouped_polyglu`` (three ``ragged_dot`` and the
norm) is the oracle and the counted fallback
(``pallas.grouped_polyglu_dispatches`` / ``_fallbacks``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "grouped_swiglu"
POLY_KERNEL_NAME = "grouped_polyglu"
WINDOW_ROWS = 128           # rows a product: the MXU's height
# a visit's row tile and output tile, two buffers each: 1,024 rows at
# H 3072, 512 at 7168 (a step's rows are always one tile)
TILE_BYTES = 48 << 20
# one weight block [H, tn] / [tn, H]; three of them, two buffers each
BLOCK_BYTES = 8 << 20
VMEM_LIMIT = 100 << 20      # tiles + blocks + a window's products; v5e: 128 MiB


def stock_grouped_swiglu(xs, w1, w3, w2, sizes):
    """xs [n, H], w1 and w3 [E, H, F], w2 [E, F, H], sizes int32 [E]
    (sum <= n) -> ys [n, H] float32."""
    def grouped(a, wts):
        return jax.lax.ragged_dot(a, wts, sizes,
                                  preferred_element_type=jnp.float32)

    mid = jax.nn.silu(grouped(xs, w1)) * grouped(xs, w3)
    return grouped(mid.astype(w2.dtype), w2)


def _sublanes(dtype) -> int:
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _lanes(h, f, dtype, block_bytes):
    """Columns of F a weight block of at most `block_bytes` holds at H
    rows: whole lane tiles that divide F."""
    tn = max(block_bytes // (h * jnp.dtype(dtype).itemsize) // 128 * 128,
             128)
    while f % tn:
        tn -= 128
    return tn


def _tiles(n, h, f, dtype):
    """(tile, window, tn) for n sorted rows at H x F, or None where the
    kernel cannot tile them."""
    if n % _sublanes(dtype) or h % 128 or f % 128:
        return None
    tile = TILE_BYTES // (2 * h * (jnp.dtype(dtype).itemsize + 4))
    tile = min(n, 1 << (tile.bit_length() - 1))
    return tile, min(tile, WINDOW_ROWS), _lanes(h, f, dtype, BLOCK_BYTES)


def _visits(sizes, n, tile):
    """Which (expert, row tile) each visit serves, sorted by row: int32
    arrays of the grid's length, the real visits first and the rest
    repeating the last real one; the groups' row offsets [E + 1]; the
    count of real visits [1]."""
    e = sizes.shape[0]
    tiles = -(-n // tile)
    length = e + tiles - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    touched = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(touched)
    total = upto[-1]
    v = jnp.minimum(jnp.arange(length, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1),
                      e - 1).astype(jnp.int32)
    tid = jnp.clip(first[gid] + v - (upto[gid] - touched[gid]), 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, tid.astype(jnp.int32), offsets, total.reshape(1)


def _kernel(gid_ref, tid_ref, off_ref, total_ref, x_ref, w1_ref, w3_ref,
            w2_ref, o_ref, *, tile, window, align):
    from jax.experimental import pallas as pl

    v, j = pl.program_id(0), pl.program_id(1)
    g, t = gid_ref[v], tid_ref[v]

    @pl.when((j == 0) & ((v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != t)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(v < total_ref[0])
    def _():
        base = t * tile
        lo = jnp.maximum(off_ref[g], base) - base
        hi = jnp.minimum(off_ref[g + 1], base + tile) - base
        lo_al = lo // align * align

        def rows_from(i, carry):
            s = lo_al + i * window
            at = pl.multiple_of(jnp.minimum(s, tile - window), align)
            x = x_ref[pl.ds(at, window), :]
            gate = jnp.dot(x, w1_ref[...],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(gate) * up).astype(w2_ref.dtype)
            y = jnp.dot(mid, w2_ref[...], preferred_element_type=jnp.float32)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (row >= jnp.maximum(s, lo)) \
                & (row < jnp.minimum(s + window, hi))
            o_ref[pl.ds(at, window), :] += jnp.where(mine, y, 0.0)
            return carry

        jax.lax.fori_loop(0, (hi - lo_al + window - 1) // window,
                          rows_from, 0)


@functools.partial(jax.jit, static_argnames=("tile", "window", "tn",
                                             "interpret"))
def _pallas_grouped_swiglu(xs, w1, w3, w2, sizes, *, tile, window, tn,
                           interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h = xs.shape
    e, _, f = w1.shape
    nj = f // tn
    gid, tid, offsets, total = _visits(sizes, n, tile)

    def block(v, j, gid, tid, off, total):
        # a visit past the last real one stays on the last block fetched
        return gid[v], jnp.where(v < total[0], j, nj - 1)

    def cols(v, j, *meta):
        g, jj = block(v, j, *meta)
        return g, 0, jj

    def rows(v, j, *meta):
        g, jj = block(v, j, *meta)
        return g, jj, 0

    def row_tile(v, j, gid, tid, off, total):
        return tid[v], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(gid.shape[0], nj),
        in_specs=[pl.BlockSpec((tile, h), row_tile),
                  pl.BlockSpec((None, h, tn), cols),
                  pl.BlockSpec((None, h, tn), cols),
                  pl.BlockSpec((None, tn, h), rows)],
        out_specs=pl.BlockSpec((tile, h), row_tile))
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, window=window,
                          align=_sublanes(xs.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME)(
            gid, tid, offsets, total, xs, w1, w3, w2)


def _route(kernel, xs, w1, w3, w2):
    """(mode, (tile, window, tn)) for `kernel`'s dispatch, or (None, None)
    with its stock fallback counted by reason: ``mode_off``, ``dtype``
    (mixed), ``shape`` (`_tiles`)."""
    from . import kernel_mode

    mode = kernel_mode()
    tiles = None
    if mode == "off":
        reason = "mode_off"
    elif not (xs.dtype == w1.dtype == w3.dtype == w2.dtype):
        reason = "dtype"
    else:
        tiles = _tiles(xs.shape[0], xs.shape[1], w1.shape[2], xs.dtype)
        reason = None if tiles else "shape"
    if reason is not None:
        telemetry.counter_add(f"pallas.{kernel}_fallbacks", 1, reason=reason)
        return None, None
    telemetry.counter_add(f"pallas.{kernel}_dispatches", 1, mode=mode)
    return mode, tiles


def grouped_swiglu(xs, w1, w3, w2, sizes):
    """The held experts' SwiGLU over rows sorted by expert (module
    docstring). Routed per ``kernel_mode()``; every stock fallback is
    counted."""
    mode, tiles = _route(KERNEL_NAME, xs, w1, w3, w2)
    if mode is None:
        return stock_grouped_swiglu(xs, w1, w3, w2, sizes)
    tile, window, tn = tiles
    return _pallas_grouped_swiglu(xs, w1, w3, w2, sizes, tile=tile,
                                  window=window, tn=tn,
                                  interpret=mode == "interpret")

def poly_norm(g, pn, *, eps, out_scale, bias_clamp):
    """PolyNorm over g's last axis, float32: pn [..., 4] = (p0, p1, p2,
    p3) broadcast against g's rows (module docstring)."""
    def unit(a):
        return a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                                 + eps)

    g2 = g * g
    return out_scale * (
        pn[..., 0:1] * unit(g) + pn[..., 1:2] * unit(g2)
        + pn[..., 2:3] * unit(g2 * g)
        + jnp.clip(pn[..., 3:4], -bias_clamp, bias_clamp))


def stock_grouped_polyglu(xs, w1, w3, w2, pn, sizes, *, eps, out_scale,
                          bias_clamp):
    """xs [n, H], w1 and w3 [E, H, F], w2 [E, F, H], pn [E, 4] float32,
    sizes int32 [E] (sum <= n) -> ys [n, H] float32."""
    def grouped(a, wts):
        return jax.lax.ragged_dot(a, wts, sizes,
                                  preferred_element_type=jnp.float32)

    rows = jnp.repeat(pn.astype(jnp.float32), sizes, axis=0,
                      total_repeat_length=xs.shape[0])
    mid = poly_norm(grouped(xs, w1), rows, eps=eps, out_scale=out_scale,
                    bias_clamp=bias_clamp) * grouped(xs, w3)
    return grouped(mid.astype(w2.dtype), w2)


def _poly_kernel(gid_ref, tid_ref, off_ref, total_ref, x_ref, w1_ref,
                 w3_ref, w2_ref, pn_ref, o_ref, g_ref, u_ref, *, tile,
                 window, align, nj, tn, eps, out_scale, bias_clamp):
    from jax.experimental import pallas as pl

    v, j = pl.program_id(0), pl.program_id(1)
    g, t = gid_ref[v], tid_ref[v]

    @pl.when((j == 0) & ((v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != t)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    base = t * tile
    lo = jnp.maximum(off_ref[g], base) - base
    hi = jnp.minimum(off_ref[g + 1], base + tile) - base
    lo_al = lo // align * align
    windows = (hi - lo_al + window - 1) // window

    def at_of(i):
        s = lo_al + i * window
        return s, pl.multiple_of(jnp.minimum(s, tile - window), align)

    @pl.when((v < total_ref[0]) & (j < nj))
    def _():
        cols = pl.ds(pl.multiple_of(j * tn, 128), tn)

        def rows_from(i, carry):
            _, at = at_of(i)
            x = x_ref[pl.ds(at, window), :]
            g_ref[pl.ds(at, window), cols] = jnp.dot(
                x, w1_ref[...], preferred_element_type=jnp.float32)
            u_ref[pl.ds(at, window), cols] = jnp.dot(
                x, w3_ref[...], preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, windows, rows_from, 0)

    @pl.when((v < total_ref[0]) & (j >= nj))
    def _():
        cols = pl.ds(pl.multiple_of((j - nj) * tn, 128), tn)
        p0, p1, p2 = pn_ref[g, 0], pn_ref[g, 1], pn_ref[g, 2]
        p3 = jnp.clip(pn_ref[g, 3], -bias_clamp, bias_clamp)

        def rows_from(i, carry):
            s, at = at_of(i)
            gate = g_ref[pl.ds(at, window), :]              # [window, F]
            g2 = gate * gate

            def inv(a):
                return jax.lax.rsqrt(
                    jnp.mean(a * a, axis=-1, keepdims=True) + eps)

            r1, r2, r3 = inv(gate), inv(g2), inv(g2 * gate)
            gj = g_ref[pl.ds(at, window), cols]
            gj2 = gj * gj
            act = out_scale * (p0 * r1 * gj + p1 * r2 * gj2
                               + p2 * r3 * (gj2 * gj) + p3)
            mid = (act * u_ref[pl.ds(at, window), cols]).astype(
                w2_ref.dtype)
            y = jnp.dot(mid, w2_ref[...], preferred_element_type=jnp.float32)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (row >= jnp.maximum(s, lo)) \
                & (row < jnp.minimum(s + window, hi))
            o_ref[pl.ds(at, window), :] += jnp.where(mine, y, 0.0)
            return carry

        jax.lax.fori_loop(0, windows, rows_from, 0)


@functools.partial(jax.jit, static_argnames=(
    "tile", "window", "tn", "eps", "out_scale", "bias_clamp", "interpret"))
def _pallas_grouped_polyglu(xs, w1, w3, w2, pn, sizes, *, tile, window, tn,
                            eps, out_scale, bias_clamp, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h = xs.shape
    e, _, f = w1.shape
    nj = f // tn
    gid, tid, offsets, total = _visits(sizes, n, tile)

    def cols(v, j, gid, tid, off, total):
        # gate and up stream over j < nj and then stay; a visit past the
        # last real one stays on the last block fetched
        return gid[v], 0, jnp.where(v < total[0], jnp.minimum(j, nj - 1),
                                    nj - 1)

    def rows(v, j, gid, tid, off, total):
        return gid[v], jnp.where(v < total[0], jnp.maximum(j - nj, 0),
                                 nj - 1), 0

    def row_tile(v, j, gid, tid, off, total):
        return tid[v], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(gid.shape[0], 2 * nj),
        in_specs=[pl.BlockSpec((tile, h), row_tile),
                  pl.BlockSpec((None, h, tn), cols),
                  pl.BlockSpec((None, h, tn), cols),
                  pl.BlockSpec((None, tn, h), rows),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((tile, h), row_tile),
        scratch_shapes=[pltpu.VMEM((tile, f), jnp.float32),
                        pltpu.VMEM((tile, f), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_poly_kernel, tile=tile, window=window,
                          align=_sublanes(xs.dtype), nj=nj, tn=tn, eps=eps,
                          out_scale=out_scale, bias_clamp=bias_clamp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=POLY_KERNEL_NAME)(
            gid, tid, offsets, total, xs, w1, w3, w2,
            pn.astype(jnp.float32))


def grouped_polyglu(xs, w1, w3, w2, pn, sizes, *, eps, out_scale,
                    bias_clamp):
    """The held PolyNorm experts over rows sorted by expert (module
    docstring). Routed per ``kernel_mode()``; every stock fallback is
    counted."""
    mode, tiles = _route(POLY_KERNEL_NAME, xs, w1, w3, w2)
    if mode is None:
        return stock_grouped_polyglu(xs, w1, w3, w2, pn, sizes, eps=eps,
                                     out_scale=out_scale,
                                     bias_clamp=bias_clamp)
    tile, window, tn = tiles
    return _pallas_grouped_polyglu(
        xs, w1, w3, w2, pn, sizes, tile=tile, window=window, tn=tn,
        eps=float(eps), out_scale=float(out_scale),
        bias_clamp=float(bias_clamp), interpret=mode == "interpret")
