"""The backward of the held experts' SwiGLU over rows sorted by expert, as
two kernels over the same sorted rows the forward ran over
(``grouped_swiglu.py``: its visits of (expert, row tile) by scalar
prefetch, its row windows, rows outside a group selected away, every
product accumulated in float32).

A sorted row of expert e with weight ``w`` gave ``w * (silu(x @ w1[e]) *
(x @ w3[e])).astype(dt) @ w2[e]``; with ``dy`` the row of the output's
cotangent (both rounded to the weights' dtype ``dt`` by the caller): what
eight ``jax.lax.ragged_dot`` and the float32 passes between them compute
(``stock_grouped_swiglu_bwd``, the oracle and the counted fallback), as
two passes over the weights:

**Rows-side**, ``name="grouped_swiglu_bwd_rows"``, grid (visits,). A visit
holds a row tile of ``xs``, ``dy`` and ``w`` and the expert's three
matrices whole (an expert's visits follow one another, so they are
fetched once an expert), and makes, a window of rows at a time: gate and
up again (a sorted row's [F] float32 pair is not kept), ``dmid = dy @
w2[e]^T`` (the matrix's LAST axis contracted in the kernel: no transposed
copy of a weight), ``sig``, ``act``, ``mid``, the weight's gradient ``dw =
sum_f(dmid * mid)`` from the float32 ``dmid`` before it is scaled by
``w``, ``dgate`` and ``dup`` rounded to ``dt``, and ``dxs = dgate @
w1[e]^T + dup @ w3[e]^T``. Out: ``dxs [n, H]`` and ``dw [n, 1]`` float32,
``dgate``, ``dup``, ``mid`` ``[n, F]`` in ``dt`` for the second kernel.
Five products.

**Weights-side**, ``name="grouped_swiglu_bwd_weights"``, grid (visits,):
``dW1[e] += xs^T dgate``, ``dW3[e] += xs^T dup``, ``dW2[e] += mid^T (dy *
w)`` over the expert's row tiles, the three float32 matrices resident
while the visits stay on the expert. Three products.

Both kernels run over one schedule of visits (``_visits_every_expert``):
the forward's, with the experts that have no row taken in too, once each.
Such a visit reads no row: weights-side it leaves the expert's three
matrices zero, rows-side it does nothing.

Rows of no expert come back zero within a visited tile and unwritten in a
tile no visit touched, as the forward's: the caller selects them away.
The kernels hold an expert's matrices whole (one pass over F): where they
and a row tile do not fit the kernel's VMEM, the ragged products run
instead (``grouped_swiglu_bwd``, counted).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry
from .grouped_swiglu import VMEM_LIMIT, _sublanes

ROWS_KERNEL_NAME = "grouped_swiglu_bwd_rows"
WEIGHTS_KERNEL_NAME = "grouped_swiglu_bwd_weights"
TILE_ROWS = 512             # a visit's row tile at most
# rows a product: the MXU's height rows-side; twice it weights-side, where
# the rows are the contraction and every window adds into the three
# resident [H, F] float32 matrices. A group's last window is part empty,
# so a larger window loses more than it saves (my chip runs, PR 44:
# 4.03 ms at 128 rows, 4.19 at 256, 5.02 at 512 rows-side; 2.85 ms at
# 256, 3.01 at 512, 3.61 at 1,024 weights-side; 512- and 1,024-row
# tiles within 2% of one another)
WINDOW_ROWS = 128
WINDOW_ROWS_WEIGHTS = 256
# the blocks of a visit, two buffers each; what is left of VMEM_LIMIT is
# for a window's float32 products
BLOCKS_BYTES = 84 << 20

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def stock_grouped_swiglu_bwd(xs, dy, dyw, w, w1, w3, w2, sizes):
    """``grouped_swiglu_bwd`` as eight ragged products over the groups and
    the float32 passes between them."""
    dt = w1.dtype
    f32 = jnp.float32
    # rows by a group's TRANSPOSED matrix go through the plain ragged
    # product over a transposed copy (66 MB a matrix at 16 x 2304 x 896):
    # the ragged product that contracts the matrix's last axis instead
    # came back 97% off at [rows, 2304] x [16, 896, 2304] over 20,480 rows
    # and more on the chip, and right at 16,448 (my chip runs, PR 43)
    w1_t, w3_t, w2_t = (jnp.swapaxes(m, 1, 2) for m in (w1, w3, w2))
    dn_w = jax.lax.RaggedDotDimensionNumbers(       # a[group]^T @ b[group]
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def rd(a, m, dn=None):
        if dn is None:
            return jax.lax.ragged_dot(a, m, sizes, preferred_element_type=f32)
        return jax.lax.ragged_dot_general(a, m, sizes, dn,
                                          preferred_element_type=f32)

    gate, up = rd(xs, w1), rd(xs, w3)
    sig = jax.nn.sigmoid(gate)
    act = gate * sig
    mid = act * up
    dmid = rd(dy, w2_t)                                     # per unit weight
    dw = jnp.sum(dmid * mid, axis=1)
    dmid = dmid * w[:, None]
    dgate = (dmid * up * (sig + act * (1.0 - sig))).astype(dt)
    dup = (dmid * act).astype(dt)
    return (rd(dgate, w1_t) + rd(dup, w3_t), dw, rd(xs, dgate, dn_w),
            rd(xs, dup, dn_w), rd(mid.astype(dt), dyw, dn_w))


def _tile(n, h, f, dtype):
    """Rows of a visit's tile for n sorted rows at H x F, the same in both
    kernels, or None where they cannot tile them."""
    if n % _sublanes(dtype) or h % 128 or f % 128:
        return None
    size = jnp.dtype(dtype).itemsize
    # rows-side: three matrices in; a row of xs, dy, dxs, the three
    # hand-overs, w and dw (a lane tile of float32 each). Weights-side:
    # three float32 matrices out; a row of xs, dy * w, the hand-overs
    rows = min((BLOCKS_BYTES // 2 - held) // row for held, row in (
        (3 * h * f * size, h * (2 * size + 4) + 3 * f * size + 2 * 128 * 4),
        (3 * h * f * 4, 2 * h * size + 3 * f * size)))
    if rows < _sublanes(dtype):
        return None
    return min(n, TILE_ROWS, 1 << (rows.bit_length() - 1))


def _visits_every_expert(sizes, n, tile):
    """``grouped_swiglu._visits`` with one visit more for each expert that
    has no row, in the experts' order (its row tile the one its neighbours
    lie in, so no row is fetched for it): the visits of both kernels."""
    e = sizes.shape[0]
    tiles = -(-n // tile)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    touched = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 1)
    upto = jnp.cumsum(touched)
    total = upto[-1]
    v = jnp.minimum(jnp.arange(e + tiles - 1, dtype=jnp.int32), total - 1)
    gid = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1),
                      e - 1).astype(jnp.int32)
    tid = jnp.clip(first[gid] + v - (upto[gid] - touched[gid]), 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, tid.astype(jnp.int32), offsets, total.reshape(1)


def _windows(off_ref, g, t, tile, window, align, body):
    """Runs ``body(at, mine)`` over the windows of group g's rows inside
    row tile t (the forward's rule): ``at`` the window's first row in the
    tile, a multiple of ``align``; ``mine`` [window, 1] the rows of it
    that are the group's and no earlier window's."""
    from jax.experimental import pallas as pl

    base = t * tile
    lo = jnp.maximum(off_ref[g], base) - base
    hi = jnp.minimum(off_ref[g + 1], base + tile) - base
    lo_al = lo // align * align

    def one(i, carry):
        s = lo_al + i * window
        at = pl.multiple_of(jnp.minimum(s, tile - window), align)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
        body(at, (row >= jnp.maximum(s, lo))
             & (row < jnp.minimum(s + window, hi)))
        return carry

    jax.lax.fori_loop(0, (hi - lo_al + window - 1) // window, one, 0)


def _rows_kernel(gid_ref, tid_ref, off_ref, total_ref, x_ref, dy_ref, w_ref,
                 w1_ref, w3_ref, w2_ref, dx_ref, dw_ref, dg_ref, du_ref,
                 mid_ref, *, tile, window, align):
    from jax.experimental import pallas as pl

    v = pl.program_id(0)
    g, t = gid_ref[v], tid_ref[v]
    outs = (dx_ref, dw_ref, dg_ref, du_ref, mid_ref)

    @pl.when((v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != t))
    def _():
        for ref in outs:
            ref[...] = jnp.zeros_like(ref)

    def rows_from(at, mine):
        at_rows = pl.ds(at, window)
        f32 = jnp.float32
        dt = w1_ref.dtype
        x = x_ref[at_rows, :]
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=f32)
        dmid = jax.lax.dot_general(dy_ref[at_rows, :], w2_ref[...], _NT,
                                   preferred_element_type=f32)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        mid = act * up
        dw = jnp.sum(dmid * mid, axis=1, keepdims=True)     # per unit weight
        dmid = dmid * w_ref[at_rows, :]
        dgate = (dmid * up * (sig + act * (1.0 - sig))).astype(dt)
        dup = (dmid * act).astype(dt)
        dxs = jax.lax.dot_general(dgate, w1_ref[...], _NT,
                                  preferred_element_type=f32) \
            + jax.lax.dot_general(dup, w3_ref[...], _NT,
                                  preferred_element_type=f32)
        for ref, new in zip(outs, (dxs, dw, dgate, dup, mid.astype(dt))):
            ref[at_rows, :] = jnp.where(mine, new, ref[at_rows, :])

    @pl.when(v < total_ref[0])
    def _():
        _windows(off_ref, g, t, tile, window, align, rows_from)


def _weights_kernel(gid_ref, tid_ref, off_ref, total_ref, x_ref, dyw_ref,
                    dg_ref, du_ref, mid_ref, d1_ref, d3_ref, d2_ref, *,
                    tile, window, align):
    from jax.experimental import pallas as pl

    v = pl.program_id(0)
    g, t = gid_ref[v], tid_ref[v]

    @pl.when((v == 0) | (gid_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        for ref in (d1_ref, d3_ref, d2_ref):
            ref[...] = jnp.zeros_like(ref)

    def rows_from(at, mine):
        # both sides of a product selected: what lies past n in the last
        # tile, on either side, is anything
        x, dyw, dg, du, mid = (
            jnp.where(mine, ref[pl.ds(at, window), :], 0)
            for ref in (x_ref, dyw_ref, dg_ref, du_ref, mid_ref))
        for ref, a, b in ((d1_ref, x, dg), (d3_ref, x, du),
                          (d2_ref, mid, dyw)):
            ref[...] += jax.lax.dot_general(
                a, b, _TN, preferred_element_type=jnp.float32)

    @pl.when(v < total_ref[0])
    def _():
        _windows(off_ref, g, t, tile, window, align, rows_from)


def _call(kernel, name, tile, meta, ins, outs, interpret):
    """One kernel over the visits `meta`: `ins` and `outs` are (array or
    its ShapeDtypeStruct, "rows" | "expert") pairs: the visit's tile of
    `tile` rows, or the visit's expert's matrix whole."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(a, kind):
        if kind == "rows":
            return pl.BlockSpec((tile, a.shape[1]),
                                lambda v, gid, tid, off, total: (tid[v], 0))
        return pl.BlockSpec((None,) + a.shape[1:],
                            lambda v, gid, tid, off, total: (gid[v], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(meta[0].shape[0],),
        in_specs=[spec(*i) for i in ins],
        out_specs=[spec(*o) for o in outs])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=[o for o, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)(*meta, *(a for a, _ in ins))


@functools.partial(jax.jit, static_argnames=("tile", "window", "interpret"))
def _pallas_bwd_rows(xs, dy, w, w1, w3, w2, sizes, *, tile, window,
                     interpret):
    n, h = xs.shape
    f = w1.shape[2]
    window = min(tile, window)
    rows, expert = "rows", "expert"
    return _call(
        functools.partial(_rows_kernel, tile=tile, window=window,
                          align=_sublanes(xs.dtype)),
        ROWS_KERNEL_NAME, tile, _visits_every_expert(sizes, n, tile),
        [(xs, rows), (dy, rows), (w.reshape(n, 1), rows), (w1, expert),
         (w3, expert), (w2, expert)],
        [(jax.ShapeDtypeStruct((n, h), jnp.float32), rows),
         (jax.ShapeDtypeStruct((n, 1), jnp.float32), rows)]
        + [(jax.ShapeDtypeStruct((n, f), w1.dtype), rows)] * 3, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "window", "interpret"))
def _pallas_bwd_weights(xs, dyw, dgate, dup, mid, sizes, *, tile, window,
                        interpret):
    n, h = xs.shape
    e, f = sizes.shape[0], dgate.shape[1]
    window = min(tile, window)
    rows, expert = "rows", "expert"
    return _call(
        functools.partial(_weights_kernel, tile=tile, window=window,
                          align=_sublanes(xs.dtype)),
        WEIGHTS_KERNEL_NAME, tile, _visits_every_expert(sizes, n, tile),
        [(a, rows) for a in (xs, dyw, dgate, dup, mid)],
        [(jax.ShapeDtypeStruct((e, h, f), jnp.float32), expert)] * 2
        + [(jax.ShapeDtypeStruct((e, f, h), jnp.float32), expert)],
        interpret)


def grouped_swiglu_bwd(xs, dy, dyw, w, w1, w3, w2, sizes):
    """The gradients of one run of sorted rows (module docstring).

    xs, dy, dyw [n, H]: the rows, their cotangents and ``dy * w``, in the
    weights' dtype; w [n] float32, 0 past the groups; w1, w3 [E, H, F],
    w2 [E, F, H]; sizes int32 [E] (sum <= n). -> (dxs [n, H], dw [n],
    dW1, dW3, dW2), all float32; the rows past the groups hold anything.
    Routed per ``kernel_mode()``; ``stock_grouped_swiglu_bwd`` runs
    wherever the kernels do not, counted."""
    from . import kernel_mode

    mode = kernel_mode()
    n, h = xs.shape
    tile = None
    if mode == "off":
        reason = "mode_off"
    elif not (xs.dtype == dy.dtype == dyw.dtype == w1.dtype == w3.dtype
              == w2.dtype):
        reason = "dtype"
    else:
        tile = _tile(n, h, w1.shape[2], xs.dtype)
        reason = None if tile else "shape"
    if reason is not None:
        telemetry.counter_add("pallas.grouped_swiglu_bwd_fallbacks", 1,
                              reason=reason)
        return stock_grouped_swiglu_bwd(xs, dy, dyw, w, w1, w3, w2, sizes)
    telemetry.counter_add("pallas.grouped_swiglu_bwd_dispatches", 1,
                          mode=mode)
    interpret = mode == "interpret"
    dxs, dw, dgate, dup, mid = _pallas_bwd_rows(
        xs, dy, w.astype(jnp.float32), w1, w3, w2, sizes, tile=tile,
        window=WINDOW_ROWS, interpret=interpret)
    return (dxs, dw[:, 0]) + tuple(_pallas_bwd_weights(
        xs, dyw, dgate, dup, mid, sizes, tile=tile,
        window=WINDOW_ROWS_WEIGHTS, interpret=interpret))
