"""The routed layer's rows spread to where the sorted rows lie, as one
kernel: ``routed_combine.py`` turned round, over the same runs.

``src [T, H]`` is token-major; ``rows[p]`` is the token of sorted row p,
``w[p]`` its weight (0 past the groups), ``sizes[e]`` the rows of expert
e's group (parallel/moe.py ``routed_experts_share``). Two uses:

    xs[p]  = src[rows[p]].astype(dtype)                       (plain)
    dy[p]  = where(w[p] > 0, src[rows[p]], 0)                 (weighted)
    -> dy.astype(dtype), (dy * w[p]).astype(dtype)

for every row p inside the groups, value for value what XLA's gather and
its rounding passes compute (``stock_routed_spread``, the oracle and the
counted fallback: ~37 ns a row whatever the bytes, and a pass over the
float32 ``dy`` for each rounding). The rows past the groups are nobody's
and come back ZERO (XLA's gather leaves real rows of ``src`` there).

**Runs and steps** are the combine's (``routed_combine._plan``, shared):
inside a group the tokens ascend, so the rows of group e whose tokens lie
in one token tile are one contiguous run of the sorted rows, and a step
of the grid is one token tile with up to ``stage / piece`` pieces
(``piece`` sorted rows) that its runs touch. The tile ``[tile, H]`` comes
as one contiguous block (fetched once while the steps stay on it, the
next tile's under this one's products) and is rounded to ``dtype`` there
(plain: rounding and selecting commute) or cut into three bfloat16 parts
(float32 kept whole: 8 + 8 + 8 bits of mantissa). The pieces' rows of
``meta [n, 128]`` (lane 0 the token, -1 past the groups; lane 1 the
weight) are staged by one DMA a piece, the next step's under this step's
products.

**The rows** are a one-hot product on the MXU: ``[stage, tile]`` (1 where
staged row j's token is tile row i, read from ``meta``, never from the
data) times the tile's parts, accumulated in float32: one term a row, so
the selected value is exact (a -0.0 comes out +0.0; an Inf or NaN in a
tile reaches the tile's other rows of that column, as in the combine),
then weighed and rounded once.

**Edge pieces.** A piece whose rows belong to several token tiles (runs
of neighbouring tiles are adjacent in a group, and a group's first piece
holds the last rows of the group before) is CARRIED in VMEM, not written
by rows: a row slice below a sublane tile is no DMA. Each tile adds its
rows to the piece's carry slot; the last tile that holds a row of it
(``last[b]``, per piece in scalar memory beside ``first[b]``) adds the
carry to its staged piece and writes the piece whole, so every piece
leaves by exactly one DMA. Tiles are visited in order and a group's
tokens ascend, so at most one piece inside a group is open at a time
(slot e) beside the one that holds the group's end (slot E + e):
``[2 E, piece, H]`` an output.

**The rows past the groups** are written zero by the kernel itself (whole
stages, then pieces, from a zero buffer; started at the first step and
awaited at the last): every consumer selects them away
(``grouped_swiglu`` and the rows-side kernel by the groups' offsets, the
weights-side kernel both sides of a product, ``routed_combine`` by
``meta``), but the ragged products that run where those kernels cannot
are XLA's to lower, so nothing is left to what a buffer held before.
``name="routed_spread"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry
from .routed_combine import VMEM_LIMIT, _plan, _tiles

KERNEL_NAME = "routed_spread"
# sorted rows a call for each (token tile, expert), in pieces, from which
# the kernel runs. Every run ends inside a piece, and a piece costs its
# copies and its carry whatever it holds: at runs of 4-5 pieces (Mellum:
# 40,960 rows of 64 tiles x 16 experts) the kernel takes 1.01 ms where the
# gather takes 1.58, and 1.82 where the weighted form's three passes take
# 3.52; at runs of 1-2 pieces every piece is staged by two or three tiles
# and XLA's gather, which costs by the row, is as fast or faster (0.38
# against 0.35 ms at Trinity's 4,096 bucket, 0.41 / 0.34 at Kimi's, 2.66 /
# 2.33 and 0.76 / 0.28 at Qwen3-Next's 16,384 and 4,096: my chip run, PR 50)
RUN_PIECES = 4


def stock_routed_spread(src, rows, w, dtype, weighted):
    """src [T, H], rows int32 [n], w [n] -> xs [n, H], or (dy, dy * w),
    in `dtype`: XLA's gather and its rounding passes."""
    if not weighted:
        return src[rows].astype(dtype)
    dy = jnp.where(w[:, None] > 0, src[rows], 0.0)
    return dy.astype(dtype), (dy * w[:, None]).astype(dtype)


def _parts(src_dtype, dtype, weighted):
    """bfloat16 parts a source value goes through the product as: 1 for
    what is bfloat16 when it is selected, 3 for a float32 kept whole."""
    selected = jnp.dtype(src_dtype if weighted else dtype)
    return 1 if selected == jnp.bfloat16 else 3


def _tiling(src_dtype, dtype, weighted, t, n, e, h):
    """The combine's (tile, piece, stage, lanes) for n sorted rows of t
    tokens in e groups at width H, or None where the gather stays: the
    combine's own refusals (``_tiles``: a decode step's rows), runs
    shorter than ``RUN_PIECES`` pieces a (token tile, expert), or a
    kernel over its VMEM: the source tile's two buffers and its parts,
    the staged rows' two halves, the carried pieces, the zero buffer."""
    tiles = _tiles(t, n, h)
    if tiles is None:
        return None
    tile, piece, stage, _lanes = tiles
    outs, size = 1 + weighted, jnp.dtype(dtype).itemsize
    held = (2 * tile * h * jnp.dtype(src_dtype).itemsize
            + _parts(src_dtype, dtype, weighted) * tile * h * 2
            + (outs * (2 * stage + 2 * e * piece) + stage) * h * size)
    if n < RUN_PIECES * piece * (t // tile) * e \
            or held > VMEM_LIMIT * 3 // 4:
        return None
    return tiles


def _pieces(rows, sizes, tile, piece):
    """Of each piece of ``piece`` sorted rows: the first and the last
    token tile that holds a row of it and its carry slot, int32
    [n / piece]; the tokens with -1 past the groups [n]; the first piece
    wholly past the groups [1]."""
    n, e = rows.shape[0], sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    p = jnp.arange(n, dtype=jnp.int32)
    tok = jnp.where(p < ends[-1], rows, -1)
    of = (tok // tile).reshape(-1, piece)             # -1 past the groups
    last = jnp.max(of, axis=1)
    first = jnp.min(jnp.where(of < 0, last[:, None], of), axis=1)

    def group(q):
        return jnp.sum(q[:, None] >= ends[None, :], axis=1).astype(jnp.int32)

    head = p[::piece]
    g0, g1 = group(head), group(head + piece - 1)
    slot = jnp.minimum(g0, e - 1) + e * (g0 != g1)
    return first, last, slot.astype(jnp.int32), tok, \
        (-(-ends[-1] // piece)).reshape(1)


def _kernel(tid_ref, count_ref, lims_ref, block_ref, first_ref, last_ref,
            slot_ref, src_ref, meta_ref, *refs, tile, piece, slots, lanes,
            weighted):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = 1 + weighted
    outs, stages, carries = refs[:k], refs[k:2 * k], refs[2 * k:3 * k]
    parts_ref, mstage_ref, zero_ref, msem, osem, zsem, sent_ref = refs[3 * k:]
    s = pl.program_id(0)
    total, tail = lims_ref[0], lims_ref[1]
    stage, h = zero_ref.shape
    n, dt = outs[0].shape[0], outs[0].dtype
    f32, bf16 = jnp.float32, jnp.bfloat16
    half = jax.lax.rem(s, 2)
    t = tid_ref[s]

    def rows_at(i, size=piece):
        return pl.ds(pl.multiple_of(i * piece, piece), size)

    def each_meta(step, act):
        """``act`` on the copy of every piece's `meta` rows of `step`,
        into the half of the staging buffer that is the step's."""
        hf = jax.lax.rem(step, 2)

        def one(q, _):
            act(pltpu.make_async_copy(
                meta_ref.at[rows_at(block_ref[step * slots + q])],
                mstage_ref.at[hf, rows_at(q)], msem.at[hf]))
            return 0

        jax.lax.fori_loop(0, count_ref[step], one, 0)

    def each_zero(act):
        """``act`` on the copies of zeros over the rows past the groups:
        whole stages (all n rows where they are fewer), then the pieces
        left."""
        most = min(slots, n // piece)
        left = n // piece - tail
        whole = jax.lax.div(left, most)

        def some(size, at):
            def one(i, _):
                for out in outs:
                    act(pltpu.make_async_copy(
                        zero_ref.at[pl.ds(0, size * piece)],
                        out.at[rows_at(at(i), size * piece)], zsem.at[0]))
                return 0
            return one

        jax.lax.fori_loop(0, whole, some(most, lambda i: tail + i * most), 0)
        jax.lax.fori_loop(0, left - whole * most,
                          some(1, lambda i: tail + whole * most + i), 0)

    def drain(hf):
        """Waits for the pieces a step wrote from half `hf`."""
        def one(i, _):
            for j in range(k):
                pltpu.make_async_copy(
                    stages[j].at[hf, pl.ds(0, piece)],
                    outs[j].at[pl.ds(0, piece)], osem.at[j, hf]).wait()
            return 0

        jax.lax.fori_loop(0, sent_ref[hf], one, 0)
        sent_ref[hf] = 0

    @pl.when(s == 0)
    def _():
        each_meta(0, lambda cp: cp.start())
        zero_ref[...] = jnp.zeros_like(zero_ref)
        for carry in carries:
            carry[...] = jnp.zeros_like(carry)
        sent_ref[0] = 0
        sent_ref[1] = 0
        each_zero(lambda cp: cp.start())

    @pl.when(s + 1 < total)
    def _():
        each_meta(s + 1, lambda cp: cp.start())

    @pl.when((s == 0) | (tid_ref[jnp.maximum(s - 1, 0)] != t))
    def _():
        for c in range(0, h, lanes):
            y = src_ref[:, c:c + lanes]
            if not weighted:
                y = y.astype(dt)            # rounded, then selected
            if y.dtype == bf16:
                parts_ref[0, :, c:c + lanes] = y
                continue
            y = y.astype(f32)
            for j in range(3):              # float32 as three bfloat16 parts
                part = y.astype(bf16)
                parts_ref[j, :, c:c + lanes] = part
                y = y - part.astype(f32)

    @pl.when(s < total)
    def _():
        each_meta(s, lambda cp: cp.wait())
        drain(half)
        meta = mstage_ref[half]                                # [stage, 128]
        tok, w = meta[:, 0:1] - (t * tile).astype(f32), meta[:, 1:2]
        # 1 where staged row j is token i of the tile; a row past the
        # groups (token -1) or of another tile is no token's
        onehot = (tok == jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                  .astype(f32)).astype(bf16)                   # [stage, tile]
        for c in range(0, h, lanes):
            acc = jnp.zeros((stage, lanes), f32)
            for j in range(parts_ref.shape[0]):
                acc += jnp.dot(onehot, parts_ref[j, :, c:c + lanes],
                               preferred_element_type=f32)
            if weighted:
                acc = jnp.where(w > 0, acc, 0.0)
                stages[1][half, :, c:c + lanes] = (acc * w).astype(dt)
            stages[0][half, :, c:c + lanes] = acc.astype(dt)

        def one(q, sent):
            b = block_ref[s * slots + q]
            at, slot = rows_at(q), slot_ref[b]
            goes_on = last_ref[b] > t

            @pl.when(first_ref[b] < t)
            def _():
                for st, carry in zip(stages, carries):
                    st[half, at, :] = st[half, at, :] + carry[slot]

            @pl.when(goes_on)
            def _():
                for st, carry in zip(stages, carries):
                    carry[slot] = st[half, at, :]

            @pl.when(jnp.logical_not(goes_on))
            def _():
                for j in range(k):
                    pltpu.make_async_copy(
                        stages[j].at[half, at], outs[j].at[rows_at(b)],
                        osem.at[j, half]).start()

            return sent + 1 - goes_on.astype(jnp.int32)

        sent_ref[half] = jax.lax.fori_loop(0, count_ref[s], one, 0)

        @pl.when(s == total - 1)
        def _():
            drain(0)
            drain(1)
            each_zero(lambda cp: cp.wait())


@functools.partial(jax.jit, static_argnames=(
    "dtype", "weighted", "tile", "piece", "stage", "lanes", "interpret"))
def _pallas_routed_spread(src, rows, w, sizes, *, dtype, weighted, tile,
                          piece, stage, lanes, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, h = src.shape
    n, e = rows.shape[0], sizes.shape[0]
    slots, k = stage // piece, 1 + weighted
    tid, count, total, block = _plan(rows, sizes, t, tile, piece, slots)
    first, last, slot, tok, tail = _pieces(rows, sizes, tile, piece)
    meta = jnp.pad(jnp.stack([tok.astype(jnp.float32),
                              w.astype(jnp.float32)], axis=1),
                   ((0, 0), (0, 126)))
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(tid.shape[0],),
        in_specs=[pl.BlockSpec((tile, h), lambda s, tid, *_: (tid[s], 0)),
                  hbm],
        out_specs=[hbm] * k,
        scratch_shapes=[pltpu.VMEM((2, stage, h), dtype)] * k
        + [pltpu.VMEM((2 * e, piece, h), dtype)] * k
        + [pltpu.VMEM((_parts(src.dtype, dtype, weighted), tile, h),
                      jnp.bfloat16),
           pltpu.VMEM((2, stage, 128), jnp.float32),
           pltpu.VMEM((stage, h), dtype),
           pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((k, 2)),
           pltpu.SemaphoreType.DMA((1,)), pltpu.SMEM((2,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, piece=piece, slots=slots,
                          lanes=lanes, weighted=weighted),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, h), dtype)] * k,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME)(
            tid, count, jnp.concatenate([total, tail]), block, first, last,
            slot, src, meta)
    return tuple(out) if weighted else out[0]


def routed_spread(src, rows, w, sizes, dtype, weighted=False):
    """The token-major rows at their sorted places (module docstring):
    src [T, H] float32 or bfloat16, rows int32 [n], w [n], sizes int32
    [E] (sum <= n) -> xs [n, H] in `dtype`, or with `weighted` the pair
    (dy, dy * w). Inside the groups the values are the gather's; past
    them the kernel leaves zeros and the gather rows of `src`. Routed per
    ``kernel_mode()`` and the shape (``_tiling``); every stock fallback
    is counted."""
    from . import kernel_mode

    mode = kernel_mode()
    t, h = src.shape
    dtype = jnp.dtype(dtype)
    floats = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    tiles = None
    if mode == "off":
        reason = "mode_off"
    elif src.dtype not in floats or dtype not in floats:
        reason = "dtype"
    else:
        tiles = _tiling(src.dtype, dtype, weighted, t, rows.shape[0],
                        sizes.shape[0], h)
        reason = None if tiles else "shape"
    if reason is not None:
        telemetry.counter_add("pallas.routed_spread_fallbacks", 1,
                              reason=reason)
        return stock_routed_spread(src, rows, w, dtype, weighted)
    telemetry.counter_add("pallas.routed_spread_dispatches", 1, mode=mode)
    tile, piece, stage, lanes = tiles
    return _pallas_routed_spread(src, rows, w, sizes, dtype=dtype,
                                 weighted=weighted, tile=tile, piece=piece,
                                 stage=stage, lanes=lanes,
                                 interpret=mode == "interpret")
