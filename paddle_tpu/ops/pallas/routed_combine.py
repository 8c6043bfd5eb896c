"""The routed layer's combine over rows sorted by expert, as one kernel.

``ys [n, H]`` float32 holds what the held experts made of the layer's
pairs sorted by expert (parallel/moe.py ``routed_experts_share``):
``rows[p]`` is the token of sorted row p, ``w[p]`` its weight (0 past
the groups), ``sizes[e]`` the rows of expert e's group. The combine is

    out[tok] = sum of w[p] * ys[p] over the rows p with rows[p] == tok
               and w[p] > 0

in float32: what ``zeros((T, H)).at[rows].add(where(w > 0, ys * w, 0))``
computes (``stock_routed_combine``, the oracle and the counted
fallback), which XLA lowers to a sort, a permuting copy of the updates
and a read-modify-write add, ~110 ns a row whatever the bytes.

**Runs.** The pairs were sorted by a stable argsort of the expert key
over token-major pairs and a token chooses an expert at most once, so
inside a group the tokens ascend strictly: the rows of group e whose
tokens lie in token tile i (``tile`` tokens) are one contiguous run of
the sorted rows. The runs' bounds are a histogram over (expert, tile)
and a cumulative sum (``_plan``, XLA on ints). The sorted rows are cut
into *pieces* of ``piece`` rows (a sublane tile); a tile needs the
pieces its runs touch, each once.

**Grid** over *steps*: a step is up to ``stage / piece`` pieces of one
token tile, a tile takes as many steps as its pieces need (at least one:
an empty tile is written zero) and nothing is dropped at any imbalance.
Which tile, how many pieces and which come by scalar prefetch. A step's
pieces are copied by one DMA each into a staging buffer of ``stage``
rows, beside the same rows of ``meta [n, 128]`` (lane 0 the token, lane
1 the weight); the next step's copies fly under this step's products
(two halves). The output tile ``[tile, H]`` float32 stays in VMEM while
the steps stay on its tile and is written once: no zero-fill pass, no
read-modify-write in HBM.

**The sum** is a one-hot product on the MXU: ``[tile, stage]`` (1 where
staged row j is token tok) times the staged rows scaled by their
weights. The one-hot is exact in bfloat16, the float32 rows go as three
bfloat16 parts (8 + 8 + 8 bits of mantissa), accumulated in float32:
equal to float32 adds to rounding. A staged row counts only if its slot
is in use, its weight is positive and its token lies in the tile, all
read from ``meta``, never from ``ys``: rows past the groups and a
piece's rows of other runs may hold anything, NaN included.
``name="routed_combine"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "routed_combine"
# my chip runs, PR 46, at the four cells' shares (16 to 128 held experts,
# runs of 5 to 32 rows): 256 / 8 / 256 is the fastest or within a tenth
# of it at each; a larger stage multiplies rows that are not the tile's
TOKEN_TILE = 256            # tokens an output tile
PIECE_ROWS = 8              # sorted rows a DMA: a float32 sublane tile
STAGE_ROWS = 256            # staged rows a step
LANE_BLOCKS = (512, 384, 256, 128)  # columns a product, the first that divides H
VMEM_LIMIT = 100 << 20      # v5e: 128 MiB


def stock_routed_combine(ys, rows, w, t):
    """ys [n, H] float32, rows int32 [n] in [0, t), w [n] -> [t, H]
    float32."""
    ys = jnp.where(w[:, None] > 0, ys * w[:, None], 0.0)
    return jnp.zeros((t, ys.shape[1]), jnp.float32).at[rows].add(ys)


def _tiles(t, n, h):
    """(tile, piece, stage, lanes) for n sorted rows of t tokens at width
    H, or None where the kernel cannot tile them: a batch of one tile
    (a decode step's handful of rows) is the scatter-add's."""
    tile, piece, stage = TOKEN_TILE, PIECE_ROWS, STAGE_ROWS
    if t % tile or t < 2 * tile or n % piece or h % 128:
        return None
    # the staging buffer's two halves and the output tile's two buffers
    if 2 * (stage + tile) * h * 4 > VMEM_LIMIT * 3 // 4:
        return None
    lanes = next(b for b in LANE_BLOCKS if h % b == 0)
    return tile, piece, stage, lanes


def _plan(rows, sizes, t, tile, piece, slots):
    """The steps of the grid: int32 arrays of its length (the real steps
    first, the rest repeating the last real one's tile) with each step's
    token tile and count of pieces, the count of real steps [1], and the
    pieces' block numbers (``piece`` rows a block), ``slots`` a step,
    flat."""
    n, e, tiles = rows.shape[0], sizes.shape[0], t // tile
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    # rows of each (expert, token tile): one-hot products, exact in f32
    expert = jnp.sum(jnp.arange(n, dtype=jnp.int32)[:, None]
                     >= ends[None, :], axis=1)        # e past the groups
    hist = jnp.einsum(
        "pe,pt->te", jax.nn.one_hot(expert, e, dtype=jnp.bfloat16),
        jax.nn.one_hot(rows // tile, tiles, dtype=jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    lo = (ends - sizes)[None, :] + jnp.cumsum(hist, axis=0) - hist
    some = hist > 0
    last = jnp.where(some, (lo + hist - 1) // piece, -1)
    # a block two runs of one tile share is staged for the first alone
    taken = jnp.pad(jax.lax.cummax(last, axis=1), ((0, 0), (1, 0)),
                    constant_values=-1)[:, :e]
    first = jnp.maximum(lo // piece, taken + 1)
    blocks = jnp.where(some, jnp.maximum(last - first + 1, 0), 0)
    upto_b = jnp.cumsum(blocks, axis=1)               # [tiles, e]
    pieces = upto_b[:, -1]

    steps = jnp.maximum(-(-pieces // slots), 1)
    upto = jnp.cumsum(steps)
    total = upto[-1]
    # a piece holds a row of its tile that no other piece of the tile
    # holds, and a run of L rows touches at most L / piece + 2 blocks
    most = min(n, n // piece + 2 * min(tiles * e, n))
    length = tiles + -(-most // slots)
    s = jnp.minimum(jnp.arange(length, dtype=jnp.int32), total - 1)
    # by compares and sums, not by index: a gather costs by the element
    tid = jnp.sum(upto[None, :] <= s[:, None], axis=1).astype(jnp.int32)
    here = tid[:, None] == jnp.arange(tiles, dtype=jnp.int32)[None, :]

    def of_tile(a):
        return jnp.sum(jnp.where(here, a[None, :], 0), axis=1)

    at = (s - of_tile(upto - steps)) * slots          # the step's first piece
    count = jnp.clip(of_tile(pieces) - at, 0, slots)
    m = (at[:, None] + jnp.arange(slots, dtype=jnp.int32)[None, :])[..., None]
    upto_s = upto_b[tid][:, None, :]                  # [length, 1, e]
    blocks_s, first_s = blocks[tid][:, None, :], first[tid][:, None, :]
    # piece m of the tile is block m - (the pieces before its run) of the
    # run it falls in, from the run's first block
    block = jnp.sum(jnp.where((m >= upto_s - blocks_s) & (m < upto_s),
                              first_s + m - (upto_s - blocks_s), 0), axis=2)
    block = jnp.clip(block, 0, n // piece - 1)
    return (tid, count.astype(jnp.int32), total.reshape(1),
            block.reshape(-1).astype(jnp.int32))


def _kernel(tid_ref, count_ref, total_ref, block_ref, ys_ref, meta_ref,
            o_ref, stage_ref, mstage_ref, sem, *, tile, piece, slots,
            lanes):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    total = total_ref[0]
    stage, h = stage_ref.shape[1:]
    f32, bf16 = jnp.float32, jnp.bfloat16

    def each_copy(step, act):
        """``act`` on the two copies of every piece of `step`, into the
        half of the staging buffers that is the step's."""
        half = jax.lax.rem(step, 2)

        def one(q, _):
            src = pl.ds(pl.multiple_of(block_ref[step * slots + q] * piece,
                                       piece), piece)
            dst = pl.ds(pl.multiple_of(q * piece, piece), piece)
            act(pltpu.make_async_copy(
                ys_ref.at[src], stage_ref.at[half, dst], sem.at[0, half]))
            act(pltpu.make_async_copy(
                meta_ref.at[src], mstage_ref.at[half, dst],
                sem.at[1, half]))
            return 0

        jax.lax.fori_loop(0, count_ref[step], one, 0)

    @pl.when(s == 0)
    def _():
        each_copy(0, lambda cp: cp.start())

    @pl.when(s + 1 < total)
    def _():
        each_copy(s + 1, lambda cp: cp.start())

    t = tid_ref[s]

    @pl.when((s == 0) | (tid_ref[jnp.maximum(s - 1, 0)] != t))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < total)
    def _():
        each_copy(s, lambda cp: cp.wait())
        half = jax.lax.rem(s, 2)
        base = (t * tile).astype(f32)
        meta = mstage_ref[half]                                # [stage, 128]
        tok, w = meta[:, 0:1] - base, meta[:, 1:2]
        used = jax.lax.broadcasted_iota(jnp.int32, (stage, 1), 0) \
            < count_ref[s] * piece
        mine = used & (w > 0) & (tok >= 0) & (tok < tile)      # [stage, 1]
        # 1 where staged row j is token i of the tile; a row that is
        # not `mine` is zero below, whatever its column says
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
                  .astype(f32) == meta.T[0:1, :] - base).astype(bf16)
        for c in range(0, h, lanes):
            y = jnp.where(mine, stage_ref[half, :, c:c + lanes] * w, 0.0)
            acc = jnp.zeros((tile, lanes), f32)
            for _ in range(3):          # float32 as three bfloat16 parts
                part = y.astype(bf16)
                acc += jnp.dot(onehot, part, preferred_element_type=f32)
                y = y - part.astype(f32)
            o_ref[:, c:c + lanes] += acc


@functools.partial(jax.jit, static_argnames=("t", "tile", "piece", "stage",
                                             "lanes", "interpret"))
def _pallas_routed_combine(ys, rows, w, sizes, *, t, tile, piece, stage,
                           lanes, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h = ys.shape
    slots = stage // piece
    tid, count, total, block = _plan(rows, sizes, t, tile, piece, slots)
    meta = jnp.pad(jnp.stack([rows.astype(jnp.float32),
                              w.astype(jnp.float32)], axis=1),
                   ((0, 0), (0, 126)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(tid.shape[0],),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, h), lambda s, tid, *_: (tid[s], 0)),
        scratch_shapes=[pltpu.VMEM((2, stage, h), jnp.float32),
                        pltpu.VMEM((2, stage, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2))])
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, piece=piece, slots=slots,
                          lanes=lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME)(
            tid, count, total, block, ys, meta)


def routed_combine(ys, rows, w, sizes, t):
    """The combine of the sorted rows into their tokens (module
    docstring): ys [n, H] float32, rows int32 [n], w [n] (a row counts
    where it is positive), sizes int32 [E] -> [t, H] float32. Routed per
    ``kernel_mode()``; every stock fallback is counted."""
    from . import kernel_mode

    mode = kernel_mode()
    n, h = ys.shape
    tiles = None
    if mode == "off":
        reason = "mode_off"
    elif ys.dtype != jnp.float32:
        reason = "dtype"
    else:
        tiles = _tiles(t, n, h)
        reason = None if tiles else "shape"
    if reason is not None:
        telemetry.counter_add("pallas.routed_combine_fallbacks", 1,
                              reason=reason)
        return stock_routed_combine(ys, rows, w, t)
    telemetry.counter_add("pallas.routed_combine_dispatches", 1, mode=mode)
    tile, piece, stage, lanes = tiles
    return _pallas_routed_combine(ys, rows, w, sizes, t=t, tile=tile,
                                  piece=piece, stage=stage, lanes=lanes,
                                  interpret=mode == "interpret")
