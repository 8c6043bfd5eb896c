"""The tail of a drafting decode step (serving/decode.py `draft_step`), one
pass over the vocabulary a distribution, q in place by slot.

After its head products a drafting step holds ``logits [2B, V]`` float32
(rows ``2i`` and ``2i + 1``: a slot's two verified positions) and, after the
module, ``draft_logits [B, V]``; by slot it carries ``q``, the distribution
each pending draft was drawn from. The plain form of what follows is
`serving/sampling.py verify_tokens` and `draft_tokens`: the text of the rule,
this module's oracle and its counted fallback. Written with `jnp` it costs a
gather and a scatter of q by slot, a retiling of the logits, three walks of
every row for a softmax, the masses written and summed again, and two
running sums a draw that the chip runs as serial loops: ~1.3 GB of HBM
traffic a step at 64 x 131,072 where the work needs each row once.

**Two kernels**, a grid step a slot, every further pass over VMEM:

* ``draft_verify`` reads the slot's two logits rows and q's row (the slot
  reaches q's index map through scalar prefetch: no gather), and gives the
  one or two tokens of `verify_tokens` and the copy of q it read (zero for
  a row without a draft: what a request that keeps its step outputs is
  shown);
* ``draft_next`` reads the module's row, writes q's row in place at the slot
  (``input_output_aliases``; a padding row names the scratch slot, the
  last) and draws the next draft from it, as `draft_tokens`.

**Layout.** The arrays stay as the head wrote them. A ``[R, V]`` float32
array lies in HBM as tiles of 8 rows x 128 lanes, so the same bytes are a
``[R V / 128, 128]`` array whose row ``8 (c + g V / 128) + r`` is the
``c``-th 128 entries of row ``8 g + r`` (`_tiles`: a bitcast to XLA, no
copy). A block of eight rows (4 MiB) is copied in by hand a block ahead
(`_resident_rows`: one copy takes longer than one slot's work) and serves
its four or eight slots. A strided sublane load (``pl.ds(r + 8 v, 8,
stride=V / 128)``) takes one row's entries out of the block as DENSE vregs,
so a row is worked on with all 8 x 128 lanes and two rows of a tile need no
``reshape``. The stride is chosen so that sublane ``s`` of vreg ``v`` holds
the 128 entries ``c = s C + v`` (``C`` vregs a row): a block of
`sampling.BLOCK` = 1,024 consecutive entries (8 consecutive ``c``) lies in
ONE sublane of 8 consecutive vregs, and the block totals of the two-level
inverse CDF are sums of whole vregs (no reduction a block). q is kept in
that order, ``q_state [slots + 1, 8 C, 128]`` with row ``8 v + s``
(`state_rows` / `vocab_rows` convert), which is the engine's ``spec["q"]``
under either lowering.

**Passes.** The eight sublanes of a strided load lie in one bank of VMEM,
so it is eight loads (the compiler issues them one a sublane): a row is
walked that way ONCE, with its exponentials under the loads, and left
dense. The maxima (and the argmaxes greedy rows deliver) are taken before,
for all eight rows of a block at once, as the tiles lie (dense loads,
`_resident_rows`). Then the dense passes: the sum, ``max(p - q, 0)`` or
``q = e / sum`` with the block totals' partial sums.

**The inverse CDF** keeps `sampling._pick`'s two levels: the block totals
(16 vregs of partial sums, reduced over lanes), their running sum in
vocabulary order (adds of vregs, then a log-step scan over 8 sublanes), the
count below ``u x total``; then the one chosen block of 1,024 by a strided
load, a log-step scan over its lanes and sublanes. No level is a
sequential loop. Sums associate differently from XLA's ``cumsum``: a token
differs from the plain form's only where a uniform lies within float32
summation error of a CDF boundary.

Dispatches and fallbacks: ``pallas.draft_tail_dispatches`` (``kernel=``)
and ``pallas.draft_tail_fallbacks`` (``kernel=``, ``reason=``: ``mode_off``;
``tpu_tiling``: a vocabulary whose padded rows are no multiple of 128 vregs'
worth of lanes is left to the plain form on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import telemetry

VERIFY_KERNEL, DRAFT_KERNEL = "draft_verify", "draft_next"
LANES, SUBLANES = 128, 8
BLOCK = 1024                # serving/sampling.py BLOCK: 8 x 128 lanes
# a row is padded to whole sublanes of whole blocks
ROW_QUANTUM = SUBLANES * BLOCK
VMEM_LIMIT = 64 << 20       # v5e has 128 MiB; a block of 8 rows is 4 MiB


def draft_tail_fingerprint() -> str:
    return f"dt{BLOCK}x{SUBLANES}"


def padded_vocab(vocab: int) -> int:
    return -(-vocab // ROW_QUANTUM) * ROW_QUANTUM


def q_state_shape(slots: int, vocab: int):
    """A row a slot and the scratch slot, each ``[8 C, 128]``."""
    return (slots + 1, padded_vocab(vocab) // LANES, LANES)


def q_state(slots: int, vocab: int):
    """The carried distributions, zero."""
    return jnp.zeros(q_state_shape(slots, vocab), jnp.float32)


def state_rows(q):
    """``[..., V]`` in vocabulary order -> ``[..., 8 C, 128]`` in the
    state's (entries past V are zero)."""
    v = q.shape[-1]
    vp = padded_vocab(v)
    lead = q.shape[:-1]
    xp = jnp if isinstance(q, jax.Array) else np
    q = xp.pad(q, [(0, 0)] * len(lead) + [(0, vp - v)])
    q = q.reshape(lead + (SUBLANES, vp // BLOCK, LANES))
    return xp.swapaxes(q, -3, -2).reshape(lead + (vp // LANES, LANES))


def vocab_rows(rows, vocab: int):
    """The inverse of `state_rows` (numpy or jax arrays)."""
    lead = rows.shape[:-2]
    xp = jnp if isinstance(rows, jax.Array) else np
    c = rows.shape[-2] // SUBLANES
    q = xp.swapaxes(rows.reshape(lead + (c, SUBLANES, LANES)), -3, -2)
    return q.reshape(lead + (c * BLOCK,))[..., :vocab]


# -- the plain form -----------------------------------------------------------
def stock_draft_verify(logits2, state, slot, draft, carried, temperature,
                       uniforms):
    """`verify_tokens` on the state's rows gathered by slot."""
    from ...serving.sampling import verify_tokens

    v = logits2.shape[-1]
    rows = state.at[slot].get(mode="clip")
    two = logits2.reshape(slot.shape[0], 2, v)
    tokens, count = verify_tokens(
        two[:, 0], two[:, 1], vocab_rows(rows, v), draft, carried,
        temperature, uniforms)
    return tokens, count, jnp.where(carried[:, None, None], rows, 0.0)


def stock_draft_next(draft_logits, state, slot, temperature, uniform):
    """`draft_tokens`, q scattered to the state by slot."""
    from ...serving.sampling import draft_tokens

    draft, q = draft_tokens(draft_logits, temperature, uniform)
    return draft, state.at[slot].set(state_rows(q), mode="drop")


# -- the kernels --------------------------------------------------------------
def _tiles(x):
    """``[R, V]`` (R a multiple of 8, V of 128) as ``[R V / 128, 128]`` in
    the order its tiles lie in HBM: a bitcast."""
    r, v = x.shape
    return x.reshape(r // SUBLANES, SUBLANES, v // LANES, LANES).transpose(
        0, 2, 1, 3).reshape(r * v // LANES, LANES)


def _all(op, x):
    """A full reduction kept as a [1, 1] array."""
    return op(op(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _one(x):
    """A scalar as a [1, 1] float32 array."""
    return jnp.full((1, 1), x, jnp.float32)


def _spread(x):
    """A [1, 1] array over a vreg: lanes, then sublanes (Mosaic broadcasts
    one axis at a time, and a select between keeps the two apart)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.broadcast_to(jnp.where(lane >= 0, x, 0), (SUBLANES, LANES))


def _scan(x, axis, exclusive=False):
    """The running sum of ``x [8, 128]`` along ``axis`` by log steps of
    rotations; ``exclusive`` leaves out the entry itself."""
    from jax.experimental.pallas import tpu as pltpu

    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    if exclusive:
        x = jnp.where(at >= 1, pltpu.roll(x, 1, axis), 0.0)
    step = 1
    while step < x.shape[axis]:
        x = x + jnp.where(at >= step, pltpu.roll(x, step, axis), 0.0)
        step *= 2
    return x


def _pick(masses_ref, tot_ref, uniform, *, c, vocab):
    """`sampling._pick` of one row: ``masses_ref [8 C, 128]`` in the state's
    order, ``tot_ref [C, 128]`` its block totals' partial sums (row
    ``8 u + s``: block ``s C / 8 + u``, summed over lanes here), ``uniform``
    a [1, 1] array -> the token, a [1, 1] int32 array."""
    from jax.experimental import pallas as pl

    g = c // SUBLANES
    tot = jnp.sum(tot_ref[...], axis=1, keepdims=True)          # [C, 1]
    # the running sum in vocabulary order: over u inside a sublane (a
    # sublane's blocks are consecutive), then over the sublanes before
    runs, run = [], jnp.zeros((SUBLANES, 1), jnp.float32)
    for u in range(g):
        run = run + tot[SUBLANES * u:SUBLANES * (u + 1)]
        runs.append(run)
    before_s = _scan(jnp.broadcast_to(run, (SUBLANES, LANES)), 0,
                     exclusive=True)[:, :1]                     # [8, 1]
    cdfs = [before_s + run for run in runs]     # [u][s]: block s g + u
    target = uniform * _all(jnp.max, cdfs[-1])
    below = sum((cdf < target).astype(jnp.float32) for cdf in cdfs)
    nb = -(-vocab // BLOCK)
    k = jnp.minimum(_all(jnp.sum, below), nb - 1.0)             # [1, 1]
    # the mass before block k: the largest running sum of a block below it
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, 1), 0)
    before = jnp.zeros((SUBLANES, 1), jnp.float32)
    for u, cdf in enumerate(cdfs):
        block = (sub * g + u).astype(jnp.float32)
        before = jnp.maximum(before, jnp.where(block < k, cdf, 0.0))
    resid = target - _all(jnp.max, before)
    # block k: sublane s of vregs 8 u .. 8 u + 7, eight rows 8 apart
    ki = jnp.sum(k).astype(jnp.int32)
    s, u = ki // g, ki % g
    inner = masses_ref[pl.ds(SUBLANES * SUBLANES * u + s, SUBLANES,
                             stride=SUBLANES), :]               # [8, 128]
    along = _scan(inner, 1)
    chunk = jnp.broadcast_to(along[:, LANES - 1:], (SUBLANES, LANES))
    cdf = along + _scan(chunk, 0, exclusive=True)
    j = _all(jnp.sum, (cdf < resid).astype(jnp.float32))
    token = k * BLOCK + jnp.minimum(j, BLOCK - 1.0)
    return jnp.minimum(token, vocab - 1.0).astype(jnp.int32)


def _at(ref, token, c):
    """The entry of vocabulary index ``token`` (a scalar) of a row held in
    the state's order, a [1, 1] array."""
    from jax.experimental import pallas as pl

    chunk, lane = token // LANES, token % LANES
    row = ref[pl.ds(SUBLANES * (chunk % c) + chunk // c, 1), :]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.sum(jnp.where(at == lane, row, 0.0), axis=1, keepdims=True)


def _resident_rows(lt_hbm, buf_ref, sem, top_ref, i, per, groups):
    """The block of eight logits rows that step ``i`` works in (``per`` grid
    steps a block), by hand: a block is 4 MiB, more than one step's work
    hides, so the copy of the NEXT block starts with the first step of this
    one and runs under all ``per`` of them. With a block's first step its
    rows' maxima are taken, all eight at once as the tiles lie (dense
    loads): ``top_ref[0]`` a lane's maximum a row, ``top_ref[1]`` the
    vocabulary index it was first seen at. -> the block's ref."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = i // per
    block = buf_ref.shape[1]
    resident = buf_ref.at[g % 2]

    def copy(group, to):
        return pltpu.make_async_copy(
            lt_hbm.at[pl.ds(pl.multiple_of(group * block, SUBLANES), block),
                      :], buf_ref.at[to], sem.at[to])

    @pl.when(i == 0)
    def _():
        copy(0, 0).start()

    @pl.when(i % per == 0)
    def _():
        @pl.when(g + 1 < groups)
        def _():
            copy(g + 1, (g + 1) % 2).start()

        copy(g, g % 2).wait()
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, LANES), 1).astype(jnp.float32)

        def highest(u, carry):
            # two running maxima, the even and the odd chunks': half the
            # chain of dependent selects; the chunk's indices ride as a
            # vector (a scalar a chunk, converted and spread, costs more
            # than the comparison)
            tops, ats, here = list(carry[:2]), list(carry[2:4]), carry[4]
            for w in range(SUBLANES):
                chunk = u * SUBLANES + w
                x = resident[pl.ds(pl.multiple_of(SUBLANES * chunk,
                                                  SUBLANES), SUBLANES), :]
                ats[w % 2] = jnp.where(x > tops[w % 2], here, ats[w % 2])
                tops[w % 2] = jnp.maximum(tops[w % 2], x)
                here = here + float(LANES)
            return (*tops, *ats, here)

        low = jnp.full((SUBLANES, LANES), -jnp.inf, jnp.float32)
        even, odd, at_even, at_odd, _ = jax.lax.fori_loop(
            0, block // (SUBLANES * SUBLANES), highest, (low,) * 4 + (lane,))
        top = jnp.maximum(even, odd)
        at = jnp.where(odd > even, at_odd, jnp.where(
            odd == even, jnp.minimum(at_even, at_odd), at_even))
        top_ref[0] = top
        top_ref[1] = at

    return resident


def _row_top(top_ref, r):
    """Row ``r`` of the resident block: (its maximum [1, 1], the lowest
    vocabulary index of it [1, 1] int32: `jnp.argmax`'s choice)."""
    from jax.experimental import pallas as pl

    lanes = top_ref[0, pl.ds(r, 1), :]
    highest = jnp.max(lanes, axis=1, keepdims=True)
    at = jnp.where(lanes == highest, top_ref[1, pl.ds(r, 1), :], jnp.inf)
    return highest, jnp.min(at, axis=1, keepdims=True).astype(jnp.int32)


def _verify_kernel(slot_ref, draft_ref, carried_ref, sampled_ref, invt_ref,
                   ua_ref, ur_ref, us_ref, lt_hbm, q_ref, out_ref, kept_ref,
                   ea_ref, eb_ref, ra_ref, ta_ref, tb_ref, buf_ref, sem,
                   top_ref, *, c, vocab, groups):
    from jax.experimental import pallas as pl

    del slot_ref        # read by the index maps alone
    i = pl.program_id(0)
    lt_ref = _resident_rows(lt_hbm, buf_ref, sem, top_ref, i, SUBLANES // 2,
                            groups)
    first = (2 * i) % SUBLANES      # the pair's rows in the block of eight
    g = c // SUBLANES
    d = draft_ref[i]
    carried = carried_ref[i] > 0
    ma, best_a = _row_top(top_ref, first)
    mb, best_b = _row_top(top_ref, first + 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def row(r, v):      # dense vreg v of logits row r of the block
        return lt_ref[pl.ds(r + SUBLANES * v, SUBLANES,
                            stride=SUBLANES * c), :]

    def vreg(v):
        return pl.ds(pl.multiple_of(SUBLANES * v, SUBLANES), SUBLANES)

    def read_q(v):      # what the acceptance reads of the carried state
        q = jnp.where(carried, q_ref[vreg(v), :], 0.0)
        kept_ref[vreg(v), :] = q
        return q

    def give(one, two, count):
        out_ref[...] = jnp.where(sub == 0, _spread(one), jnp.where(
            sub == 1, _spread(two), _spread(count))).astype(jnp.int32)

    @pl.when(sampled_ref[i] > 0)
    def _():
        inv_t = invt_ref[i]

        def masses(u, carry):
            # the one strided walk of the two rows (a strided load is eight
            # loads, one a sublane: all eight lie in one bank), with the
            # exponentials under it; the masses are left dense
            sa = carry
            tb = jnp.zeros((SUBLANES, LANES), jnp.float32)
            for w in range(SUBLANES):
                v = u * SUBLANES + w
                ea = jnp.exp((row(first, v) - ma) * inv_t)
                eb = jnp.exp((row(first + 1, v) - mb) * inv_t)
                ea_ref[vreg(v), :] = ea
                eb_ref[vreg(v), :] = eb
                sa, tb = sa + ea, tb + eb
            tb_ref[vreg(u), :] = tb
            return sa

        sa = jax.lax.fori_loop(0, g, masses,
                               jnp.zeros((SUBLANES, LANES), jnp.float32))
        inv_s = 1.0 / _all(jnp.sum, sa)

        def residual(u, carry):
            ta = jnp.zeros((SUBLANES, LANES), jnp.float32)
            for w in range(SUBLANES):
                v = u * SUBLANES + w
                r = jnp.maximum(ea_ref[vreg(v), :] * inv_s - read_q(v), 0.0)
                ra_ref[vreg(v), :] = r
                ta = ta + r
            ta_ref[vreg(u), :] = ta
            return carry

        jax.lax.fori_loop(0, g, residual, 0)
        p_d = _at(ea_ref, d, c) * inv_s
        q_d = _at(kept_ref, d, c)
        accept = jnp.logical_and(carried, ua_ref[i] * q_d < p_d)
        redrawn = _pick(ra_ref, ta_ref, _one(ur_ref[i]), c=c, vocab=vocab)
        second = _pick(eb_ref, tb_ref, _one(us_ref[i]), c=c, vocab=vocab)
        give(jnp.where(accept, d, redrawn), second,
             1 + accept.astype(jnp.int32))

    @pl.when(sampled_ref[i] <= 0)
    def _():
        def copy(u, carry):
            for w in range(SUBLANES):
                read_q(u * SUBLANES + w)
            return carry

        jax.lax.fori_loop(0, g, copy, 0)
        accept = jnp.logical_and(carried, best_a == d)
        give(best_a, best_b, 1 + accept.astype(jnp.int32))


def _draft_kernel(slot_ref, sampled_ref, invt_ref, u_ref, lt_hbm, _state,
                  out_ref, q_ref, t_ref, buf_ref, sem, top_ref, *, c, vocab,
                  groups):
    from jax.experimental import pallas as pl

    del slot_ref, _state
    i = pl.program_id(0)
    lt_ref = _resident_rows(lt_hbm, buf_ref, sem, top_ref, i, SUBLANES,
                            groups)
    r = i % SUBLANES
    g = c // SUBLANES
    inv_t = invt_ref[i]
    m, best = _row_top(top_ref, r)

    def vreg(v):
        return pl.ds(pl.multiple_of(SUBLANES * v, SUBLANES), SUBLANES)

    def masses(u, s):
        # the one strided walk of the row, the exponentials under it
        for w in range(SUBLANES):
            v = u * SUBLANES + w
            e = jnp.exp((lt_ref[pl.ds(r + SUBLANES * v, SUBLANES,
                                      stride=SUBLANES * c), :] - m) * inv_t)
            q_ref[vreg(v), :] = e
            s = s + e
        return s

    s = jax.lax.fori_loop(0, g, masses,
                          jnp.zeros((SUBLANES, LANES), jnp.float32))
    inv_s = 1.0 / _all(jnp.sum, s)

    def normalise(u, carry):
        t = jnp.zeros((SUBLANES, LANES), jnp.float32)
        for w in range(SUBLANES):
            v = u * SUBLANES + w
            q = q_ref[vreg(v), :] * inv_s
            q_ref[vreg(v), :] = q
            t = t + q
        t_ref[vreg(u), :] = t
        return carry

    jax.lax.fori_loop(0, g, normalise, 0)

    @pl.when(sampled_ref[i] > 0)
    def _():
        out_ref[...] = _spread(
            _pick(q_ref, t_ref, _one(u_ref[i]), c=c, vocab=vocab))

    @pl.when(sampled_ref[i] <= 0)
    def _():
        out_ref[...] = _spread(best)


def _rows_of_eight(x, vocab_to):
    """Rows padded to a multiple of 8 and the vocabulary to `vocab_to` (with
    -inf: no mass, never the maximum), as `_tiles` wants them; no copy where
    the array has that shape already."""
    r, v = x.shape
    pad_r, pad_v = -r % SUBLANES, vocab_to - v
    if pad_r or pad_v:
        x = jnp.pad(x, ((0, pad_r), (0, pad_v)), constant_values=-jnp.inf)
    return _tiles(x)


def _block_buffers(rows):
    """Two blocks of eight logits rows, their copies' semaphores, and the
    resident block's rows' maxima (`_resident_rows`)."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((2, SUBLANES * rows, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((2, SUBLANES, LANES), jnp.float32)]


def _cost(b, rows, arrays, exps):
    """What a kernel moves and computes over ``b`` slots: ``arrays`` rows
    of HBM traffic a slot, an exponential an entry of ``exps`` of them."""
    from jax.experimental import pallas as pl

    entries = b * rows * LANES
    return pl.CostEstimate(flops=8 * arrays * entries,
                           transcendentals=exps * entries,
                           bytes_accessed=4 * arrays * entries)


def _sampling_scalars(temperature):
    sampled = temperature > 0
    return sampled.astype(jnp.int32), \
        1.0 / jnp.where(sampled, temperature, 1.0)


def _pallas_draft_verify(logits2, state, slot, draft, carried, temperature,
                         uniforms, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, vocab = slot.shape[0], logits2.shape[1]
    rows = state.shape[1]
    c = rows // SUBLANES
    sampled, inv_t = _sampling_scalars(temperature)
    by_slot = pl.BlockSpec((None, rows, LANES),
                           lambda i, slot, *_: (slot[i], 0, 0))
    by_row = pl.BlockSpec((None, rows, LANES), lambda i, *_: (i, 0, 0))
    out = pl.BlockSpec((None, SUBLANES, LANES), lambda i, *_: (i, 0, 0))
    logits = _rows_of_eight(logits2, rows * LANES)
    scratch = [pltpu.VMEM((rows, LANES), jnp.float32)] * 3 \
        + [pltpu.VMEM((c, LANES), jnp.float32)] * 2 + _block_buffers(rows)
    out, kept = pl.pallas_call(
        functools.partial(_verify_kernel, c=c, vocab=vocab,
                          groups=logits.shape[0] // (SUBLANES * rows)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8, grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), by_slot],
            out_specs=[out, by_row], scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((b, SUBLANES, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((b, rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        # two logits rows and q's read, the copy of q written, a slot
        cost_estimate=_cost(b, rows, arrays=4, exps=2),
        interpret=interpret, name=VERIFY_KERNEL)(
            slot, draft, carried.astype(jnp.int32), sampled, inv_t,
            uniforms[:, 0], uniforms[:, 1], uniforms[:, 2], logits, state)
    return out[:, :2, 0], out[:, 2, 0], kept


def _pallas_draft_next(draft_logits, state, slot, temperature, uniform,
                       interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, vocab = draft_logits.shape
    rows = state.shape[1]
    c = rows // SUBLANES
    sampled, inv_t = _sampling_scalars(temperature)
    by_slot = pl.BlockSpec((None, rows, LANES),
                           lambda i, slot, *_: (slot[i], 0, 0))
    out = pl.BlockSpec((None, SUBLANES, LANES), lambda i, *_: (i, 0, 0))
    logits = _rows_of_eight(draft_logits, rows * LANES)
    out, state = pl.pallas_call(
        functools.partial(_draft_kernel, c=c, vocab=vocab,
                          groups=logits.shape[0] // (SUBLANES * rows)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=[out, by_slot],
            scratch_shapes=[pltpu.VMEM((c, LANES), jnp.float32)]
            + _block_buffers(rows)),
        out_shape=[jax.ShapeDtypeStruct((b, SUBLANES, LANES), jnp.int32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (after the four prefetched): the state, in place; only
        # the rows' slots are written
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        # the module's row read and q's written, a slot
        cost_estimate=_cost(b, rows, arrays=2, exps=1),
        interpret=interpret, name=DRAFT_KERNEL)(
            slot, sampled, inv_t, uniform, logits, state)
    return out[:, 0, 0], state


def _route(kernel, state):
    from . import kernel_mode

    mode = kernel_mode()
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif mode == "tpu" and (state.shape[1] // SUBLANES) % LANES:
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.draft_tail_fallbacks", 1,
                              kernel=kernel, reason=reason)
        return None
    telemetry.counter_add("pallas.draft_tail_dispatches", 1, kernel=kernel,
                          mode=mode)
    return mode


def draft_verify(logits2, state, slot, draft, carried, temperature,
                 uniforms):
    """`sampling.verify_tokens` of a drafting step's rows: ``logits2 [2B,
    V]`` as the head wrote them (rows 2i, 2i + 1 a slot's two positions),
    ``state`` = `q_state` read at ``slot [B]``, ``draft [B]``, ``carried
    [B]`` bool, ``temperature [B]``, ``uniforms [B, 3]`` -> ``(tokens [B,
    2], count [B], kept [B, 8 C, 128])``, ``kept`` the rows of q the rule
    read (zero without a draft), in the state's order."""
    mode = _route(VERIFY_KERNEL, state)
    if mode is None:
        return stock_draft_verify(logits2, state, slot, draft, carried,
                                  temperature, uniforms)
    return _pallas_draft_verify(logits2, state, slot, draft, carried,
                                temperature, uniforms,
                                interpret=mode == "interpret")


def draft_next(draft_logits, state, slot, temperature, uniform):
    """`sampling.draft_tokens` with q written to ``state`` in place at
    ``slot``: -> ``(draft [B], state)``."""
    mode = _route(DRAFT_KERNEL, state)
    if mode is None:
        return stock_draft_next(draft_logits, state, slot, temperature,
                                uniform)
    return _pallas_draft_next(draft_logits, state, slot, temperature,
                              uniform, interpret=mode == "interpret")
