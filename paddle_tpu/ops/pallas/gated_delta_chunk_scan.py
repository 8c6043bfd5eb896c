"""A whole prompt through the gated delta rule in chunks, one kernel: the
chunk's triangular solve and the scan over chunks in VMEM.

The rule a value head, float32 (ops/linear_attention_ops.py), in its
chunked (WY) form. Inside a chunk of C tokens, with G the running sum of
the log decay g, D[l, s] = exp(G_l - G_s) for s <= l and N = strictly lower
(beta k k^T * D), T = (I + N)^-1:

    v_new = T beta (v - (k exp G) S)
    o = (q exp G) S + (q k^T * D) v_new
    S = exp(G_C) S + (k exp(G_C - G))^T v_new

`stock_gated_delta_chunk_scan` is that in XLA's words (u = T beta v and w =
T beta k exp G apart, v_new = u - w S): a `fori_loop` of C - 1 rows for the
solve and a `lax.scan` over the chunks, six [B, H, chunks, C, ...] float32
arrays written and read back between them. It is the kernel's oracle and
its counted fallback.

**Grid** (batch, key head, steps of `CHUNKS_A_STEP` chunks), the last axis
sequential: a step is the value heads that read one key head (their q and
k are the same rows, repeated by the op: ``heads_per_key``, so ``[k; q]
k^T`` is one product for all of them). The heads' [K, V] states are the
output block, which keeps its place in VMEM along the chunk axis: zeroed
at the first step, read and updated by every chunk, written to HBM once.
Nothing of a chunk but its rows of q, k, v, G and beta comes from HBM, and
nothing but its rows of o goes back: N, T, v_new, the decayed q k^T and
the decayed copies of q and k live and die in VMEM.

**The solve** is forward substitution, column by column: row c of T is
final once the columns before c are eliminated, and ``T[l] -= N[l, c]
T[c]`` for the rows under it is, a tile of 8 rows, an [8, 1] column of N
spread over the lanes against the [1, C] row spread over the sublanes. No
series and no bfloat16 pass: the same solve as the stock form's, which
the keys' overlap cannot break; every product is float32 at 'highest'.

**What bounds a step** (v5e, from the compiled bundles): a matrix unit
takes one float32 row tile of a 'highest' product every 8 cycles and a
permute unit one lane spread every 8, and a chunk-head needs ~336 of the
first and ~300 of the second (the solve's columns of N); the vector slots
are half empty. So a column ([C, 1]: G, beta) is spread ONCE and every use
reads that, w S and q S are one product, T is applied once (to beta (v -
k S), not to beta v and beta k apart), and the solve of chunk c + 1, which
reads no state, is TRACED beside chunk c's products (`_together`): the
scheduler reads ops in the order they are traced and looks only so far
ahead.

**A step that holds padding alone** (g = 0 and beta = 0 at every row: the
tail of a padded bucket) only reads the state, o = q S, which is what the
rule gives there; which steps those are is read from g and beta themselves
before the call (scalar prefetch).

**Layout**: q, k [B, S, H x K] and v, o [B, S, H x V] as the projections
hold them (a head is a lane block); G and beta come twice, tiny: as
columns ([.., S, 2 x heads] a key head: what scales a row) and G as rows
([.., heads x chunks, C]: the subtrahend of D). G is XLA's `cumsum` over
each chunk, as the stock form's.

``name="gated_delta_chunk_scan"``; `pallas.gated_delta_chunk_scan_dispatches`
/ `_fallbacks{reason}` at trace time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "gated_delta_chunk_scan"
# chunks a grid step: chunk c + 1's solve is independent of the carried
# state, so it is traced beside chunk c's products and fills their waits
# (PERF.md, PR 57: 1, 2 and 4 timed)
CHUNKS_A_STEP = 2
SUBLANES = 8
# columns of the solve between two turns of the work traced beside it
SOLVE_STEPS_A_TURN = 8
_HI = jax.lax.Precision.HIGHEST


def stock_gated_delta_chunk_scan(q, k, v, g, beta, chunk):
    """The chunked (WY) form of the rule from a zero state. q, k [B, S, H,
    K], v [B, S, H, V], g and beta [B, S, H] (both 0 where a position is
    padding) -> (o [B, S, H, V], the state after the last position [B, H,
    K, V]). Float32 at 'highest'.

    Inside a chunk, with G the running sum of g, D[l, s] = exp(G_l - G_s)
    for s <= l, and N = strictly lower (beta k k^T * D): the C corrected
    values are T (beta v) and the keys that read the carried state
    T (beta k exp(G)), T = (I + N)^-1, a unit lower-triangular inverse made
    row by row (forward substitution: stable whatever the keys' overlap,
    where the nilpotent series is not)."""
    hi = _HI
    b, s, h, kd = q.shape
    vd = v.shape[-1]
    ln = min(int(chunk), s)
    if s % ln:
        raise ValueError(f"prompt length {s} is no multiple of the chunk "
                         f"{ln}")
    nc = s // ln

    def chunks(x):          # [B, S, H, ...] -> [B, H, nc, ln, ...]
        x = x.reshape((b, nc, ln) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc = jnp.cumsum(chunks(g), axis=-1)              # [B, H, nc, ln], <= 0
    bc = chunks(beta)
    lower = jnp.tril(jnp.ones((ln, ln), bool))
    seg = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    k_beta = kc * bc[..., None]
    n = jnp.einsum("bhclk,bhcsk->bhcls", k_beta, kc, precision=hi) * decay
    n = jnp.where(jnp.tril(jnp.ones((ln, ln), bool), -1), n, 0.0)

    def solve_row(i, t):
        # rows < i of T are final; N[i, j >= i] is 0
        row = jax.lax.dynamic_slice_in_dim(n, i, 1, axis=-2)
        new = jnp.einsum("bhcls,bhcsj->bhclj", row, t, precision=hi)
        old = jax.lax.dynamic_slice_in_dim(t, i, 1, axis=-2)
        return jax.lax.dynamic_update_slice_in_dim(t, old - new, i, axis=-2)

    eye = jnp.broadcast_to(jnp.eye(ln, dtype=jnp.float32), n.shape)
    t = jax.lax.fori_loop(1, ln, solve_row, eye)
    u = jnp.einsum("bhcls,bhcsv->bhclv", t, vc * bc[..., None],
                   precision=hi)
    w = jnp.einsum("bhcls,bhcsk->bhclk", t,
                   k_beta * jnp.exp(gc)[..., None], precision=hi)
    qk = jnp.einsum("bhclk,bhcsk->bhcls", qc, kc, precision=hi) * decay
    q_in = qc * jnp.exp(gc)[..., None]               # reads the carried state
    k_out = kc * jnp.exp(gc[..., -1:] - gc)[..., None]   # decays to the end
    whole = jnp.exp(gc[..., -1])                     # [B, H, nc]

    def carry(state, c):
        u_c, w_c, qk_c, q_c, k_c, whole_c = c
        v_new = u_c - jnp.einsum("bhlk,bhkv->bhlv", w_c, state,
                                 precision=hi)
        o_c = jnp.einsum("bhlk,bhkv->bhlv", q_c, state, precision=hi) \
            + jnp.einsum("bhls,bhsv->bhlv", qk_c, v_new, precision=hi)
        state = state * whole_c[..., None, None] \
            + jnp.einsum("bhlk,bhlv->bhkv", k_c, v_new, precision=hi)
        return state, o_c

    last, o = jax.lax.scan(
        carry, jnp.zeros((b, h, kd, vd), jnp.float32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, qk, q_in, k_out, whole)))
    # [nc, B, H, ln, V] -> [B, S, H, V]
    o = jnp.moveaxis(o, 0, 2)
    return jnp.moveaxis(o, 1, 3).reshape(b, s, h, vd), last


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _together(*turns):
    """Advance the generators in turn until each has ended; what they
    returned. The order ops are traced in is the order the scheduler reads
    them in, and it looks only so far ahead: work that can overlap has to
    be written side by side."""
    ended = [None] * len(turns)
    live = dict(enumerate(turns))
    while live:
        for i, turn in list(live.items()):
            try:
                next(turn)
            except StopIteration as end:
                ended[i] = end.value
                del live[i]
    return ended


def _inverses(ns):
    """(I + n)^-1 of each strictly lower triangular n [C, C] of `ns`, by
    forward substitution (module docstring), the systems side by side: a
    step of one waits on its last, the others fill the wait. A generator:
    a turn is `SOLVE_STEPS_A_TURN` columns."""
    ln = ns[0].shape[0]
    tiles = range(0, ln, SUBLANES)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, ln), 0)
    col8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, ln), 1)
    eye = [(row8 + a == col8).astype(jnp.float32) for a in tiles]
    n_rows = [[n[a:a + SUBLANES] for a in tiles] for n in ns]
    t_rows = [list(eye) for _ in ns]
    for c in range(ln - 1):
        for n_r, t_r in zip(n_rows, t_rows):
            done = t_r[c // SUBLANES][c % SUBLANES:c % SUBLANES + 1]
            # rows <= c of the tile that holds c: zeros in column c
            for i in range((c + 1) // SUBLANES, ln // SUBLANES):
                t_r[i] = t_r[i] - n_r[i][:, c:c + 1] * done
        if c % SOLVE_STEPS_A_TURN == SOLVE_STEPS_A_TURN - 1:
            yield
    return [jnp.concatenate(t_r, axis=0) for t_r in t_rows]


def _local(q_ref, k_ref, cols_ref, rows_ref, c, *, ln, vd, heads):
    """What chunk c's heads need that does not read the carried state (a
    generator, `_inverses`' turns). A column ([C, 1]: G, beta) is spread
    over the lanes ONCE (a lane permute a tile of 8 rows) and every use
    reads that; G's last row comes spread from `rows_ref`."""
    row = jax.lax.broadcasted_iota(jnp.int32, (ln, ln), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ln, ln), 1)
    lower, strict = row >= col, row > col
    kd = q_ref.shape[-1]
    wide = max(ln, kd, vd)
    at = slice(c * ln, (c + 1) * ln)
    q, k = q_ref[at, :], k_ref[at, :]
    both = _dot(jnp.concatenate([k, q], axis=0), k, ((1,), (1,)))
    local = []
    for j in range(heads):
        g = jnp.broadcast_to(cols_ref[at, j:j + 1], (ln, wide))
        beta = jnp.broadcast_to(
            cols_ref[at, heads + j:heads + j + 1], (ln, wide))
        g_row = rows_ref[c * heads + j:c * heads + j + 1, :]       # [1, C]
        # (Mosaic broadcasts one axis at a time; a bare broadcast_to would
        # be folded into the next)
        last = g_row[:, ln - 1:ln] + jnp.zeros((1, wide), jnp.float32)
        decay = jnp.where(
            lower, jnp.exp(jnp.where(lower, g[:, :ln] - g_row, 0.0)), 0.0)
        grown = jnp.exp(g[:, :kd])
        local.append(dict(
            at=at, lanes=slice(j * vd, (j + 1) * vd), head=j,
            beta=beta[:, :vd],
            n=jnp.where(strict, beta[:, :ln] * both[:ln] * decay, 0.0),
            qk=both[ln:] * decay,
            # the decayed k and q read the carried state in one product
            reads=jnp.concatenate([k * grown, q * grown], axis=0),
            k_out=k * jnp.exp(last[:, :kd] - g[:, :kd]),
            whole=jnp.exp(last[:, :vd])))
    yield
    inverses = yield from _inverses([x["n"] for x in local])
    for x, t in zip(local, inverses):
        x["t"] = t
    return local


def _carry(v_ref, o_ref, s_ref, local, ln):
    """A chunk's heads through the carried state (a generator, a turn a
    product): v_new = T beta (v - (k exp G) S), the corrected values (u -
    w S with T taken out of both terms)."""
    states = [s_ref[x["head"]] for x in local]
    reads = [_dot(x["reads"], state) for x, state in zip(local, states)]
    yield
    new = [_dot(x["t"], x["beta"] * (v_ref[x["at"], x["lanes"]] - r[:ln]))
           for x, r in zip(local, reads)]
    yield
    for x, r, v_new in zip(local, reads, new):
        o_ref[x["at"], x["lanes"]] = r[ln:] + _dot(x["qk"], v_new)
    yield
    for x, state, v_new in zip(local, states, new):
        s_ref[x["head"]] = state * x["whole"] + _dot(x["k_out"], v_new,
                                                     ((0,), (0,)))
    yield


def _kernel(still_ref, q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref, s_ref,
            *, chunk, chunks, heads):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    ln, vd = chunk, s_ref.shape[-1]
    still = still_ref[(pl.program_id(0) * pl.num_programs(1)
                       + pl.program_id(1)) * pl.num_programs(2)
                      + pl.program_id(2)]

    def local(c):
        return _local(q_ref, k_ref, cols_ref, rows_ref, c, ln=ln, vd=vd,
                      heads=heads)

    @pl.when(still == 0)
    def _():
        # chunk c through the state beside chunk c + 1's solve
        ready, = _together(local(0))
        for c in range(1, chunks):
            _, ready = _together(_carry(v_ref, o_ref, s_ref, ready, ln),
                                 local(c))
        _together(_carry(v_ref, o_ref, s_ref, ready, ln))

    @pl.when(still != 0)
    def _():
        # g = 0 and beta = 0 at every row (a padded bucket's tail): G = 0,
        # T = I, v_new = 0; the state stands and o = q S, as the rule gives
        for j in range(heads):
            o_ref[:, j * vd:(j + 1) * vd] = _dot(q_ref[...], s_ref[j])


def _chunks_a_step(nc):
    step = CHUNKS_A_STEP
    while nc % step:
        step -= 1
    return step


@functools.partial(jax.jit,
                   static_argnames=("ln", "shared", "step", "interpret"))
def _pallas_gated_delta_chunk_scan(q, k, v, g, beta, *, ln, shared, step,
                                   interpret):
    # jitted: the layers of one program share one trace of the kernel
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, kd = q.shape
    vd = v.shape[-1]
    nk, nc = h // shared, s // ln
    run = step * ln
    # G, the running log decay inside each chunk (XLA's cumsum over a
    # chunk, tokens on the lanes), as rows; G and beta, a key head's value
    # heads side by side, as columns
    total = jnp.cumsum(
        jnp.moveaxis(g, 2, 1).reshape(b, nk, shared, nc, ln), axis=-1)
    rows = total.reshape(b, nk, shared, nc // step, step, ln)
    rows = rows.transpose(0, 1, 3, 4, 2, 5).reshape(
        b, nk, nc // step, step * shared, ln)
    cols = jnp.concatenate(
        [total.reshape(b, nk, shared, s),
         jnp.moveaxis(beta, 2, 1).reshape(b, nk, shared, s)], axis=2)
    cols = jnp.swapaxes(cols, 2, 3)                 # [B, nk, S, 2 shared]
    # grid steps in which nothing decays and nothing is written, by what
    # the rows hold: [B x nk x steps] int32, read before the step's body
    nothing = (g == 0.0) & (beta == 0.0)
    still = jnp.all(nothing.reshape(b, nc // step, run, nk, shared),
                    axis=(2, 4))
    still = jnp.swapaxes(still, 1, 2).reshape(-1).astype(jnp.int32)
    keys = pl.BlockSpec((None, run, kd),
                        lambda i, j, c, _: (i, c, j * shared))
    vals = pl.BlockSpec((None, run, shared * vd),
                        lambda i, j, c, _: (i, c, j))
    o, last = pl.pallas_call(
        functools.partial(_kernel, chunk=ln, chunks=step, heads=shared),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nk, nc // step),
            in_specs=[keys, keys, vals,
                      pl.BlockSpec((None, None, run, 2 * shared),
                                   lambda i, j, c, _: (i, j, c, 0)),
                      pl.BlockSpec((None, None, None, step * shared, ln),
                                   lambda i, j, c, _: (i, j, c, 0, 0))],
            out_specs=[vals, pl.BlockSpec((None, shared, kd, vd),
                                          lambda i, j, c, _: (i, j, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((b, s, h * vd), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, kd, vd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAME)(
            still,
            q.reshape(b, s, h * kd), k.reshape(b, s, h * kd),
            v.reshape(b, s, h * vd), cols, rows)
    return o.reshape(b, s, h, vd), last


def gated_delta_chunk_scan(q, k, v, g, beta, chunk, heads_per_key: int = 1):
    """The chunked rule over whole (padded) prompts from a zero state: q, k
    [B, S, H, K], v [B, S, H, V], g and beta [B, S, H] float32 -> (o [B, S,
    H, V], the state after the last position [B, H, K, V]).
    `heads_per_key` says that each run of that many heads carries the same
    q and k. Routed per ``kernel_mode()``; every stock fallback is counted."""
    from . import kernel_mode

    mode = kernel_mode()
    s, h, kd = q.shape[1:]
    vd = v.shape[-1]
    ln = min(int(chunk), s)
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif s % ln or ln % SUBLANES or h % heads_per_key \
            or any(x.dtype != jnp.float32 for x in (q, k, v, g, beta)):
        reason = "shape"
    elif mode == "tpu" and (kd % 128 or vd % 128):
        # Mosaic lane alignment of a head's block of q, k, v and o
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.gated_delta_chunk_scan_fallbacks", 1,
                              reason=reason)
        return stock_gated_delta_chunk_scan(q, k, v, g, beta, chunk)
    telemetry.counter_add("pallas.gated_delta_chunk_scan_dispatches", 1,
                          mode=mode)
    return _pallas_gated_delta_chunk_scan(
        q, k, v, g, beta, ln=ln, shared=int(heads_per_key),
        step=_chunks_a_step(s // ln),
        interpret=mode == "interpret")
