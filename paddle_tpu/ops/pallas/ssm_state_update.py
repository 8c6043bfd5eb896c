"""One decode step of the selective state-space recurrence over per-slot
states, in place.

Every row of a step owns a slot of ``state`` [slots + 1, H, N, P] float32
(serving/kv_cache.py; a padding row names the scratch slot, the last). A
row's step a head is

    S = decay * S + B (x) xdt          y = C . S

with ``xdt = dt * x`` [P] and ``decay = exp(dt * A)`` (a scalar) already
formed by the op (ops/ssm_ops.py), B and C [N] those of the head's group.
The state is the whole cost: 2 x H x N x P x 4 bytes a row (4.19 MB read
and as much written at 32 x 256 x 128) against a few KB of everything
else, and ~5 vector operations an element: bound by bytes.

**Grid** (rows, head blocks): a step is `HEADS_A_STEP` heads of one row.
The slot reaches the state's index map through scalar prefetch, so the
pipeline DMAs block (slot, head block) in, and, with the state aliased to
the output (``input_output_aliases``), back to the same place: a state is
read once and written once a token and nothing else of the array moves.
Live rows own distinct slots; padding rows share the scratch slot, whose
content nobody reads.

**Layout**: d_state on sublanes and head_dim on lanes, so ``xdt`` and
``y`` are lane rows as the projections around the op hold them and the
reduction over d_state is over sublanes (vector adds, one sublane
reduce a vreg column). B and C come as rows too; a group's
``[N, P]`` broadcast is one transpose of the row stretched over P
sublanes, made once a step for its heads (a head block lies within one
group).

``stock_ssm_state_update`` is the kernel's oracle and the counted
fallback (``pallas.ssm_state_update_dispatches`` / ``_fallbacks``).
``name="ssm_state_update"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "ssm_state_update"
# heads of one row a grid step: 8 x [256, 128] float32 are 1 MiB, in and
# out double-buffered 4 MiB of VMEM
HEADS_A_STEP = 8


def stock_ssm_state_update(state, slots, xdt, decay, bm, cm):
    """state [S1, H, N, P] float32, slots [B], xdt [B, H, P], decay [B, H],
    bm and cm [B, G, N] -> (y [B, H, P], state with the rows' slots
    advanced)."""
    b, h, p = xdt.shape
    g = bm.shape[1]
    hg = h // g
    s = state[slots].astype(jnp.float32)                    # [B, H, N, P]
    bh = jnp.repeat(bm, hg, axis=1)                         # [B, H, N]
    ch = jnp.repeat(cm, hg, axis=1)
    s = decay[:, :, None, None] * s \
        + bh[:, :, :, None] * xdt[:, :, None, :]
    y = jnp.sum(s * ch[:, :, :, None], axis=2)
    return y, state.at[slots].set(s.astype(state.dtype))


def _kernel(slots_ref, xdt_ref, decay_ref, b_ref, c_ref, s_ref, y_ref,
            o_ref, *, heads, per_group):
    from jax.experimental import pallas as pl

    del slots_ref       # read by the index maps alone
    n, p = s_ref.shape[1], s_ref.shape[2]
    grp = (pl.program_id(1) * heads) // per_group
    # [1, N] -> [N, P]: every lane column the group's B (C)
    bmat = jnp.broadcast_to(b_ref[pl.ds(grp, 1), :], (p, n)).T
    cmat = jnp.broadcast_to(c_ref[pl.ds(grp, 1), :], (p, n)).T
    for j in range(heads):
        row = pl.ds(j, 1)
        s = s_ref[j] * decay_ref[row, :] + bmat * xdt_ref[row, :]
        o_ref[j] = s
        y_ref[row, :] = jnp.sum(s * cmat, axis=0, keepdims=True)


def _pallas_ssm_state_update(state, slots, xdt, decay, bm, cm, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, p = xdt.shape
    g, n = bm.shape[1], bm.shape[2]
    hb = min(HEADS_A_STEP, h // g)
    rows = pl.BlockSpec((None, hb, p), lambda i, j, s: (i, j, 0))
    group = pl.BlockSpec((None, g, n), lambda i, j, s: (i, 0, 0))
    block = pl.BlockSpec((None, hb, n, p), lambda i, j, s: (s[i], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, h // hb),
        in_specs=[rows, rows, group, group, block],
        out_specs=[rows, block])
    # decay is a scalar a head: carried as a lane row like xdt
    decay = jnp.broadcast_to(decay[:, :, None], (b, h, p))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, per_group=h // g),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (after the prefetched slots): the state, in place
        input_output_aliases={5: 1},
        interpret=interpret, name=KERNEL_NAME)(
            slots, xdt, decay, bm, cm, state)
    return y, state


def ssm_state_update(state, slots, xdt, decay, bm, cm):
    """Advance each row's state by one token, in place at its slot, and
    give the row's ``y = C . S``. Routed per ``kernel_mode()``; every
    stock fallback is counted."""
    from . import kernel_mode

    mode = kernel_mode()
    h, p = xdt.shape[1], xdt.shape[2]
    g, n = bm.shape[1], bm.shape[2]
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif state.dtype != jnp.float32 or h % g \
            or (h // g) % min(HEADS_A_STEP, h // g):
        reason = "shape"
    elif mode == "tpu" and (p % 128 or n % 128
                            or min(HEADS_A_STEP, h // g) % 8):
        # Mosaic lane / sublane alignment of a head's [N, P] block, of the
        # transposed [P, N] broadcast and of a step's rows of xdt and y
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.ssm_state_update_fallbacks", 1,
                              reason=reason)
        return stock_ssm_state_update(state, slots, xdt, decay, bm, cm)
    telemetry.counter_add("pallas.ssm_state_update_dispatches", 1,
                          mode=mode)
    return _pallas_ssm_state_update(state, slots, xdt, decay, bm, cm,
                                    interpret=mode == "interpret")
