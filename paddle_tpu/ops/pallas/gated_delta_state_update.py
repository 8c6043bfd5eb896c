"""One decode step of the gated delta rule over per-slot matrix states, in
place.

Every row of a step owns a slot of ``state`` [slots + 1, H, K, V] float32
(serving/kv_cache.py; a padding row names the scratch slot, the last). A
row's step a value head, with S [K, V], q and k [K] (l2-normed, q scaled),
v [V], ``decay = exp(g)`` and ``beta`` scalars, all formed by the op
(ops/linear_attention_ops.py):

    S = decay * S        u = k^T S
    S = S + k (x) (beta * (v - u))        o = q^T S

The state is READ (``k^T S``) before it is written, which a rank-one add
(`ssm_state_update.py`, beside this file) cannot express. The state is the
whole cost: 2 x H x K x V x 4 bytes a row (1.05 MB at 8 x 128 x 128)
against a few KB of everything else, and ~8 vector operations an element:
bound by bytes.

**Grid** (rows, head blocks): a step is `HEADS_A_STEP` heads of one row.
The slot reaches the state's index map through scalar prefetch, so the
pipeline DMAs block (slot, head block) in, and, with the state aliased to
the output (``input_output_aliases``), back to the same place: a head's
[K, V] tile is decayed, reduced against k, updated and reduced against q
while it is in VMEM, read once and written once a token, and nothing else
of the array moves. Live rows own distinct slots; padding rows share the
scratch slot, whose content nobody reads.

**Layout**: K on sublanes and V on lanes, so ``v``, ``u`` and ``o`` are
lane rows as the projections around the op hold them and both reductions
run over sublanes. q and k come as rows [1, K] too; a head's [K, V]
broadcast of each is one transpose of the row stretched over V sublanes,
made once for the ``shared`` value heads that read one key head (their
rows of q and k are the same row, repeated by the op). ``decay`` and
``beta`` are carried as lane rows like ``v``.

``stock_gated_delta_state_update`` is the kernel's oracle and the counted
fallback (``pallas.gated_delta_state_update_dispatches`` / ``_fallbacks``).
``name="gated_delta_state_update"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import telemetry

KERNEL_NAME = "gated_delta_state_update"
# heads of one row a grid step: 8 x [128, 128] float32 are 512 KiB, in and
# out double-buffered 2 MiB of VMEM
HEADS_A_STEP = 8


def stock_gated_delta_state_update(state, slots, q, k, v, decay, beta):
    """state [S1, H, K, V] float32, slots [B], q and k [B, H, K], v
    [B, H, V], decay and beta [B, H] -> (o [B, H, V], state with the rows'
    slots advanced)."""
    hi = jax.lax.Precision.HIGHEST
    s = state[slots].astype(jnp.float32) * decay[:, :, None, None]
    u = jnp.einsum("bhk,bhkv->bhv", k, s, precision=hi)
    delta = beta[:, :, None] * (v - u)
    s = s + k[:, :, :, None] * delta[:, :, None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision=hi)
    return o, state.at[slots].set(s.astype(state.dtype))


def _kernel(slots_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref, s_ref,
            o_ref, out_ref, *, heads, shared):
    del slots_ref       # read by the index maps alone
    kd, vd = s_ref.shape[1], s_ref.shape[2]
    for j in range(heads):
        row = slice(j, j + 1)
        if j % shared == 0:
            # [1, K] -> [K, V]: every lane column the key head's k (q)
            kmat = jnp.broadcast_to(k_ref[row, :], (vd, kd)).T
            qmat = jnp.broadcast_to(q_ref[row, :], (vd, kd)).T
        s = s_ref[j] * decay_ref[row, :]
        u = jnp.sum(s * kmat, axis=0, keepdims=True)            # [1, V]
        s = s + kmat * (beta_ref[row, :] * (v_ref[row, :] - u))
        out_ref[j] = s
        o_ref[row, :] = jnp.sum(s * qmat, axis=0, keepdims=True)


def _pallas_gated_delta_state_update(state, slots, q, k, v, decay, beta,
                                     shared, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, vd = v.shape
    kd = q.shape[2]
    hb = min(HEADS_A_STEP, h)
    keys = pl.BlockSpec((None, hb, kd), lambda i, j, s: (i, j, 0))
    vals = pl.BlockSpec((None, hb, vd), lambda i, j, s: (i, j, 0))
    block = pl.BlockSpec((None, hb, kd, vd), lambda i, j, s: (s[i], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, h // hb),
        in_specs=[keys, keys, vals, vals, vals, block],
        out_specs=[vals, block])
    # scalars a head: carried as lane rows like v
    decay = jnp.broadcast_to(decay[:, :, None], (b, h, vd))
    beta = jnp.broadcast_to(beta[:, :, None], (b, h, vd))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, shared=shared),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, vd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the prefetched slots): the state, in place
        input_output_aliases={6: 1},
        interpret=interpret, name=KERNEL_NAME)(
            slots, q, k, v, decay, beta, state)
    return o, state


def gated_delta_state_update(state, slots, q, k, v, decay, beta,
                             heads_per_key: int = 1):
    """Advance each row's state by one token of the gated delta rule, in
    place at its slot, and give the row's ``o = q^T S``. `heads_per_key`
    says that each run of that many heads carries the same q and k (value
    heads repeated from one key head). Routed per ``kernel_mode()``; every
    stock fallback is counted."""
    from . import kernel_mode

    mode = kernel_mode()
    h, vd = v.shape[1], v.shape[2]
    kd = q.shape[2]
    hb = min(HEADS_A_STEP, h)
    reason = None
    if mode == "off":
        reason = "mode_off"
    elif state.dtype != jnp.float32 or h % hb or hb % heads_per_key:
        reason = "shape"
    elif mode == "tpu" and (kd % 128 or vd % 128 or hb % 8):
        # Mosaic lane / sublane alignment of a head's [K, V] block, of the
        # transposed [V, K] broadcast and of a step's rows of v and o
        reason = "tpu_tiling"
    if reason is not None:
        telemetry.counter_add("pallas.gated_delta_state_update_fallbacks", 1,
                              reason=reason)
        return stock_gated_delta_state_update(state, slots, q, k, v, decay,
                                              beta)
    telemetry.counter_add("pallas.gated_delta_state_update_dispatches", 1,
                          mode=mode)
    return _pallas_gated_delta_state_update(
        state, slots, q, k, v, decay, beta, int(heads_per_key),
        interpret=mode == "interpret")
