"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

Greenfield capability (SURVEY.md §2.7: EP is absent from the reference —
its sparse story is parameter servers). TPU-native design, the
Switch/GShard recipe: top-1 gating with capacity, dense one-hot dispatch
(einsum-shaped for the MXU), experts sharded over 'ep', and
`lax.all_to_all` carrying token slots to their expert's rank and back over
ICI. Reverse AD flows through (all_to_all transposes to all_to_all).

Outside an SPMD region every expert lives on the one device and the
all_to_alls drop out — same math, no comm.
"""

from __future__ import annotations

import numpy as np

from ..ops.collective_ops import _in_spmd


def switch_moe(x, gate_w, w1, b1, w2, b2, capacity_factor: float = 1.25,
               axis_name: str = "ep", activation: str = "gelu",
               tokens_sharded: bool = False):
    """Top-1 (Switch) MoE FFN.

    x       [T, H]   tokens (flattened batch)
    gate_w  [H, E]   router (replicated)
    w1      [E_local, H, F], b1 [E_local, F]   this rank's expert shard
    w2      [E_local, F, H], b2 [E_local, H]
    Returns ([T, H] combined output, aux_loss scalar) — aux_loss is the
    Switch load-balancing loss (mean_prob · fraction_routed · E).

    tokens_sharded=False: tokens are REPLICATED over 'ep' (each rank sees
    all T tokens, computes its expert shard, all_gathers results).
    tokens_sharded=True: x is THIS RANK's token shard [T_local, H] (the
    batch is data-parallel over the same 'ep' axis — the GShard dp x ep
    composition); token slots travel to their expert's rank and back via
    two lax.all_to_all collectives. Capacity is per (expert, source
    rank): C = ceil(T_local / E * capacity_factor).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    t, h = x.shape
    e_local = w1.shape[0]
    spmd = _in_spmd(axis_name)
    ep = lax.axis_size(axis_name) if spmd else 1  # see pipeline_ops._check_ring note
    e = e_local * ep

    xf = x.astype(jnp.float32)
    logits = xf @ gate_w.astype(jnp.float32)           # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)            # [T]
    gate = jnp.max(probs, axis=-1)                     # [T]

    cap = int(np.ceil(t / e * capacity_factor))
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # [T, E]
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [T, E]
    keep = (pos >= 0) & (pos < cap)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                            dtype=jnp.float32) * keep[..., None]
    dispatch = onehot[..., None] * pos_oh                       # [T, E, C]
    combine = dispatch * gate[:, None, None]

    # aux load-balancing loss (Switch Transformer eq. 4)
    frac_routed = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_routed * mean_prob) * e

    act = jax.nn.gelu if activation == "gelu" else getattr(jax.nn, activation)

    def experts(exp_in):
        """[E_local, K, H] queues -> expert FFN -> [E_local, K, H]."""
        hmid = act(jnp.einsum("ekh,ehf->ekf", exp_in,
                              w1.astype(jnp.float32))
                   + b1[:, None, :].astype(jnp.float32))
        return jnp.einsum("ekf,efh->ekh", hmid, w2.astype(jnp.float32)) \
            + b2[:, None, :].astype(jnp.float32)

    if spmd and tokens_sharded:
        # GShard all_to_all dispatch: x here is THIS RANK's token shard
        # ([T_local, H]); each rank builds per-expert queues from its own
        # tokens, all_to_all rotates the expert-group axis so rank j
        # receives every rank's queues for ITS experts, the FFN runs on
        # the [E_local, ep*C] slots, and the reverse all_to_all carries
        # results home. Two collectives, both riding ICI; grads flow
        # (all_to_all transposes to all_to_all).
        exp_in = jnp.einsum("tec,th->ech", dispatch, xf)    # [E, C, H]
        # tiled a2a: dim0 (ep*E_l) splits into ep chunks of E_l, received
        # chunks concat along the slot dim -> [E_l, ep*C, H]. (The
        # non-tiled form's transpose is broken in this jax version, and
        # tiled is the natural layout here anyway.)
        exp_in = lax.all_to_all(exp_in, axis_name, split_axis=0,
                                concat_axis=1, tiled=True)  # [E_l, ep*C, H]
        exp_out = experts(exp_in)                           # [E_l, ep*C, H]
        exp_out = lax.all_to_all(exp_out, axis_name, split_axis=1,
                                 concat_axis=0, tiled=True)  # [E, C, H]
        out = jnp.einsum("tec,ech->th", combine, exp_out)
        # aux is a per-shard statistic; average it over the shards so every
        # rank adds the same scalar to its loss
        aux = lax.pmean(aux, axis_name)
    elif spmd:
        # tokens (and hence the dispatch tensor) are replicated over 'ep',
        # so each rank SLICES its own experts' queues BEFORE the dispatch
        # einsum (slicing after would burn ep-times the MXU work) and the
        # results all_gather back — one collective.
        idx = lax.axis_index(axis_name)
        disp_local = lax.dynamic_index_in_dim(
            dispatch.reshape(t, ep, e_local, cap), idx, axis=1,
            keepdims=False)                                 # [T,E_l,C]
        exp_in = jnp.einsum("tec,th->ech", disp_local, xf)  # [E_l,C,H]
        exp_out = lax.all_gather(experts(exp_in),
                                 axis_name).reshape(e, cap, h)
        out = jnp.einsum("tec,ech->th", combine, exp_out)
    else:
        exp_in = jnp.einsum("tec,th->ech", dispatch, xf)    # [E, C, H]
        exp_out = experts(exp_in)
        out = jnp.einsum("tec,ech->th", combine, exp_out)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def routed_experts_share(x, router_w, select_bias, w1, w3, w2, *,
                         top_k: int, held_lo: int, route_scale: float = 1.0,
                         route_norm: bool = True, live=None):
    """One chip's share of a dropless top-k routed expert layer with
    sigmoid scores (the serving form; `switch_moe` above is the trained
    top-1 layer with a capacity).

    x           [T, H]   tokens (any float dtype)
    router_w    [H, E]   the router over ALL E experts, as published
    select_bias [E]      added to the scores for SELECTION only
    w1, w3      [E_held, H, F]   gate and up projections of the experts
    w2          [E_held, F, H]   held here: experts held_lo .. held_lo+E_held
    live        [T] bool, optional: rows that carry a token. An engine's
                empty slots and a padded prompt's tail are rows too; their
                pairs join no expert's group (a pad's thousand copies of
                one token would all land on the same four experts), are
                not counted, and their output is zero

    Every token scores all E experts in float32, keeps the ``top_k``
    largest of ``score + select_bias`` and weighs them by
    ``score / (sum of the kept scores + 1e-20) * route_scale`` (no
    renormalisation when ``route_norm`` is false). This chip then computes,
    without dropping a pair, ``sum_i w_i * Expert_i(x)`` over the kept
    pairs whose expert it holds: the pairs are sorted by expert and the
    held experts' SwiGLUs run over the sorted rows as one grouped kernel
    (``ops/pallas/grouped_swiglu.py``: a stream of the hit experts' weight
    blocks with the three products under each; where ``kernel_mode()`` is
    off or the shape cannot be tiled, three ``jax.lax.ragged_dot``,
    counted), so an expert no token chose costs no weight read. It runs
    over the leading rows that hold the held pairs when those are few, as
    they nearly always are, and otherwise over that many sorted rows at a
    time, as far as the held pairs reach (one ``lax.cond``). What the absent experts would
    have added is left out; nothing stands in for their chips or the exchange.

    Returns (out [T, H] float32, counts int32 [3]): the kept pairs of live
    rows, those of them on held experts, and the held experts with at
    least one live pair."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.grouped_swiglu import grouped_swiglu

    t, h = x.shape
    e_held = w1.shape[0]
    xf = x.astype(jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(
        xf, router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))                    # [T, E]
    _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    kept = jnp.take_along_axis(scores, idx, axis=1)              # [T, k]
    weight = kept
    if route_norm:
        weight = kept / (jnp.sum(kept, axis=1, keepdims=True) + 1e-20)
    weight = weight * route_scale
    local = idx - held_lo
    alive = jnp.ones((t,), bool) if live is None \
        else live.reshape(-1).astype(bool)
    held = (local >= 0) & (local < e_held) & alive[:, None]
    # pairs sorted by held expert; pairs of absent experts and of dead
    # rows sort behind every group and lie outside the groups' rows
    key = jnp.where(held, local, e_held).reshape(-1)             # [T*k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, e_held + 1, dtype=jnp.int32),
                    axis=0)[:e_held]
    rows = (order // top_k).astype(jnp.int32)
    w_sorted = jnp.where(held, weight, 0.0).reshape(-1)[order]

    def experts(r, w, sizes):
        """The held experts over sorted pairs: their tokens `r`, their
        weights `w`, `sizes` of them in each expert's group."""
        xs = x[r].astype(w1.dtype)                               # [n, H]
        ys = grouped_swiglu(xs, w1, w3, w2, sizes)               # [n, H]
        # rows past the groups hold nothing of a held expert: weight 0,
        # and a `where` so that whatever the grouped product left there
        # stays out
        ys = jnp.where(w[:, None] > 0, ys * w[:, None], 0.0)
        return jnp.zeros((t, h), jnp.float32).at[r].add(ys)

    # the pairs on held experts sort first, and with evenly spread routing
    # they are e_held / E of all pairs. Twice that share (and a margin)
    # of the sorted rows holds them nearly always, and the grouped
    # kernel, the gather and the scatter then run over that many rows
    # (the kernel's time is its hit experts' weights whatever the rows,
    # PR 41; the gather and the scatter-add grow with them); when more
    # pairs land here than that, the sorted
    # rows are processed that many at a time, as far as the held pairs
    # reach: no pair is ever dropped, and a prompt's every pair is never
    # held at once (pairs x H floats three times over: 3 GB at 6144
    # tokens, top-8, H 7168).
    pairs = t * top_k
    few = -(-(2 * pairs * e_held // router_w.shape[1] + 32) // 64) * 64

    def every():
        pad = -pairs % few
        rows_p, w_p = jnp.pad(rows, (0, pad)), jnp.pad(w_sorted, (0, pad))
        ends = jnp.cumsum(sizes)

        def some(i, out):
            lo = i * few
            part = jnp.clip(ends, lo, lo + few) \
                - jnp.clip(ends - sizes, lo, lo + few)
            return out + experts(
                jax.lax.dynamic_slice_in_dim(rows_p, lo, few),
                jax.lax.dynamic_slice_in_dim(w_p, lo, few), part)

        return jax.lax.fori_loop(0, (ends[-1] + few - 1) // few, some,
                                 jnp.zeros((t, h), jnp.float32))

    if few < pairs:
        out = jax.lax.cond(
            jnp.sum(sizes) <= few,
            lambda: experts(rows[:few], w_sorted[:few], sizes), every)
    else:
        out = experts(rows, w_sorted, sizes)
    counts = jnp.stack([jnp.sum(alive) * top_k, jnp.sum(sizes),
                        jnp.sum(sizes > 0)]).astype(jnp.int32)
    return out, counts
