"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

Greenfield capability (SURVEY.md §2.7: EP is absent from the reference —
its sparse story is parameter servers). TPU-native design, the
Switch/GShard recipe: top-1 gating with capacity, dense one-hot dispatch
(einsum-shaped for the MXU), experts sharded over 'ep', and
`lax.all_to_all` carrying token slots to their expert's rank and back over
ICI. Reverse AD flows through (all_to_all transposes to all_to_all).

Outside an SPMD region every expert lives on the one device and the
all_to_alls drop out — same math, no comm.

`routed_experts_share` is one chip's share of a dropless top-k routed
layer, served and (``trainable=True``) trained: the pairs sorted by held
expert (their plan compares and sorts, and gathers no single float),
the tokens' rows spread to their sorted places by one kernel
(ops/pallas/routed_spread.py), the forward one grouped kernel over the
sorted rows (ops/pallas/grouped_swiglu.py), the hand-written backward two
(ops/pallas/grouped_swiglu_bwd.py: rows-side, weights-side), the sorted
rows summed into their tokens by a fourth, forward and backward
(ops/pallas/routed_combine.py); a gather, ragged products and a
scatter-add wherever a kernel cannot run, counted.
"""

from __future__ import annotations

import functools

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


def _held_experts_fwd(x, w_sorted, w1, w3, w2, rows, sizes, few):
    out = _held_experts(x, w_sorted, w1, w3, w2, rows, sizes, few)
    return out, (x, w_sorted, w1, w3, w2, rows, sizes)


def _held_experts_bwd(few, res, dout):
    """The backward of the held experts' part, over the same sorted rows
    the forward ran over and as far as the held pairs reach: the gate and
    up products again (a sorted row's [F] float32 pair is not kept: 2 x
    470 MB a layer at 65,536 rows x 896), then the six products of the
    gradients, as two grouped kernels
    (``ops/pallas/grouped_swiglu_bwd.py``: rows-side and weights-side)
    over what ``routed_spread`` makes of `x` (again: the sorted rows are
    not kept) and of `dout` (its rows and their weighed copy, each
    rounded once from the float32 row), or as eight
    ragged products over the groups (`stock_grouped_swiglu_bwd`) where
    ``kernel_mode()`` is off, the dtypes are mixed or the kernels cannot
    tile the shape, counted; the rows' gradients summed into their
    tokens as the forward sums its rows (``routed_combine``, the held
    rows weighing 1). Nothing is dropped at any imbalance: past
    `few` rows the chunks go on."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.grouped_swiglu_bwd import grouped_swiglu_bwd
    from ..ops.pallas.routed_combine import routed_combine
    from ..ops.pallas.routed_spread import routed_spread

    x, w_sorted, w1, w3, w2, rows, sizes = res
    t, h = x.shape
    dt = w1.dtype
    f32 = jnp.float32
    pairs = rows.shape[0]

    def back(r, w, part):
        """One run of sorted rows -> (dx combined [T, H], dw [n], dW1,
        dW3, dW2 in float32)."""
        mine = w > 0
        # the rows again (they are not kept), and the cotangent's rows
        # with their weighed copy, each rounded once
        dy, dyw = routed_spread(dout, r, w, part, dt, weighted=True)
        dxs, dw, d1, d3, d2 = grouped_swiglu_bwd(
            routed_spread(x, r, w, part, dt), dy, dyw, w, w1, w3, w2, part)
        dx = routed_combine(dxs, r, mine.astype(f32), part, t)
        return dx, jnp.where(mine, dw, 0.0), d1, d3, d2

    def every():
        pad = -pairs % few
        rows_p, w_p = jnp.pad(rows, (0, pad)), jnp.pad(w_sorted, (0, pad))
        ends = jnp.cumsum(sizes)

        def some(i, acc):
            lo = i * few
            part = jnp.clip(ends, lo, lo + few) \
                - jnp.clip(ends - sizes, lo, lo + few)
            dx, dw, d1, d3, d2 = back(
                jax.lax.dynamic_slice_in_dim(rows_p, lo, few),
                jax.lax.dynamic_slice_in_dim(w_p, lo, few), part)
            return (acc[0] + dx,
                    jax.lax.dynamic_update_slice_in_dim(acc[1], dw, lo, 0),
                    acc[2] + d1, acc[3] + d3, acc[4] + d2)

        zero = (jnp.zeros((t, h), f32), jnp.zeros((pairs + pad,), f32),
                jnp.zeros(w1.shape, f32), jnp.zeros(w3.shape, f32),
                jnp.zeros(w2.shape, f32))
        dx, dw, d1, d3, d2 = jax.lax.fori_loop(
            0, (ends[-1] + few - 1) // few, some, zero)
        return dx, dw[:pairs], d1, d3, d2

    def leading():
        dx, dw, d1, d3, d2 = back(rows[:few], w_sorted[:few], sizes)
        return dx, jnp.pad(dw, (0, pairs - few)), d1, d3, d2

    if few < pairs:
        grads = jax.lax.cond(jnp.sum(sizes) <= few, leading, every)
    else:
        grads = back(rows, w_sorted, sizes)
    dx, dw, d1, d3, d2 = grads
    return (dx.astype(x.dtype), dw.astype(w_sorted.dtype), d1.astype(dt),
            d3.astype(dt), d2.astype(dt), None, None)


def _held_experts(x, w_sorted, w1, w3, w2, rows, sizes, few):
    """SwiGLU experts: the eight arguments `_held_experts_fwd` / `_bwd`
    are written for (`jax.custom_vjp` hands them a default too)."""
    return _held_experts_of(x, w_sorted, w1, w3, w2, rows, sizes, few, None)


def _held_experts_of(x, w_sorted, w1, w3, w2, rows, sizes, few, poly):
    """sum_i w_i * Expert_i(x) over the pairs sorted by held expert: their
    tokens `rows`, their weights `w_sorted` (0 past the groups), `sizes`
    of them in each expert's group. Out [T, H] float32. Differentiable in
    x, the weights and the three matrices (`_held_experts_bwd`). `poly`
    (pn [E_held, 4], eps, out_scale, bias_clamp) makes the experts
    PolyNorm ones (``grouped_polyglu``), served only."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.grouped_swiglu import grouped_polyglu, grouped_swiglu
    from ..ops.pallas.routed_combine import routed_combine
    from ..ops.pallas.routed_spread import routed_spread

    t, h = x.shape
    pairs = rows.shape[0]

    def experts(r, w, sizes):
        xs = routed_spread(x, r, w, sizes, w1.dtype)             # [n, H]
        if poly is None:
            ys = grouped_swiglu(xs, w1, w3, w2, sizes)           # [n, H]
        else:
            pn, eps, out_scale, bias_clamp = poly
            ys = grouped_polyglu(xs, w1, w3, w2, pn, sizes, eps=eps,
                                 out_scale=out_scale,
                                 bias_clamp=bias_clamp)
        # rows past the groups hold nothing of a held expert: weight 0,
        # and whatever the grouped product left there stays out
        return routed_combine(ys, r, w, sizes, t)

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
        return jax.lax.cond(
            jnp.sum(sizes) <= few,
            lambda: experts(rows[:few], w_sorted[:few], sizes), every)
    return experts(rows, w_sorted, sizes)


@functools.lru_cache(maxsize=None)
def _trained_held_experts():
    """`_held_experts` with its hand-written backward, made once."""
    import jax

    trained = jax.custom_vjp(_held_experts, nondiff_argnums=(7,))
    trained.defvjp(_held_experts_fwd, _held_experts_bwd)
    return trained


def _sort_pairs(key, w):
    import jax.numpy as jnp
    from jax import lax

    # `jnp.argsort(key, stable=True)` with the weights as one more operand
    iota = lax.iota(jnp.int32, key.shape[0])
    _, order, w_sorted = lax.sort((key, iota, w), num_keys=1, is_stable=True)
    return order, w_sorted


def _sort_pairs_fwd(key, w):
    order, w_sorted = _sort_pairs(key, w)
    return (order, w_sorted), order


def _sort_pairs_bwd(order, cts):
    from jax import lax

    # a sort by the permutation itself puts each sorted pair's cotangent
    # back at its pair; the key and the order take no gradient
    return None, lax.sort((order, cts[1]), num_keys=1)[1]


@functools.lru_cache(maxsize=None)
def _sorted_pairs():
    """(key int [P], w [P]) -> (order int32 [P], w_sorted [P]): the stable
    order of the pairs by `key` and the weights in that order, from one
    sort that carries the weights along (no gather by `order`), with its
    transpose by hand, a second sort (no scatter by `order`; JAX's own
    rule for a sort of several operands gathers the tangents). Made
    once; the served path runs the same function."""
    import jax

    sorted_pairs = jax.custom_vjp(_sort_pairs)
    sorted_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)
    return sorted_pairs


def _kept_scores(scores, idx):
    """scores[t, idx[t, j]] by comparison: a top-k's indices are distinct,
    so each sum over E has one term that is not zero and is exact. JAX's
    transpose is the same comparison summed over k, where a gather's is a
    scatter-add into [T, E]; [T, k, E] lives inside one fusion, forward
    and transposed, and is never written out."""
    import jax.numpy as jnp

    chosen = idx[:, :, None] == jnp.arange(scores.shape[1], dtype=idx.dtype)
    return jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=2)


def _pair_plan(scores, select_bias, alive, *, top_k, held_lo, e_held,
               route_scale, route_norm, norm_eps=1e-20):
    """The plan over the T x k pairs of a routed layer, with no gather and
    no scatter of single elements, forward or transposed: scores float32
    [T, E], `alive` bool [T] -> (idx int32 [T, k] the chosen experts,
    kept [T, k] their scores, order [T*k] the pairs sorted by held
    expert, rows int32 [T*k] the sorted pairs' tokens, sizes int32
    [e_held] the pairs in each held expert's group, w_sorted [T*k] the
    sorted pairs' weights, 0 past the groups)."""
    import jax
    import jax.numpy as jnp

    _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    kept = _kept_scores(scores, idx)                             # [T, k]
    weight = kept
    if route_norm:
        weight = kept / (jnp.sum(kept, axis=1, keepdims=True) + norm_eps)
    weight = weight * route_scale
    local = idx - held_lo
    held = (local >= 0) & (local < e_held) & alive[:, None]
    # pairs sorted by held expert; pairs of absent experts and of dead
    # rows sort behind every group and lie outside the groups' rows
    key = jnp.where(held, local, e_held).reshape(-1)             # [T*k]
    order, w_sorted = _sorted_pairs()(
        key, jnp.where(held, weight, 0.0).reshape(-1))
    sizes = jnp.sum(jax.nn.one_hot(key, e_held + 1, dtype=jnp.int32),
                    axis=0)[:e_held]
    rows = (order // top_k).astype(jnp.int32)
    return idx, kept, order, rows, sizes, w_sorted


def routed_experts_share(x, router_w, select_bias, w1, w3, w2, *,
                         top_k: int, held_lo: int, route_scale: float = 1.0,
                         route_norm: bool = True, live=None,
                         score_func: str = "sigmoid",
                         trainable: bool = False, with_chosen: bool = False,
                         poly=None, norm_eps: float = 1e-20):
    """One chip's share of a dropless top-k routed expert layer
    (`switch_moe` above is the top-1 layer with a capacity). Scores are
    ``sigmoid(x Wr)`` or, with ``score_func="softmax"``, the softmax over
    all E experts.

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
    ``score / (sum of the kept scores + norm_eps) * route_scale`` (no
    renormalisation when ``route_norm`` is false; ``norm_eps`` is 1e-20
    unless the model's published rule states another: models/lfm2.py's is
    1e-6). The plan over the T x k
    pairs (``_pair_plan``: kept scores -> weights -> sorted order ->
    sorted weights) gathers and scatters no single element, forward or
    transposed: a kept score is the sum over E of the scores where the
    chosen index equals the expert (``_kept_scores``), and the one sort
    of the pairs by held expert carries the weights along, its transpose
    a sort by the permutation (``_sorted_pairs``). This chip then computes,
    without dropping a pair, ``sum_i w_i * Expert_i(x)`` over the kept
    pairs whose expert it holds: the pairs are sorted by expert and the
    held experts' SwiGLUs run over the sorted rows as one grouped kernel
    (``ops/pallas/grouped_swiglu.py``: a stream of the hit experts' weight
    blocks with the three products under each; where ``kernel_mode()`` is
    off or the shape cannot be tiled, three ``jax.lax.ragged_dot``,
    counted), so an expert no token chose costs no weight read; the
    sorted rows are then weighed and summed into their tokens in float32
    by one kernel over the contiguous runs a token tile holds in each
    group (``ops/pallas/routed_combine.py``; a scatter-add where
    ``kernel_mode()`` is off, the tokens are not two tiles of 256 or more
    or H is not of 128, counted). It runs
    over the leading rows that hold the held pairs when those are few, as
    they nearly always are, and otherwise over that many sorted rows at a
    time, as far as the held pairs reach (one ``lax.cond``); the tokens'
    rows reach their sorted places by the combine's transpose over the
    same runs (``ops/pallas/routed_spread.py``; XLA's gather wherever the
    combine keeps its scatter-add, counted). What the absent experts would
    have added is left out; nothing stands in for their chips or the exchange.

    ``poly`` left out means SwiGLU experts, as above; ``poly`` = (pn
    [E_held, 4] float32, eps, out_scale, bias_clamp) means PolyNorm ones:
    every expert ``(PN_e(x W1) * (x W3)) W2`` with a row statistic over
    its whole width; the grouped kernel is then
    ``grouped_polyglu`` beside ``grouped_swiglu`` in the same module, its
    fallback three ragged products and the norm, counted
    (``pallas.grouped_polyglu_fallbacks``). Served only.

    ``trainable`` makes the result differentiable in x, the router and
    the held experts' three matrices (the selection is discrete and takes
    no gradient): the forward is the same, the held experts' part carries
    a hand-written backward over the same sorted rows
    (``_held_experts_bwd``), dropless too: two grouped kernels
    (``ops/pallas/grouped_swiglu_bwd.py``: the rows' gradients with gate
    and up made again, then the three matrices' gradients) between the
    ``routed_spread`` and the ``routed_combine`` kernels; where
    ``kernel_mode()`` is off, the dtypes
    are mixed or the kernels cannot tile the shape (rows not a multiple
    of the sublane tile, H or F not of 128, an expert's three matrices
    and a row tile over the kernel's VMEM), eight ragged products,
    counted (``pallas.grouped_swiglu_bwd_fallbacks``).

    Returns (out [T, H] float32, counts int32 [3]): the kept pairs of live
    rows, those of them on held experts, and the held experts with at
    least one live pair; ``trainable`` adds a fourth, the rows of the
    largest held group; ``with_chosen`` a third result, the chosen
    experts int32 [T, top_k]."""
    import jax
    import jax.numpy as jnp

    t, h = x.shape
    e_held = w1.shape[0]
    xf = x.astype(jnp.float32)
    logits = jnp.matmul(xf, router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)     # [T, E]
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score_func {score_func!r}")
    alive = jnp.ones((t,), bool) if live is None \
        else live.reshape(-1).astype(bool)
    idx, _kept, _order, rows, sizes, w_sorted = _pair_plan(
        scores, select_bias, alive, top_k=top_k, held_lo=held_lo,
        e_held=e_held, route_scale=route_scale, route_norm=route_norm,
        norm_eps=norm_eps)

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
    if trainable:
        # a trained layer's held pairs are thousands a group, the ragged
        # products of its backward cost by the rows they run over (and
        # four times less over a multiple of 4,096 rows than over 65,600:
        # 3.6 ms against 14.2, my chip run, PR 43), and routing that is
        # not even is the `every` chunks' to catch: a quarter over the
        # even share, in whole tiles
        even = pairs * e_held / router_w.shape[1]
        tile = 4096 if even >= 4096 else 64
        few = int(-(-(1.25 * even) // tile) * tile)

    if poly is not None:
        if trainable:
            raise ValueError("PolyNorm experts have no hand-written backward")
        out = _held_experts_of(x, w_sorted, w1, w3, w2, rows, sizes,
                               min(few, pairs), poly)
    else:
        held_experts = _trained_held_experts() if trainable \
            else _held_experts
        out = held_experts(x, w_sorted, w1, w3, w2, rows, sizes,
                           min(few, pairs))
    counts = [jnp.sum(alive) * top_k, jnp.sum(sizes), jnp.sum(sizes > 0)]
    if trainable:
        counts.append(jnp.max(sizes))
    counts = jnp.stack(counts).astype(jnp.int32)
    if with_chosen:
        return out, counts, idx.astype(jnp.int32)
    return out, counts
