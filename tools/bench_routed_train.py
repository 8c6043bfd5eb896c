"""The trained routed layer on the chip, forward and forward + backward,
at the Mellum cell's shapes (16,384 tokens, hidden 2304, 16 of 64 experts
of width 896 held, top-8, bfloat16): `routed_experts_share(trainable=True)`
with its held experts' forward as the `grouped_swiglu` kernel and as the
three stock `ragged_dot`s, its backward as the two `grouped_swiglu_bwd`
kernels and as the eight ragged products, and the stock forward under
JAX's own differentiation rules over the leading rows. ms a call, and %
of the bf16 peak for the nine products of the held pairs. Then the two
backward kernels alone over the layer's 40,960 leading rows beside the
eight ragged products, and the combine alone (`routed_combine`, the
kernel) beside the scatter-add it replaces (`stock_routed_combine`)
over the same leading rows of a real routing, the spread alone
(`routed_spread`, PR 50) beside the gather and the rounding passes it
replaces (`spread_ms` against `gather_ms`, `rows_equal`), plain and
weighted, and the plan over the
pairs alone (`moe._pair_plan`: scores -> rows, sizes, sorted weights;
PR 47) beside the gathers it replaces, whole and as its two pieces.
Lines in chiprun_out/routed_train_bench.jsonl (a call's file replaces
the last one's). ~3 min.

    python tools/bench_routed_train.py [--tokens 16384]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK = 197e12


def _timed(fn, iters, *a):
    """(ms a call of jit(fn)(*a) after one call that compiles, its result)."""
    import jax

    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def _leading_rows(t, k, e, eh, n):
    """(rows, w, sizes, pairs in the groups) of the n leading sorted rows
    of t tokens' top-k of e experts (softmax of a random router), eh of
    them held."""
    import jax
    import jax.numpy as jnp

    top, idx = jax.lax.top_k(jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(1), (t, e)), -1), k)
    held = idx < eh
    key = jnp.where(held, idx, eh).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, eh + 1, dtype=jnp.int32), 0)[:eh]
    rows = (order // k).astype(jnp.int32)[:n]
    w = jnp.where(held, top, 0.0).reshape(-1)[order][:n]
    in_groups = int(jnp.sum(sizes))
    assert in_groups <= n, "the leading rows do not hold the pairs"
    return rows, w, sizes, in_groups


def combine_alone(t, k, e, eh, h, n, iters=10):
    """The combine over the n leading sorted rows of t tokens' top-k of e
    experts (softmax of a random router), eh of them held, at width h:
    ms a call of the scatter-add (with the weigh-and-select pass in front
    of it, and alone) and of the `routed_combine` kernel with its plan,
    the largest difference between the two, and the kernel's steps."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kernel_mode
    from paddle_tpu.ops.pallas import routed_combine as rc

    rows, w, sizes, in_groups = _leading_rows(t, k, e, eh, n)
    # what lies past the groups may be anything
    ys = jnp.where((jnp.arange(n) < in_groups)[:, None],
                   jax.random.normal(jax.random.PRNGKey(2), (n, h),
                                     jnp.float32), jnp.nan)

    def timed(fn):
        return _timed(fn, iters, ys, rows, w, sizes)

    stock_ms, want = timed(lambda ys, r, w, s: rc.stock_routed_combine(
        ys, r, w, t))
    add_ms, _ = timed(lambda ys, r, w, s: jnp.zeros((t, h), jnp.float32)
                      .at[r].add(ys))
    line = dict(piece="the combine alone", tokens=t, rows=n, width=h,
                held_experts=eh, in_groups=in_groups,
                weigh_and_scatter_add_ms=stock_ms, scatter_add_ms=add_ms,
                bytes_ms_at_peak=(in_groups + t) * h * 4 / 819e9 * 1e3)
    tiling = rc._tiles(t, n, h)
    if tiling is not None and kernel_mode() != "off":
        tile, piece, stage, _lanes = tiling
        ms, got = timed(lambda ys, r, w, s: rc.routed_combine(ys, r, w, s, t))
        _tid, count, total, _block = rc._plan(rows, sizes, t, tile, piece,
                                              stage // piece)
        line.update(
            kernel_ms=ms, tile=tile, piece_rows=piece, stage=stage,
            steps=int(total[0]), pieces=int(jnp.sum(count[:int(total[0])])),
            max_diff_of_scale=float(jnp.max(jnp.abs(got - want))
                                    / jnp.max(jnp.abs(want))))
    return line


def spread_alone(t, k, e, eh, h, n, weighted, iters=10):
    """The spread over the same leading rows as `combine_alone`, from
    float32 tokens as every routed layer holds them: ms a call
    of the reference gather (`stock_routed_spread`: `src[rows]` rounded to
    bfloat16, or with `weighted` the float32 cotangent's rows selected,
    rounded, and weighed and rounded again) and of the `routed_spread`
    kernel with its plan (the kernel itself; `dispatched` says whether
    `routed_spread` hands it this shape or keeps the gather); whether the
    rows inside the groups are equal bit for bit and the kernel's rows
    past them zero."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kernel_mode
    from paddle_tpu.ops.pallas import routed_combine as rc
    from paddle_tpu.ops.pallas import routed_spread as rs

    rows, w, sizes, in_groups = _leading_rows(t, k, e, eh, n)
    src = jax.random.normal(jax.random.PRNGKey(3), (t, h), jnp.float32)
    bf = jnp.bfloat16

    def timed(fn):
        return _timed(fn, iters, src, rows, w, sizes)

    gather_ms, want = timed(lambda x, r, w, s: rs.stock_routed_spread(
        x, r, w, bf, weighted))
    outs = 1 + weighted
    line = dict(piece="the spread alone", tokens=t, rows=n, width=h,
                held_experts=eh, in_groups=in_groups, weighted=weighted,
                gather_ms=gather_ms,
                bytes_ms_at_peak=(t * h * 4 + outs * n * h * 2) / 819e9
                * 1e3)
    tiling = rc._tiles(t, n, h)
    if tiling is not None and kernel_mode() != "off":
        tile, piece, stage, lanes = tiling
        # the kernel itself, also where the dispatcher keeps the gather
        ms, got = timed(lambda x, r, w, s: rs._pallas_routed_spread(
            x, r, w, s, dtype=jnp.dtype(bf), weighted=weighted, tile=tile,
            piece=piece, stage=stage, lanes=lanes,
            interpret=kernel_mode() == "interpret"))
        line["dispatched"] = rs._tiling(src.dtype, bf, weighted, t, n, eh,
                                        h) is not None
        inside = (jnp.arange(n) < in_groups)[:, None]
        pairs = list(zip(got, want)) if weighted else [(got, want)]
        line.update(
            spread_ms=ms,
            rows_equal=all(bool(jnp.all(jnp.where(inside, g == v, True)))
                           for g, v in pairs),
            zero_past_the_groups=all(bool(jnp.all(jnp.where(
                inside, True, g == 0))) for g, _ in pairs))
    return line


def plan_alone(t, k, e, eh, iters=50):
    """The plan over t tokens' top-k of e experts (softmax of a random
    router), eh of them held: ms a call of `moe._pair_plan` (scores ->
    rows, sizes, w_sorted) forward, and forward + backward of a weighted
    sum of w_sorted, beside the reference form (the same plan with its
    two pieces as they stood until PR 47: the kept scores and the sorted
    weights by a gather each), and the largest difference between the
    two; then the two pieces alone in both forms (the kept scores of
    given indices; the sorted weights of given keys) and the argsort both
    forms hold. `four_ops_ms`: the kept scores and the sorted weights,
    forward + backward, less the argsort."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    scores = jax.nn.softmax(jax.random.normal(keys[0], (t, e)), -1)
    co = jax.random.uniform(keys[1], (t * k,), minval=0.5)
    w = jax.random.uniform(keys[2], (t * k,), minval=0.05)
    idx = jax.lax.top_k(scores, k)[1]
    key = jnp.where(idx < eh, idx, eh).reshape(-1)
    args = (jnp.zeros((e,)), jnp.ones((t,), bool))
    kw = dict(top_k=k, held_lo=0, e_held=eh, route_scale=1.0,
              route_norm=True)

    def gathered(key, w):
        order = jnp.argsort(key, stable=True)
        return order, w[order]

    line = dict(piece="the plan alone", tokens=t, pairs=t * k, experts=e,
                held_experts=eh)
    # (the indices and the keys are arguments: a closed-over key's sort is
    # folded at compile time)
    line["argsort_ms"], _ = _timed(
        lambda key: jnp.argsort(key, stable=True), iters, key)
    own = moe._kept_scores, moe._sorted_pairs
    outs = []
    for name, kept, sorted_pairs in (
            ("form", moe._kept_scores, moe._sorted_pairs()),
            ("reference_form",
             lambda s, idx: jnp.take_along_axis(s, idx, axis=1), gathered)):
        moe._kept_scores, moe._sorted_pairs = kept, lambda: sorted_pairs
        try:
            # a function object of its own a form: jit caches by it
            ms_f, fwd = _timed(
                lambda s: moe._pair_plan(s, *args, **kw)[3:], iters, scores)
            ms_fb, (_, grad) = _timed(jax.value_and_grad(lambda s: jnp.sum(
                moe._pair_plan(s, *args, **kw)[5] * co)), iters, scores)
        finally:
            moe._kept_scores, moe._sorted_pairs = own
        kept_f, _ = _timed(kept, iters, scores, idx)
        kept_fb, _ = _timed(jax.value_and_grad(lambda s, idx: jnp.sum(
            kept(s, idx) * co.reshape(t, k))), iters, scores, idx)
        sort_f, _ = _timed(lambda key, w: sorted_pairs(key, w)[1], iters,
                           key, w)
        sort_fb, _ = _timed(jax.value_and_grad(lambda w, key: jnp.sum(
            sorted_pairs(key, w)[1] * co)), iters, w, key)
        line[name] = dict(
            fwd_ms=ms_f, fwd_bwd_ms=ms_fb, kept_fwd_ms=kept_f,
            kept_fwd_bwd_ms=kept_fb, sorted_weights_fwd_ms=sort_f,
            sorted_weights_fwd_bwd_ms=sort_fb,
            four_ops_ms=kept_fb + sort_fb - line["argsort_ms"])
        outs.append((fwd, grad))
    ((rows, sizes, ws), grad), ((rows_r, sizes_r, ws_r), grad_r) = outs
    line.update(
        rows_and_sizes_equal=bool(jnp.all(rows == rows_r)
                                  & jnp.all(sizes == sizes_r)),
        w_sorted_max_diff=float(jnp.max(jnp.abs(ws - ws_r))),
        grad_max_diff_of_scale=float(jnp.max(jnp.abs(grad - grad_r))
                                     / jnp.max(jnp.abs(grad_r))))
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kernel_mode
    from paddle_tpu.ops.pallas import grouped_swiglu as gs
    from paddle_tpu.ops.pallas import grouped_swiglu_bwd as gb
    from paddle_tpu.parallel import moe

    t, h, f, e, eh, k = args.tokens, 2304, 896, 64, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (t, h), jnp.float32)
    rw = (jax.random.normal(keys[1], (h, e)) * h ** -0.5).astype(bf)
    w1 = (jax.random.normal(keys[2], (eh, h, f)) * h ** -0.5).astype(bf)
    w3 = (jax.random.normal(keys[3], (eh, h, f)) * h ** -0.5).astype(bf)
    w2 = (jax.random.normal(keys[4], (eh, f, h)) * f ** -0.5).astype(bf)
    co = jax.random.normal(keys[5], (t, h), jnp.float32)
    bias = jnp.zeros((e,), jnp.float32)

    def layer(x, rw, w1, w3, w2):
        return moe.routed_experts_share(
            x, rw, bias, w1, w3, w2, top_k=k, held_lo=0,
            score_func="softmax", trainable=True)

    def loss(x, rw, w1, w3, w2):
        out, counts = layer(x, rw, w1, w3, w2)
        return jnp.sum(out * co), counts

    def own_rules(x, rw, w1, w3, w2):
        """The stock products under jax's own rules, over the leading
        rows only (no branch: what a cond would keep twice)."""
        logits = x @ rw.astype(jnp.float32)
        p = jax.nn.softmax(logits, -1)
        top, idx = jax.lax.top_k(p, k)
        w = top / jnp.sum(top, -1, keepdims=True)
        held = idx < eh
        key = jnp.where(held, idx, eh).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, eh + 1, dtype=jnp.int32), 0)[:eh]
        even = t * k * eh / e          # the trained layer's leading rows
        tile = 4096 if even >= 4096 else 64
        few = int(-(-(1.25 * even) // tile) * tile)
        rows = (order // k)[:few]
        ws = jnp.where(held, w, 0.0).reshape(-1)[order][:few]
        ys = gs.stock_grouped_swiglu(x[rows].astype(bf), w1, w3, w2, sizes)
        ys = jnp.where(ws[:, None] > 0, ys * ws[:, None], 0.0)
        out = jnp.zeros((t, h), jnp.float32).at[rows].add(ys)
        return jnp.sum(out * co), sizes

    def timed(fn, *a):
        fn = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, first, out

    stock_kernel, bwd_kernels = gs.grouped_swiglu, gb.grouped_swiglu_bwd
    rows = []
    a = (x, rw, w1, w3, w2)

    for name, forward, backward in (
            ("kernel", stock_kernel, bwd_kernels),
            ("kernel, ragged backward", stock_kernel,
             gb.stock_grouped_swiglu_bwd),
            ("stock, ragged backward", gs.stock_grouped_swiglu,
             gb.stock_grouped_swiglu_bwd)):
        gs.grouped_swiglu, gb.grouped_swiglu_bwd = forward, backward
        # a function object of its own a variant: jit caches by it
        ms_f, c_f, out = timed(lambda *v: layer(*v), *a)
        held = int(out[1][1])
        ms_fb, c_fb, _ = timed(jax.value_and_grad(
            lambda *v: loss(*v), argnums=(0, 1, 2, 3, 4), has_aux=True), *a)
        rows.append(dict(forward=name, held_pairs=held,
                         max_group=int(out[1][3]), fwd_ms=ms_f,
                         fwd_bwd_ms=ms_fb, compile_s=[c_f, c_fb]))
    gs.grouped_swiglu, gb.grouped_swiglu_bwd = stock_kernel, bwd_kernels
    ms_fb, c_fb, out = timed(jax.value_and_grad(
        own_rules, argnums=(0, 1, 2, 3, 4), has_aux=True), *a)
    rows.append(dict(forward="stock, jax's own rules, leading rows",
                     held_pairs=int(jnp.sum(out[0][1])), fwd_bwd_ms=ms_fb,
                     compile_s=[c_fb]))
    for r in rows:
        nine = 9 * r["held_pairs"] * 2 * h * f
        r["nine_products_pct_of_peak"] = round(
            100 * nine / PEAK / (r["fwd_bwd_ms"] / 1e3), 2)

    # the backward's products alone, over the leading rows of the layer
    n = int(-(-(1.25 * t * k * eh / e) // 4096) * 4096)
    sizes = jnp.asarray(jax.random.randint(        # ~2,048 a group of 2,560
        keys[0], (eh,), n * 5 // (8 * eh), n * 39 // (40 * eh)), jnp.int32)
    held = int(jnp.sum(sizes))
    w = jnp.where(jnp.arange(n) < held,
                  jax.random.uniform(keys[1], (n,), minval=0.05), 0.0)
    xs = jax.random.normal(keys[2], (n, h), jnp.float32).astype(bf)
    dy = jnp.where((w > 0)[:, None],
                   jax.random.normal(keys[5], (n, h), jnp.float32), 0.0)
    ops = (xs, dy.astype(bf), (dy * w[:, None]).astype(bf), w, w1, w3, w2,
           sizes)
    ms, first, _ = timed(lambda *v: gb.stock_grouped_swiglu_bwd(*v), *ops)
    rows.append(dict(piece="the eight ragged products", rows=n, held=held,
                     ms=ms, compile_s=[first],
                     pct_of_peak=100 * 8 * held * 2 * h * f / PEAK / ms * 1e3))
    tile = gb._tile(n, h, f, bf)
    interpret = kernel_mode() == "interpret"        # a rehearsal on the CPU
    ms_r, c_r, hand = timed(
        lambda xs, dy, w, w1, w3, w2, sizes: gb._pallas_bwd_rows(
            xs, dy, w, w1, w3, w2, sizes, tile=tile, window=gb.WINDOW_ROWS,
            interpret=interpret), *ops[:2], *ops[3:])
    ms_w, c_w, _ = timed(
        lambda *v: gb._pallas_bwd_weights(
            *v, tile=tile, window=gb.WINDOW_ROWS_WEIGHTS,
            interpret=interpret), xs, ops[2], *hand[2:], sizes)
    rows.append(dict(
        piece="the two backward kernels", rows=n, held=held, tile=tile,
        rows_side=dict(window=gb.WINDOW_ROWS, ms=ms_r, pct_of_peak=100 * 5
                       * held * 2 * h * f / PEAK / ms_r * 1e3),
        weights_side=dict(window=gb.WINDOW_ROWS_WEIGHTS, ms=ms_w,
                          pct_of_peak=100 * 3 * held * 2 * h * f / PEAK
                          / ms_w * 1e3),
        compile_s=[c_r, c_w]))
    rows.append(combine_alone(t, k, e, eh, h, n, args.iters))
    # the forward's and the backward's `x`, then `dout` with its weighed copy
    rows.append(spread_alone(t, k, e, eh, h, n, False, args.iters))
    rows.append(spread_alone(t, k, e, eh, h, n, True, args.iters))
    rows.append(plan_alone(t, k, e, eh))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/routed_train_bench.jsonl", "w") as fh:
        for r in rows:
            r["device"] = jax.devices()[0].device_kind
            print(json.dumps(r), flush=True)
            fh.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
