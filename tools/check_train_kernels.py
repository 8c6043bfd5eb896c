"""On the chip: the pieces of the trained routed layer and the window
flash kernels against dense forms, and the time of each ragged product;
the two `grouped_swiglu_bwd` kernels against a dense loop over the
experts, their times beside the eight ragged products'; the whole trained
layer (forward + backward inside `jax.grad`, under its `cond`) against a
dense loop. Prints one JSON line a piece.

    python tools/check_train_kernels.py [rows H F experts lo hi seq_len]
    (CHECK_BATCH=2 ... 40960 2304 896 16 1600 2500 8192: the Mellum
    cell's 40,960 leading rows and 16,384 tokens)"""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def timed(fn, *a, iters=5):
    out = jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def main():
    n, h, f, e = (int(v) for v in (sys.argv[1:5] or (65600, 2304, 896, 16)))
    lo, hi, s_len = (int(v) for v in (sys.argv[5:8] or (1500, 2600, 4096)))
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    sizes = jnp.asarray(np.random.RandomState(0).randint(lo, hi, e), jnp.int32)
    total = int(sizes.sum())
    xs = jax.random.normal(ks[0], (n, h), jnp.float32).astype(bf)
    dy = jax.random.normal(ks[1], (n, h), jnp.float32).astype(bf)
    g = jax.random.normal(ks[2], (n, f), jnp.float32).astype(bf)
    w1 = (jax.random.normal(ks[3], (e, h, f)) * h ** -0.5).astype(bf)
    w2 = (jax.random.normal(ks[4], (e, f, h)) * f ** -0.5).astype(bf)
    gid = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(n), side="right")
    inside = (jnp.arange(n) < total)[:, None]
    f32 = jnp.float32
    dn_t = jax.lax.RaggedDotDimensionNumbers(dot_dimension_numbers=(([1], [2]), ([], [])), lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    dn_w = jax.lax.RaggedDotDimensionNumbers(dot_dimension_numbers=(([0], [0]), ([], [])), lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def dense_rows(a, w):          # a [n, K], w [e, K, N] by group, in chunks
        out = jnp.zeros((n, w.shape[2]), f32)
        for i in range(e):
            out = out + jnp.where((gid == i)[:, None], jnp.dot(a, w[i], preferred_element_type=f32), 0.0)
        return out

    def dense_w(a, b):             # [e, K, N] = a[group]^T b[group]
        return jnp.stack([jnp.dot(jnp.where((gid == i)[:, None], a, 0).T, b, preferred_element_type=f32) for i in range(e)])

    cases = {
        "rows @ w[e] (mode 1)": (jax.jit(lambda: jnp.where(inside, jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=f32), 0.0)), jax.jit(lambda: dense_rows(xs, w1)), 2 * total * h * f),
        "rows @ w[e]^T (dn_t)": (jax.jit(lambda: jnp.where(inside, jax.lax.ragged_dot_general(g, w1, sizes, dn_t, preferred_element_type=f32), 0.0)), jax.jit(lambda: dense_rows(g, jnp.swapaxes(w1, 1, 2))), 2 * total * h * f),
        "rows[e]^T @ rows[e] (dn_w)": (jax.jit(lambda: jax.lax.ragged_dot_general(xs, g, sizes, dn_w, preferred_element_type=f32)), jax.jit(lambda: dense_w(xs, g)), 2 * total * h * f),
        "rows @ w2[e]^T (dn_t, H->F)": (jax.jit(lambda: jnp.where(inside, jax.lax.ragged_dot_general(dy, w2, sizes, dn_t, preferred_element_type=f32), 0.0)), jax.jit(lambda: dense_rows(dy, jnp.swapaxes(w2, 1, 2))), 2 * total * h * f),
        "rows @ w2[e] (mode 1, F->H)": (jax.jit(lambda: jnp.where(inside, jax.lax.ragged_dot(g, w2, sizes, preferred_element_type=f32), 0.0)), jax.jit(lambda: dense_rows(g, w2)), 2 * total * h * f),
    }
    if os.environ.get("CHECK_SKIP_RAGGED"):
        cases = {}
    for name, (fn, ref, flops) in cases.items():
        ms, out = timed(fn)
        want = ref()
        print(json.dumps({"piece": name, "ms": round(ms, 3), "pct_of_peak": round(100 * flops / 197e12 / (ms / 1e3), 1), "rel_err": rel(out, want), "finite": bool(jnp.isfinite(out).all()), "held_rows": total, "rows": n}), flush=True)

    # does the product's time follow the rows or the groups' rows?
    for rows_n in () if os.environ.get("CHECK_SKIP_RAGGED") else (
            n * 5 // 8 // 64 * 64, total // 64 * 64 + 64):
        a = xs[:rows_n]
        ms, _ = timed(jax.jit(lambda a=a: jax.lax.ragged_dot(a, w1, sizes, preferred_element_type=f32)))
        print(json.dumps({"piece": f"mode 1 over the leading {rows_n} rows", "ms": round(ms, 3)}), flush=True)

    # sizes that fill every row: what a kernel that assumes it would need
    if not os.environ.get("CHECK_SKIP_RAGGED"):
        full = sizes.at[-1].add(n - total)
        fn = jax.jit(lambda: jax.lax.ragged_dot_general(xs, g, full, dn_w, preferred_element_type=f32))
        ms, _ = timed(fn)
        print(json.dumps({"piece": "dn_w with sizes summing to every row", "ms": round(ms, 3)}), flush=True)

    # the backward's two kernels against a dense loop over the experts
    # with the same rounding points, beside the eight ragged products
    from paddle_tpu.ops.pallas import grouped_swiglu_bwd as gb
    w3 = (jax.random.normal(ks[5], (e, h, f)) * h ** -0.5).astype(bf)
    wt = jnp.where(inside[:, 0], jax.random.uniform(ks[6], (n,), minval=0.05), 0.0)
    dy32 = jnp.where(inside, dy.astype(f32), 0.0)
    ops = (xs, dy32.astype(bf), (dy32 * wt[:, None]).astype(bf), wt, w1, w3, w2, sizes)

    def dense_bwd(xs, dy, dyw, wt, w1, w3, w2, sizes):
        def dot(a, b, dims=(((1,), (0,)), ((), ()))):
            return jax.lax.dot_general(a, b, dims, preferred_element_type=f32)
        nt, tn = (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))
        dxs, dw, d1, d3, d2 = jnp.zeros((n, h), f32), jnp.zeros((n,), f32), [], [], []
        for i in range(e):
            m = (gid == i)[:, None] & inside
            gate, up = dot(xs, w1[i]), dot(xs, w3[i])
            sig = jax.nn.sigmoid(gate)
            act = gate * sig
            mid = act * up
            dmid = dot(dy, w2[i], nt)
            dw = dw + jnp.where(m[:, 0], jnp.sum(dmid * mid, axis=1), 0.0)
            dmid = dmid * wt[:, None]
            dgate = (dmid * up * (sig + act * (1.0 - sig))).astype(bf)
            dup = (dmid * act).astype(bf)
            dxs = dxs + jnp.where(m, dot(dgate, w1[i], nt) + dot(dup, w3[i], nt), 0.0)
            xm = jnp.where(m, xs, 0)
            d1.append(dot(xm, dgate, tn)); d3.append(dot(xm, dup, tn))
            d2.append(dot(jnp.where(m, mid, 0).astype(bf), dyw, tn))
        return dxs, dw, jnp.stack(d1), jnp.stack(d3), jnp.stack(d2)

    def held_rows(out):
        return (jnp.where(inside, out[0], 0.0), jnp.where(inside[:, 0], out[1], 0.0)) + tuple(out[2:])

    want = jax.jit(dense_bwd)(*ops)
    for name, fn in (("the two grouped_swiglu_bwd kernels", jax.jit(lambda *a: held_rows(gb.grouped_swiglu_bwd(*a)))),
                     ("the eight ragged products", jax.jit(lambda *a: held_rows(gb.stock_grouped_swiglu_bwd(*a))))):
        ms, got = timed(fn, *ops)
        print(json.dumps({"piece": name, "ms": round(ms, 3), "pct_of_peak": round(100 * 8 * 2 * total * h * f / 197e12 / (ms / 1e3), 1), "rel_err dxs dw dW1 dW3 dW2": [rel(a, c) for a, c in zip(got, want)], "finite": all(bool(jnp.isfinite(a).all()) for a in got), "held_rows": total, "rows": n}), flush=True)
    del want, got

    from paddle_tpu.ops.pallas import flash_window as fw
    b, s, hq, hd = int(os.environ.get("CHECK_BATCH", 1)), s_len, 8, 128
    q = jax.random.normal(ks[5], (b, s, hq * hd), f32).astype(bf)
    k = jax.random.normal(ks[6], (b, s, hd), f32).astype(bf)
    v = jax.random.normal(ks[7], (b, s, hd), f32).astype(bf)
    do = jax.random.normal(ks[0], (b, s, hq * hd), f32).astype(bf)
    for window in () if os.environ.get("CHECK_SKIP_FLASH") else (1024, 0):
        kern = jax.jit(lambda q, k, v: jax.vjp(lambda *a: fw.flash_window_attention(*a, hq, 1, window, None, None), q, k, v)[1](do) + (fw.flash_window_attention(q, k, v, hq, 1, window, None, None),))
        plain = jax.jit(lambda q, k, v: jax.vjp(lambda *a: fw.masked_attention(*a, num_heads=hq, num_kv_heads=1, window=window), q, k, v)[1](do) + (fw.masked_attention(q, k, v, num_heads=hq, num_kv_heads=1, window=window),))
        ms, got = timed(kern, q, k, v)
        do32 = do.astype(f32)
        plain = jax.jit(lambda q, k, v: jax.vjp(lambda *a: fw.masked_attention(*a, num_heads=hq, num_kv_heads=1, window=window), q, k, v)[1](do32) + (fw.masked_attention(q, k, v, num_heads=hq, num_kv_heads=1, window=window),))
        with jax.default_matmul_precision("highest"):
            want = plain(q.astype(f32), k.astype(f32), v.astype(f32))
        print(json.dumps({"piece": f"flash window {window} s{s}", "ms_fwd_bwd": round(ms, 3), "rel_err dq dk dv out": [rel(a, c) for a, c in zip(got, want)]}), flush=True)

    # the whole trained routed layer against a dense loop, bfloat16 weights
    from paddle_tpu.parallel.moe import routed_experts_share
    t, ne, k = s_len * b, 4 * e, 8
    ks2 = jax.random.split(jax.random.PRNGKey(7), 8)
    x = jax.random.normal(ks2[0], (t, h), f32)
    rw = (jax.random.normal(ks2[1], (h, ne)) * h ** -0.5).astype(bf)
    a1 = (jax.random.normal(ks2[2], (e, h, f)) * h ** -0.5).astype(bf)
    a3 = (jax.random.normal(ks2[3], (e, h, f)) * h ** -0.5).astype(bf)
    a2 = (jax.random.normal(ks2[4], (e, f, h)) * f ** -0.5).astype(bf)
    co = jax.random.normal(ks2[5], (t, h), f32)

    def layer(x, rw, a1, a3, a2):
        out, _ = routed_experts_share(x, rw, jnp.zeros((ne,), f32), a1, a3, a2, top_k=k, held_lo=0, score_func="softmax", trainable=True)
        return jnp.sum(out * co)

    def dense(x, rw, a1, a3, a2):
        p = jax.nn.softmax(jnp.matmul(x, rw.astype(f32), precision="highest"), -1)
        top, idx = jax.lax.top_k(p, k)
        w = top / jnp.sum(top, -1, keepdims=True)
        out = jnp.zeros_like(x)
        for i in range(e):
            mine = jnp.sum(jnp.where(idx == i, w, 0.0), -1)
            xb = x.astype(bf)
            mid = jax.nn.silu(jnp.dot(xb, a1[i], preferred_element_type=f32)) * jnp.dot(xb, a3[i], preferred_element_type=f32)
            out = out + mine[:, None] * jnp.dot(mid.astype(bf), a2[i], preferred_element_type=f32)
        return jnp.sum(out * co)

    # as routed, the leading rows hold the held pairs; with every token's
    # top 8 among the 16 held experts the chunks past them run (`every`)
    skew = jnp.where(jnp.arange(ne)[None, :] < e, jnp.abs(rw), -jnp.abs(rw))
    for name, xx, router in (("leading rows", x, rw), ("every chunk", jnp.abs(x) + 0.5, skew)):
        got = jax.jit(jax.value_and_grad(layer, (0, 1, 2, 3, 4)))(xx, router, a1, a3, a2)
        want = jax.jit(jax.value_and_grad(dense, (0, 1, 2, 3, 4)))(xx, router, a1, a3, a2)
        print(json.dumps({"piece": f"routed layer, {t} tokens, {name}", "value": [float(got[0]), float(want[0])], "rel_err dx drouter dw1 dw3 dw2": [rel(a, c) for a, c in zip(got[1], want[1])]}), flush=True)


if __name__ == "__main__":
    main()
