"""Time the parts of one Qwen3-Next prefill alone, on the chip, at the cell's
share (PERF.md, PR 45): what a bucket's `while`s and `fusion`s hold.

    chiprun -- python tools/bench_qwen3_next_prefill.py [BUCKET ...]
    chiprun -- python tools/bench_qwen3_next_prefill.py --attention
    chiprun -- python tools/bench_qwen3_next_prefill.py --delta-rule

For each padded bucket (4096, 8192 and 16384 by default), ms a call of: the
chunked gated delta rule of one layer as the op dispatches it
(`gated_delta_chunk_scan`, 8 value heads on 4 key heads x [128, 128],
chunks of 64: the kernel on the chip) beside its stock form and the stock
form's two sequential parts alone (the row-by-row triangular solve; the
scan over chunks), whole-prompt attention
of one layer (`gqa_prefill_attention`, 4 query heads on one K/V head of
256, bfloat16 products) in both of the op's forms (`attention_alone`:
`attention_ms` the flash forward kernel, `attention_stock_ms` the XLA
products, `rows_equal`, and `dispatched`: which of them the op's shape
rule hands this length), one routed layer (`routed_experts_share`, 128 of
512 experts held at 2048 x 512, top-10 by softmax) and its combine alone
(`routed_combine`, the kernel, beside the scatter-add it replaces, over the
layer's leading sorted rows), its spread alone (`routed_spread` beside the
gather it replaces: `spread_ms` against `gather_ms`, `rows_equal`) and its
plan over the pairs alone
(`moe._pair_plan` beside the gathers it replaces), and one decode step's
state kernel over 64 rows beside its stock form. One JSON line a bucket on
stdout and in
``chiprun_out/qwen3_next_prefill_bench.jsonl``.

`--attention` times the attention line alone, at every shape a served
family hands the op above 1,024 (`ATTENTION_SHAPES`: Qwen3-Next's 4 + 1
heads of 256 and Trinity's 6 + 1 heads of 128 with its window of 4,096 and
without): the table `llm_ops.GQA_PREFILL_KERNEL_FROM` was set from, a line
a shape in ``chiprun_out/gqa_prefill_attention_bench.jsonl`` (~1.5 min of
the chip).

`--delta-rule [chunks_a_step ...]` times the rule alone at the cell's five
buckets: the kernel (`ops/pallas/gated_delta_chunk_scan.py`) beside the
stock form, ms a layer (`kernel_padded_ms`: the same bucket holding a
prompt of 5/8 of it), and the largest difference between the two; each
`chunks_a_step` (the kernel's tile constant; by default the one it ships
with) is a column. A line a bucket in
``chiprun_out/gated_delta_chunk_scan_bench.jsonl`` (~2 min of the chip).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.core import registry, telemetry
from paddle_tpu.ops import linear_attention_ops as la
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops.pallas import gated_delta_chunk_scan as gdc
from paddle_tpu.ops.pallas import gated_delta_state_update as gdu
from paddle_tpu.ops.pallas import kernel_mode
from paddle_tpu.parallel.moe import routed_experts_share

from bench_routed_train import combine_alone, plan_alone, spread_alone

H, DK, DV, CHUNK = 8, 128, 128, 64
KEY_HEADS = 4
BUCKETS = (1024, 2048, 4096, 8192, 16384)
HIDDEN, EXPERTS, HELD, WIDTH, TOP_K = 2048, 512, 128, 512, 10
# (query heads, K/V heads, head, window) -> the padded lengths timed
ATTENTION_SHAPES = {(4, 1, 256, 0): (2048, 4096, 8192, 16384),
                    (6, 1, 128, 4096): (2048, 4096, 8192),
                    (6, 1, 128, 0): (2048, 4096, 8192)}


def ms_a_call(fn, args, reps=5):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / reps * 1e3, 3)


def attention_alone(s, n=4, nkv=1, hd=256, window=0, reps=20):
    """One layer's `gqa_prefill_attention` over a padded prompt of s in
    bfloat16 products, ms a call of each form (the shape rule's constant
    moved out of the way), the largest difference between the two (the
    kernel rounds a block's unnormalised weights where the XLA form rounds
    the softmax: both are one bfloat16 rounding of numbers under 1) and
    which form the op as it stands hands this length."""
    key = jax.random.PRNGKey(s)
    q = jax.random.normal(key, (1, s, n * hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, nkv * hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, nkv * hd))
    attend = registry.lookup("gqa_prefill_attention").forward
    attrs = {"num_heads": n, "num_kv_heads": nkv, "head_dim": hd,
             "window": window, "compute_dtype": "bfloat16",
             "block_q": min(512, s)}

    def form(kernel_from):
        rule = llm_ops.GQA_PREFILL_KERNEL_FROM
        llm_ops.GQA_PREFILL_KERNEL_FROM = kernel_from
        try:
            fn = jax.jit(lambda q_, k_, v_: attend(
                {"Q": [q_], "K": [k_], "V": [v_]}, attrs)["Out"])
            return ms_a_call(fn, (q, k, v), reps), fn(q, k, v)
        finally:
            llm_ops.GQA_PREFILL_KERNEL_FROM = rule

    stock_ms, want = form(1 << 62)
    sent = telemetry.counter_get("pallas.gqa_prefill_dispatches")
    jax.eval_shape(lambda: attend({"Q": [q], "K": [k], "V": [v]}, attrs))
    line = dict(piece="attention alone", bucket=s, heads=n, kv_heads=nkv,
                head_dim=hd, window=window, attention_stock_ms=stock_ms,
                dispatched=telemetry.counter_get(
                    "pallas.gqa_prefill_dispatches") > sent)
    if kernel_mode() != "off":
        ms, got = form(0)
        diff = float(jnp.max(jnp.abs(got - want)))
        line.update(attention_ms=ms, max_abs_diff=round(diff, 5),
                    rows_equal=diff <= 2e-2 * float(jnp.max(jnp.abs(want))))
    return line


def attention_table():
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gqa_prefill_attention_bench.jsonl", "w") as out:
        for (n, nkv, hd, window), lengths in ATTENTION_SHAPES.items():
            for s in lengths:
                line = attention_alone(s, n, nkv, hd, window)
                line["device"] = jax.devices()[0].device_kind
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")


def rule_inputs(key, s):
    """A layer's terms as `delta_rule_terms` leaves them: each key head's q
    and k repeated over its value heads."""
    ks = jax.random.split(key, 5)
    r = H // KEY_HEADS
    q = la._l2norm(jax.random.normal(ks[0], (1, s, KEY_HEADS, DK))) \
        * DK ** -0.5
    k = la._l2norm(jax.random.normal(ks[1], (1, s, KEY_HEADS, DK)))
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    v = jax.random.normal(ks[2], (1, s, H, DV))
    g = -0.05 * jax.random.uniform(ks[3], (1, s, H))
    beta = jax.random.uniform(ks[4], (1, s, H))
    return q, k, v, g, beta


def rule_kernel(*terms):
    return gdc.gated_delta_chunk_scan(*terms, CHUNK,
                                      heads_per_key=H // KEY_HEADS)


def delta_rule_table(steps):
    """The rule alone, kernel beside stock, at the cell's buckets."""
    shipped = gdc.CHUNKS_A_STEP
    steps = steps or [shipped]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gated_delta_chunk_scan_bench.jsonl", "w") as out:
        for s in BUCKETS:
            terms = rule_inputs(jax.random.fold_in(jax.random.PRNGKey(0), s),
                                s)
            stock = jax.jit(lambda *a: gdc.stock_gated_delta_chunk_scan(
                *a, CHUNK))
            line = {"piece": "delta rule alone", "bucket": s,
                    "device": jax.devices()[0].device_kind,
                    "mode": kernel_mode(),
                    "stock_ms": ms_a_call(stock, terms)}
            want = stock(*terms)
            for step in steps:
                gdc.CHUNKS_A_STEP = step
                sent = telemetry.counter_get(
                    "pallas.gated_delta_chunk_scan_dispatches")
                fn = jax.jit(lambda *a: rule_kernel(*a))    # a trace a column
                got = fn(*terms)
                line[f"kernel_ms_{step}"] = ms_a_call(fn, terms, 10)
                line[f"dispatched_{step}"] = telemetry.counter_get(
                    "pallas.gated_delta_chunk_scan_dispatches") > sent
                line[f"max_abs_diff_{step}"] = max(
                    float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(got, want))
                # a prompt of 5/8 of the bucket: the tail's grid steps only
                # read the state
                line[f"kernel_padded_ms_{step}"] = ms_a_call(
                    fn, terms[:3] + tuple(
                        x * (jnp.arange(s) < s * 5 // 8)[None, :, None]
                        for x in terms[3:]), 10)
            gdc.CHUNKS_A_STEP = shipped
            line["max_abs"] = float(jnp.max(jnp.abs(want[1])))
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")


def solve_alone(n):
    """The forward substitution of the stock rule over [1, H, nc, C, C]."""
    ln = n.shape[-1]

    def row(i, t):
        r = jax.lax.dynamic_slice_in_dim(n, i, 1, axis=-2)
        new = jnp.einsum("bhcls,bhcsj->bhclj", r, t,
                         precision=jax.lax.Precision.HIGHEST)
        old = jax.lax.dynamic_slice_in_dim(t, i, 1, axis=-2)
        return jax.lax.dynamic_update_slice_in_dim(t, old - new, i, axis=-2)

    eye = jnp.broadcast_to(jnp.eye(ln, dtype=jnp.float32), n.shape)
    return jax.lax.fori_loop(1, ln, row, eye)


def scan_alone(u, w, q, k):
    """The carry over chunks of the stock rule: four products a chunk
    against the [H, DK, DV] state."""
    hi = jax.lax.Precision.HIGHEST

    def carry(state, c):
        u_c, w_c, q_c, k_c = c
        v_new = u_c - jnp.einsum("bhlk,bhkv->bhlv", w_c, state, precision=hi)
        o_c = jnp.einsum("bhlk,bhkv->bhlv", q_c, state, precision=hi)
        state = state * 0.97 + jnp.einsum("bhlk,bhlv->bhkv", k_c, v_new,
                                          precision=hi)
        return state, o_c + v_new

    return jax.lax.scan(carry, jnp.zeros((1, H, DK, DV), jnp.float32),
                        (u, w, q, k))


def main():
    if sys.argv[1:] == ["--attention"]:
        return attention_table()
    if sys.argv[1:2] == ["--delta-rule"]:
        return delta_rule_table([int(a) for a in sys.argv[2:]])
    buckets = [int(a) for a in sys.argv[1:]] or [4096, 8192, 16384]
    key = jax.random.PRNGKey(0)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/qwen3_next_prefill_bench.jsonl", "w")
    bf = jnp.bfloat16
    w1 = jax.random.normal(key, (HELD, HIDDEN, WIDTH), bf) * HIDDEN ** -0.5
    w2 = jax.random.normal(key, (HELD, WIDTH, HIDDEN), bf) * WIDTH ** -0.5
    router = (jax.random.normal(key, (HIDDEN, EXPERTS)) * 3
              * HIDDEN ** -0.5).astype(bf)
    for s in buckets:
        q, k, v, g, beta = rule_inputs(jax.random.fold_in(key, s), s)
        nc = s // CHUNK
        line = {"bucket": s, "device": jax.devices()[0].device_kind}
        line["chunk_delta_rule_ms"] = ms_a_call(
            jax.jit(rule_kernel), (q, k, v, g, beta))
        line["chunk_delta_rule_stock_ms"] = ms_a_call(
            jax.jit(lambda *a: gdc.stock_gated_delta_chunk_scan(*a, CHUNK)),
            (q, k, v, g, beta))
        n = 0.1 * jax.random.normal(key, (1, H, nc, CHUNK, CHUNK))
        line["solve_alone_ms"] = ms_a_call(jax.jit(solve_alone), (n,))
        parts = [jax.random.normal(key, (nc, 1, H, CHUNK, d))
                 for d in (DV, DK, DK, DK)]
        line["scan_alone_ms"] = ms_a_call(jax.jit(scan_alone), tuple(parts))
        line["attention_alone"] = attention_alone(s)
        x = jax.random.normal(key, (s, HIDDEN))
        line["routed_experts_ms"] = ms_a_call(
            jax.jit(lambda x_: routed_experts_share(
                x_, router, jnp.zeros((EXPERTS,)), w1, w1, w2, top_k=TOP_K,
                held_lo=0, score_func="softmax")[0]), (x,))
        pairs = s * TOP_K               # the served layer's leading rows
        few = -(-(2 * pairs * HELD // EXPERTS + 32) // 64) * 64
        line["combine_alone"] = combine_alone(
            s, TOP_K, EXPERTS, HELD, HIDDEN, few, 5)
        line["spread_alone"] = spread_alone(
            s, TOP_K, EXPERTS, HELD, HIDDEN, few, False, 5)
        line["plan_alone"] = plan_alone(s, TOP_K, EXPERTS, HELD, 20)
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
    # the decode step's state kernel over 64 rows of 65 slots
    state = jax.random.normal(key, (65, H, DK, DV))
    slots = jnp.arange(64, dtype=jnp.int32)
    qs, ks_, vs, gs, bs = (a[0, :64] for a in rule_inputs(key, 64))
    args = (state, slots, qs, ks_, vs, jnp.exp(gs), bs)
    line = {"state_kernel_ms": ms_a_call(
        jax.jit(lambda *a: gdu.gated_delta_state_update(
            *a, heads_per_key=2)), args, 20),
        "state_kernel_unshared_ms": ms_a_call(
        jax.jit(gdu.gated_delta_state_update), args, 20),
        "state_stock_ms": ms_a_call(
        jax.jit(gdu.stock_gated_delta_state_update), args, 20),
        "state_bytes_ms_at_peak": round(
            64 * 2 * H * DK * DV * 4 / 819e9 * 1e3, 4)}
    print(json.dumps(line), flush=True)
    out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
