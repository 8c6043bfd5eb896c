"""Time the parts of models/xing4.py's drafting decode step apart, on the chip
at the cell's shapes (xing4_29b_pp8: 64 slots, two positions a slot,
contexts of ~1,500 tokens, every expert hit): what PERF.md section 5's
breakdown of cell 9's step is read from.

    chiprun -- python tools/bench_xing4_step_parts.py

One JSON line a part, also in ``chiprun_out/xing4_step_parts.jsonl``, ms a
call over 20 calls with the pools threaded through as the engine threads
them:

* ``held``: the step program (five held layers over 128 rows, the head on
  all of them); ``module``: the draft program (the module's layer over 128
  rows, the head on the 64 picked); ``head_128`` / ``head_64``: the final
  norm and the head's product alone at those rows (inside the two above);
* ``acceptance``: the step's tail as `draft_step` runs it
  (ops/pallas/draft_tail.py `draft_verify` and `draft_next`: the rule on
  [128, 131072] logits, q read and written at the slot, the next draft;
  under ``PT_PALLAS=off`` the plain form, `sampling.verify_tokens` and
  `draft_tokens` with q gathered and scattered), ten tails a call so that
  the host's dispatch is not what is read, and ``hbm_gb``, the bytes a
  tail moves by the compiled program's ``cost_analysis()``, beside the
  budget of 0.30 GB; ``step``: `serving/decode.py draft_step`, all of it
  as the engine runs it.

``--only acceptance step`` times those parts alone. The tail's other form:
``PT_PALLAS=off python tools/bench_xing4_step_parts.py --only acceptance``
(every kernel is then the stock lowering, so its ``step`` is not the
parent's).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

OUT = os.path.join("chiprun_out", "xing4_step_parts.jsonl")
CONTEXT = 1500
TAIL_BUDGET_GB = 0.30
TAILS_A_CALL = 10


def main():
    import paddle_tpu.ops  # noqa: F401
    from benchmark.manifest import Manifest
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import draft_tail
    from paddle_tpu.serving.decode import draft_step, run_program
    from paddle_tpu.serving.kv_cache import PagedKVCache
    from paddle_tpu.serving.served_model import DRAFT_SPARE_TOKENS

    man = Manifest(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))
    config = man.config_doc("xing4_29b_pp8")
    family = man.family("xing4")
    cfg, eng = family.model_config(config), config["engine"]
    params = family.make_params(cfg, 7)
    model = cfg.served()
    slots, page = eng["max_slots"], eng["page_size"]
    kv = PagedKVCache(model.cache_layout(), page, config["kv_pages"], None,
                      dtype=model.kv_dtype, slots=slots)
    pools = kv.make_arrays()
    rng = np.random.RandomState(0)
    mp = -(-(cfg.max_seq_len + DRAFT_SPARE_TOKENS) // page)
    per = -(-cfg.max_seq_len // page)
    table = np.zeros((slots, mp), np.int32)
    for s in range(slots):
        table[s, :per] = 1 + s * per + np.arange(per)
    pos = rng.randint(CONTEXT - 200, CONTEXT + 200, slots).astype(np.int32)
    tokens = rng.randint(3, cfg.vocab_size, 2 * slots).astype(np.int32)
    pairs = {"tokens": jnp.asarray(tokens),
             "positions": jnp.asarray(np.stack([pos, pos + 1], 1).reshape(-1)),
             "page_table": jnp.asarray(np.repeat(table, 2, axis=0)),
             "live": jnp.ones((2 * slots,), bool)}

    held_block = model.build_step_program(slots, kv, "none")[0].global_block()
    module_block = model.build_draft_program(slots, kv,
                                             "none")[0].global_block()

    def held(params, pools, feed):
        env, pools = run_program(held_block, params, pools, feed)
        return (env["logits"], env["hidden"]), pools

    def module(params, pools, feed):
        env, pools = run_program(module_block, params, pools, feed)
        return env["draft_logits"], pools

    def head(norm):
        def fn(params, x):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(ms + cfg.rms_norm_eps) * params[norm]
            return jnp.dot(x.astype(params["x4_head_w"].dtype),
                           params["x4_head_w"],
                           preferred_element_type=jnp.float32)
        return fn

    only = set(sys.argv[sys.argv.index("--only") + 1:]) \
        if "--only" in sys.argv else None

    def timed(name, fn, feed, donate=True, reps=20):
        nonlocal pools
        reps = reps if not only or name in only else 1
        jitted = jax.jit(fn, donate_argnums=(1,) if donate else ())
        for _ in range(2):
            out, pools = jitted(params, pools, feed)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(reps):
            out, pools = jitted(params, pools, feed)
        jax.block_until_ready(out)
        emit(name, (time.perf_counter() - t) / reps * 1e3)
        return out

    def emit(name, ms, **more):
        if only and name not in only:
            return
        line = json.dumps({"part": name, "ms": round(ms, 3), **more,
                           "kernels": pallas.kernel_mode(),
                           "device": jax.devices()[0].device_kind})
        print(line, flush=True)
        with open(OUT, "a") as f:
            f.write(line + "\n")

    def plain(name, fn, *args, reps=20):
        if only and name not in only:
            return
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))
        t = time.perf_counter()
        for _ in range(reps):
            out = jitted(*args)
        jax.block_until_ready(out)
        emit(name, (time.perf_counter() - t) / reps * 1e3)
        return out

    os.makedirs("chiprun_out", exist_ok=True)
    logits, hidden = timed("held", held, pairs)
    timed("module", module, dict(
        pairs, hidden=hidden,
        pick=2 * jnp.arange(slots, dtype=jnp.int32) + 1))
    plain("head_128", head("x4_norm_f"), params, hidden)
    plain("head_64", head("x4_mtp_norm"), params, hidden[::2])
    temp = jnp.full((slots,), 2.8, jnp.float32)
    u = jnp.asarray(rng.random_sample((slots, 4)), jnp.float32)
    by_slot = jnp.asarray(rng.permutation(slots).astype(np.int32))
    carried = jnp.ones((slots,), bool)
    q = draft_tail.state_rows(jnp.pad(
        jax.nn.softmax(logits[1::2] / 2.8, axis=-1), ((0, 1), (0, 0))))

    def tail(q, logits, module_logits, draft):
        tokens, count, read = draft_tail.draft_verify(
            logits, q, by_slot, draft, carried, temp, u[:, :3])
        draft, q = draft_tail.draft_next(module_logits, q, by_slot, temp,
                                         u[:, 3])
        return q, tokens, count, read, draft

    def tails(q, logits, module_logits, draft):
        def body(_, carry):
            return tail(carry[0], logits, module_logits, carry[4])
        return jax.lax.fori_loop(0, TAILS_A_CALL - 1, body, tail(
            q, logits, module_logits, draft))

    if not only or "acceptance" in only:
        draft = jnp.asarray(tokens[1::2])
        cost = jax.jit(tail, donate_argnums=(0,)).lower(
            q, logits, logits[::2], draft).compile().cost_analysis()
        jitted = jax.jit(tails, donate_argnums=(0,))
        q = jitted(q, logits, logits[::2], draft)[0]
        jax.block_until_ready(q)
        t = time.perf_counter()
        for _ in range(10):
            q = jitted(q, logits, logits[::2], draft)[0]
        jax.block_until_ready(q)
        emit("acceptance",
             (time.perf_counter() - t) / 10 / TAILS_A_CALL * 1e3,
             hbm_gb=round(cost["bytes accessed"] / 1e9, 3),
             hbm_budget_gb=TAIL_BUDGET_GB)
    if only and "step" not in only:
        return

    step = draft_step(model, kv, "none", slots, held_block)
    feed = {"tokens": jnp.asarray(tokens[::2]), "positions": jnp.asarray(pos),
            "page_table": jnp.asarray(table),
            "sampling": jnp.concatenate([temp[:, None], u], axis=1),
            "carry": jnp.stack([jnp.arange(slots, dtype=jnp.int32),
                                jnp.ones((slots,), jnp.int32)], axis=1)}
    last = jnp.asarray(tokens[::2])
    spec = {"pos": jnp.asarray(pos),
            "draft": jnp.asarray(tokens[1::2]),
            "q": q, "hidden": hidden[::2]}
    jitted = jax.jit(step, donate_argnums=(1, 4))
    for _ in range(2):
        fetch, pools, last, spec, _ = jitted(params, pools, feed, last, spec)
        spec = dict(spec, pos=jnp.asarray(pos))
    jax.block_until_ready(fetch)
    t = time.perf_counter()
    for _ in range(20):
        fetch, pools, last, spec, _ = jitted(params, pools, feed, last, spec)
    jax.block_until_ready(fetch)
    emit("step", (time.perf_counter() - t) / 20 * 1e3)


if __name__ == "__main__":
    main()
