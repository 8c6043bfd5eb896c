"""By hand, on the chip: the readings behind the `kimi_k2` family's limits
(reference_kimi_k2.LOGIT_ERR, MARGIN, UNDECIDED_MARGIN), for the check
prompts of a configuration and a seed.

    python3 -m benchmark.readings_kimi_k2 [--config kimi_k2_dp_ep32] [--seed 11]
                                          [--routing [TOKENS ...]]
                                          [--plant page_table]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends
them (every other slot live), and what came out is judged, by the same
`judge`, against the reference on the weights as they are and against each
lower-precision control of it (reference_kimi_k2.CONTROLS, float8 e4m3,
the nearest precision below bfloat16): the latent rows as pages hold them,
W_kvb alone (the absorbed form's W_uk and W_uv), every weight matrix. One
line a judge: what it compared beside the limits, and `correct`. A control
has to come out as not correct by at least one of the limits. A last line
reads the routed pairs on the held experts sequence by sequence (PR 28's
trap: seeded weights that send a whole sequence to the same experts).

`--routing` adds a line a check prompt (or for those named): the experts
the ENGINE's prefill program kept at every position of every MoE layer
beside the reference's (`engine_routing`), where they differ, and the
prefill's logits held against the reference made to keep the engine's
experts: what of a
prompt's `logit_err` is routing that turned on rounding, and what is
rounding itself. `--plant page_table` reads the fault the CPU test plants
(`share_slot_zeros_pages`) at the timed size instead: the engine's outputs
with every later live row of a step fed the first row's page table, judged
against the reference as it is; no control is read beside it. It is the
upper reading of UNDECIDED_MARGIN.
"""

from __future__ import annotations

import argparse
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def share_slot_zeros_pages(engine):
    """The planted fault: every later live row of a step is fed the first
    row's page table, so several requests write their latent rows over and
    attend one another's. One live row is fed what it always was."""
    feed = engine._feed

    def faulty(phase, bucket, parts):
        if phase == "step":
            table = parts["page_table"].copy()
            live = table.any(axis=1)
            table[1:][live[1:]] = table[0]
            parts = dict(parts, page_table=table)
        return feed(phase, bucket, parts)

    engine._feed = faulty


def engine_routing(engine, sent):
    """[len(sent), MoE layers, k]: the experts the engine's own prefill
    program keeps for `sent`. The program of the prompt's bucket is run as
    `DecodeEngine._entry` runs it (on the engine's parameters and pools,
    which are read and not written here) and each `routed_experts` op's
    input is scored as that op scores it: sigmoid in float32, the bias for
    selection, the k largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.executor import run_block

    cfg = engine.model_cfg
    n = len(sent)
    bucket = min(b for b in engine.config.prefill_buckets if b >= n)
    program, feeds, _ = engine.model.build_prefill_program(
        bucket, engine.kv, engine.config.weight_quant)
    block = program.global_block()
    ops = [op for op in block.ops if op.type == "routed_experts"]

    def kept(params, pools, feed):
        env = dict(params)
        env.update(pools)
        env.update(feed)
        run_block(block, env)
        out = []
        for op in ops:
            x = env[op.input("X")[0]].reshape(bucket, -1)
            scores = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32),
                env[op.input("RouterW")[0]].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            out.append(jax.lax.top_k(
                scores + env[op.input("SelectBias")[0]],
                cfg.num_experts_per_tok)[1])
        return jnp.stack(out, axis=1)

    parts = {"tokens": np.zeros((1, bucket), np.int32),
             "positions": np.arange(bucket, dtype=np.int32)[None],
             "lengths": np.asarray([n], np.int32),
             "page_table": np.zeros((1, engine._mp), np.int32)}
    parts["tokens"][0, :n] = sent
    feed = {name: jnp.asarray(parts[name]) for name in feeds}
    return np.asarray(jax.jit(kept)(engine._params, engine._pools,
                                    feed))[:n]


def routing_reader(ref, engine, pad_to):
    """-> read(sent, first_logits): one check prompt's routing, the
    engine's beside the reference's -> dict: `differ` (position, layer)
    pairs that keep other experts, those of them `on_held` experts with
    the reference's gap there (k-th score over the (k+1)-th, where one of
    the two is held), and `logit_err` of the prefill's row against the
    reference as it is and against the reference keeping the engine's
    experts. One compile of each reference for every prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_kimi_k2 as rk

    lo, count = ref.cfg["experts_held"]
    moe = [i for i in range(ref.cfg["n_layers"])
           if i >= ref.cfg["first_k_dense"]]

    def as_it_is(params, tokens, last):
        seen = []
        logits, _ = rk.forward(
            params, tokens, ref.cfg, last, 1,
            on_route=lambda _layer, w, g: seen.append((w > 0, g)))
        return (logits[0], jnp.stack([w for w, _ in seen], axis=1),
                jnp.stack([g for _, g in seen], axis=1))

    def on_engines(params, tokens, last, forced):
        return rk.forward(params, tokens, ref.cfg, last, 1,
                          forced=forced)[0][0]

    as_it_is, on_engines = jax.jit(as_it_is), jax.jit(on_engines)

    def read(sent, first_logits):
        n = len(sent)
        kept = engine_routing(engine, sent)              # [n, L, k]
        forced = {i: np.zeros((pad_to, kept.shape[2]), np.int32)
                  for i in moe}
        for j, i in enumerate(moe):
            forced[i][:n] = kept[:, j]
        tokens = jnp.asarray(rk.padded(sent, pad_to))
        own, ref_kept, gap = as_it_is(ref.params, tokens, n - 1)
        engines = on_engines(ref.params, tokens, n - 1,
                             {i: jnp.asarray(v) for i, v in forced.items()})
        ref_kept, gap = np.asarray(ref_kept)[:n], np.asarray(gap)[:n]
        eng_kept = np.zeros_like(ref_kept)
        np.put_along_axis(eng_kept, kept, True, axis=2)
        differ = ref_kept != eng_kept                    # [n, L, E]
        held_at = np.argwhere(differ[:, :, lo:lo + count].any(axis=2))
        return {
            "sent": n, "positions_x_layers": n * len(moe),
            "differ": int(differ.any(axis=2).sum()),
            "on_held": len(held_at),
            # the gap is infinite where a held expert differs deeper than
            # the k-th and (k+1)-th: an earlier layer's difference moved
            # the scores
            "on_held_at": [
                [int(t), moe[int(j)],
                 float(gap[t, j]) if np.isfinite(gap[t, j]) else None]
                for t, j in held_at[:12]],
            "logit_err": rk.logit_error(first_logits, np.asarray(own)),
            "logit_err_on_the_engines_routing":
            rk.logit_error(first_logits, np.asarray(engines))}

    return read


def held_pair_share_by_sequence(ref, sents, pad_to):
    """For each check prompt, the share (%) of its routed pairs that land
    on the held experts in each MoE layer, by the reference's own routing:
    even routing reads held / all experts for every sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_kimi_k2 as rk

    lo, count = ref.cfg["experts_held"]

    def shares(params, tokens, n):
        real = (jnp.arange(tokens.shape[0]) < n)[:, None]
        held = []
        rk.forward(params, tokens, ref.cfg, on_route=lambda _layer, w, _g:
                   held.append(jnp.sum((w[:, lo:lo + count] > 0) & real)))
        return jnp.stack(held) / (n * ref.cfg["num_experts_per_tok"])

    fn = jax.jit(shares)
    return [[round(100 * float(v), 2) for v in np.asarray(
        fn(ref.params, jnp.asarray(rk.padded(s, pad_to)), len(s)))]
        for s in sents]


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="kimi_k2_dp_ep32")
    ap.add_argument("--traffic", default="closed_c96_code")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--routing", type=int, nargs="*", metavar="TOKENS",
                    help="check prompts (by their configured length; none "
                         "named: all) whose routing is read")
    ap.add_argument("--plant", choices=("page_table",))
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_kimi_k2 as rk
    from benchmark.generators.requests import FIRST_TOKEN_ID
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check = config["check"]
    seed = args.seed
    params = family.make_params(cfg, seed)
    engine = family.make_engine(cfg, params, config,
                                man.traffic_doc(args.traffic))
    if args.plant:
        share_slot_zeros_pages(engine)
    engine.start(warmup=False)
    rc = family.reference_config(cfg)
    # every sequence padded to one length: one compile a reference
    check = dict(check, pad_min=family.pad_to(max(check["prompt_tokens"]),
                                              check["new_tokens"], 512))
    ref = rk.Reference(params, rc)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    try:
        sents = [family.cut_prompt(
            ref, rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n),
            check["new_tokens"], check["pad_min"])
            for n in check["prompt_tokens"]]
        outs, live = family.engine_outputs(engine, sents, check, rng)
    finally:
        engine.close(drain=False, timeout=30)
    device = jax.devices()[0].device_kind
    for only in (None,) + (() if args.plant else rk.CONTROLS):
        judged = ref if only is None else rk.Reference(
            params, rc, via="float8_e4m3fn", only=only)
        compared, notes, detail = family.judge(judged, sents, outs, live,
                                               check)
        print(json.dumps({
            "seed": seed, "reference": only or "as it is",
            "planted": args.plant, "correct": not notes,
            "compared": compared, "notes": notes,
            "undecided": [g["undecided"]
                          for g in detail["prompts"].values()],
            "device": device}), flush=True)
    if args.plant:
        return
    if args.routing is not None:
        read = routing_reader(ref, engine, check["pad_min"])
        for n, sent, (first_logits, _) in zip(check["prompt_tokens"], sents,
                                              outs):
            if n in (args.routing or check["prompt_tokens"]):
                print(json.dumps(dict(read(sent, first_logits), seed=seed,
                                      prompt=n, device=device)), flush=True)
    lo, count = cfg.experts_held
    print(json.dumps({
        "seed": seed, "held_pair_share_by_sequence_and_layer":
        held_pair_share_by_sequence(ref, sents, check["pad_min"]),
        "even": round(100 * count / cfg.num_experts, 2),
        "device": device}), flush=True)


if __name__ == "__main__":
    main()
