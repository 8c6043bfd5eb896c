"""By hand, on the chip: the readings behind the `lfm2` family's limits
(reference_lfm2.LOGIT_ERR, PROMPT_LOGIT_ERR, PREFILL_LOGIT_ERR,
FIRST_STEPS_LOGIT_ERR, TAIL_ERR, TAIL_ERR_FIRST, KV_ERR, KV_LAST_ERR,
KV_TURNED_SHARE), for the check prompts of a configuration and a seed.

    python3 -m benchmark.readings_lfm2 [--config lfm2_24b_pp5] [--seed 11]
                                       [--plant conv_tail] [--routing]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends them
(every other slot live), and what came out is judged, by the same `judge`,
against the reference on the weights as they are and then against the
reference built WRONG, each of which has to come out as NOT correct by at
least one limit: what the check would read of an engine with that fault.

  gates_swapped   C gates the convolution's input and B its output
  bias_weighted   the expert bias weighed into the kept scores
  no_qk_norm      the norms on q and k left out
  bf16            the lower-precision control: the router's scores and the
                  convolution's sum through bfloat16, where the
                  configuration states float32

One line a judge: what it compared beside the limits, and `correct`.

`--plant conv_tail` plants a fault in the ENGINE instead (no reference has
buckets) and judges it against the reference as it is: the prefill keeps z
at the END of the padded bucket as the slot's tail, not z of the prompt's
last two real tokens.

`--routing` adds one line a check prompt, the witness that what parts a
position from the reference by more than rounding is a routing choice that
turned, and nothing else: the experts the ENGINE keeps for the prompt
(`engine_routing`: its own prefill program of the prompt's bucket, each
routed layer's input scored as the op scores it) beside the reference's,
the (position, layer) pairs at which the two keep other experts, and the
prefill's logits row and the prompt's positions in the last attention
layer's pages against the reference as it is and against the reference
KEEPING THE ENGINE'S experts.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# judge's name -> Reference(...)'s arguments
JUDGES = {"gates_swapped": {"fault": "gates_swapped"},
          "bias_weighted": {"fault": "bias_weighted"},
          "no_qk_norm": {"fault": "no_qk_norm"},
          "bf16": {"via": "bfloat16"}}
PLANTS = ("conv_tail",)


@contextlib.contextmanager
def tail_from_the_buckets_end():
    """The planted fault: while this is open, `gated_short_conv_prefill`
    keeps z of the last positions of the padded BUCKET as the slot's tail
    (programs are traced under it: open it around the engine's life)."""
    import jax.numpy as jnp

    from paddle_tpu.core import registry

    op = registry.get("gated_short_conv_prefill")
    sound = op.forward

    def faulty(ins, attrs):
        out = sound(ins, attrs)
        whole = dict(ins, Lengths=[jnp.full_like(
            ins["Lengths"][0], ins["BCX"][0].shape[1])])
        out["ConvTailOut"] = sound(whole, attrs)["ConvTailOut"]
        return out

    op.forward = faulty
    try:
        yield
    finally:
        op.forward = sound


def engine_routing(engine, seq):
    """{routed layer: [len(seq), k]}: the experts the engine's own prefill
    program keeps for `seq`. The program of the sequence's bucket is run as
    `DecodeEngine._entry` runs it (on the engine's parameters and pools,
    read and not written; the scratch slot, the scratch page) and each
    `routed_experts` op's input is scored as that op scores it
    (readings_kimi_k2.py `engine_routing`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.executor import run_block

    cfg, n = engine.model_cfg, len(seq)
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
        return jnp.stack(out)

    parts = {"tokens": np.zeros((1, bucket), np.int32),
             "positions": np.arange(bucket, dtype=np.int32)[None],
             "lengths": np.asarray([n], np.int32),
             "state_slots": np.asarray([engine.config.max_slots], np.int32),
             "page_table": np.zeros((1, engine._mp), np.int32)}
    parts["tokens"][0, :n] = seq
    got = np.asarray(jax.jit(kept)(
        engine._params, engine._pools,
        {name: jnp.asarray(parts[name]) for name in feeds}))[:, :n]
    moe = [i for i in range(cfg.n_layers) if cfg.is_moe(i)]
    return dict(zip(moe, got))


def routing_line(ref, family, engine, sent, out, pad_min):
    """One check prompt's PREFILL against the reference as it is and
    against the reference keeping the engine's experts (`--routing`): the
    prefill's logits row and the prompt's positions in the last attention
    layer's pages (the steps' routing is the step program's own, which no
    prefill program repeats)."""
    import numpy as np

    from benchmark import reference_lfm2 as rl

    first_logits, pages = out[0], out[4]
    n, pad = sent.size, family.pad_to(sent.size, pad_min)
    engines = engine_routing(engine, sent)
    forced = {}
    for layer, idx in engines.items():
        forced[layer] = np.zeros((pad, idx.shape[1]), np.int32)
        forced[layer][:n] = idx
    args = (sent, pad, n - 1, 1, n - 1)
    own = ref.routed_rows(*args)
    on_engines = ref.routed_rows(*args, forced=forced)
    differ = int(sum(np.any(np.sort(own[3][j], axis=1)
                            != np.sort(idx, axis=1), axis=1).sum()
                     for j, idx in enumerate(engines.values())))
    line = {"sent": int(n), "positions_x_layers": int(n * len(engines)),
            "keep_other_experts": differ}
    for name, (rows, _tails, kv, _kept) in (
            ("as_it_is", own), ("on_the_engines_routing", on_engines)):
        kv_last = rl.kv_errors(pages[-1][:n], kv[-1])
        line[name] = {
            "prefill_logit_err": float(rl.logit_errors(first_logits[None],
                                                       rows)[0]),
            "kv_last_median": float(np.median(kv_last)),
            "kv_last_max": float(kv_last.max()),
            "kv_last_over_kv_turned": int(np.sum(kv_last > rl.KV_TURNED))}
    return line


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lfm2_24b_pp5")
    ap.add_argument("--traffic", default="closed_c192_assistant")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--plant", choices=PLANTS)
    ap.add_argument("--routing", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_lfm2 as rl
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check, seed = config["check"], args.seed
    params = family.make_params(cfg, seed)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    prompts = family.check_prompts(cfg, check, rng)
    with (tail_from_the_buckets_end if args.plant
          else contextlib.nullcontext)():
        engine = family.make_engine(cfg, params, config,
                                    man.traffic_doc(args.traffic))
        engine.start(warmup=False)
        rc = family.reference_config(cfg)
        try:
            outs, live = family.engine_outputs(engine, prompts, check, rng)
            if args.routing:
                ref = rl.Reference(params, rc)
                for (sent, _t, _seed), out in zip(prompts, outs):
                    print(json.dumps({"seed": seed, "routing": routing_line(
                        ref, family, engine, sent, out, check["pad_min"])}),
                        flush=True)
                del ref
        finally:
            engine.close(drain=False, timeout=30)
    device = jax.devices()[0].device_kind
    # one judge at a time: each reference is a compile and its activations
    for name, how in [("as it is", {})] + (
            [] if args.plant else list(JUDGES.items())):
        ref = rl.Reference(params, rc, **how)
        compared, notes, detail = family.judge(ref, prompts, outs, live,
                                               check)
        print(json.dumps({
            "seed": seed, "reference": name, "planted": args.plant,
            "correct": not notes, "compared": compared, "notes": notes,
            **{k: v for k, v in detail.items() if k != "prompts"},
            "row_errs": {n: p["rows"] for n, p in detail["prompts"].items()},
            "kv_first_max": {n: round(p["kv_first_max"], 6)
                             for n, p in detail["prompts"].items()},
            "kv_last_median": {n: round(p["kv_last_median"], 6)
                               for n, p in detail["prompts"].items()},
            "tail_err": {n: [round(e, 6) for e in p["tail_err"]]
                         for n, p in detail["prompts"].items()},
            "device": device}), flush=True)
        del ref


if __name__ == "__main__":
    main()
