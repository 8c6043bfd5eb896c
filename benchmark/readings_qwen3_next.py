"""By hand, on the chip: the readings behind the `qwen3_next` family's limits
(reference_qwen3_next.LOGIT_ERR, MARGIN, STATE_ERR, STATE_ERR_FIRST), for
the check prompts of a configuration and a seed.

    python3 -m benchmark.readings_qwen3_next [--config qwen3_next_80b_tp4ep4]
                                             [--seed 11]
                                             [--plant state_slot|conv_tail|
                                                      head_pairing]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends
them (every other slot live), and what came out is judged, by the same
`judge`, against the reference on the weights as they are and against each
control of it in the nearest precision below the configuration's
(reference_qwen3_next.CONTROLS): every weight matrix through float8 e4m3
(`weights`), K and V through float8 as pages would hold them (`kv`), the
matrix state rounded to bfloat16 after every token (`state`). One line a
judge: what it compared beside the limits, and `correct`. A control has to
come out as not correct by at least one of the limits.

`--plant` reads a planted fault at the timed size instead, against the
reference as it is, and no control beside it: `state_slot` feeds, in every
step, the last two live rows each other's matrix states and conv tails
(readings_falcon_h1 `swap_last_rows_state`: the state class is the same);
`conv_tail` makes the prefill keep the inputs at the END of the padded
bucket as the slot's conv tail, not those of the last real tokens
(readings_falcon_h1 `tail_from_the_buckets_end`: the convolution is the same
op); `head_pairing` pairs value head j with key head j mod (key heads) in
step and prefill alike, as a `tile` where the layout asks for a `repeat`
would (`value_heads_on_the_wrong_key_head`): the reference pairs 2i and
2i + 1 with i.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os

from benchmark.readings_falcon_h1 import (swap_last_rows_state,
                                          tail_from_the_buckets_end)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VIA = {"weights": "float8_e4m3fn", "kv": "float8_e4m3fn",
       "state": "bfloat16"}
PLANTS = ("state_slot", "conv_tail", "head_pairing")


@contextlib.contextmanager
def value_heads_on_the_wrong_key_head():
    """The planted fault: while this is open, the delta rule's terms give
    value head j the q and k of key head j mod (key heads), not of j // r
    (programs are traced under it: open it around the engine's life)."""
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention_ops as ops

    sound = ops.delta_rule_terms

    def faulty(q, k, v, a, b, a_log, dt_bias, nk, dk, nv, dv):
        qh, kh, vh, g, beta = sound(q, k, v, a, b, a_log, dt_bias, nk, dk,
                                    nv, dv)
        r = nv // nk
        tiled = [jnp.concatenate([x[..., ::r, :]] * r, axis=-2)
                 for x in (qh, kh)]
        return tiled[0], tiled[1], vh, g, beta

    ops.delta_rule_terms = faulty
    try:
        yield
    finally:
        ops.delta_rule_terms = sound


def planted(plant):
    """The context a planted fault's programs are traced under."""
    return {"conv_tail": tail_from_the_buckets_end,
            "head_pairing": value_heads_on_the_wrong_key_head}.get(
        plant, contextlib.nullcontext)()


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="qwen3_next_80b_tp4ep4")
    ap.add_argument("--traffic", default="closed_c96_longdoc")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--plant", choices=PLANTS)
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_qwen3_next as rq
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check, seed = config["check"], args.seed
    params = family.make_params(cfg, seed)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    sents = family.check_prompts(cfg, check, rng)
    with planted(args.plant):
        engine = family.make_engine(cfg, params, config,
                                    man.traffic_doc(args.traffic))
        if args.plant == "state_slot":
            swap_last_rows_state(engine)
        engine.start(warmup=False)
        try:
            outs, live = family.engine_outputs(engine, sents, check, rng)
        finally:
            engine.close(drain=False, timeout=30)
    rc = family.reference_config(cfg)
    device = jax.devices()[0].device_kind
    for only in (None,) + (() if args.plant else rq.CONTROLS):
        judged = rq.Reference(params, rc) if only is None else rq.Reference(
            params, rc, via=VIA[only], only=only)
        compared, notes, detail = family.judge(judged, sents, outs, live,
                                               check)
        print(json.dumps({
            "seed": seed, "reference": only or "as it is",
            "planted": args.plant, "correct": not notes,
            "compared": compared, "notes": notes, "device": device,
            "state_err_by_layer": {
                n: p["state_err_by_layer"]
                for n, p in detail["prompts"].items()}}), flush=True)
        del judged


if __name__ == "__main__":
    main()
