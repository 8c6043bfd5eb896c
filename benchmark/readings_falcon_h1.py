"""By hand, on the chip: the readings behind the `falcon_h1` family's limits
(reference_falcon_h1.LOGIT_ERR, MARGIN, STATE_ERR), for the check prompts
of a configuration and a seed.

    python3 -m benchmark.readings_falcon_h1 [--config falcon_h1_34b_pp12]
                                            [--seed 11]
                                            [--plant state_slot|conv_tail|
                                                     mup_layout]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends
them (every other slot live), and what came out is judged, by the same
`judge`, against the reference on the weights as they are and against each
control of it in the nearest precision below the configuration's
(reference_falcon_h1.CONTROLS): every weight matrix through float8 e4m3
(`weights`), K and V through float8 as pages would hold them (`kv`), the
recurrent state rounded to bfloat16 after every token (`state`). One line
a judge: what it compared beside the limits, and `correct`. A control has
to come out as not correct by at least one of the limits.

`--plant` reads a planted fault at the timed size instead, against the
reference as it is, and no control beside it: `state_slot` feeds, in every
step, the last two live rows each other's recurrent state and conv tail
(`swap_last_rows_state`); `conv_tail` makes the prefill keep the inputs at
the END of the padded bucket as the slot's conv tail, not those of the
last real tokens (`tail_from_the_buckets_end`); `mup_layout` hands the
engine a muP table with the multipliers of B and C exchanged
(`b_and_c_swapped`): the reference lays its own out from the configuration.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VIA = {"weights": "float8_e4m3fn", "kv": "float8_e4m3fn",
       "state": "bfloat16"}


def swap_last_rows_state(engine):
    """The planted fault: before every step the last two live rows'
    slots exchange their recurrent states and conv tails, so each row is
    advanced on another request's state. The check prompts are seated last:
    they are the rows it hits."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving.kv_cache import state_array_names

    entry_of = engine._entry
    names = [n for i in engine.kv.state_layers for n in state_array_names(i)]

    def faulty_entry(phase, bucket):
        entry = entry_of(phase, bucket)
        if phase != "step":
            return entry

        def step(params, pools, feed, last_tokens):
            slots = np.asarray(feed["carry"])[:, 0]
            live = slots[slots < engine.config.max_slots]
            if live.size >= 2:
                ab = jnp.asarray(live[-2:])
                pools = dict(pools)
                for n in names:
                    pools[n] = pools[n].at[ab].set(pools[n][ab[::-1]])
            return entry(params, pools, feed, last_tokens)

        return step

    engine._entry = faulty_entry


@contextlib.contextmanager
def tail_from_the_buckets_end():
    """The planted fault: while this is open, `ssm_conv_prefill` keeps the
    last inputs of the padded BUCKET as the slot's tail (programs are
    traced under it: open it around the engine's life)."""
    import jax.numpy as jnp

    from paddle_tpu.core import registry

    op = registry.get("ssm_conv_prefill")
    sound = op.forward

    def faulty(ins, attrs):
        out = sound(ins, attrs)
        whole = dict(ins, Lengths=[jnp.full_like(
            ins["Lengths"][0], ins["XBC"][0].shape[1])])
        out["ConvTailOut"] = sound(whole, attrs)["ConvTailOut"]
        return out

    op.forward = faulty
    try:
        yield
    finally:
        op.forward = sound


def b_and_c_swapped(cfg, params) -> dict:
    """The planted fault: `params` with the program's muP table laid out
    z, x, C, B, dt, as a misreading of `ssm_multipliers` would have it. The
    reference does not read the table, so it judges the layout."""
    import numpy as np

    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    at = 2 * cfg.d_ssm
    mup = np.array(params["fh_mup_vector"], np.float32)
    mup[at:at + 2 * gn] = np.concatenate([mup[at + gn:at + 2 * gn],
                                          mup[at:at + gn]])
    return dict(params, fh_mup_vector=mup)


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="falcon_h1_34b_pp12")
    ap.add_argument("--traffic", default="closed_c96_chat")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--plant", choices=("state_slot", "conv_tail",
                                        "mup_layout"))
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_falcon_h1 as rf
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check, seed = config["check"], args.seed
    params = family.make_params(cfg, seed)
    planted = tail_from_the_buckets_end() if args.plant == "conv_tail" \
        else contextlib.nullcontext()
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    sents = family.check_prompts(cfg, check, rng)
    with planted:
        engine = family.make_engine(
            cfg, b_and_c_swapped(cfg, params) if args.plant == "mup_layout"
            else params, config, man.traffic_doc(args.traffic))
        if args.plant == "state_slot":
            swap_last_rows_state(engine)
        engine.start(warmup=False)
        try:
            outs, live = family.engine_outputs(engine, sents, check, rng)
        finally:
            engine.close(drain=False, timeout=30)
    rc = family.reference_config(cfg)
    device = jax.devices()[0].device_kind
    for only in (None,) + (() if args.plant else rf.CONTROLS):
        judged = rf.Reference(params, rc) if only is None else rf.Reference(
            params, rc, via=VIA[only], only=only)
        compared, notes, _ = family.judge(judged, sents, outs, live, check)
        print(json.dumps({
            "seed": seed, "reference": only or "as it is",
            "planted": args.plant, "correct": not notes,
            "compared": compared, "notes": notes, "device": device}),
            flush=True)
        del judged


if __name__ == "__main__":
    main()
