"""By hand, on the chip: the readings behind the `afmoe` family's limits
(reference_afmoe.LOGIT_ERR, MARGIN, UNDECIDED_MARGIN), for the check
prompts of a configuration and a seed.

    python3 -m benchmark.readings_afmoe [--config trinity_large_tp8ep8] [--seed 11]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends
them (every other slot live), and what came out is judged, by the same
`judge`, against the reference on the weights as they are and against each
lower-precision control of it (reference_afmoe.CONTROLS, float8 e4m3, the
nearest precision below bfloat16): every weight matrix, the routed
experts' matrices alone, the keys and values alone. One line a judge:
what it compared beside the limits, and `correct`. A control has to come
out as not correct by at least one of the limits.
"""

from __future__ import annotations

import argparse
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="trinity_large_tp8ep8")
    ap.add_argument("--traffic", default="closed_c96_agent")
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_afmoe as ra
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
    engine.start(warmup=False)
    rc = family.reference_config(cfg)
    # every sequence padded to one length: one compile a reference
    check = dict(check, pad_min=family.pad_to(max(check["prompt_tokens"]),
                                              check["new_tokens"], 512))
    ref = ra.Reference(params, rc)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    try:
        sents = [family.cut_prompt(
            ref, rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n),
            check["new_tokens"], check["pad_min"])
            for n in check["prompt_tokens"]]
        outs, live = family.engine_outputs(engine, sents, check, rng)
    finally:
        engine.close(drain=False, timeout=30)
    for only in (None,) + ra.CONTROLS:
        judged = ref if only is None else ra.Reference(
            params, rc, via="float8_e4m3fn", only=only)
        compared, notes, detail = family.judge(judged, sents, outs, live,
                                               check)
        print(json.dumps({
            "seed": seed, "reference": only or "as it is",
            "correct": not notes, "compared": compared, "notes": notes,
            "undecided": [g["undecided"]
                          for g in detail["prompts"].values()],
            "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
