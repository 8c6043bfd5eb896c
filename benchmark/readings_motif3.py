"""By hand, on the chip: the readings behind the `motif3` family's limits
(reference_motif3.LOGIT_ERR, MARGIN, UNDECIDED_MARGIN, LATENT_ERR), for the
check prompts of a configuration and a seed.

    python3 -m benchmark.readings_motif3 [--config motif3_beta_dp_ep8]
                                         [--seed 11]
                                         [--plant ring_table|no_noise|
                                                  sinkhorn_1]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends them
(every other slot live), and what came out is judged, by the same `judge`,
against the reference on the weights as they are and against each control of
it in the nearest precision below the configuration's
(reference_motif3.CONTROLS, float8 e4m3 below bfloat16): the latent rows as
a ring or a page would hold them (`latent`), every weight matrix
(`weights`). One line a judge: what it compared beside the limits, and `correct`. A control
has to come out as not correct by at least one of the limits.

`--plant` reads a planted fault at the timed size instead, against the
reference as it is: `ring_table` feeds every later live row of a step the
first row's RING table (its page table stays its own: the window layers
alone attend and overwrite a neighbour's rows); `no_noise` judges the sound
engine against the reference WITHOUT the noise head's subtraction, and
`sinkhorn_1` against the reference with one Sinkhorn iteration: what the
check would read of an engine that dropped either.
"""

from __future__ import annotations

import argparse
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VIA = "float8_e4m3fn"
PLANTS = ("ring_table", "no_noise", "sinkhorn_1")


def share_slot_zeros_ring(engine):
    """The planted fault: every later live row of a step is fed the first
    row's ring table, so several requests write their window layers' rows
    over and attend one another's. One live row is fed what it always
    was."""
    feed = engine._feed

    def faulty(phase, bucket, parts):
        if phase == "step":
            ring = parts["ring_table"].copy()
            live = parts["page_table"].any(axis=1)
            ring[1:][live[1:]] = ring[0]
            parts = dict(parts, ring_table=ring)
        return feed(phase, bucket, parts)

    engine._feed = faulty


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="motif3_beta_dp_ep8")
    ap.add_argument("--traffic", default="closed_c96_longdoc")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--plant", choices=PLANTS)
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_motif3 as rm
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check, seed = config["check"], args.seed
    params = family.make_params(cfg, seed)
    rc = family.reference_config(cfg)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    ref = rm.Reference(params, rc)
    sents = family.check_prompts(ref, cfg, check, rng)
    engine = family.make_engine(cfg, params, config,
                                man.traffic_doc(args.traffic))
    if args.plant == "ring_table":
        share_slot_zeros_ring(engine)
    engine.start(warmup=False)
    try:
        outs, live = family.engine_outputs(engine, sents, check, rng)
    finally:
        engine.close(drain=False, timeout=30)
    device = jax.devices()[0].device_kind
    judges = {"as it is": ref}
    if args.plant == "no_noise":
        judges = {"without the noise head": rm.Reference(params, rc,
                                                         noise=False)}
    elif args.plant == "sinkhorn_1":
        judges = {"one Sinkhorn iteration": rm.Reference(
            params, rc, sinkhorn_iters=1)}
    elif not args.plant:
        for only in rm.CONTROLS:
            judges[only] = (params, rc, only)
    for name, judged in judges.items():
        if isinstance(judged, tuple):       # made one at a time: each is a
            judged = rm.Reference(judged[0], judged[1], via=VIA,  # compile
                                  only=judged[2])
        compared, notes, _ = family.judge(judged, sents, outs, live, check)
        print(json.dumps({
            "seed": seed, "reference": name, "planted": args.plant,
            "correct": not notes, "compared": compared, "notes": notes,
            "device": device}), flush=True)
        del judged


if __name__ == "__main__":
    main()
