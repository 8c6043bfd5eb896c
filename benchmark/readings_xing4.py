"""By hand, on the chip: the readings behind the `xing4` family's limits
(reference_xing4.LOGIT_ERR, DRAFT_LOGIT_ERR, LATENT_ERR, RULE_DISTANCE ...),
for the check prompts of a configuration and a seed.

    python3 -m benchmark.readings_xing4 [--config xing4_29b_pp8] [--seed 11]
                                        [--plant stale_row]
                                        [--temperatures 2.0 2.8 ...]

(one seed a process: two sets of weights do not fit the chip)

The check prompts go through the engine once, as `check_correct` sends them
(every other slot live, the module drafting), and what came out is judged,
by the same `judge`, against the reference on the weights as they are and
then, each of which has to come out as NOT correct by at least one limit:
against its controls in the nearest precision below what the configuration
states (reference_xing4.CONTROLS: `weights`, every weight matrix through
float8 e4m3 below bfloat16; `maps`, the residual maps computed in bfloat16
where float32 is stated), against the reference with the module fed the
hidden state one position off (`hidden_off`), and against the rule with a
rejection that redraws from p and not from norm(max(p - q, 0))
(`redraw_p`): what the check would read of an engine with that fault. One
line a judge: what it compared beside the limits, and `correct`.

`--plant stale_row` plants a fault in the ENGINE instead and judges it
against the reference as it is: after a rejection the slot's position moves
on by two and not by one, so the rejected draft's latent row stays where it
was written and the next step attends it.

`--temperatures` reads, for each, the share of drafts accepted over the
requests beside the check at that sampling temperature and nothing else
(the sweep the traffic file's temperature was set from; seeded weights).
"""

from __future__ import annotations

import argparse
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VIA = {"weights": "float8_e4m3fn", "maps": "bfloat16"}
PLANTS = ("stale_row",)


def leave_rejected_rows(engine):
    """The planted fault: a slot whose draft was rejected moves on by two
    positions, as if it had been accepted, so the rejected draft's latent
    row is never overwritten and the next step attends it."""
    import jax.numpy as jnp

    build = engine._draft_step

    def faulty(bucket, step_block):
        step = build(bucket, step_block)

        def stepped(params, pools, feed, last_tokens, spec):
            fetch, pools, last_tokens, spec, kept = step(
                params, pools, feed, last_tokens, spec)
            slot, carried = feed["carry"][:, 0], feed["carry"][:, 1] > 0
            count = fetch[2 * bucket:3 * bucket]
            moved = kept["pos"] + jnp.where(carried, 2, count)
            return fetch, pools, last_tokens, dict(
                spec, pos=spec["pos"].at[slot].set(moved, mode="drop")), kept

        return stepped

    engine._draft_step = faulty


def accept_share(engine, cfg, check: dict, temperature: float, rng) -> dict:
    """The requests beside the check alone, at `temperature`: the share of
    drafts accepted and the tokens a row took a step."""
    from paddle_tpu.core import telemetry

    from benchmark.generators.requests import FIRST_TOKEN_ID

    beside = check["beside"]
    before = dict(telemetry.counters())
    reqs = [engine.submit(
        rng.randint(FIRST_TOKEN_ID, cfg.vocab_size,
                    beside["prompt_tokens"][i % len(beside["prompt_tokens"])]),
        max_new_tokens=min(beside["new_tokens"], 200), stop_at_eos=False,
        temperature=temperature, seed=int(rng.randint(2 ** 31)))
        for i in range(beside["requests"])]
    for r in reqs:
        r.result(900.0)
    c = {k: v - before.get(k, 0) for k, v in telemetry.counters().items()
         if k.startswith("decode.")}
    return {"temperature": temperature,
            "accept_share": round(100.0 * c["decode.draft_accepted"]
                                  / c["decode.draft_proposed"], 2),
            "tokens_per_row_step": round(
                c["decode.tokens"] / c["decode.rows_stepped"], 3)}


def main(argv=None, root=CHECKOUT):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="xing4_29b_pp8")
    ap.add_argument("--traffic", default="closed_c96_reasoning")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--plant", choices=PLANTS)
    ap.add_argument("--temperatures", type=float, nargs="*")
    args = ap.parse_args(argv)
    from benchmark import run

    run._prepare_environment()
    import jax
    import numpy as np

    from benchmark import reference_xing4 as rx
    from benchmark.manifest import Manifest

    man = Manifest(root)
    config = man.config_doc(args.config)
    family = man.family(config["family"])
    cfg = family.model_config(config)
    check, seed = config["check"], args.seed
    params = family.make_params(cfg, seed)
    rc = family.reference_config(cfg)
    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    engine = family.make_engine(cfg, params, config,
                                man.traffic_doc(args.traffic))
    if args.plant == "stale_row":
        leave_rejected_rows(engine)
    engine.start(warmup=False)
    device = jax.devices()[0].device_kind
    try:
        if args.temperatures:
            for t in args.temperatures:
                print(json.dumps(dict(accept_share(engine, cfg, check, t,
                                                   rng),
                                      seed=seed, device=device)), flush=True)
            return
        prompts = family.check_prompts(cfg, check, rng)
        outs, live = family.engine_outputs(engine, prompts, check, rng)
    finally:
        engine.close(drain=False, timeout=30)
    # one judge at a time: each reference is a compile and its activations
    judges = [("as it is", {}, False)]
    if not args.plant:
        judges += [(only, {"via": VIA[only], "only": only}, False)
                   for only in rx.CONTROLS]
        judges += [("hidden_off", {"fault": "hidden_off"}, False),
                   ("redraw_p", {}, True)]
    for name, how, redraw in judges:
        ref = rx.Reference(params, rc, **how)
        compared, notes, detail = family.judge(ref, prompts, outs, live,
                                               check, redraw_from_p=redraw)
        print(json.dumps({
            "seed": seed, "reference": name, "planted": args.plant,
            "correct": not notes, "compared": compared, "notes": notes,
            "steps": detail["steps"], "accepted": detail["accepted"],
            "device": device}), flush=True)
        del ref


if __name__ == "__main__":
    main()
