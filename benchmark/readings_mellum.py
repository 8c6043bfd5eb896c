"""By hand, on the chip: the readings behind the `train_lm` check's limits
(reference_mellum.LOSS_TOL, GRAD_TOL, ROUTING_AGREE_MIN, UPDATE_TOL) for a
configuration, its traffic and a seed.

    python3 -m benchmark.readings_mellum [--config mellum2_12b_tp4ep4]
                                         [--traffic lm_ring8_b2_s8192]
                                         [--seed 11]

(one seed a process: the program's state and the reference's float32
weights do not fit the chip side by side twice)

The timed program's first step (runners/train_lm.first_step) is judged, by
`reference_mellum.compare`, against the reference on the weights as they
are; then the reference's own step with every matrix rounded through
float8_e4m3fn (3 bits of mantissa where bfloat16 keeps 7: the nearest
precision below the configuration's), its AdamW step made from its own
gradients, is judged the same way, as if it were a program's. One line a
judge: what it compared beside the limits, and `correct`. The control has
to come out as not correct by at least one of the limits; each limit lies
between the two readings (the update's between its reading and 1, what an
unchanged state reads). A third line splits the update's reading: the
program's change against the reference's AdamW step made from THE
PROGRAM'S OWN fetched gradients (the optimizer op alone: lr, decay,
moments, rounding), and the share of the reference's moved elements whose
gradient's sign the program turns.
"""

from __future__ import annotations

import argparse
import gc
import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mellum2_12b_tp4ep4")
    ap.add_argument("--traffic", default="lm_ring8_b2_s8192")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--all", action="store_true",
                    help="hold every parameter's gradient, not the check's")
    args = ap.parse_args(argv)
    from . import run

    run._prepare_environment()
    import jax.numpy as jnp

    from . import reference_mellum as ref
    from .common import device_info
    from .manifest import Manifest
    from .runners import train_lm

    man = Manifest(CHECKOUT)
    config, traffic = man.config_doc(args.config), \
        man.traffic_doc(args.traffic)
    if args.all:
        from paddle_tpu.models import mellum

        config = dict(config, check={"grads": sorted(mellum.param_specs(
            train_lm.model_config(config)))})
    import numpy as np

    import paddle_tpu as pt

    built = train_lm.build(config, traffic, args.seed) + (pt.Executor(),
                                                          pt.Scope())
    step = train_lm.first_step(config, traffic, args.seed, built)
    gc.collect()
    params, batch, names = step["params"], step["batch"], list(step["grads"])
    model = train_lm.reference_model(built[0])
    reference = ref.loss_and_grads(params, batch["tokens"], batch["labels"],
                                   model)

    def judged(who, loss, grads, chosen, after):
        notes, compared = train_lm.judge(config, params, reference, loss,
                                         grads, chosen, after)
        print(json.dumps({
            "judged": who, "seed": args.seed, "device": device_info(),
            "correct": not notes, "loss": float(loss),
            "reference_loss": reference[0],
            "compared": {n: [v, lim] for n, v, lim in compared}}),
            flush=True)

    judged("program", step["loss"], step["grads"], step["chosen"],
           step["after"])
    low = ref.loss_and_grads(params, batch["tokens"], batch["labels"], model,
                             through=jnp.dtype("float8_e4m3fn"))
    low_grads = {n: low[1][n] for n in names}
    optimizer = train_lm.optimizer_of(config)
    judged("control: every matrix through float8_e4m3fn", low[0], low_grads,
           low[2], ref.adamw_first_step(params, low_grads, **optimizer))
    own = ref.adamw_first_step(params, step["grads"], **optimizer)
    wanted = ref.adamw_first_step(
        params, {n: reference[1][n] for n in names}, **optimizer)
    split = {}
    for n, (got, want) in ref.changes(params, step["after"], own).items():
        moved = np.asarray(wanted[n]) != np.asarray(params[n])
        turned = np.sign(np.asarray(step["grads"][n], np.float32)) \
            != np.sign(np.asarray(reference[1][n], np.float32))
        split[n] = {
            "op_alone_rel_err": ref._rel_err(got, want),
            "moved_share": float(moved.mean()),
            "sign_turned_share_of_moved":
                float(turned[moved].mean()) if moved.any() else None}
    print(json.dumps({"judged": "the update, split", "seed": args.seed,
                      "by_leaf": split}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
