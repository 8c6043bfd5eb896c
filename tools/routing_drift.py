"""On the chip: how the Mellum cell's routing and loss move over the first
steps at a learning rate, from the seeded weights: the cell's own program
(runners/train_lm.build), 13 warm-up steps on the ring's first batch as the
runner makes them, then `--steps` steps round the ring. One JSON line every
`--every` steps: loss, the step's pairs on held experts a layer, the
largest group's rows.

    python tools/routing_drift.py --lr 1e-4 3e-5 1e-5 [--steps 120]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lr", type=float, nargs="+", default=[1e-4])
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000004311)
    ap.add_argument("--config", default="mellum2_12b_tp4ep4")
    ap.add_argument("--traffic", default="lm_ring8_b2_s8192")
    args = ap.parse_args()
    from benchmark import run

    run._prepare_environment()
    import numpy as np

    import paddle_tpu as pt
    from benchmark import generators
    from benchmark.manifest import Manifest
    from benchmark.runners import train_lm
    from paddle_tpu.models import mellum

    man = Manifest(run.CHECKOUT)

    def doc(name, load):
        if name.endswith(".json"):          # a file: a rehearsal's preset
            with open(name) as fh:
                return json.load(fh)
        return load(name)

    config = doc(args.config, man.config_doc)
    traffic = doc(args.traffic, man.traffic_doc)
    for lr in args.lr:
        cfg, main_p, startup, loss_v = train_lm.build(
            dict(config, runner=dict(config["runner"], lr=lr)), traffic,
            args.seed)
        ring = generators.load(traffic["generator"]).make(
            traffic, args.seed, cfg.vocab_size)
        exe, scope = pt.Executor(), pt.Scope()
        exe.run(startup, scope=scope, use_compiled=False)
        fetch = [loss_v, mellum.COUNTS_VAR, mellum.MAX_ROWS_VAR]
        t0 = time.perf_counter()
        for i in range(13 + args.steps):
            feed = ring[0] if i < 12 else ring[(i - 12) % len(ring)]
            loss, counts, rows = exe.run(main_p, feed=feed, scope=scope,
                                         fetch_list=fetch)
            if i < 13 or (i - 13) % args.every == 0 \
                    or i == 12 + args.steps:
                print(json.dumps({
                    "lr": lr, "step": i,
                    "loss": round(float(np.asarray(loss).reshape(-1)[0]), 4),
                    "held_rows_per_expert": round(float(
                        counts[1]) / cfg.n_layers / cfg.experts_held[1], 1),
                    "max_group_rows": int(np.asarray(rows).reshape(-1)[0]),
                    "s": round(time.perf_counter() - t0, 1)}), flush=True)
        del exe, scope


if __name__ == "__main__":
    main()
