"""ERNIE-large step-time ablation — decompose the north-star step.

Runs several program variants in ONE process on the chip and prints
ms/step for each, so the full step can be attributed to
forward / backward / optimizer / attention-dropout / chunking.

Measurement traps handled (see tools/bench_models.py):
  * feeds pre-transferred once;
  * variants whose steps do NOT advance device state (fwd-only,
    fwd+bwd) rotate across 8 distinct staged feeds so no two
    consecutive dispatches see identical inputs;
  * fetch-free windows closed by one loss fetch.

Usage: python tools/ablate_ernie.py [--steps 12] [--variants a,b,...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build(attn_dropout=0.1, hidden_dropout=0.1, optimizer="adamw",
          prune=None, chunk_mb=None):
    """Build the bench-identical ERNIE-large program; prune='fwd' drops
    backward+optimizer ops, prune='bwd' drops optimizer ops."""
    import paddle_tpu as pt
    from paddle_tpu.core import ir, unique_name
    from paddle_tpu.models import bert

    if chunk_mb is not None:
        from paddle_tpu.ops.pallas import flash_attention as fa

        fa.XLA_ATTN_CHUNK_TARGET_BYTES = chunk_mb << 20
    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    unique_name.switch()
    cfg = bert.ernie_large()
    cfg.dtype = "bfloat16"
    cfg.use_flash_attention = True
    cfg.attention_probs_dropout_prob = attn_dropout
    cfg.hidden_dropout_prob = hidden_dropout
    main, startup, feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=512, optimizer_name=optimizer,
        max_predictions_per_seq=80)
    fetch = fetches["loss"]
    if prune:
        fetch = prune_program(main, startup, fetches["loss"], prune)
    return cfg, main, startup, fetch


def prune_program(main, startup, loss_var, prune):
    """Drop optimizer (+ backward for prune='fwd') ops and install the
    probe machinery that defeats the executor's DCE (see module doc).
    Returns the fetch variable for the pruned program."""
    from paddle_tpu.core.ir import OpDesc, OpRole

    blk = main.global_block()
    fetch = loss_var

    def drop(op):
        r = int(op.attrs.get("op_role", 0))
        if r & int(OpRole.Optimize) or r & int(OpRole.LRSched):
            return True
        if prune == "fwd" and (r & 0xF) == int(OpRole.Backward):
            return True
        return False

    blk.ops = [op for op in blk.ops if not drop(op)]
    if prune == "bwd":
        # grads are not persistable: without a consumer XLA would DCE
        # the whole backward (especially every dW matmul, which only
        # feeds the removed optimizer). Probe = sum of all grad means,
        # fetched instead of the loss (~one extra bf16 read pass).
        parts = []
        for i, (p, g) in enumerate(sorted(main.grad_var_map.items())):
            if not blk.has_var(g):
                continue
            out = blk.create_var(name=f"_probe_{i}", shape=(1,),
                                 dtype="float32")
            blk.ops.append(OpDesc(
                "reduce_mean", {"X": [g]}, {"Out": [out.name]},
                {"dim": None, "keep_dim": False, "reduce_all": True}))
            parts.append(out.name)
        probe = blk.create_var(name="_grad_probe", shape=(1,),
                               dtype="float32")
        blk.ops.append(OpDesc("sum", {"X": parts},
                              {"Out": [probe.name]}, {}))
        fetch = probe
    # Without persistable writes the executor's no-fetch executable
    # DCEs the whole computation (outputs = state + fetches only).
    # Accumulate the probe into a persistable scalar: keeps every
    # step's compute alive AND chains steps through device state so
    # no dispatch sees repeated inputs.
    acc = blk.create_var(name="_probe_acc", shape=(1,),
                         dtype="float32", persistable=True)
    src = fetch.name if prune == "bwd" else loss_var.name
    blk.ops.append(OpDesc("cast", {"X": [src]}, {"Out": ["_probe_f32"]},
                          {"out_dtype": "float32"}))
    blk.create_var(name="_probe_f32", shape=(1,), dtype="float32")
    blk.ops.append(OpDesc("sum", {"X": [acc.name, "_probe_f32"]},
                          {"Out": [acc.name]}, {}))
    sblk = startup.global_block()
    sblk.create_var(name=acc.name, shape=(1,), dtype="float32",
                    persistable=True)
    sblk.ops.append(OpDesc("fill_constant", {}, {"Out": [acc.name]},
                           {"shape": [1], "value": 0.0,
                            "dtype": "float32"}))
    main._bump_version()
    startup._bump_version()
    return fetch


def measure(main, startup, loss_v, *, steps, rotate_feeds, windows=3,
            make_feed=None, n_rotate=8):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import bert

    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    if make_feed is None:
        cfg = bert.ernie_large()
        make_feed = lambda i: bert.synthetic_pretraining_batch(  # noqa: E731
            cfg, 32, 512, seed=i, max_predictions_per_seq=80)
    n_feeds = n_rotate if rotate_feeds else 1
    feeds = []
    for i in range(n_feeds):
        data = make_feed(i)
        feeds.append({k: jnp.asarray(v) for k, v in data.items()})
    for _ in range(2):
        exe.run(main, feed=feeds[0], fetch_list=[loss_v], scope=scope)
        exe.run(main, feed=feeds[0], fetch_list=[], scope=scope)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for s in range(steps - 1):
            exe.run(main, feed=feeds[s % n_feeds], fetch_list=[],
                    scope=scope)
        out = exe.run(main, feed=feeds[(steps - 1) % n_feeds],
                      fetch_list=[loss_v], scope=scope)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e3, float(np.asarray(out[0]).reshape(-1)[0])


VARIANTS = {
    # name: (build kwargs, rotate_feeds)
    "full": (dict(), False),
    "no_attn_dropout": (dict(attn_dropout=0.0), False),
    "no_hid_dropout": (dict(hidden_dropout=0.0), False),
    "no_dropout": (dict(attn_dropout=0.0, hidden_dropout=0.0), False),
    "sgd": (dict(optimizer="sgd"), False),
    "fwd_bwd": (dict(prune="bwd"), True),
    "fwd": (dict(prune="fwd"), True),
    "chunk512": (dict(chunk_mb=512), False),
    "chunk128": (dict(chunk_mb=128), False),
    "pallas_adamw": (dict(), False),       # PT_FUSED_ADAMW=1
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--variants", default="full,fwd,fwd_bwd,pallas_adamw")
    args = ap.parse_args()
    results = {}
    for name in args.variants.split(","):
        kw, rotate = VARIANTS[name]
        if name == "pallas_adamw":
            os.environ["PT_FUSED_ADAMW"] = "1"
        try:
            cfg, mainp, startup, loss_v = build(**kw)
            ms, loss = measure(mainp, startup, loss_v,
                               steps=args.steps, rotate_feeds=rotate)
            results[name] = {"ms": round(ms, 2), "loss": round(loss, 4)}
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            if name == "pallas_adamw":
                os.environ.pop("PT_FUSED_ADAMW", None)
        print(json.dumps({name: results[name]}), flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
