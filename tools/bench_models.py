"""Model-step benchmark harness for the BASELINE workload ladder.

Methodology:
  * warmup steps (compile + pipeline fill), then N steps WITHOUT fetches
    (state advances on-device via donation), one final loss fetch to
    sync; ms/step = window / N. Repeat windows and take the fastest.
    On the TPU runtime block_until_ready blocks and a host fetch syncs
    in about a millisecond (checked on a v5e chip, PR 21), so a fetch
    per step would be a valid window too; the fetch-free window stays
    until the benchmark PR (ROADMAP A1) replaces the timing method.
  * vs_baseline = MFU / 0.35 (BASELINE.json north-star target).
  * MFU needs a peak for the device kind (core/costmodel.py): a kind the
    table lacks, the CPU included, raises instead of scoring against
    another chip's peak.

Usage: python tools/bench_models.py --workload ernie_large [--steps 40]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def peak_flops_per_chip() -> float:
    # the device table lives in core/costmodel.py (shared with the live
    # MFU gauge + roofline verdicts); FLAGS_device_peak_flops overrides,
    # and a device kind the table lacks raises
    from paddle_tpu.core.costmodel import peak_device_flops

    return peak_device_flops()


def transformer_step_flops(cfg, batch, seq, lm_positions=None) -> float:
    """6 * non-embedding-params * tokens + attention term (fwd+bwd)."""
    h, l, ff, v = (cfg.hidden_size, cfg.num_hidden_layers,
                   cfg.intermediate_size, cfg.vocab_size)
    per_layer = 4 * h * h + 2 * h * ff
    tokens = batch * seq
    lm_tokens = batch * (lm_positions if lm_positions else seq)
    matmul = 6.0 * l * per_layer * tokens + 6.0 * h * v * lm_tokens
    attn = 6.0 * 2 * l * batch * seq * seq * h
    return matmul + attn


def _time_steps(exe, prog, feed, loss_v, scope, *, steps, windows=3,
                warmup=2):
    """ms/step: fetch-free windows closed by a single loss fetch.

    Feeds are pre-transferred to the device ONCE — re-feeding numpy every
    step would time the host-to-device copy with the step (real input
    pipelines overlap transfers).
    Both cache entries (with and without the loss fetch) are warmed so
    no compile lands inside a timed window.

    FLAGS_exec_steps_per_dispatch=k > 1 switches the window to K-step
    fused dispatches (Executor.run_steps, one lax.scan per k steps):
    the window becomes n fused dispatches + one closing single-step loss
    fetch, so the measured ms/step carries 1/k of the per-dispatch host
    overhead — the pipelined-execution configuration BENCH rows record
    via extra.steps_per_dispatch.
    """
    import jax.numpy as jnp

    from paddle_tpu.core.flags import flag as _flag

    k = max(1, int(_flag("exec_steps_per_dispatch")))
    feed = {kk: jnp.asarray(v) for kk, v in feed.items()}
    stacked = None
    if k > 1:
        stacked = {kk: jnp.stack([v] * k) for kk, v in feed.items()}
    for _ in range(warmup):
        exe.run(prog, feed=feed, fetch_list=[loss_v], scope=scope)
        if stacked is not None:
            exe.run_steps(prog, feed=stacked, fetch_list=[], k=k,
                          scope=scope)
        else:
            exe.run(prog, feed=feed, fetch_list=[], scope=scope)
    best = float("inf")
    loss = None
    n_disp = max(1, (steps - 1) // k)
    total = n_disp * k + 1 if k > 1 else steps
    for _ in range(windows):
        t0 = time.perf_counter()
        if stacked is not None:
            for _ in range(n_disp):
                exe.run_steps(prog, feed=stacked, fetch_list=[], k=k,
                              scope=scope)
        else:
            for _ in range(steps - 1):
                exe.run(prog, feed=feed, fetch_list=[], scope=scope)
        out = exe.run(prog, feed=feed, fetch_list=[loss_v], scope=scope)
        dt = (time.perf_counter() - t0) / total
        best = min(best, dt)
        loss = float(np.asarray(out[0]).reshape(-1)[0])
    return best * 1e3, loss


def bench_bert_like(model_cfg_fn, *, seq, batch, max_preds, steps,
                    metric_name):
    import paddle_tpu as pt
    from paddle_tpu.models import bert

    cfg = model_cfg_fn()
    cfg.dtype = "bfloat16"
    cfg.use_flash_attention = True

    main_prog, startup, feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=seq, optimizer_name="adamw",
        max_predictions_per_seq=max_preds)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    data = bert.synthetic_pretraining_batch(
        cfg, batch, seq, max_predictions_per_seq=max_preds)
    ms, loss = _time_steps(exe, main_prog, data, fetches["loss"], scope,
                           steps=steps)
    dt = ms / 1e3
    tokens_per_sec = batch * seq / dt
    flops = transformer_step_flops(cfg, batch, seq, lm_positions=max_preds)
    mfu = flops / dt / peak_flops_per_chip()
    return {
        "metric": metric_name,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {"ms_per_step": round(ms, 2), "mfu": round(mfu, 4),
                  "model_flops": flops,
                  "batch": batch, "seq_len": seq, "loss": round(loss, 4)},
    }


def bench_ernie_large(steps=30, batch=None, seq=512, max_preds=80):
    from paddle_tpu.models import bert

    # batch 40: round-5 re-sweep (30/32/34/36/40/44/48) after the packed
    # kernels — 66.2k tok/s / 66.5% MFU at 40 vs 64.8k at 32 and 63.9k
    # at the old round-4 optimum 34 (reproduced twice within 0.15%);
    # the round-2 "b40 worse / b48 OOM" no longer holds on this graph
    batch = batch or int(os.environ.get("PT_BENCH_BATCH", "40"))
    return bench_bert_like(
        bert.ernie_large, seq=seq, batch=batch, max_preds=max_preds,
        steps=steps, metric_name="ernie_large_pretrain_tokens_per_sec_per_chip")


def bench_bert_base(steps=30, batch=None, seq=128, max_preds=20):
    from paddle_tpu.models import bert

    batch = batch or int(os.environ.get("PT_BENCH_BATCH", "384"))
    return bench_bert_like(
        bert.bert_base, seq=seq, batch=batch, max_preds=max_preds,
        steps=steps, metric_name="bert_base_pretrain_tokens_per_sec_per_chip")


def bench_resnet50(steps=20, batch=None, amp=True):
    import paddle_tpu as pt
    from paddle_tpu.models import resnet

    batch = batch or int(os.environ.get("PT_BENCH_BATCH", "256"))
    cfg = resnet.resnet50()
    main_prog, startup, feeds, fetches = resnet.build_classifier_program(
        cfg, batch_size=batch, amp=amp)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    data = resnet.synthetic_batch(cfg, batch)
    ms, loss = _time_steps(exe, main_prog, data, fetches["loss"], scope,
                           steps=steps)
    dt = ms / 1e3
    # ResNet-50 ~3.8 GFLOPs fwd per 224x224 image -> ~3x for fwd+bwd
    flops = 3 * 3.8e9 * batch
    mfu = flops / dt / peak_flops_per_chip()
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(batch / dt, 1),
        "unit": "imgs/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {"ms_per_step": round(ms, 2), "mfu": round(mfu, 4),
                  "model_flops": flops,
                  "batch": batch, "loss": round(loss, 4)},
    }


def bench_long_context(steps=8, batch=None, seq=2048, max_preds=None):
    """Long-context single-chip rows (round-4/5 table in BASELINE.md):
    ERNIE-large geometry with the position table extended to seq.
    seq=2048 b4 / seq=4096 b2; bf16, attention dropout on."""
    import dataclasses

    from paddle_tpu.models import bert

    batch = batch or int(os.environ.get(
        "PT_BENCH_BATCH", "4" if seq <= 2048 else "2"))
    max_preds = max_preds or max(80, seq * 15 // 100)

    def cfg_fn():
        cfg = bert.ernie_large()
        return dataclasses.replace(cfg, max_position_embeddings=seq)

    return bench_bert_like(
        cfg_fn, seq=seq, batch=batch, max_preds=max_preds, steps=steps,
        metric_name=f"ernie_large_s{seq}_tokens_per_sec_per_chip")


def bench_long_context_4096(steps=8, batch=None):
    return bench_long_context(steps=steps, batch=batch, seq=4096)


def bench_mnist(steps=200, batch=None):
    """Ladder config 1: LeNet MNIST smoke (reference fixture:
    tests/book/test_recognize_digits.py). Tiny model — dispatch-bound,
    so the window is long to amortise its closing fetch."""
    import paddle_tpu as pt
    from paddle_tpu.models import lenet

    batch = batch or 512
    main_prog, startup, feeds, fetches = lenet.build_lenet_program(
        batch_size=batch)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    rng = np.random.RandomState(0)
    data = {"img": rng.randn(batch, 1, 28, 28).astype(np.float32),
            "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
    ms, loss = _time_steps(exe, main_prog, data, fetches["loss"], scope,
                           steps=steps)
    dt = ms / 1e3
    flops = 3 * 2.3e6 * batch  # ~2.3 MFLOPs/img fwd
    mfu = flops / dt / peak_flops_per_chip()
    return {
        "metric": "mnist_lenet_images_per_sec_per_chip",
        "value": round(batch / dt, 1),
        "unit": "imgs/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {"ms_per_step": round(ms, 2), "batch": batch,
                  "model_flops": flops, "loss": round(loss, 4)},
    }


def bench_transformer_big(steps=15, batch=None, seq=256):
    """Ladder config 5: Transformer-big WMT14 En-De (reference
    dist_transformer.py fixture geometry), bf16 via static AMP."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    batch = batch or int(os.environ.get("PT_BENCH_BATCH", "48"))
    cfg = transformer.transformer_big()
    main_prog, startup, feeds, fetches = transformer.build_wmt_program(
        cfg, seq_len=seq, amp=True)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    data = transformer.synthetic_batch(cfg, batch, seq)
    ms, loss = _time_steps(exe, main_prog, data, fetches["loss"], scope,
                           steps=steps)
    dt = ms / 1e3
    h, ff, v = cfg.d_model, cfg.d_inner, cfg.tgt_vocab_size
    l_enc, l_dec = cfg.n_encoder_layers, cfg.n_decoder_layers
    tokens = batch * seq
    # enc: qkv/out + ffn; dec adds cross-attention projections
    enc = l_enc * (4 * h * h + 2 * h * ff)
    dec = l_dec * (8 * h * h + 2 * h * ff)
    matmul = 6.0 * (enc + dec) * tokens + 6.0 * h * v * tokens
    attn = 6.0 * 2 * (l_enc + 3 * l_dec) * batch * seq * seq * h
    mfu = (matmul + attn) / dt / peak_flops_per_chip()
    return {
        "metric": "transformer_big_wmt_tokens_per_sec_per_chip",
        "value": round(tokens / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {"ms_per_step": round(ms, 2), "mfu": round(mfu, 4),
                  "model_flops": matmul + attn,
                  "batch": batch, "seq_len": seq, "loss": round(loss, 4)},
    }


def finalize_bench_result(out):
    """Attach telemetry accounting to a bench result and emit it as a
    `metric` event: bench rows carry the run's compile / cache-hit
    / donation-copy counters in `extra`, and when a JSONL run log is
    enabled (PT_TELEMETRY_LOG) the measured throughput/MFU lands in it."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.core.flags import flag as _flag

    ex = out.setdefault("extra", {})
    ex.update(telemetry.bench_extra())
    # dispatch-amortization config of this run (K-step fused execution)
    ex["steps_per_dispatch"] = max(
        1, int(_flag("exec_steps_per_dispatch")))
    # sharded-training config: mesh geometry, rule-table hash and ZeRO
    # stage ride every BENCH row so multi-chip results are attributable
    # (MULTICHIP rows stay TPU-ready; on the 1-chip container these are
    # null/0 — validated on the MLP/LeNet harness)
    from paddle_tpu.parallel import axis_rules
    from paddle_tpu.parallel.mesh import get_mesh

    m = get_mesh()
    ex["mesh_shape"] = ({a: int(s) for a, s in m.shape.items()}
                        if m is not None else None)
    ex["axis_rules_hash"] = axis_rules.fingerprint()
    # cost & memory observability (core/costmodel.py): the live MFU
    # gauge (windowed captured-flop rate / peak device flops) rides
    # every BENCH row next to the analytic model_flops the workload
    # embedded, so rows are self-attributing — an MFU claim can be
    # cross-checked against what XLA says the program actually does
    from paddle_tpu.core import costmodel

    mfu = costmodel.live_mfu()
    if mfu is not None:     # None: no peak for this device kind, no figure
        ex["live_mfu"] = round(mfu, 6)
    c = telemetry.counters()
    if c.get("cost.captures"):
        ex["cost_captures"] = int(c["cost.captures"])
        ex["cost_dispatch_flops"] = int(c.get("cost.dispatch_flops", 0))
    g0 = telemetry.gauges()
    if g0.get("mem.hbm_total_bytes") is not None:
        ex["mem_hbm_total_bytes"] = int(g0["mem.hbm_total_bytes"])
    g = telemetry.gauges()
    if g.get("sharding.zero_stage") is not None:
        ex["zero_stage"] = int(g["sharding.zero_stage"])
        for key in ("sharding.optimizer_state_bytes",
                    "sharding.optimizer_state_bytes_per_device"):
            if g.get(key) is not None:
                ex[key.replace(".", "_")] = int(g[key])
    # goodput ledger (core/goodput.py): every BENCH row embeds where the
    # run's wall-clock went — productive device compute vs the badput
    # phases — so a throughput regression is attributable (data stall?
    # compile churn? checkpoint overhang?) from the row alone. Falls
    # back to the process-lifetime window when the workload never opened
    # an explicit one.
    from paddle_tpu.core import goodput

    b = goodput.breakdown()
    ex["goodput"] = {"ratio": b["ratio"], "wall_ms": b["wall_ms"],
                     "productive_ms": b["productive_ms"],
                     "window": b["window"], "phases": b["phases"]}
    # offline SLO gate (tools/slo_check.py): judge this row against the
    # BENCH_r*/MULTICHIP_r* history beside it so every fresh row is
    # self-judging; a tree that commits no history reads no_baseline
    from tools.slo_check import embed_verdict

    ex["slo"] = embed_verdict(out)
    attrs = {k: ex[k] for k in ("ms_per_step", "mfu", "batch", "seq_len",
                                "steps_per_dispatch")
             if k in ex}
    attrs["vs_baseline"] = out.get("vs_baseline")
    attrs["unit"] = out.get("unit")
    if "mfu" in ex:
        telemetry.gauge_set("bench.mfu", ex["mfu"])
    if "ms_per_step" in ex:
        telemetry.gauge_set("bench.ms_per_step", ex["ms_per_step"])
    telemetry.event("metric", out.get("metric", "bench"), out.get("value"),
                    attrs)
    return out


WORKLOADS = {
    "mnist": bench_mnist,
    "ernie_large": bench_ernie_large,
    "bert_base": bench_bert_base,
    "resnet50": bench_resnet50,
    "transformer_big": bench_transformer_big,
    "long2048": bench_long_context,
    "long4096": bench_long_context_4096,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ernie_large")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()
    kw = {}
    if args.steps:
        kw["steps"] = args.steps
    if args.batch:
        kw["batch"] = args.batch
    out = finalize_bench_result(WORKLOADS[args.workload](**kw))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
