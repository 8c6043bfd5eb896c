"""Training cells of a causal language model (`"kind": "train_lm"`): one
chip's share of a decoder's pretraining program (paddle_tpu/models/
mellum.py) through pt.Executor, with the window discipline of
runners/train.py: warm-up on one repeated batch (the compile, then a loss
that must be finite and falling), then the window, fed from a ring of host
batches with the host at most PIPELINE_DEPTH steps ahead; a traced run
traces TRACE_STEPS pipelined steps and closes the rest one by one for
`step_ms`; nothing may compile inside the window.

The configuration file's keys this runner reads: the published
`hidden_size`, `head_dim`, `layer_types`, `moe_intermediate_size`,
`num_experts`, `num_experts_per_tok`, `norm_topk_prob`, `sliding_window`,
`rms_norm_eps`, `rope_parameters` (`sliding_attention.rope_theta`; the
`full_attention` group's YaRN keys); the chip's share of them,
`layers_held` (indices into `layer_types`), `q_heads_held`,
`kv_heads_held`, `experts_held` (first, how many), `vocab_size` (rows of
embedding and head held); a `runner` group (`dtype`, `optimizer`, `lr`,
`weight_decay`, `loss_chunk`: rows of logits alive at a time) and a
`check` group (`grads`: the parameters whose gradients AND whose change
by the optimizer are held against the reference; `expert_rows`: of a
stacked expert matrix, the one expert compared). `published` and
`deployment` say what the share is a share of.

`correct` is the comparison of THE TIMED PROGRAM at the timed sizes with
benchmark/reference_mellum.py, from the seeded weights: the cell builds
one program, one Executor and one Scope, and the check's first step is
the compiled step the window then times (the same fetch list: its first
call, compile included, is the cell's `first_step_s`). Held are that
step's loss and what it did to the check's parameters (against the
reference's own AdamW step from the reference's own gradients), and, from
the same step run once more from the seeded state with them fetched (a
second compiled program, by its outputs alone), the gradients the check
names and the routed layers' choices. The scope is emptied while the
reference has the chip, and seeded again for the window.
"""

from __future__ import annotations

import collections
import gc
import math
import os
import time

from .. import flops_mellum, generators, reference_mellum, trace_reduce
from ..common import log, peak_hbm
from . import result
from .train import FALL_OVER, MIN_CLOSED_STEPS, PIPELINE_DEPTH, TRACE_STEPS


def model_config(config: dict):
    """The program's own configuration from the file's keys."""
    from paddle_tpu.models import mellum

    rope = config["rope_parameters"]
    full = rope["full_attention"]
    yarn = {}
    if full.get("rope_type") == "yarn":
        yarn = dict(factor=float(full["factor"]),
                    original_max=int(full["original_max_position_embeddings"]),
                    beta_fast=float(full["beta_fast"]),
                    beta_slow=float(full["beta_slow"]),
                    attention_factor=float(full["attention_factor"]))
    run = config["runner"]
    return mellum.MellumConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        head_dim=config["head_dim"], num_heads=config["q_heads_held"],
        num_kv_heads=config["kv_heads_held"],
        layer_types=[config["layer_types"][i] for i in config["layers_held"]],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        experts_held=config["experts_held"],
        norm_topk_prob=config["norm_topk_prob"],
        sliding_window=config["sliding_window"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=yarn, dtype=run["dtype"], loss_chunk=run["loss_chunk"])


def reference_model(cfg) -> dict:
    """The sizes benchmark/reference_mellum.py reads."""
    return dict(head_dim=cfg.head_dim, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, layer_types=cfg.layer_types,
                num_experts_per_tok=cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                sliding_window=cfg.sliding_window,
                rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                yarn=cfg.yarn)


def optimizer_of(config: dict) -> dict:
    """`reference_mellum.adamw_first_step`'s keywords from the `runner`
    group (the betas and epsilon are the optimizer's own defaults)."""
    run = config["runner"]
    return dict(lr=run["lr"], weight_decay=run.get("weight_decay", 0.01))


def build(config: dict, traffic: dict, seed: int):
    from paddle_tpu.models import mellum

    cfg, run = model_config(config), config["runner"]
    main, startup, _feeds, fetches = mellum.build_pretraining_program(
        cfg, batch=traffic["batch_per_replica"], seq_len=traffic["seq_len"],
        optimizer_name=run["optimizer"], seed=seed, **optimizer_of(config))
    return cfg, main, startup, fetches["loss"]


def seeded(exe, startup, scope):
    """The seeded state into `scope`, in place of whatever it held (the
    scope stays the same object, so the steps compiled for it stay)."""
    for name in scope.local_var_names():
        scope.erase(name)
    gc.collect()
    exe.run(startup, scope=scope, use_compiled=False)


def first_step(config: dict, traffic: dict, seed: int, built):
    """The cell's own first step, twice from its seeded weights, in the
    cell's own Executor and Scope (`built`: what `build` gave, and those
    two). Once as THE TIMED STEP, the fetch list the window uses: its
    loss, and the check's parameters read again after it. Once more with
    the check's gradients and the routed layers' choices fetched beside
    the loss: another compiled program by its outputs alone. -> a dict:
    params (before the step), batch, loss, after, grads, chosen [B,
    layers, S, k], first_step_s (the timed step's first call, its compile
    included), all on the host; the scope is left empty."""
    import numpy as np

    from paddle_tpu.models import mellum

    cfg, main, startup, loss_v, exe, scope = built
    seeded(exe, startup, scope)
    batch = generators.load(traffic["generator"]).batch(
        cfg.vocab_size, traffic["batch_per_replica"], traffic["seq_len"],
        seed)
    params = {p.name: np.array(scope.find_var(p.name))   # copies: the step
              for p in main.all_parameters()}            # donates its state
    names = config["check"]["grads"]
    t0 = time.perf_counter()
    loss, = exe.run(main, feed=batch, scope=scope, fetch_list=[loss_v])
    first_step_s = time.perf_counter() - t0
    after = {n: np.array(scope.find_var(n)) for n in names}
    seeded(exe, startup, scope)
    again, *rest = exe.run(
        main, feed=batch, scope=scope,
        fetch_list=[loss_v] + [n + "@GRAD" for n in names]
        + [mellum.chosen_var(i) for i in range(cfg.n_layers)])
    for name in scope.local_var_names():
        scope.erase(name)

    def scalar(v):
        return float(np.asarray(v).reshape(-1)[0])

    return dict(params=params, batch=batch, loss=scalar(loss),
                loss_with_grads=scalar(again), after=after,
                grads=dict(zip(names, rest[:len(names)])),
                chosen=np.stack(rest[len(names):], axis=1),
                first_step_s=first_step_s)


def one_expert(config: dict, tensors: dict) -> dict:
    """`check.expert_rows` applied: of a stacked expert matrix (or its
    gradient), the one expert the check names, under `name[expert]`."""
    rows = config["check"].get("expert_rows", {})
    return {(f"{n}[{rows[n]}]" if n in rows else n):
            (g[rows[n]] if n in rows else g) for n, g in tensors.items()}


def judge(config: dict, params: dict, reference, loss, grads: dict, chosen,
          after: dict):
    """`reference_mellum.compare` of a step's loss, gradients (by the
    parameters' own names), routed choices and the parameters `after` it
    with `reference`, what `loss_and_grads` gave, and with the reference's
    own AdamW step from `params`; each stacked expert matrix cut to the
    one expert the check names. -> (notes, compared)."""
    import jax.numpy as jnp

    ref_loss, ref_grads, ref_chosen = reference
    ref_grads = {n: jnp.asarray(ref_grads[n]) for n in grads}
    stepped = reference_mellum.adamw_first_step(
        params, {n: ref_grads[n] for n in after}, **optimizer_of(config))
    moved = reference_mellum.changes(
        one_expert(config, {n: params[n] for n in after}),
        one_expert(config, after), one_expert(config, stepped))
    return reference_mellum.compare(
        loss, one_expert(config, grads), chosen,
        (ref_loss, one_expert(config, ref_grads), ref_chosen), moved)


def check_against_reference(config: dict, traffic: dict, seed: int, built):
    """-> (notes, compared, first_step_s) of the timed program's first
    step against the reference."""
    step = first_step(config, traffic, seed, built)
    gc.collect()               # the reference has the chip to itself
    reference = reference_mellum.loss_and_grads(
        step["params"], step["batch"]["tokens"], step["batch"]["labels"],
        reference_model(built[0]))
    notes, compared = judge(config, step["params"], reference, step["loss"],
                            step["grads"], step["chosen"], step["after"])
    if not abs(step["loss_with_grads"] / step["loss"] - 1) \
            <= reference_mellum.LOSS_TOL:
        notes.append(f"the timed step's loss {step['loss']} is not the "
                     f"loss {step['loss_with_grads']} of the same step "
                     f"with the gradients fetched")
    return notes, compared, step["first_step_s"]


def run(job):
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import telemetry

    config, traffic = job.config, job.traffic
    cfg, main, startup, loss_v = build(config, traffic, job.seed)
    exe, scope = pt.Executor(), pt.Scope()
    t0 = time.perf_counter()
    notes, compared, first_step_s = check_against_reference(
        config, traffic, job.seed, (cfg, main, startup, loss_v, exe, scope))
    gc.collect()
    log("train_lm.check", seconds=round(time.perf_counter() - t0, 2),
        compared={n: v for n, v, _ in compared})
    ring = generators.load(traffic["generator"]).make(
        traffic, job.seed, cfg.vocab_size)
    tokens_per_step = ring[0]["tokens"].size
    seq = traffic["seq_len"]

    log("train_lm.built", tokens_per_step=int(tokens_per_step),
        parameters=flops_mellum.parameters(config))
    t0 = time.perf_counter()
    seeded(exe, startup, scope)
    log("train_lm.startup", seconds=round(time.perf_counter() - t0, 2))

    def step(feed, sync):
        out, = exe.run(main, feed=feed, fetch_list=[loss_v], scope=scope,
                       sync_fetch=sync)
        return out

    # the step is compiled: the check's first call of it was the cell's
    # `first_step_s`
    losses = [float(np.asarray(step(ring[0], True)).reshape(-1)[0])
              for _ in range(traffic["warmup_steps"])]
    a_param = "ml_norm_f"
    jax.block_until_ready(step(ring[1 % len(ring)], False))  # the async path
    exe.flush_telemetry()
    if not all(math.isfinite(v) for v in losses):
        notes.append(f"non-finite warm-up loss {losses}")
    elif not min(losses[-FALL_OVER:]) < losses[0]:
        notes.append(f"warm-up loss did not fall: {losses}")
    log("train_lm.warmup", losses=losses,
        first_step_s=round(first_step_s, 2))

    compiles_before = telemetry.counter_get("executor.compiles") or 0
    counters_before = dict(telemetry.counters())
    job.watch.mark()
    kept, step_ms, dispatch_ms, trace = [], [], [], None
    steps = 0
    setup_s = job.clock()
    t_open = time.perf_counter()
    if not job.trace:
        pending = collections.deque()
        while time.perf_counter() - t_open < job.seconds:
            t1 = time.perf_counter()
            out = step(ring[steps % len(ring)], False)
            dispatch_ms.append((time.perf_counter() - t1) * 1e3)
            pending.append(out)
            if steps % traffic["loss_every"] == 0:
                kept.append(out)
            steps += 1
            if len(pending) > PIPELINE_DEPTH:
                jax.block_until_ready(pending.popleft())
    else:
        trace_dir = os.path.join(job.work_dir, "trace")
        pending = collections.deque(
            step(ring[i % len(ring)], False) for i in range(PIPELINE_DEPTH))
        steps = PIPELINE_DEPTH
        trace_reduce.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(TRACE_STEPS):
                with jax.profiler.TraceAnnotation("bench.exe_run"):
                    t1 = time.perf_counter()
                    out = step(ring[steps % len(ring)], False)
                    dispatch_ms.append((time.perf_counter() - t1) * 1e3)
                pending.append(out)
                steps += 1
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(pending.popleft())
        jax.block_until_ready(list(pending))
        jax.profiler.stop_trace()
        trace = trace_reduce.reduce_trace(
            trace_dir, default_gap_label="host outside exe.run and block",
            platform=job.platform)
        while (time.perf_counter() - t_open < job.seconds
               or len(step_ms) < MIN_CLOSED_STEPS):
            t1 = time.perf_counter()
            out = step(ring[steps % len(ring)], False)
            jax.block_until_ready(scope.find_var(a_param))
            step_ms.append((time.perf_counter() - t1) * 1e3)
            if steps % traffic["loss_every"] == 0:
                kept.append(out)
            steps += 1
    jax.block_until_ready(scope.find_var(a_param))
    window_s = time.perf_counter() - t_open
    exe.flush_telemetry()

    window_losses = [float(np.asarray(v).reshape(-1)[0]) for v in kept]
    if not all(math.isfinite(v) for v in window_losses):
        notes.append(f"non-finite loss in the window: {window_losses}")
    moved = (telemetry.counter_get("executor.compiles") or 0) - compiles_before
    if moved or job.watch.since_mark():
        notes.append(f"compiled inside the window: executor.compiles +{moved}, "
                     f"backend compiles +{job.watch.since_mark()}")
    # the window's own counters; the kernels' routes are counted where a
    # program is traced, before the window, and go in as they stand
    snap = telemetry.snapshot()
    counters = {k: v - counters_before.get(k, 0)
                if k.startswith("moe.train.") else v
                for k, v in snap["counters"].items()}
    window_steps = counters.get("moe.train.steps") or 0
    held = None
    if window_steps:
        held = counters["moe.train.pairs_held"] / window_steps \
            / tokens_per_step / cfg.n_layers
    fpt = flops_mellum.train_flops_per_token(config, seq, held)
    rate = steps * tokens_per_step / window_s
    log("train_lm.telemetry", **{k: v for k, v in counters.items()
                                 if k.startswith(("moe.", "pallas."))},
        max_group_rows=snap["hists"].get("moe.train.max_group_rows"))
    log("train_lm.window", steps=steps, window_s=round(window_s, 3),
        tokens_per_s=round(rate, 1),
        losses=window_losses[:4] + window_losses[-2:],
        held_pairs_per_token_layer=held, flops_per_token=fpt,
        model_flops_utilization_pct=round(
            100 * rate * fpt / (job.chips * job.peaks["bf16_flops_per_s"]), 2))
    return result(
        kind="train", correct=not notes, attempted=steps, failed=0,
        notes=notes, compared=compared + [
            ["compiles_in_window", moved + job.watch.since_mark(), 0]],
        setup_s=setup_s, window_s=window_s, steps=steps,
        tokens_per_step=tokens_per_step, first_step_s=first_step_s,
        step_ms=step_ms, dispatch_ms=dispatch_ms, flops_per_token=fpt,
        peak_hbm_bytes=peak_hbm(job.chips), trace=trace,
        telemetry={"counters": counters, "hists": snap["hists"],
                   "gauges": snap["gauges"]})
