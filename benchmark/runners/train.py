"""Training cells: a Fluid-style pretraining program through pt.Executor.

The body is chip_smoke.py's `phase_train` (run on the chip in PR 21) with a
timed window where the smoke had a fixed step count. The window opens after
warm-up and closes on `block_until_ready` of a state array; inside it the
host runs at most PIPELINE_DEPTH steps ahead of the device, fed from a ring
of host batches.
"""

from __future__ import annotations

import collections
import gc
import math
import os
import time

from .. import flops, generators, reference, trace_reduce
from ..common import log, peak_hbm
from . import result

PIPELINE_DEPTH = 2        # steps the host may run ahead of the device
TRACE_STEPS = 10          # steps inside the profiler's sub-window
MIN_CLOSED_STEPS = 3      # of a traced run, however short its window
FALL_OVER = 4             # the lowest of the last few warm-up losses must
#                           be under the first: AdamW without a learning-rate
#                           warm-up spikes for a few steps on a repeated batch
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size",
              "max_position_embeddings", "type_vocab_size",
              "hidden_dropout_prob", "attention_probs_dropout_prob",
              "initializer_range", "hidden_act")


def build(config: dict, traffic: dict, **changed):
    """The configuration's pretraining program; `changed` replaces model
    keys (the reference check's depth and dropout)."""
    from paddle_tpu.models import bert

    run = config["runner"]
    cfg = bert.BertConfig(**{
        **{k: config[k] for k in MODEL_KEYS if k in config}, **changed})
    cfg.dtype = run["dtype"]
    cfg.use_flash_attention = bool(run["use_flash_attention"])
    main, startup, _feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=traffic["seq_len"], optimizer_name=run["optimizer"],
        lr=run["lr"],
        max_predictions_per_seq=traffic["max_predictions_per_seq"])
    return cfg, main, startup, fetches["loss"]


def mesh_of(config: dict, chips: int):
    """-> (mesh, data-parallel replicas) as `runner.mesh_by_chips` gives
    them for this many chips; (None, 1) where it names none."""
    axes = config["runner"].get("mesh_by_chips", {}).get(str(chips))
    if not axes:
        return None, 1
    import jax

    from paddle_tpu.parallel import create_mesh

    mesh = create_mesh(dict(axes), devices=jax.devices()[:chips])
    return mesh, int(axes.get("dp", 1))


def check_against_reference(config: dict, traffic: dict, seed: int,
                            mesh=None, replicas: int = 1):
    """One step of the same program at the check's depth with dropout off,
    through the same Executor and dtype and down the path the cell's own
    steps take (one device and its kernels, or `mesh` and the partitioned
    step with its collectives), held against the plain float32 reference:
    the loss and the gradients the check names. Built under a name guard,
    so that the cell's own program gets the names (and the compile-cache
    keys) it would get alone. Everything it put on the devices dies with
    this frame, before the cell's program is built."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import unique_name

    check = config["check"]
    if check["batch"] % replicas:
        raise ValueError(f"the check's batch of {check['batch']} rows does "
                         f"not divide over {replicas} replicas")
    with unique_name.guard():
        cfg, main, startup, loss_v = build(
            config, traffic, num_hidden_layers=check["num_hidden_layers"],
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    batch = generators.load(traffic["generator"]).batch(
        cfg.vocab_size, cfg.type_vocab_size, check["batch"],
        traffic["seq_len"], seed, traffic["max_predictions_per_seq"])
    params = {p.name: np.array(scope.find_var(p.name))   # copies: the step
              for p in main.all_parameters()}            # donates its state
    loss, *grads = exe.run(
        main, feed=batch, scope=scope, mesh=mesh,
        fetch_list=[loss_v] + [n + "@GRAD" for n in check["grads"]])
    return reference.check_train_step(
        params, batch, cfg.num_hidden_layers, cfg.num_attention_heads,
        np.asarray(loss).reshape(-1)[0], dict(zip(check["grads"], grads)))


def run(job):
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import telemetry

    config, traffic = job.config, job.traffic
    t0 = time.perf_counter()
    mesh, replicas = mesh_of(config, job.chips)
    notes, facts = check_against_reference(config, traffic, job.seed, mesh,
                                           replicas)
    gc.collect()               # the check's Executor, Scope and arrays
    log("train.check", seconds=round(time.perf_counter() - t0, 2),
        mesh=dict(mesh.shape) if mesh is not None else None, **facts)
    cfg, main, startup, loss_v = build(config, traffic)
    ring = generators.load(traffic["generator"]).make(
        traffic, job.seed, cfg.vocab_size, cfg.type_vocab_size, replicas)
    tokens_per_step = ring[0]["src_ids"].size
    fpt = flops.bert_train_flops_per_token(
        hidden=cfg.hidden_size, layers=cfg.num_hidden_layers,
        ffn=cfg.intermediate_size, vocab=cfg.vocab_size,
        seq=traffic["seq_len"], max_preds=traffic["max_predictions_per_seq"])

    log("train.built", tokens_per_step=int(tokens_per_step))
    exe, scope = pt.Executor(), pt.Scope()
    t0 = time.perf_counter()
    # interpreted: the compiled route leaves the scope empty (PERF.md)
    exe.run(startup, scope=scope, use_compiled=False)
    log("train.startup", seconds=round(time.perf_counter() - t0, 2))

    def step(feed, sync):
        out, = exe.run(main, feed=feed, fetch_list=[loss_v], scope=scope,
                       mesh=mesh, sync_fetch=sync)
        return out

    # warm-up on one repeated batch: the compile (or cache read), then a
    # loss that must be finite and falling (PR 21's check)
    t0 = time.perf_counter()
    losses = [float(np.asarray(step(ring[0], True)).reshape(-1)[0])]
    first_step_s = time.perf_counter() - t0
    for _ in range(traffic["warmup_steps"] - 1):
        losses.append(float(np.asarray(step(ring[0], True)).reshape(-1)[0]))
    a_param = next(n for n, v in sorted(scope.items())
                   if getattr(v, "ndim", 0) == 2)
    jax.block_until_ready(step(ring[1 % len(ring)], False))  # the async path
    if not all(math.isfinite(v) for v in losses):
        notes.append(f"non-finite warm-up loss {losses}")
    elif not min(losses[-FALL_OVER:]) < losses[0]:
        notes.append(f"warm-up loss did not fall: {losses}")
    log("train.warmup", losses=losses, first_step_s=round(first_step_s, 2))

    compiles_before = telemetry.counter_get("executor.compiles") or 0
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
        # first the traced sub-window, pipelined as the untraced run is;
        # then steps closed one by one for step_ms
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

    window_losses = [float(np.asarray(v).reshape(-1)[0]) for v in kept]
    if not all(math.isfinite(v) for v in window_losses):
        notes.append(f"non-finite loss in the window: {window_losses}")
    moved = (telemetry.counter_get("executor.compiles") or 0) - compiles_before
    if moved or job.watch.since_mark():
        notes.append(f"compiled inside the window: executor.compiles +{moved}, "
                     f"backend compiles +{job.watch.since_mark()}")
    rate = steps * tokens_per_step / window_s
    log("train.window", steps=steps, window_s=round(window_s, 3),
        tokens_per_s=round(rate, 1), losses=window_losses[:4] + window_losses[-2:],
        model_flops_utilization_pct=round(
            100 * rate * fpt / (job.chips * job.peaks["bf16_flops_per_s"]), 2))
    if mesh is not None:
        from paddle_tpu.parallel.mesh import set_mesh

        set_mesh(None)
    return result(
        kind="train", correct=not notes, attempted=steps, failed=0,
        notes=notes, compared=[
            ["loss_rel_err", abs(facts["loss"] / facts["reference_loss"] - 1),
             reference.LOSS_TOL]] + [
            [f"grad_rel_err.{n}", err, reference.GRAD_TOL]
            for n, err in facts["grad_rel_err"].items()] + [
            ["compiles_in_window", moved + job.watch.since_mark(), 0]],
        setup_s=setup_s, window_s=window_s, steps=steps,
        tokens_per_step=tokens_per_step, first_step_s=first_step_s,
        step_ms=step_ms, dispatch_ms=dispatch_ms, flops_per_token=fpt,
        peak_hbm_bytes=peak_hbm(job.chips), trace=trace)
