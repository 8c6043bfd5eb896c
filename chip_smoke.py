#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip (default) — it touches JAX itself and starts no child
that needs the chip. Phases, in order; any failure is a non-zero exit, and
nothing is caught and continued:

  device  jax.devices() must be a TPU; no JAX_PLATFORMS default, no CPU path.
  train   ERNIE-large at published width (24 layers x 1024 x 16 heads), seq
          512, bf16, flash attention, AdamW, batch 40, through
          bert.build_pretraining_program + pt.Executor: losses finite and
          falling, the flash kernels present in the compiled step's HLO,
          parameters on the TPU, a block_until_ready-closed step timed next
          to a host-fetch-closed one, and the persistent compile cache hit
          by a second identical compile.
  serve   models/decoder_lm at d_model 2048 (16 heads x 128, 16 layers),
          saved with save_decoder_lm, served by serving.server.serve_decode,
          asked over HTTP (POST /v1/generate, two greedy + two sampled,
          prompts >= 256 tokens) with fp32 weights and then the int8
          weight-only export: 200s with the asked token counts, both Pallas
          serving kernels dispatched in mode 'tpu' with zero fallbacks, and
          each kernel against its stock lowering at the decode-step shapes.

`--chips 4` runs ONLY the sharded-training phase and its one-device
comparison: the same BERT-family program on create_mesh({"dp": 2, "mp": 2}).

Every phase prints one JSON object; the last stdout line is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Weights and data come from --seed; model dirs go under ./chip_smoke_work
(git-ignored). Times printed here are facts of one run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(ROOT, "chip_smoke_work")

# kernel-vs-stock tolerance on the chip: max |kernel - stock| <= KERNEL_TOL *
# max(1, max |stock|). The MXU's default f32 matmul precision (bf16 passes)
# differs between Mosaic and the XLA lowering; bitwise identity is a CPU
# interpret-mode property and is not demanded of the chip.
KERNEL_TOL = 2e-2
# sharded vs one-device loss, per step: |a - b| <= SHARDED_TOL * |b|
SHARDED_TOL = 1e-2

TRAIN_SIZE = dict(layers=24, batch=40, seq=512, max_preds=80, steps=8)
# max_seq_len 512 = two 256-token KV chunks at d_model 2048: the multi-chunk
# online-softmax branch of the paged-attention kernel is the one compiled.
# vocab % 128 == 0 and slots % 8 == 0 admit every int8-GEMM call.
SERVE_SIZE = dict(vocab=32000, d_model=2048, n_head=16, n_layers=16,
                  d_inner=8192, max_seq_len=512, slots=8, page=16,
                  prefill_bucket=384, prompt_lens=(256, 300, 272, 320),
                  max_new=16)
SHARDED_SIZE = dict(layers=4, batch=8, seq=512, max_preds=80, steps=4,
                    hidden=1024, heads=16, d_inner=4096, vocab=18000)


class SmokeFailure(AssertionError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CacheWatch:
    """Counts JAX's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"hits": self.hits, "misses": self.misses}


def _peak_hbm():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(platform="tpu", count=1):
    """The device as JAX reports it; anything but `count` chips of
    `platform` is a failure (there is no CPU branch)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit("device", **info)
    require(info["platform"] == platform,
            f"need platform {platform!r}, JAX found {info['platform']!r}")
    require(info["count"] >= count,
            f"need {count} device(s), JAX found {info['count']}")
    return info


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _ernie_program(size, hidden=None):
    from paddle_tpu.models import bert

    cfg = bert.ernie_large()
    if hidden is not None:                      # test-only toy widths
        cfg = bert.BertConfig(**hidden)
    cfg.num_hidden_layers = size["layers"]
    cfg.dtype = "bfloat16"
    cfg.use_flash_attention = True
    main, startup, _feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=size["seq"], optimizer_name="adamw",
        max_predictions_per_seq=size["max_preds"])
    return cfg, main, startup, fetches["loss"]


def _flash_route(cfg, size):
    """The route ops/pallas/flash_attention takes at this geometry."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    packed = jax.ShapeDtypeStruct(
        (size["batch"], size["seq"], cfg.hidden_size), jnp.bfloat16)
    return fa.attention_route(packed, packed, None,
                              cfg.num_attention_heads)[0]


def phase_train(size=TRAIN_SIZE, seed=0, cache=None, hidden=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import kernel_mode

    cfg, main, startup, loss_v = _ernie_program(size, hidden)
    mode, route = kernel_mode(), _flash_route(cfg, size)
    require(route in ("packed", "pallas")
            or (route == "pallas_interpret" and mode != "tpu"),
            f"flash attention takes route {route!r} in mode {mode!r}, not "
            f"the Pallas kernels")
    exe, scope = pt.Executor(), pt.Scope()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope, use_compiled=False)
    startup_s = time.perf_counter() - t0
    feed = {k: jnp.asarray(v) for k, v in bert.synthetic_pretraining_batch(
        cfg, size["batch"], size["seq"], seed=seed,
        max_predictions_per_seq=size["max_preds"]).items()}
    # a weight matrix: every step is closed by block_until_ready on it
    a_param = next(n for n, v in sorted(scope.items())
                   if getattr(v, "ndim", 0) == 2)

    losses, fetch_ms, block_ms = [], [], []
    for i in range(size["steps"]):
        t0 = time.perf_counter()
        if i % 2 == 0:
            # clock stopped by the host fetch of the loss (run() returns
            # numpy), then the step is closed on the state array
            out, = exe.run(main, feed=feed, fetch_list=[loss_v], scope=scope)
            fetch_ms.append((time.perf_counter() - t0) * 1e3)
            jax.block_until_ready(scope.find_var(a_param))
        else:
            # clock stopped by block_until_ready on the state array; the
            # loss stays on the device until after
            out, = exe.run(main, feed=feed, fetch_list=[loss_v], scope=scope,
                           sync_fetch=False)
            jax.block_until_ready(scope.find_var(a_param))
            block_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(np.asarray(out).reshape(-1)[0]))
    first_step_s = fetch_ms.pop(0) / 1e3        # the compile

    require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall: first {losses[0]}, last {losses[-1]}")

    # parameters live on the accelerator the device phase found
    dev = jax.devices()[0]
    homes = {d for _, v in scope.items()
             for d in getattr(v, "devices", set)()}
    require(homes == {dev}, f"state lives on {homes}, expected {{{dev}}}")

    # the run's second identical compile: a re-created Executor traces and
    # lowers the same step again, and its compile is a read of the
    # persistent cache when that works
    before = cache.snapshot() if cache else None
    exe2 = pt.Executor()
    t0 = time.perf_counter()
    exe2.run(main, feed=feed, fetch_list=[loss_v], scope=scope)
    recompile_s = time.perf_counter() - t0

    # the compiled step's HLO
    (entry,) = exe2._cache.values()
    state = {n: scope.find_var(n) for n in entry.state_names}
    ro = {n: scope.find_var(n) for n in entry.ro_names}
    hlo = entry.jitted.lower(state, ro, feed,
                             scope.find_var("@STEP_COUNTER@")
                             ).compile().as_text()
    lse = f"f32[{size['batch']},{cfg.num_attention_heads},{size['seq']}]"
    flash_calls = sum(1 for line in hlo.splitlines()
                      if "tpu_custom_call" in line and lse in line)
    emit("train", model="ernie_large", layers=cfg.num_hidden_layers,
         hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
         seq=size["seq"], batch=size["batch"], dtype=cfg.dtype,
         losses=losses, startup_s=round(startup_s, 2),
         first_step_s=round(first_step_s, 2),
         host_fetch_closed_step_ms=[round(v, 2) for v in fetch_ms],
         block_until_ready_closed_step_ms=[round(v, 2) for v in block_ms],
         kernel_mode=mode, flash_route=route,
         custom_calls_in_step=hlo.count("tpu_custom_call"),
         flash_custom_calls=flash_calls,
         second_executor_first_step_s=round(recompile_s, 2),
         second_compile_cache=(
             {k: cache.snapshot()[k] - before[k] for k in before}
             if cache else None),
         state_device=str(dev), peak_hbm_bytes=_peak_hbm())
    if mode == "tpu":
        # forward and backward kernel of every layer carry the lse
        require(flash_calls >= 2 * cfg.num_hidden_layers,
                f"{flash_calls} flash tpu_custom_calls in the step HLO, "
                f"expected >= {2 * cfg.num_hidden_layers}")
    return losses


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _post(url, doc, timeout=600.0):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, timeout=60.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _serve_one(model_dir, size, quant, seed):
    """One server lifetime: start, four /v1/generate requests over HTTP,
    read /v1/stats, stop. Returns the phase record."""
    import numpy as np

    from paddle_tpu.serving.decode import DecodeConfig
    from paddle_tpu.serving.server import serve_decode

    mp = -(-size["max_seq_len"] // size["page"])
    config = DecodeConfig(
        max_slots=size["slots"], page_size=size["page"],
        kv_pages=size["slots"] * mp + 1,          # every slot full + scratch
        max_new_tokens=size["max_new"], weight_quant=quant,
        prefill_buckets=[size["prefill_bucket"]])
    t0 = time.perf_counter()
    server = serve_decode(model_dir, config=config, warmup=True)
    start_s = time.perf_counter() - t0
    try:
        rng = np.random.RandomState(seed)
        answers = []
        for i, n in enumerate(size["prompt_lens"]):
            doc = {"prompt_ids": rng.randint(
                       3, size["vocab"], n).tolist(),
                   "max_new_tokens": size["max_new"], "stop_at_eos": False}
            if i >= 2:                                    # two sampled
                doc.update(temperature=0.8, seed=seed + i)
            status, body = _post(server.url + "/v1/generate", doc)
            require(status == 200, f"/v1/generate answered {status}")
            require(body["num_tokens"] == size["max_new"]
                    and len(body["tokens"]) == size["max_new"],
                    f"asked {size['max_new']} tokens, got "
                    f"{body['num_tokens']}")
            require(all(0 <= t < size["vocab"] for t in body["tokens"]),
                    "token id outside the vocabulary")
            answers.append({"prompt": n,
                            "sampled": i >= 2, "tokens": body["num_tokens"],
                            "ttft_ms": body["ttft_ms"],
                            "latency_ms": body["latency_ms"]})
        stats = _get(server.url + "/v1/stats")["decode"]
    finally:
        server.shutdown()
        server.decode_engine.close()
    return {"weights": quant, "start_s": round(start_s, 2),
            "answers": answers, "pallas": stats["pallas"],
            "compiles": stats.get("compiles"),
            "kv_cache": stats["kv_cache"]}


def _kernel_vs_stock(size, seed):
    """Each serving kernel against its stock lowering at the decode-step
    shapes, on seeded data. Returns max |diff| / max(1, max |stock|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import int8_gemm as ig
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.RandomState(seed)
    d, n, page, slots = (size["d_model"], size["n_head"], size["page"],
                         size["slots"])
    hd, mp = d // n, -(-size["max_seq_len"] // size["page"])
    pool = slots * mp + 1

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b))
                     / max(1.0, float(jnp.max(jnp.abs(b)))))

    x = jnp.asarray(rng.randn(slots, d).astype(np.float32))
    w8 = jnp.asarray(rng.randint(-127, 128, (d, size["d_inner"]),
                                 dtype=np.int8))
    sc = jnp.asarray((rng.rand(size["d_inner"]) * 1e-2 + 1e-3
                      ).astype(np.float32))
    b = jnp.asarray(rng.randn(size["d_inner"]).astype(np.float32))
    gemm = rel(
        jax.jit(lambda *a: ig.int8_weight_only_gemm(*a, act="relu"))(
            x, w8, sc, b),
        jax.jit(lambda *a: ig.stock_int8_gemm(*a, "relu"))(x, w8, sc, b))

    q = jnp.asarray(rng.randn(slots, d).astype(np.float32))
    pk = jnp.asarray(rng.randn(pool, page, d).astype(np.float32))
    pv = jnp.asarray(rng.randn(pool, page, d).astype(np.float32))
    table = jnp.asarray(1 + rng.permutation(pool - 1)[:slots * mp]
                        .reshape(slots, mp).astype(np.int32))
    pos = jnp.asarray(rng.randint(0, mp * page, slots).astype(np.int32))
    attn = rel(
        jax.jit(lambda *a: pa.paged_decode_attention(
            *a, n, hd, hd ** -0.5))(q, pk, pv, table, pos),
        jax.jit(lambda *a: pa.stock_paged_attention(
            *a, n, hd, hd ** -0.5))(q, pk, pv, table, pos))
    return {"int8_gemm": gemm, "paged_attention": attn}


def phase_serve(size=SERVE_SIZE, seed=0, work_dir=WORK_DIR):
    import gc

    from paddle_tpu.core import telemetry
    from paddle_tpu.models import decoder_lm as dl
    from paddle_tpu.ops.pallas import kernel_mode
    from paddle_tpu.ops.pallas.paged_attention import _chunk_pages

    mp = -(-size["max_seq_len"] // size["page"])
    kv_chunks = -(-mp // _chunk_pages(size["page"], mp, size["d_model"]))
    require(kv_chunks >= 2,
            "the context fits one KV chunk: the paged-attention kernel "
            "would compile its single-chunk branch, not the online-softmax "
            "one this phase is meant to run")
    cfg = dl.DecoderLMConfig(
        vocab_size=size["vocab"], d_model=size["d_model"],
        n_head=size["n_head"], n_layers=size["n_layers"],
        d_inner=size["d_inner"], max_seq_len=size["max_seq_len"])
    model_dir = os.path.join(work_dir, "decoder_lm")
    shutil.rmtree(model_dir, ignore_errors=True)
    t0 = time.perf_counter()
    dl.save_decoder_lm(model_dir, cfg, dl.decoder_lm_params(cfg, seed))
    emit("serve.model", d_model=cfg.d_model, n_head=cfg.n_head,
         head_dim=cfg.head_dim, n_layers=cfg.n_layers, d_inner=cfg.d_inner,
         vocab=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
         model_dir=os.path.relpath(model_dir, ROOT),
         make_and_save_s=round(time.perf_counter() - t0, 2))
    mode = kernel_mode()
    try:
        for quant in ("none", "int8"):
            telemetry.reset()          # per-server kernel counters
            rec = _serve_one(model_dir, size, quant, seed)
            gc.collect()               # free the server's weights and pools
            emit("serve", kernel_mode=mode, **rec,
                 peak_hbm_bytes=_peak_hbm())
            p = rec["pallas"]
            require(p["kernels"].split("|")[0] == mode,
                    f"server compiled kernels in mode {p['kernels']!r}")
            require(p.get("paged_attn_dispatches", 0) > 0,
                    "paged-attention kernel never dispatched")
            if quant == "int8":
                require(p.get("int8_gemm_dispatches", 0) > 0,
                        "int8 GEMM kernel never dispatched")
            fallbacks = {k: v for k, v in p.items()
                         if k.endswith("_fallbacks") and v}
            require(not fallbacks, f"kernel fallbacks counted: {fallbacks}")
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    cmp = _kernel_vs_stock(size, seed)
    emit("serve.kernel_vs_stock", tolerance=KERNEL_TOL,
         kv_chunks=kv_chunks, **cmp)
    require(cmp["int8_gemm"] <= KERNEL_TOL,
            f"int8 GEMM kernel off its stock lowering by {cmp['int8_gemm']}")
    require(cmp["paged_attention"] <= KERNEL_TOL,
            f"paged-attention kernel off its stock lowering by "
            f"{cmp['paged_attention']}")


# ---------------------------------------------------------------------------
# --chips 4: sharded training against one device
# ---------------------------------------------------------------------------

def phase_sharded(size=SHARDED_SIZE, seed=0, n_devices=4, hidden=None):
    """The BERT-family pretraining program on a dp=2 x mp=2 mesh against
    the one-device run of the same program, seed and batch."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.parallel.mesh import set_mesh

    hidden = hidden or dict(
        vocab_size=size["vocab"], hidden_size=size["hidden"],
        num_attention_heads=size["heads"], intermediate_size=size["d_inner"])
    cfg, main, startup, loss_v = _ernie_program(size, hidden)
    data = bert.synthetic_pretraining_batch(
        cfg, size["batch"], size["seq"], seed=seed,
        max_predictions_per_seq=size["max_preds"])

    def run(mesh):
        exe, scope = pt.Executor(), pt.Scope()
        exe.run(startup, scope=scope, use_compiled=False)
        losses = []
        for _ in range(size["steps"]):
            out, = exe.run(main, feed=data, fetch_list=[loss_v], scope=scope,
                           mesh=mesh)
            losses.append(float(np.asarray(out).reshape(-1)[0]))
        return losses, scope

    one, _ = run(None)
    devs = jax.devices()[:n_devices]
    mesh = create_mesh({"dp": 2, "mp": n_devices // 2}, devices=devs)
    try:
        sharded, scope = run(mesh)
    finally:
        set_mesh(None)

    # where the parameters and optimizer state ended up
    per_dev = {d.id: 0 for d in devs}
    total = split = 0
    for _, arr in scope.items():
        if not hasattr(arr, "addressable_shards"):
            continue
        total += arr.nbytes
        for sh in arr.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
        split += any(sh.data.nbytes < arr.nbytes
                     for sh in arr.addressable_shards)
    shares = {str(k): round(v / total, 4) for k, v in per_dev.items()}
    diffs = [abs(a - b) / abs(b) for a, b in zip(sharded, one)]
    emit("sharded", mesh={"dp": 2, "mp": n_devices // 2},
         layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
         seq=size["seq"], batch=size["batch"], losses_sharded=sharded,
         losses_one_device=one, max_rel_diff=max(diffs),
         tolerance=SHARDED_TOL, state_bytes=total,
         state_share_per_device=shares, arrays_split_across_devices=split)
    require(all(np.isfinite(sharded)), f"non-finite loss in {sharded}")
    require(max(diffs) <= SHARDED_TOL,
            f"sharded losses {sharded} vs one device {one}: off by "
            f"{max(diffs)}")
    require(split > 0, "no array is split across the mesh")
    # dp x mp: every device holds its half of the mp-split weights plus the
    # replicated rest; a mesh that left everything on device 0 reads 1/0/0/0
    require(min(shares.values()) >= 0.25,
            f"a device holds under a quarter of a full state copy: {shares}")
    return sharded, one


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the sharded-training phase and its "
                         "one-device comparison (the builder's run; the "
                         "driver runs the default)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    info = phase_device("tpu", args.chips)
    if args.chips == 4:
        phase_sharded(seed=args.seed)
    else:
        os.makedirs(WORK_DIR, exist_ok=True)
        cache = CacheWatch()
        phase_train(seed=args.seed, cache=cache)
        phase_serve(seed=args.seed)
        import jax

        emit("compile_cache", dir=jax.config.jax_compilation_cache_dir,
             dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
             **cache.snapshot())
    emit("done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
