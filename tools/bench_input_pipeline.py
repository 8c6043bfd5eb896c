"""End-to-end input-pipeline throughput (VERDICT r4 #9).

Measures the BERT-base ladder row with ROTATING REAL BATCHES flowing
host → device against the device-resident number, with a double-buffered
feed: batch k+1 is device_put (async) while step k runs, so steady-state
step time is max(feed, compute) — the DataFeed/buffered_reader property
(reference: operators/reader/buffered_reader.cc overlapping its
TensorCopySync stream; here XLA async transfers are the stream).

Where the host->device path is slower than the step, the pipelined
number shows that path's bandwidth bound; on a TPU host (PCIe, GB/s) the
same code is compute-bound. Both numbers + the implied bandwidth print.

Usage: python tools/bench_input_pipeline.py [--steps 30]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=384)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import bert

    cfg = bert.bert_base()
    cfg.dtype = "bfloat16"
    cfg.use_flash_attention = True
    main_prog, startup, feeds, fetches = bert.build_pretraining_program(
        cfg, seq_len=args.seq, optimizer_name="adamw",
        max_predictions_per_seq=20)
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    loss_v = fetches["loss"]

    def make_batch(seed):
        return bert.synthetic_pretraining_batch(
            cfg, args.batch, args.seq, max_predictions_per_seq=20,
            seed=seed)

    bytes_per_batch = sum(np.asarray(v).nbytes
                          for v in make_batch(0).values())

    # -- reference: device-resident (the ladder methodology) ------------
    warm = {k: jnp.asarray(v) for k, v in make_batch(0).items()}
    for _ in range(2):
        exe.run(main_prog, feed=warm, fetch_list=[loss_v], scope=scope)
        exe.run(main_prog, feed=warm, fetch_list=[], scope=scope)
    t0 = time.perf_counter()
    for _ in range(args.steps - 1):
        exe.run(main_prog, feed=warm, fetch_list=[], scope=scope)
    out = exe.run(main_prog, feed=warm, fetch_list=[loss_v], scope=scope)
    resident_ms = (time.perf_counter() - t0) / args.steps * 1e3
    _ = float(np.asarray(out[0]).reshape(-1)[0])

    # -- pipelined: buffered reader (thread prefetch, the
    # buffered_reader.cc analog) + async double buffer ------------------
    from paddle_tpu.reader import buffered

    def gen():
        for s in range(args.steps + 4):
            yield make_batch(100 + s)

    it = buffered(gen, size=4)()

    def put(b):
        return {k: jax.device_put(jnp.asarray(v)) for k, v in b.items()}

    nxt = put(next(it))
    t0 = time.perf_counter()
    n_done = 0
    for _ in range(args.steps):
        cur = nxt
        try:
            host_b = next(it)
        except StopIteration:
            host_b = None
        if host_b is not None:
            nxt = put(host_b)     # async: overlaps the step below
        exe.run(main_prog, feed=cur, fetch_list=[], scope=scope)
        n_done += 1
    out = exe.run(main_prog, feed=cur, fetch_list=[loss_v], scope=scope)
    _ = float(np.asarray(out[0]).reshape(-1)[0])
    piped_ms = (time.perf_counter() - t0) / (n_done + 1) * 1e3

    feed_ms = max(piped_ms - resident_ms, 1e-9)
    print(json.dumps({
        "workload": "bert_base_pretrain",
        "device_resident_ms": round(resident_ms, 2),
        "pipelined_ms": round(piped_ms, 2),
        "delta_pct": round(100 * (piped_ms / resident_ms - 1.0), 1),
        "batch_bytes": int(bytes_per_batch),
        "implied_feed_MBps": round(
            bytes_per_batch / (feed_ms * 1e-3) / 1e6, 1)
        if piped_ms > resident_ms * 1.05 else "feed fully overlapped",
    }))


if __name__ == "__main__":
    main()
