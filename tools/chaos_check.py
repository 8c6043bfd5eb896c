#!/usr/bin/env python
"""chaos_check — run a short PS training loop under fault injection and
prove it still converges, with an auditable tally of what was injected.

The CLI twin of the `chaos` pytest marker (tests/test_fault_tolerance.py):
point it at a fault spec (core/faults.py grammar) and it

  1. starts N in-process pservers (localhost TCP, real transport),
  2. transpiles a small deterministic net and runs a 1-trainer sync
     training loop through the send/recv program ops,
  3. asserts every loss is finite and the last loss beat the first,
  4. prints the fault/retry telemetry tally (faults.injected,
     ps.rpc_retries, ps.rpc_reconnects, ps.rpc_dedup_hits, ...).

With ``--serving`` it instead chaos-tests the micro-batching serving
engine (paddle_tpu/serving/): concurrent clients push requests through a
``serving.handler`` fault spec and the run asserts every request got a
response — injected handler faults must surface as per-request error
responses, never a wedged queue — and that the engine still serves
cleanly once the fault spec is cleared.

With ``--checkpoint`` it chaos-tests the crash-consistent checkpoint
protocol (paddle_tpu/checkpoint.py): an ElasticRunner trains under a
``ckpt.*`` fault spec (save write/commit failures become elastic
restarts from the newest VERIFIED checkpoint), the run then "dies" —
the trainer scope is discarded — and a fresh scope restores and keeps
training. Asserts convergence across the kill/restart and prints the
ckpt.saves / verify_failures / fallbacks / quarantined tally.

With ``--decode`` it chaos-tests the generative decode engine
(paddle_tpu/serving/decode.py): concurrent clients run generations
under ``decode.step`` / ``decode.kv_alloc`` fault specs and the run
asserts every request got a response (a mid-generation fault surfaces
as a per-request error, never a wedged queue), that the KV page pool's
accounting returns to baseline — zero pages leaked across fault-killed
generations — and that the engine still generates cleanly once the
spec is cleared.

With ``--prefix`` it chaos-tests the prefix-sharing KV store and the
disaggregated prefill/decode plane (paddle_tpu/serving/prefix_store.py
+ disagg.py): concurrent shared-prefix generations and prefill-ship
requests run under ``kv.prefix_lookup`` / ``disagg.ship`` fault specs
— injected faults must surface as per-request errors, never a wedged
queue — then the page/refcount plane is audited (zero leaked or
double-freed pages once every idle prefix chain is reclaimed), and a
decode-role engine is fed a corrupted-CRC shipment: the shipment must
be REJECTED (disagg.crc_rejects) and the request re-prefilled locally
(disagg.fallback_prefills) with output bitwise identical to a unified
replica — a clean shipment must actually install.

With ``--slo`` it gates the flight-recorder + SLO watchdog plane
(paddle_tpu/core/incidents.py) in both directions: one leg per fault
class drives that subsystem's failure signature through the real
telemetry registry into the real rule engine (step-time p99 regression,
live-MFU drop, serving/decode queue saturation, pallas fallback spike,
router failover burst, ckpt verify failure) and asserts the MATCHING
watchdog rule trips EXACTLY once under a sustained breach (the firing
latch + cooldown pin the rate limit) with exactly one kind:"incident"
dump that tools/incident_report.py renders with timeline + counter
deltas; and the clean leg runs a real fault-free training loop with
every clean signature and asserts ZERO rules trip — the false-positive
gate. (The emit side of each subsystem is chaos-gated by the other
legs; --slo gates the consume side.)

With ``--cluster`` it chaos-tests the whole serving control plane
(paddle_tpu/serving/cluster.py): N real replica processes behind the
router, concurrent closed-loop clients with unique request ids, the
fault spec armed BOTH router-side (``router.dispatch``) and inside
every replica (``serving.handler``, via PT_FAULT_SPEC in the replica
env) — then one replica is SIGKILLed mid-load and a new model version
is published mid-load, driving a rolling hot swap while traffic flows.
The gate asserts: every accepted request got EXACTLY one successful
response (dedup-verified by request id), p99 stays under --p99-bound,
the swap completed (responses carry the new version), and the fault /
failover / swap telemetry tally is printed.

With ``--fleet`` it chaos-tests the fleet observatory
(paddle_tpu/core/fleetobs.py): a live cluster of replica processes with
the fleet aggregator scraping every member's /metrics. A clean phase
must show every member OK with zero fleet SLO rule trips; then one
replica is SIGKILLed mid-scrape and the gate asserts the aggregator
marks exactly that member STALE without wedging the scrape loop (the
survivors' scrape ages stay fresh), the ``fleet_member_stale`` rule
trips EXACTLY once for the whole episode, and tools/fleet_report.py
still renders the plane.

With ``--resize`` it gates the elastic-resize protocol
(paddle_tpu/distributed/scaler.py + elastic.py + the PS barrier-regrow
and KV-rebalance paths): a trainer is killed mid-run — its heartbeats
stop and its in-flight step dies — and the REAL pserver heartbeat
verdict drives the ScalerPolicy to a ScaleDown executed by the
ElasticRunner as checkpoint → drain → relaunch at the smaller world;
the trainer then rejoins (plus a brand-new trainer id announcing
itself through elastic admission) and the policy scales back up from
the checkpoint. The gate asserts the per-step loss trajectory is
BITWISE identical to an uninterrupted fixed-world run (resize is
loss-transparent at preserved global batch), ScaleUp and ScaleDown
each fired EXACTLY once with exactly one kind:"scale" record per
transition, and a KV-server-count resize (2 → 3 → 2) conserves the
row set exactly — zero leaked, zero duplicated, pull parity across
the resharded set.

With ``--orchestrator`` it gates process-level crash survival
(paddle_tpu/distributed/launch.py + the serving session-failover
plane): real trainer/pserver subprocesses under the supervising
orchestrator and real replica processes under the ClusterController,
with every role SIGKILLed once — a trainer and the pserver mid-run, a
prefill-tier replica under load, and (four times, one per
greedy/sampled × fp32/int8 identity leg) the decode replica serving a
session, i.e. the router's affinity/probe target, mid-generation. The
gate asserts zero lost work everywhere: the LOSS row stream completes
with no step missing, every request answers 200, each death lands
EXACTLY one kind:"incident" record, killed tier members respawn with
their role sticky, and the resumed token stream is BITWISE-identical
to an uninterrupted run in all four legs.

Examples:
    python tools/chaos_check.py --fault-spec "ps.rpc.send:0.1" --seed 7
    python tools/chaos_check.py --fault-spec "ps.rpc.recv:%9" --steps 8 \
        --servers 2 --telemetry-log /tmp/chaos.jsonl
    python tools/chaos_check.py --serving \
        --fault-spec "serving.handler:%3" --requests 24
    python tools/chaos_check.py --decode \
        --fault-spec "decode.step:%7,decode.kv_alloc:@3" --requests 16
    python tools/chaos_check.py --checkpoint \
        --fault-spec "ckpt.save.commit:%3,ckpt.restore.read:@1" --steps 8
    python tools/chaos_check.py --cluster --replicas 2 --requests 400 \
        --fault-spec "router.dispatch:0.02,serving.handler:%7"
    python tools/chaos_check.py --fleet --replicas 2
    python tools/chaos_check.py --resize --steps 8

Exit status: 0 on success, 2 when the run failed or did not converge.
Stdlib-only CLI surface (argparse); everything heavier lives in
paddle_tpu itself.
"""

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def build_net(lr):
    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [16], stop_gradient=True)
        h = layers.fc(x, 16, act="relu",
                      param_attr=pt.ParamAttr(
                          name="cc_w0",
                          initializer=pt.initializer.Xavier(seed=21)),
                      bias_attr=pt.ParamAttr(name="cc_b0"))
        y = layers.fc(h, 4,
                      param_attr=pt.ParamAttr(
                          name="cc_w1",
                          initializer=pt.initializer.Xavier(seed=22)),
                      bias_attr=pt.ParamAttr(name="cc_b1"))
        loss = layers.mean(y * y)
        pt.optimizer.SGDOptimizer(lr).minimize(loss)
    return main, startup, loss


def run(args) -> int:
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.distributed.ps import DistributeTranspiler, PServer

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    pt.set_flags({"FLAGS_ps_rpc_timeout": args.rpc_timeout,
                  "FLAGS_ps_rpc_max_retries": args.max_retries,
                  "FLAGS_ps_rpc_backoff": args.backoff,
                  "FLAGS_trace_sample_rate": args.trace_sample})
    faults.configure(args.fault_spec, seed=args.seed)

    main, startup, loss = build_net(args.lr)
    # the transpiler pins params to endpoint strings, so allocate real
    # free ports up front (instead of port-0 rebinding + op rewriting)
    import socket

    probes = []
    for _ in range(args.servers):
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        probes.append(s)
    endpoints = [f"127.0.0.1:{s.getsockname()[1]}" for s in probes]
    for s in probes:
        s.close()
    t = DistributeTranspiler()
    t.transpile(0, program=main, startup_program=startup,
                pservers=",".join(endpoints), trainers=1, sync_mode=True)
    servers = []
    for ep in endpoints:
        prog, ps_startup = t.get_pserver_programs(ep)
        servers.append(PServer(
            ep, prog, ps_startup, num_trainers=1, sync_mode=True,
            grad_to_param=prog._ps_grad_to_param,
            grad_to_ops=prog._ps_grad_to_ops,
            common_ops=prog._ps_common_ops))
    trainer_prog = t.get_trainer_program()
    startup_prog = t.get_startup_program()

    losses = []
    try:
        exe = pt.Executor(pt.CPUPlace())
        scope = pt.Scope()
        exe.run(startup_prog, scope=scope, use_compiled=False)
        # one fixed batch: the loss then decreases monotonically under
        # SGD, so "last < first" is a sound convergence check even for
        # very short runs
        feed = {"x": np.random.RandomState(3000).randn(16, 16)
                .astype(np.float32)}
        for step in range(args.steps):
            out = exe.run(trainer_prog, feed=feed, fetch_list=[loss],
                          scope=scope, use_compiled=False)
            val = float(np.asarray(out[0]).reshape(-1)[0])
            losses.append(val)
            print(f"LOSS {step} {val:.6f}", flush=True)
    finally:
        for srv in servers:
            srv.shutdown()

    tally_keys = ("faults.injected", "ps.rpc_calls", "ps.rpc_retries",
                  "ps.rpc_reconnects", "ps.rpc_dedup_hits",
                  "ps.rpc_deadline_exceeded", "ps.rpc_errors",
                  "trace.spans")
    counters = telemetry.counters()
    print("-- telemetry tally " + "-" * 30)
    for key in tally_keys:
        print(f"{key:28s} {int(counters.get(key, 0))}")
    inj = faults.counts()["injected"]
    if inj:
        for site, n in sorted(inj.items()):
            print(f"  injected@{site:18s} {n}")

    if not all(np.isfinite(v) for v in losses):
        print("CHAOS FAIL: non-finite loss under injected faults")
        return 2
    if losses[-1] >= losses[0]:
        print(f"CHAOS FAIL: loss did not converge "
              f"({losses[0]:.6f} -> {losses[-1]:.6f})")
        return 2
    if args.fault_spec and not counters.get("faults.injected", 0):
        print("CHAOS WARN: fault spec never fired (run too short for "
              "the trigger?)")
    print(f"CHAOS OK: {args.steps} steps, loss {losses[0]:.6f} -> "
          f"{losses[-1]:.6f}, {int(counters.get('faults.injected', 0))} "
          f"faults injected, {int(counters.get('ps.rpc_retries', 0))} "
          f"rpc retries")
    return 0


def run_serving(args) -> int:
    """--serving mode: injected serving.handler faults must produce
    per-request error responses, never a wedged queue."""
    import tempfile
    import threading

    import numpy as np

    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.inference import AnalysisConfig, create_predictor
    from paddle_tpu.serving import (LocalClient, ServingConfig,
                                    ServingEngine, ServingError)
    from tools.bench_serving import build_lenet_model

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    if args.trace_sample:
        from paddle_tpu.core import flags as _flags

        _flags.set_flags({"trace_sample_rate": args.trace_sample})
    spec = args.fault_spec or "serving.handler:%3"
    faults.configure(spec, seed=args.seed)

    with tempfile.TemporaryDirectory(prefix="pt_chaos_serving_") as tmp:
        make_batch = build_lenet_model(tmp + "/lenet")
        engine = ServingEngine(
            create_predictor(AnalysisConfig(tmp + "/lenet")),
            config=ServingConfig(max_batch_size=4, batch_timeout_ms=2.0))
        # no warmup: warmup runs through the predictor, and a probabilistic
        # handler spec must not decide the run before clients even start
        engine.start(warmup=False)
        client = LocalClient(engine)
        batch = make_batch(1)

        ok, failed, hung = [], [], []
        lock = threading.Lock()

        def worker(n):
            for _ in range(n):
                try:
                    out = client.infer({"img": batch}, timeout=30)
                except TimeoutError as e:
                    with lock:
                        hung.append(e)
                except Exception as e:
                    with lock:
                        failed.append(type(e).__name__)
                else:
                    with lock:
                        ok.append(out)

        threads = [threading.Thread(target=worker, args=(args.requests // 4,),
                                    name=f"pt-chaos-serving-{i}",
                                    daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # the queue must still move once the faults stop
        faults.configure("")
        try:
            final = client.infer({"img": batch}, timeout=30)
        except Exception as e:
            print(f"CHAOS FAIL: post-fault request failed ({e!r}) — "
                  f"engine wedged")
            return 2
        finally:
            engine.close(drain=True, timeout=10)

    counters = telemetry.counters()
    injected = int(counters.get("faults.injected", 0))
    print("-- serving chaos tally " + "-" * 26)
    for key in ("faults.injected", "serving.requests", "serving.batches",
                "serving.handler_errors", "serving.rejects",
                "trace.spans"):
        print(f"{key:28s} {int(counters.get(key, 0))}")
    print(f"responses: {len(ok)} ok / {len(failed)} error / "
          f"{len(hung)} hung")

    if hung:
        print(f"CHAOS FAIL: {len(hung)} requests never got a response — "
              f"wedged queue")
        return 2
    total = len(ok) + len(failed)
    if total != 4 * (args.requests // 4):
        print("CHAOS FAIL: lost responses")
        return 2
    if injected and not failed:
        print("CHAOS FAIL: faults were injected but no request saw an "
              "error response")
        return 2
    if not injected:
        print("CHAOS WARN: fault spec never fired (run too short for "
              "the trigger?)")
    if not ok or not np.all(np.isfinite(np.asarray(final["logits"]
                                        if "logits" in final
                                        else next(iter(final.values()))))):
        print("CHAOS FAIL: no clean responses / non-finite output")
        return 2
    print(f"CHAOS OK: {total} requests, {len(failed)} per-request error "
          f"responses from {injected} injected handler faults, queue "
          f"never wedged")
    return 0


def run_decode(args) -> int:
    """--decode mode: injected decode.step / decode.kv_alloc faults must
    surface as per-request errors, the KV page pool must account back to
    baseline (zero leaked pages), and the queue must never wedge.

    Runs TWO legs: the default kernel mode, then one with
    ``PT_PALLAS=interpret`` forced — fault injection at decode.step must
    compose with the Pallas paged-attention/int8-GEMM kernel path
    exactly as with the stock lowerings (per-request errors, zero
    leaked pages, live queue)."""
    if args.telemetry_log:
        from paddle_tpu.core import telemetry

        telemetry.configure(args.telemetry_log)
    if args.trace_sample:
        from paddle_tpu.core import flags as _flags

        _flags.set_flags({"trace_sample_rate": args.trace_sample})
    for leg, mode in (("default", None), ("pallas-interpret", "interpret")):
        print(f"== decode chaos leg: {leg} ==")
        old = os.environ.get("PT_PALLAS")
        if mode is not None:
            os.environ["PT_PALLAS"] = mode
        try:
            rc = _run_decode_leg(args, kernel_leg=mode is not None)
        finally:
            if mode is not None:
                if old is None:
                    os.environ.pop("PT_PALLAS", None)
                else:
                    os.environ["PT_PALLAS"] = old
        if rc:
            return rc
    return 0


def _run_decode_leg(args, kernel_leg=False) -> int:
    import threading

    import numpy as np

    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.models.decoder_lm import (DecoderLMConfig,
                                              decoder_lm_params)
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    # a decode.step fault fails the WHOLE in-flight slot array (every
    # affected generation gets a per-request error), so the default uses
    # one-shot triggers — a %N step spec would leave no survivors
    spec = args.fault_spec or "decode.step:@4,decode.kv_alloc:@3"
    counters0 = dict(telemetry.counters())
    attn_disp0 = int(counters0.get("pallas.paged_attn_dispatches", 0))

    cfg = DecoderLMConfig(vocab_size=128, d_model=32, n_head=2, n_layers=2,
                          d_inner=64, max_seq_len=48)
    engine = DecodeEngine(cfg, decoder_lm_params(cfg, seed=0),
                          DecodeConfig(max_slots=4, page_size=4,
                                       kv_pages=32, prefill_buckets=[16]))
    # warm OUTSIDE the fault window: a probabilistic step spec must not
    # decide the run before clients even start
    engine.start(warmup=True)
    baseline_free = engine.pool.free_pages()
    faults.configure(spec, seed=args.seed)

    rng = np.random.RandomState(5)
    prompts = [rng.randint(3, 120, rng.randint(3, 13)).astype(np.int32)
               for _ in range(args.requests)]
    ok, failed, hung = [], [], []
    lock = threading.Lock()

    def worker(indices):
        for i in indices:
            try:
                toks = engine.generate(prompts[i], max_new_tokens=12,
                                       timeout=60)
            except TimeoutError as e:
                with lock:
                    hung.append(e)
            except Exception as e:
                with lock:
                    failed.append(type(e).__name__)
            else:
                with lock:
                    ok.append(toks)

    workers = 4
    threads = [threading.Thread(
        target=worker, args=(list(range(w, args.requests, workers)),),
        name=f"pt-chaos-decode-{w}", daemon=True) for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # the queue must still move — and the pool must be back to baseline —
    # once the faults stop
    faults.configure("")
    try:
        final = engine.generate(prompts[0], max_new_tokens=8, timeout=60)
    except Exception as e:
        print(f"CHAOS FAIL: post-fault generation failed ({e!r}) — "
              f"engine wedged")
        return 2
    finally:
        pool_stats = engine.pool.stats()
        engine.close(drain=True, timeout=10)

    # per-LEG deltas: the interpret leg must not inherit the default
    # leg's injection/error tallies through the process-global counters
    raw = telemetry.counters()
    counters = {k: int(v) - int(counters0.get(k, 0))
                for k, v in raw.items() if isinstance(v, (int, float))}
    injected = int(counters.get("faults.injected", 0))
    print("-- decode chaos tally (this leg) " + "-" * 16)
    for key in ("faults.injected", "decode.requests", "decode.prefills",
                "decode.steps", "decode.tokens", "decode.retired",
                "decode.errors", "decode.kv_pages_allocated",
                "decode.kv_pages_freed", "decode.kv_refusals",
                "pallas.paged_attn_dispatches", "trace.spans"):
        print(f"{key:28s} {int(counters.get(key, 0))}")
    inj = faults.counts()["injected"]
    for site, n in sorted(inj.items()):
        print(f"  injected@{site:18s} {n}")
    print(f"responses: {len(ok)} ok / {len(failed)} error / "
          f"{len(hung)} hung; pool free {pool_stats['pages_free']}/"
          f"{pool_stats['pages_total']} (baseline {baseline_free})")

    if hung:
        print(f"CHAOS FAIL: {len(hung)} generations never got a response "
              f"— wedged queue")
        return 2
    if len(ok) + len(failed) != args.requests:
        print("CHAOS FAIL: lost responses")
        return 2
    if pool_stats["pages_free"] != baseline_free or \
            pool_stats["pages_used"] != 0:
        print(f"CHAOS FAIL: KV pool leaked pages "
              f"({pool_stats['pages_used']} still allocated after every "
              f"request resolved)")
        return 2
    alloc = int(counters.get("decode.kv_pages_allocated", 0))
    freed = int(counters.get("decode.kv_pages_freed", 0))
    if alloc != freed:
        print(f"CHAOS FAIL: page alloc/free imbalance ({alloc} vs {freed})")
        return 2
    if injected and not failed:
        print("CHAOS FAIL: faults were injected but no request saw an "
              "error response")
        return 2
    if not injected:
        print("CHAOS WARN: fault spec never fired (run too short for "
              "the trigger?)")
    if not ok or not np.asarray(final).size:
        print("CHAOS FAIL: no clean generations")
        return 2
    if kernel_leg and int(raw.get("pallas.paged_attn_dispatches", 0)) \
            <= attn_disp0:
        print("CHAOS FAIL: PT_PALLAS=interpret leg never dispatched the "
              "paged-attention kernel — the fault/kernel composition "
              "went untested")
        return 2
    print(f"CHAOS OK: {args.requests} generations, {len(failed)} "
          f"per-request error responses from {injected} injected faults, "
          f"pool accounting back to baseline, queue never wedged")
    return 0


def run_prefix(args) -> int:
    """--prefix mode: gate the prefix-sharing KV store + disaggregated
    prefill plane (serving/prefix_store.py + disagg.py) in three legs:

    1. concurrent shared-prefix generations and prefill-ship requests
       under ``kv.prefix_lookup`` / ``disagg.ship`` faults — injected
       faults must become per-request errors (never a wedged queue)
       while prefix sharing still engages for the survivors;
    2. page/refcount hygiene — ``pool.audit(owned=store.owned_pages())``
       must reconcile with zero violations, and reclaiming every idle
       prefix chain must return the pool exactly to its post-warmup
       baseline (no page leaked into or out of the store);
    3. shipment integrity — a decode-role engine fed a corrupted-CRC
       shipment must REJECT it (disagg.crc_rejects), fall back to a
       local prefill (disagg.fallback_prefills), and still produce
       output bitwise identical to a unified replica; a clean shipment
       must actually install (disagg.installs).
    """
    import threading

    import numpy as np

    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.models.decoder_lm import (DecoderLMConfig,
                                              decoder_lm_params)
    from paddle_tpu.serving import DecodeConfig, DecodeEngine, disagg

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    if args.trace_sample:
        from paddle_tpu.core import flags as _flags

        _flags.set_flags({"trace_sample_rate": args.trace_sample})

    # both default sites are one-shot: a lookup fault kills the whole
    # admission and a ship fault the whole shipment, so %N specs would
    # leave too few clean requests to exercise the sharing path
    spec = args.fault_spec or "kv.prefix_lookup:@3,disagg.ship:@2"
    counters0 = dict(telemetry.counters())

    cfg = DecoderLMConfig(vocab_size=128, d_model=32, n_head=2, n_layers=2,
                          d_inner=64, max_seq_len=48)
    params = decoder_lm_params(cfg, seed=0)
    engine = DecodeEngine(cfg, params,
                          DecodeConfig(max_slots=4, page_size=4,
                                       kv_pages=32, prefill_buckets=[16],
                                       prefix_cache=True))
    engine.start(warmup=True)
    # drain whatever the warmup generation left resident in the store so
    # the baseline is the true empty-store page count
    engine.prefix_store.reclaim(1 << 20)
    baseline_free = engine.pool.free_pages()
    faults.configure(spec, seed=args.seed)

    rng = np.random.RandomState(5)
    shared = rng.randint(3, 120, 8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.randint(3, 120, int(rng.randint(2, 7)))
                                  .astype(np.int32)])
               for _ in range(args.requests)]
    n_ships = max(3, args.requests // 4)
    ok, failed, hung = [], [], []
    ship_ok, ship_failed = [], []
    lock = threading.Lock()

    def gen_worker(indices):
        for i in indices:
            try:
                toks = engine.generate(prompts[i], max_new_tokens=8,
                                       timeout=60)
            except TimeoutError as e:
                with lock:
                    hung.append(e)
            except Exception as e:
                with lock:
                    failed.append(type(e).__name__)
            else:
                with lock:
                    ok.append(toks)

    def ship_worker():
        for i in range(n_ships):
            try:
                blob = engine.submit_prefill(
                    prompts[i % args.requests][:12]).result(60)
            except TimeoutError as e:
                with lock:
                    hung.append(e)
            except Exception as e:
                with lock:
                    ship_failed.append(type(e).__name__)
            else:
                with lock:
                    ship_ok.append(blob)

    gen_workers = 3
    threads = [threading.Thread(
        target=gen_worker, args=(list(range(w, args.requests, gen_workers)),),
        name=f"pt-chaos-prefix-{w}", daemon=True) for w in range(gen_workers)]
    threads.append(threading.Thread(target=ship_worker,
                                    name="pt-chaos-prefix-ship", daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # queue must still move once the faults stop
    faults.configure("")
    try:
        final = engine.generate(prompts[0], max_new_tokens=8, timeout=60)
    except Exception as e:
        print(f"CHAOS FAIL: post-fault generation failed ({e!r}) — "
              f"engine wedged")
        return 2

    # leg 2: refcount/page hygiene while the store is still warm
    violations = engine.pool.audit(owned=engine.prefix_store.owned_pages())
    reclaimed = engine.prefix_store.reclaim(1 << 20)
    free_after = engine.pool.free_pages()
    blocks_after = engine.prefix_store.num_blocks()
    engine.close(drain=True, timeout=10)

    raw = telemetry.counters()
    counters = {k: int(v) - int(counters0.get(k, 0))
                for k, v in raw.items() if isinstance(v, (int, float))}
    injected = int(counters.get("faults.injected", 0))
    print("-- prefix chaos tally " + "-" * 27)
    for key in ("faults.injected", "decode.requests", "decode.prefills",
                "kv.prefix_hits", "kv.prefix_misses", "kv.bytes_saved",
                "kv.cow_forks", "kv.reclaims", "kv.audit_failures",
                "disagg.ships", "disagg.ship_bytes",
                "disagg.fallback_prefills"):
        print(f"{key:28s} {int(counters.get(key, 0))}")
    for site, n in sorted(faults.counts()["injected"].items()):
        print(f"  injected@{site:18s} {n}")
    print(f"responses: {len(ok)} ok / {len(failed)} error; ships: "
          f"{len(ship_ok)} ok / {len(ship_failed)} error; {len(hung)} "
          f"hung; reclaimed {reclaimed} pages, pool free {free_after} "
          f"(baseline {baseline_free})")

    if hung:
        print(f"CHAOS FAIL: {len(hung)} requests never got a response — "
              f"wedged queue")
        return 2
    if len(ok) + len(failed) != args.requests or \
            len(ship_ok) + len(ship_failed) != n_ships:
        print("CHAOS FAIL: lost responses")
        return 2
    if injected and not (failed or ship_failed):
        print("CHAOS FAIL: faults were injected but no request saw an "
              "error response")
        return 2
    if not injected:
        print("CHAOS WARN: fault spec never fired (run too short for "
              "the trigger?)")
    if not ok or not np.asarray(final).size:
        print("CHAOS FAIL: no clean generations")
        return 2
    if int(counters.get("kv.prefix_hits", 0)) < 1:
        print("CHAOS FAIL: shared-prefix workload never hit the prefix "
              "cache — sharing path untested")
        return 2
    if not ship_ok:
        print("CHAOS FAIL: no shipment survived the fault window")
        return 2
    if violations:
        print(f"CHAOS FAIL: pool audit violations: {violations}")
        return 2
    if int(counters.get("kv.audit_failures", 0)):
        print("CHAOS FAIL: kv.audit_failures counted during the run")
        return 2
    if free_after != baseline_free or blocks_after != 0:
        print(f"CHAOS FAIL: prefix store leaked pages (free {free_after} "
              f"vs baseline {baseline_free}, {blocks_after} blocks still "
              f"resident after a full reclaim)")
        return 2

    # leg 3: corrupted-CRC shipment at a decode-role replica — rejected,
    # locally re-prefilled, bitwise identical to the unified answer
    probe = prompts[0][:10].copy()
    ref = DecodeEngine(cfg, params,
                       DecodeConfig(max_slots=2, page_size=4, kv_pages=24,
                                    prefill_buckets=[16],
                                    prefix_cache=False))
    ref.start(warmup=True)
    dec = DecodeEngine(cfg, params,
                       DecodeConfig(max_slots=2, page_size=4, kv_pages=24,
                                    prefill_buckets=[16],
                                    prefix_cache=False, role="decode",
                                    prefill_urls=["http://127.0.0.1:9"]))
    dec.start(warmup=True)
    orig_fetch = disagg.fetch_prefill
    try:
        blob = ref.submit_prefill(probe).result(60)
        want = ref.generate(probe, max_new_tokens=8, timeout=60)
        bad = bytearray(blob)
        bad[-40] ^= 0xFF
        bad = bytes(bad)

        disagg.fetch_prefill = lambda url, prompt, timeout=30.0: bad
        c0 = dict(telemetry.counters())
        got_bad = dec.generate(probe, max_new_tokens=8, timeout=60)
        c1 = dict(telemetry.counters())
        crc = int(c1.get("disagg.crc_rejects", 0)) \
            - int(c0.get("disagg.crc_rejects", 0))
        fb = int(c1.get("disagg.fallback_prefills", 0)) \
            - int(c0.get("disagg.fallback_prefills", 0))
        inst_bad = int(c1.get("disagg.installs", 0)) \
            - int(c0.get("disagg.installs", 0))

        disagg.fetch_prefill = lambda url, prompt, timeout=30.0: blob
        got_good = dec.generate(probe, max_new_tokens=8, timeout=60)
        c2 = dict(telemetry.counters())
        inst_good = int(c2.get("disagg.installs", 0)) \
            - int(c1.get("disagg.installs", 0))
    finally:
        disagg.fetch_prefill = orig_fetch
        dec.close(drain=True, timeout=10)
        ref.close(drain=True, timeout=10)

    print(f"shipment leg: crc_rejects +{crc}, fallback_prefills +{fb}, "
          f"installs +{inst_bad} (corrupt) / +{inst_good} (clean)")
    if crc < 1 or fb < 1:
        print("CHAOS FAIL: corrupted shipment was not rejected / not "
              "re-prefilled locally")
        return 2
    if inst_bad != 0:
        print("CHAOS FAIL: a corrupted shipment was INSTALLED into the "
              "KV pool")
        return 2
    if not np.array_equal(np.asarray(got_bad), np.asarray(want)):
        print("CHAOS FAIL: fallback output diverged from the unified "
              "replica's (corrupt-shipment leg)")
        return 2
    if inst_good != 1:
        print(f"CHAOS FAIL: clean shipment installs +{inst_good} "
              f"(expected exactly 1)")
        return 2
    if not np.array_equal(np.asarray(got_good), np.asarray(want)):
        print("CHAOS FAIL: shipped-prefill output diverged from the "
              "unified replica's")
        return 2
    print(f"CHAOS OK: {args.requests} generations + {n_ships} ships, "
          f"{len(failed) + len(ship_failed)} per-request errors from "
          f"{injected} injected faults, pool back to baseline after "
          f"reclaim, corrupted shipment rejected and re-prefilled "
          f"bitwise-identically")
    return 0


def run_checkpoint(args) -> int:
    """--checkpoint mode: train under ckpt.* faults with elastic
    checkpoint-restart, kill the trainer (drop its scope), restore into
    a fresh one, and prove the run still converges with every rejected
    checkpoint accounted for."""
    import tempfile

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.distributed.elastic import ElasticRunner

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    if args.trace_sample:
        pt.set_flags({"FLAGS_trace_sample_rate": args.trace_sample})
    spec = args.fault_spec or "ckpt.save.commit:%3"
    faults.configure(spec, seed=args.seed)

    main_prog, startup, loss = build_net(args.lr)
    exe = pt.Executor(pt.CPUPlace())
    feed = {"x": np.random.RandomState(3000).randn(16, 16)
            .astype(np.float32)}
    losses = []

    def make_step_fn(scope):
        def step_fn(step):
            out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          scope=scope, use_compiled=False)
            val = float(np.asarray(out[0]).reshape(-1)[0])
            losses.append(val)
            print(f"LOSS {step} {val:.6f}", flush=True)
            return val
        return step_fn

    half = max(2, args.steps // 2)
    with tempfile.TemporaryDirectory(prefix="pt_chaos_ckpt_") as ckpt_dir:
        # phase 1: train half the steps under injected checkpoint faults
        scope = pt.Scope()
        exe.run(startup, scope=scope, use_compiled=False)
        runner = ElasticRunner(ckpt_dir, main_prog, scope,
                               save_interval_steps=1, max_restarts=100,
                               async_save=False)
        runner.run(make_step_fn(scope), half)
        restarts1 = runner.restarts
        # phase 2: the "kill" — discard the scope, restore into a fresh
        # one (still under the fault spec: restore must fall back past
        # any candidate it can't verify) and finish the run
        del scope
        scope2 = pt.Scope()
        exe.run(startup, scope=scope2, use_compiled=False)
        runner2 = ElasticRunner(ckpt_dir, main_prog, scope2,
                                save_interval_steps=1, max_restarts=100,
                                async_save=False)
        runner2.run(make_step_fn(scope2), args.steps)
        runner2.close()

    counters = telemetry.counters()
    tally_keys = ("faults.injected", "ckpt.saves", "ckpt.restores",
                  "ckpt.verify_failures", "ckpt.fallbacks",
                  "ckpt.quarantined", "trace.spans")
    print("-- checkpoint chaos tally " + "-" * 23)
    for key in tally_keys:
        print(f"{key:28s} {int(counters.get(key, 0))}")
    inj = faults.counts()["injected"]
    for site, n in sorted(inj.items()):
        print(f"  injected@{site:18s} {n}")
    print(f"elastic restarts: {restarts1} + {runner2.restarts}")

    if not all(np.isfinite(v) for v in losses):
        print("CHAOS FAIL: non-finite loss under injected ckpt faults")
        return 2
    if losses[-1] >= losses[0]:
        print(f"CHAOS FAIL: loss did not converge across the "
              f"kill/restart ({losses[0]:.6f} -> {losses[-1]:.6f})")
        return 2
    injected = int(counters.get("faults.injected", 0))
    if args.fault_spec and not injected:
        print("CHAOS WARN: fault spec never fired (run too short for "
              "the trigger?)")
    if injected and not (counters.get("ckpt.verify_failures", 0)
                         or restarts1 or runner2.restarts):
        print("CHAOS FAIL: faults were injected but neither the verifier "
              "nor the elastic runner ever saw one")
        return 2
    print(f"CHAOS OK: {args.steps} steps across a kill/restart, loss "
          f"{losses[0]:.6f} -> {losses[-1]:.6f}, {injected} faults "
          f"injected, {int(counters.get('ckpt.saves', 0))} commits, "
          f"{int(counters.get('ckpt.verify_failures', 0))} checkpoints "
          f"rejected")
    return 0


def run_resize(args) -> int:
    """--resize mode: the elastic-resize gate. One process plays the
    whole scale story end to end:

      1. baseline leg — an uninterrupted fixed-world run on a fixed
         batch records the reference loss trajectory;
      2. chaos leg — the same net trains under an ElasticRunner at
         world 2 against a REAL pserver liveness plane (heartbeat
         monitor + elastic admission). A trainer is killed mid-run,
         the heartbeat verdict drives the ScalerPolicy to a ScaleDown
         (checkpoint → drain → relaunch at world 1), the trainer
         rejoins alongside a brand-new trainer id and the policy
         scales back up to 2 from the checkpoint. Because every
         trainer carries the full global batch (the mean of identical
         grads is bitwise exact), the per-step losses must be BITWISE
         identical to the baseline — resize is loss-transparent;
      3. KV leg — rows pushed to 2 KV servers are checkpointed and
         restored into 3 servers, then back into 2: each resize must
         conserve the row set exactly (zero leaked, zero duplicated,
         every row in its `id % N` residue class) with pull parity.
    """
    import socket
    import tempfile
    import time as _time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import telemetry
    from paddle_tpu.distributed.elastic import ElasticRunner
    from paddle_tpu.distributed.ps import DistributeTranspiler, PServer
    from paddle_tpu.distributed.ps.kv_service import DistributedKV, KVServer
    from paddle_tpu.distributed.ps.rpc import RPCClient, start_heartbeat
    from paddle_tpu.distributed.scaler import ScalerPolicy

    def wait_counter(name, floor, timeout=20.0):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if int(telemetry.counters().get(name, 0)) >= floor:
                return True
            _time.sleep(0.05)
        return False

    with tempfile.TemporaryDirectory(prefix="pt_chaos_resize_") as tmp:
        log_path = args.telemetry_log or os.path.join(tmp, "resize.jsonl")
        telemetry.configure(log_path)
        steps = max(8, args.steps)
        feed = {"x": np.random.RandomState(3000).randn(16, 16)
                .astype(np.float32)}
        exe = pt.Executor(pt.CPUPlace())

        # -- leg 1: the uninterrupted reference trajectory ------------------
        base_prog, base_startup, base_loss = build_net(args.lr)
        base_scope = pt.Scope()
        exe.run(base_startup, scope=base_scope, use_compiled=False)
        baseline = []
        for _ in range(steps):
            out = exe.run(base_prog, feed=feed, fetch_list=[base_loss],
                          scope=base_scope, use_compiled=False)
            baseline.append(float(np.asarray(out[0]).reshape(-1)[0]))

        c0 = dict(telemetry.counters())

        # -- leg 2: kill -> scale-down -> rejoin -> scale-up ----------------
        # the liveness plane: one real pserver with a heartbeat monitor;
        # its verdicts (ps.trainer_dead / ps.barrier_regrown) are the ONLY
        # signals the policy sees — no driver shortcuts
        ps_main, ps_boot, _ = build_net(args.lr)
        probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        probe.bind(("127.0.0.1", 0))
        ep = f"127.0.0.1:{probe.getsockname()[1]}"
        probe.close()
        t = DistributeTranspiler()
        t.transpile(0, program=ps_main, startup_program=ps_boot,
                    pservers=ep, trainers=2, sync_mode=True)
        prog, ps_startup = t.get_pserver_programs(ep)
        server = PServer(ep, prog, ps_startup, num_trainers=2,
                         sync_mode=True, heartbeat_timeout=1.0,
                         grad_to_param=prog._ps_grad_to_param,
                         grad_to_ops=prog._ps_grad_to_ops,
                         common_ops=prog._ps_common_ops)

        chaos_prog, chaos_startup, chaos_loss = build_net(args.lr)
        chaos_scope = pt.Scope()
        exe.run(chaos_startup, scope=chaos_scope, use_compiled=False)
        policy = ScalerPolicy(min_world=1, max_world=2, cooldown_s=0.0,
                              source="chaos")
        runner = ElasticRunner(os.path.join(tmp, "ckpt"), chaos_prog,
                               chaos_scope, save_interval_steps=1,
                               max_restarts=5, async_save=False,
                               restart_window_s=120.0, world_size=2,
                               scaler=policy,
                               on_scale=lambda d: {"world_size": d.target})
        stops = {0: start_heartbeat([ep], 0, interval=0.1),
                 1: start_heartbeat([ep], 1, interval=0.1)}
        state = {"killed": False, "revived": False}
        losses = {}
        k_kill = 2

        def step_fn(step):
            if step == k_kill and not state["killed"]:
                state["killed"] = True
                stops[1]()      # the "SIGKILL": trainer 1 goes silent...
                raise ConnectionError("trainer 1 killed mid-step")
            if state["killed"] and not state["revived"] \
                    and runner.world_size == 2:
                # hold the replayed step until the monitor's verdict
                # lands — the ScaleDown must come from the real signal
                if not wait_counter("ps.trainer_dead",
                                    int(c0.get("ps.trainer_dead", 0)) + 1):
                    raise AssertionError(
                        "heartbeat monitor never marked the killed "
                        "trainer dead")
            if state["killed"] and not state["revived"] \
                    and runner.world_size == 1:
                state["revived"] = True
                stops[1] = start_heartbeat([ep], 1, interval=0.1)  # rejoin
                stops[2] = start_heartbeat([ep], 2, interval=0.1)  # new id
                ok = wait_counter(
                    "ps.trainer_revived",
                    int(c0.get("ps.trainer_revived", 0)) + 1) and \
                    wait_counter(
                        "ps.barrier_regrown",
                        int(c0.get("ps.barrier_regrown", 0)) + 2)
                if not ok:
                    raise AssertionError(
                        "pserver barrier never regrew after the rejoin "
                        "+ new-trainer announce")
            out = exe.run(chaos_prog, feed=feed, fetch_list=[chaos_loss],
                          scope=chaos_scope, use_compiled=False)
            val = float(np.asarray(out[0]).reshape(-1)[0])
            losses[step] = val
            print(f"LOSS {step} {val:.6f} world={runner.world_size}",
                  flush=True)
            return val

        try:
            runner.run(step_fn, steps)
        except AssertionError as e:
            print(f"CHAOS FAIL: {e}")
            return 2
        finally:
            runner.close()
            for stop in stops.values():
                try:
                    stop()
                except Exception:
                    pass
            server.shutdown()

        # -- leg 3: KV server-count resize conserves the row set ------------
        dim = 8
        ids = np.arange(64, dtype=np.int64) * 3 + 1
        grads = (np.random.RandomState(7).randn(len(ids), dim)
                 .astype(np.float32))

        def audit(kv_servers, want):
            """None if the resident rows across kv_servers are exactly
            `want` with correct `id % N` routing; else the failure."""
            got = []
            for j, srv in enumerate(kv_servers):
                tab = srv.kv.tables.get("emb")
                mine = (tab.ids() if tab is not None
                        else np.empty(0, np.int64))
                if mine.size and not np.all(mine % len(kv_servers) == j):
                    return (f"server {j}/{len(kv_servers)} holds rows "
                            f"outside its residue class")
                got.append(mine)
            got = np.concatenate(got) if got else np.empty(0, np.int64)
            if got.size != len(want):
                return (f"{got.size} resident rows != {len(want)} saved "
                        f"(leaked or duplicated)")
            if not np.array_equal(np.sort(got), np.sort(want)):
                return "row ID set changed across the resize"
            return None

        kv_dir1 = os.path.join(tmp, "kv_snap_2")
        kv_dir2 = os.path.join(tmp, "kv_snap_3")
        servers2 = [KVServer("127.0.0.1:0") for _ in range(2)]
        servers3 = [KVServer("127.0.0.1:0") for _ in range(3)]
        servers2b = [KVServer("127.0.0.1:0") for _ in range(2)]
        kv_errors = []
        try:
            eps2 = [s.endpoint for s in servers2]
            cli = DistributedKV(eps2, "emb", dim, seed=5)
            cli.pull(ids)                    # materialise, then train
            cli.push(ids, grads, lr=0.5)
            rows0 = cli.pull(ids)
            for j, kep in enumerate(eps2):
                RPCClient.get(kep).call("checkpoint", f"{kv_dir1}|{j}")
            # scale up 2 -> 3 (audit BEFORE pull: a pull would quietly
            # re-init any leaked row)
            eps3 = [s.endpoint for s in servers3]
            for j, kep in enumerate(eps3):
                RPCClient.get(kep).call("checkpoint_load",
                                        f"{kv_dir1}|n{j}|{j}/3")
            err = audit(servers3, ids)
            if err:
                kv_errors.append(f"2->3: {err}")
            if not np.array_equal(
                    rows0, DistributedKV(eps3, "emb", dim, seed=5)
                    .pull(ids)):
                kv_errors.append("2->3: pull parity broken")
            # scale back down 3 -> 2 from the NEW snapshot set
            for j, kep in enumerate(eps3):
                RPCClient.get(kep).call("checkpoint", f"{kv_dir2}|{j}")
            eps2b = [s.endpoint for s in servers2b]
            for j, kep in enumerate(eps2b):
                RPCClient.get(kep).call("checkpoint_load",
                                        f"{kv_dir2}|n{j}|{j}/2")
            err = audit(servers2b, ids)
            if err:
                kv_errors.append(f"3->2: {err}")
            if not np.array_equal(
                    rows0, DistributedKV(eps2b, "emb", dim, seed=5)
                    .pull(ids)):
                kv_errors.append("3->2: pull parity broken")
        finally:
            for srv in servers2 + servers3 + servers2b:
                srv.shutdown()

        # -- the audit ------------------------------------------------------
        telemetry.flush_sink()
        counters = telemetry.counters()

        def delta(name):
            return int(counters.get(name, 0)) - int(c0.get(name, 0))

        tally_keys = ("scaler.evaluations", "scaler.decisions",
                      "scaler.scale_up", "scaler.scale_down",
                      "scaler.clamped", "scaler.suppressed_cooldown",
                      "elastic.restarts", "elastic.scale_events",
                      "incidents.scale_events", "ps.trainer_dead",
                      "ps.trainer_revived", "ps.barrier_regrown",
                      "ps.kv_rebalanced_rows", "ckpt.saves",
                      "ckpt.restores")
        print("-- resize chaos tally " + "-" * 27)
        for key in tally_keys:
            print(f"{key:28s} {delta(key)}")

        scale_recs = []
        try:
            with open(log_path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "scale":
                        scale_recs.append(rec)
        except OSError:
            pass
        restart_recs = [r for r in scale_recs
                        if r.get("name") == "elastic.restart"]
        resize_recs = [r for r in scale_recs
                       if r.get("name") == "elastic.resize"]
        transitions = [(int((r.get("attrs") or {}).get("old_world", -1)),
                        int((r.get("attrs") or {}).get("new_world", -1)))
                       for r in resize_recs]

        failures = []
        chaos = [losses.get(i) for i in range(steps)]
        if any(v is None for v in chaos):
            failures.append(
                f"chaos leg skipped steps "
                f"{[i for i in range(steps) if losses.get(i) is None]}")
        elif not all(np.isfinite(v) for v in chaos):
            failures.append("non-finite loss in the chaos leg")
        else:
            diverged = [i for i in range(steps) if chaos[i] != baseline[i]]
            if diverged:
                i = diverged[0]
                failures.append(
                    f"loss trajectory diverged from the uninterrupted "
                    f"run at step {i}: {chaos[i]!r} != {baseline[i]!r} "
                    f"(resize must be loss-transparent)")
            if chaos[-1] >= chaos[0]:
                failures.append(f"loss did not converge "
                                f"({chaos[0]:.6f} -> {chaos[-1]:.6f})")
        if delta("scaler.scale_down") != 1 or delta("scaler.scale_up") != 1:
            failures.append(
                f"ScaleDown/ScaleUp must each fire exactly once, got "
                f"{delta('scaler.scale_down')}/{delta('scaler.scale_up')}")
        if delta("elastic.scale_events") != 2:
            failures.append(f"expected 2 executed resizes, got "
                            f"{delta('elastic.scale_events')}")
        if delta("elastic.restarts") != 1:
            failures.append(f"expected exactly 1 elastic restart, got "
                            f"{delta('elastic.restarts')}")
        if delta("incidents.scale_events") != 3:
            failures.append(
                f"expected exactly one scale incident per transition "
                f"(1 restart + 2 resizes), got "
                f"{delta('incidents.scale_events')}")
        if len(restart_recs) != 1 or transitions != [(2, 1), (1, 2)]:
            failures.append(
                f"kind:\"scale\" ring records wrong: {len(restart_recs)} "
                f"restart(s), resize transitions {transitions} "
                f"(want 1 restart, [(2, 1), (1, 2)])")
        if delta("ps.barrier_regrown") < 2:
            failures.append(
                f"barrier never regrew for both the rejoined and the "
                f"new trainer (ps.barrier_regrown +"
                f"{delta('ps.barrier_regrown')})")
        if delta("ps.kv_rebalanced_rows") != 2 * len(ids):
            failures.append(
                f"kv rebalance ingested {delta('ps.kv_rebalanced_rows')} "
                f"rows, want {2 * len(ids)} across the two resizes")
        failures.extend(kv_errors)

        if failures:
            for msg in failures:
                print(f"CHAOS FAIL: {msg}")
            return 2
        print(f"CHAOS OK: {steps} steps across kill -> scale-down -> "
              f"scale-up, trajectory bitwise-identical to the "
              f"uninterrupted run (loss {chaos[0]:.6f} -> "
              f"{chaos[-1]:.6f}), {delta('incidents.scale_events')} "
              f"scale incidents for 3 transitions, {len(ids)} KV rows "
              f"conserved across 2 -> 3 -> 2 servers")
        return 0


def run_orchestrator(args) -> int:
    """--orchestrator mode: the process-level crash-survival gate.
    Every role in the stack is SIGKILLed once — trainer, pserver,
    prefill replica, decode replica, and the router's probe/affinity
    target mid-generation — and the run asserts zero lost work:

      1. training leg — a supervising Orchestrator (distributed/
         launch.py) runs 2 trainer + 1 pserver subprocesses; a trainer
         and the pserver are each SIGKILLed mid-run, both respawn
         within the restart budget, and the LOSS row stream completes
         with no step missing; every death lands EXACTLY one
         kind:"incident" record;
      2. prefill-tier leg — a ClusterController provisions a prefill
         tier next to the decode tier; the prefill replica is
         SIGKILLed and every in-flight/subsequent request still
         answers 200 (decode replicas fall back to local prefill while
         the tier member respawns role-sticky);
      3. identity legs — greedy/sampled x fp32/int8: the decode
         replica SERVING a session (the router's affinity/probe
         target) is SIGKILLed mid-generation; the journaled session
         resumes on the survivor and the merged output must be
         BITWISE-identical to an uninterrupted run.
    """
    import json as _json
    import signal as _signal
    import tempfile
    import threading
    import time as _time
    import urllib.request

    import numpy as np

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core import incidents, telemetry
    from paddle_tpu.distributed.launch import Orchestrator
    from paddle_tpu.models.decoder_lm import (DecoderLMConfig,
                                              decoder_lm_params,
                                              save_decoder_lm)
    from paddle_tpu.serving.cluster import ClusterController

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)

    def incident_count(name):
        return len([r for r in
                    incidents.flight_recorder().snapshot(window_s=1e9)
                    if r.get("kind") == "incident"
                    and r.get("name") == name])

    def generate(url, body):
        req = urllib.request.Request(
            url + "/v1/generate", data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return _json.loads(resp.read())

    failures = []
    with tempfile.TemporaryDirectory(prefix="pt_chaos_orch_") as tmp:
        # -- leg 1: trainer + pserver SIGKILL under the orchestrator --------
        out_path = os.path.join(tmp, "rows.txt")
        steps = max(10, args.steps)
        child_argv = [sys.executable, "-m",
                      "paddle_tpu.distributed.demo_trainer",
                      "--steps", str(steps),
                      "--ckpt-dir", os.path.join(tmp, "ckpt"),
                      "--out", out_path, "--step-delay-ms", "60"]
        deaths0 = int(telemetry.counters().get("orch.child_deaths", 0))
        inc0 = incident_count("child_death")
        orch = Orchestrator(child_argv, world=2,
                            pserver_argv=child_argv, n_pservers=1,
                            ready_timeout_s=120, drain_timeout_s=20)
        orch.start()

        def killer():
            while orch.max_step() < 2:
                _time.sleep(0.02)
            orch.trainers[1].signal(_signal.SIGKILL)
            while orch.respawns < 1 or orch.max_step() < 5:
                _time.sleep(0.02)
            orch.pservers[0].signal(_signal.SIGKILL)

        threading.Thread(target=killer, daemon=True,
                         name="pt-chaos-orch-killer").start()
        orch.run()
        rows = {}
        with open(out_path) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 3 and parts[0] == "LOSS":
                    rows[int(parts[1])] = parts[2]
        deaths = int(telemetry.counters().get("orch.child_deaths",
                                              0)) - deaths0
        incs = incident_count("child_death") - inc0
        if sorted(rows) != list(range(steps)):
            failures.append(f"training leg lost rows: have "
                            f"{sorted(rows)} want 0..{steps - 1}")
        if deaths != 2 or orch.respawns != 2:
            failures.append(f"training leg: {deaths} deaths / "
                            f"{orch.respawns} respawns, want 2/2")
        if incs != deaths:
            failures.append(f"training leg: {incs} child_death "
                            f"incidents for {deaths} deaths")
        print(f"leg 1 (trainer+pserver kill): {steps} steps complete, "
              f"{deaths} deaths -> {orch.respawns} respawns, "
              f"{incs} incidents", flush=True)

        # shared decode model + pacing for the serving legs
        lm_dir = os.path.join(tmp, "lm")
        cfg = DecoderLMConfig(vocab_size=97, d_model=32, n_head=2,
                              n_layers=2, d_inner=64, max_seq_len=64)
        save_decoder_lm(lm_dir, cfg, decoder_lm_params(cfg, seed=0))
        prompt = [int(t) for t in
                  np.random.RandomState(3).randint(3, 96, 6)]
        prior_env = {}

        def set_flags_everywhere(**over):
            prior = _flags.apply(over)
            for k, v in over.items():
                key = f"FLAGS_{k}"
                prior_env.setdefault(key, os.environ.get(key))
                os.environ[key] = str(v)
            return prior

        prior_flags = set_flags_everywhere(decode_step_delay_ms=60.0)
        try:
            # -- leg 2: prefill replica SIGKILL, zero lost requests ---------
            rdeaths0 = incident_count("replica_death")
            cluster = ClusterController(
                "", decode_model_dir=lm_dir,
                role_counts={"prefill": 1, "decode": 1},
            ).start(ready_timeout_s=180)
            try:
                body = {"prompt_ids": prompt, "max_new_tokens": 6,
                        "temperature": 0.0}
                before = generate(cluster.url, body)
                victim = cluster.tier_members("prefill")[0]
                victim.kill(_signal.SIGKILL)
                answered = 0
                for i in range(4):
                    got = generate(cluster.url,
                                   dict(body, request_id=f"pf-{i}"))
                    if got["tokens"] == before["tokens"]:
                        answered += 1
                deadline = _time.monotonic() + 120
                while _time.monotonic() < deadline:
                    members = cluster.tier_members("prefill")
                    if members and members[0] is not victim \
                            and members[0].alive():
                        break
                    _time.sleep(0.1)
                members = cluster.tier_members("prefill")
                if not members or members[0] is victim \
                        or members[0].role != "prefill":
                    failures.append("prefill tier member never "
                                    "respawned role-sticky")
                if answered != 4:
                    failures.append(f"prefill-kill leg: only {answered}"
                                    f"/4 requests answered identically")
            finally:
                cluster.close()
            rdeaths = incident_count("replica_death") - rdeaths0
            if rdeaths != 1:
                failures.append(f"prefill-kill leg: {rdeaths} "
                                f"replica_death incidents, want 1")
            print(f"leg 2 (prefill kill): 4/4 requests answered, "
                  f"{rdeaths} incident, tier respawned", flush=True)

            # -- leg 3: the four identity legs ------------------------------
            for leg, temperature, quant in (
                    ("greedy-fp32", 0.0, "none"),
                    ("sampled-fp32", 0.8, "none"),
                    ("greedy-int8", 0.0, "int8"),
                    ("sampled-int8", 0.8, "int8")):
                prior_leg = set_flags_everywhere(decode_weight_quant=quant)
                try:
                    body = {"prompt_ids": prompt, "max_new_tokens": 14,
                            "temperature": temperature, "seed": 11}
                    ref_cluster = ClusterController(
                        "", decode_model_dir=lm_dir,
                        role_counts={"decode": 1},
                        inprocess=True).start(ready_timeout_s=120)
                    try:
                        ref = generate(ref_cluster.url, body)
                    finally:
                        ref_cluster.close()
                    rdeaths0 = incident_count("replica_death")
                    cluster = ClusterController(
                        "", decode_model_dir=lm_dir,
                        role_counts={"decode": 2},
                    ).start(ready_timeout_s=180)
                    try:
                        result = {}

                        def client():
                            result.update(generate(
                                cluster.url,
                                dict(body, request_id=f"id-{leg}")))

                        t = threading.Thread(
                            target=client,
                            name=f"pt-chaos-failover-client-{leg}")
                        t.start()
                        victim = None
                        deadline = _time.monotonic() + 90
                        while _time.monotonic() < deadline:
                            rec = cluster.router.sessions.get(
                                f"id-{leg}")
                            if rec and len(rec["accepted"]) >= 3:
                                handle = cluster.router.pick_generate(
                                    prompt)
                                victim = next(
                                    r for r in cluster.replicas
                                    if r.name == handle.name)
                                victim.kill(_signal.SIGKILL)
                                break
                            _time.sleep(0.01)
                        t.join(timeout=180)
                    finally:
                        cluster.close()
                    if victim is None:
                        failures.append(f"[{leg}] journal never showed "
                                        f"progress — no kill landed")
                    elif not result:
                        failures.append(f"[{leg}] client never "
                                        f"completed after the kill")
                    elif result["tokens"] != ref["tokens"]:
                        failures.append(
                            f"[{leg}] resumed output diverged: "
                            f"{result['tokens']} vs {ref['tokens']}")
                    elif not result.get("failed_over"):
                        failures.append(f"[{leg}] response not marked "
                                        f"failed_over")
                    rdeaths = incident_count("replica_death") - rdeaths0
                    if rdeaths != 1:
                        failures.append(f"[{leg}] {rdeaths} "
                                        f"replica_death incidents, "
                                        f"want 1")
                    print(f"leg 3 [{leg}]: bitwise-identical across "
                          f"the mid-generation kill "
                          f"({len(ref['tokens'])} tokens)", flush=True)
                finally:
                    _flags.apply(prior_leg)
        finally:
            _flags.apply(prior_flags)
            for key, val in prior_env.items():
                if val is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = val

    counters = telemetry.counters()
    tally = {k: counters.get(k, 0)
             for k in ("orch.spawns", "orch.child_deaths",
                       "orch.respawns", "session.failovers",
                       "session.resumed", "router.prefill_forwards",
                       "router.affinity_remaps", "incidents.reported")}
    print("telemetry:", _json.dumps(tally, sort_keys=True), flush=True)
    if failures:
        for f in failures:
            print(f"CHAOS FAIL: {f}", flush=True)
        return 2
    print("CHAOS OK: every role SIGKILLed once (trainer, pserver, "
          "prefill, decode, router target) with zero lost rows/"
          "requests, exactly one incident per death, and all four "
          "identity legs bitwise-identical across the mid-generation "
          "kill", flush=True)
    return 0


def _slo_fault_classes():
    """fault class -> (expected rule, clean driver, fault driver). Each
    driver pushes that subsystem's signature through the REAL telemetry
    registry — the same counters/gauges/timers the subsystems emit — so
    the run exercises the real windowing, baseline learning, rule and
    incident machinery end to end."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.core.flags import flag as _flag

    def steps_clean():
        for _ in range(25):
            telemetry.observe("executor.run_ms", 5.0, kind="timer")

    def steps_fault():
        for _ in range(25):
            telemetry.observe("executor.run_ms", 60.0, kind="timer")

    def mfu_clean():
        telemetry.gauge_set("cost.live_mfu", 0.5)

    def mfu_fault():
        telemetry.gauge_set("cost.live_mfu", 0.05)

    def q_serving():
        telemetry.gauge_set(
            "serving.queue_depth",
            int(0.95 * _flag("serving_max_queue_depth")))

    def q_decode():
        telemetry.gauge_set(
            "decode.queue_depth",
            int(0.95 * _flag("decode_max_queue_depth")))

    def counters(name, n):
        def drive():
            telemetry.counter_add(name, n)
        return drive

    return {
        "step_time": ("step_time_p99", steps_clean, steps_fault),
        "mfu_drop": ("live_mfu_drop", mfu_clean, mfu_fault),
        "serving_queue": ("serving_queue_saturation", None, q_serving),
        "decode_queue": ("decode_queue_saturation", None, q_decode),
        "pallas_gemm": ("pallas_gemm_fallback_spike", None,
                        counters("pallas.int8_gemm_fallbacks", 5)),
        "pallas_attn": ("pallas_attn_fallback_spike", None,
                        counters("pallas.paged_attn_fallbacks", 5)),
        "router_failover": ("router_failover_burst", None,
                            counters("router.failovers", 5)),
        "ckpt_verify": ("ckpt_verify_failures", None,
                        counters("ckpt.verify_failures", 1)),
    }


def _slo_warmup(wd, classes, t0):
    """Drive every clean signature and run enough evaluations for all
    ratio rules to learn their baselines; returns trips seen (must be
    none)."""
    trips = []
    for _name, (_rule, clean, _fault) in classes.items():
        if clean is not None:
            clean()
    for i in range(7):
        trips += wd.evaluate(now=t0 + i * 0.01)
    return trips


def run_slo(args) -> int:
    """--slo mode: per-fault-class true-positive legs (matching rule
    trips exactly once, one incident dump, postmortem renders) + the
    clean false-positive leg (a real fault-free training loop + all
    clean signatures, zero trips)."""
    import glob as _glob
    import io
    import json as _json
    import tempfile
    import time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core import incidents, telemetry
    from tools.incident_report import (load_incidents, render_incident,
                                       summarize_incident)
    from tools.perf_report import load_counted

    classes = _slo_fault_classes()
    only = [c for c in (args.slo_class or "").split(",") if c]
    for c in only:
        if c not in classes and c != "clean":
            print(f"SLO FAIL: unknown fault class {c!r} "
                  f"(have {sorted(classes)} + 'clean')")
            return 2
    run_classes = only or (list(classes) + ["clean"])
    tmpdir = tempfile.mkdtemp(prefix="pt_chaos_slo_")
    failures = []

    for cls in run_classes:
        log = os.path.join(tmpdir, f"slo_{cls}.jsonl")
        telemetry.configure(None)
        telemetry.reset()
        incidents.reset()
        telemetry.configure(log)
        wd = incidents.arm()
        t0 = time.time()
        if cls != "clean":
            warm_trips = _slo_warmup(wd, classes, t0)
            if warm_trips:
                failures.append(f"{cls}: warmup tripped {warm_trips}")
                continue

        if cls == "clean":
            # the false-positive gate: a REAL fault-free training loop
            # (the same net the PS chaos leg trains) with the live
            # signals it actually produces — run_ms timers, the real
            # (tiny, CPU) live-MFU gauge — evaluated many times; zero
            # rules may trip. No synthetic signatures here: mixing them
            # with real signals would poison the learned baselines
            main, startup, loss = build_net(0.1)
            exe = pt.Executor(pt.CPUPlace())
            scope = pt.Scope()
            exe.run(startup, scope=scope, use_compiled=False)
            feed = {"x": np.random.RandomState(3000).randn(16, 16)
                    .astype(np.float32)}
            trips = []
            for step in range(args.steps):
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
                trips += wd.evaluate()
            for i in range(20):
                trips += wd.evaluate(now=t0 + 1 + i * 0.01)
            telemetry.flush_sink()
            recs, _m = load_counted(log)
            incident_recs = load_incidents(recs)
            if trips or incident_recs:
                failures.append(f"clean: FALSE POSITIVE — trips {trips}, "
                                f"{len(incident_recs)} incident dumps")
                continue
            print(f"SLO leg clean: {args.steps} real fault-free steps, "
                  f"0 trips, 0 incidents (ok)")
            continue

        rule_name, _clean, fault = classes[cls]
        fault()
        trips = []
        # sustained breach across many evaluations: the firing latch +
        # cooldown must pin the trip (and the incident dump) to ONE
        for i in range(10):
            trips += wd.evaluate(now=t0 + 1 + i * 0.01)
        telemetry.flush_sink()
        recs, _m = load_counted(log)
        incident_recs = load_incidents(recs)
        if trips != [rule_name]:
            failures.append(f"{cls}: expected exactly one "
                            f"{rule_name!r} trip, got {trips}")
            continue
        if len(incident_recs) != 1:
            failures.append(f"{cls}: {len(incident_recs)} incident "
                            f"dumps (want exactly 1)")
            continue
        s = summarize_incident(incident_recs[0])
        if s["source"] != "slo" or (s["rule"] or {}).get("name") \
                != rule_name:
            failures.append(f"{cls}: incident names rule "
                            f"{(s['rule'] or {}).get('name')!r}, "
                            f"want {rule_name!r}")
            continue
        buf = io.StringIO()
        render_incident(s, out=buf)
        text = buf.getvalue()
        missing = [sec for sec in ("-- tripped rule --",
                                   "-- counter deltas",
                                   "-- timeline around the trip")
                   if sec not in text]
        if missing:
            failures.append(f"{cls}: postmortem missing {missing}")
            continue
        print(f"SLO leg {cls}: rule {rule_name} tripped exactly once "
              f"over 10 breached evaluations, 1 incident dump "
              f"({s['ring_records']} ring records), postmortem ok")

    telemetry.configure(None)
    c = telemetry.counters()
    print("-- slo chaos tally " + "-" * 30)
    for key in ("slo.trips", "slo.evaluations", "incidents.reported",
                "incidents.rate_limited"):
        print(f"{key:28s} {int(c.get(key, 0))}")
    for f in _glob.glob(os.path.join(tmpdir, "*.jsonl")):
        try:
            os.remove(f)
        except OSError:
            pass
    try:
        os.rmdir(tmpdir)
    except OSError:
        pass
    if failures:
        for f in failures:
            print(f"SLO FAIL: {f}")
        return 2
    print(f"CHAOS OK: {len(run_classes)} SLO legs — every fault class "
          f"tripped its matching watchdog rule exactly once, the clean "
          f"leg tripped zero")
    return 0


def run_cluster(args) -> int:
    """--cluster mode: the full control-plane gate. Replica processes
    behind the router, faults armed on both sides of the hop, one
    replica SIGKILLed mid-load, one model version published mid-load
    (rolling hot swap) — and still: every accepted request answered
    exactly once, p99 bounded."""
    import json
    import tempfile
    import threading
    import time
    import urllib.error
    import urllib.request

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu import io, layers
    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.serving import ClusterController, ServingConfig

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    if args.trace_sample:
        pt.set_flags({"FLAGS_trace_sample_rate": args.trace_sample})
    spec = args.fault_spec or "router.dispatch:0.02,serving.handler:%7"
    # the SAME spec arms both sides of the hop: router.dispatch fires in
    # THIS process (the router), serving.handler inside every replica
    # (PT_FAULT_SPEC in the replica env — each site only exists where its
    # code runs, so one spec string covers the fleet)
    faults.configure(spec, seed=args.seed)
    replica_env = dict(os.environ)
    replica_env["PT_FAULT_SPEC"] = spec
    replica_env["PT_FAULT_SEED"] = str(args.seed)

    def save_mlp(d, seed):
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup):
            x = layers.data("x", [16])
            h = layers.fc(x, 16, act="relu", param_attr=pt.ParamAttr(
                name="ch_w0", initializer=pt.initializer.Xavier(seed=seed)))
            y = layers.fc(h, 4, param_attr=pt.ParamAttr(
                name="ch_w1",
                initializer=pt.initializer.Xavier(seed=seed + 1)))
        scope = pt.Scope()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        io.save_inference_model(d, ["x"], [y], main_program=main_p,
                                scope=scope)

    n_requests = args.requests
    workers = 4
    results: dict = {}
    latencies: list = []
    versions_seen: set = set()
    lock = threading.Lock()
    xbatch = np.random.RandomState(7).randn(1, 16).astype(np.float32)

    with tempfile.TemporaryDirectory(prefix="pt_chaos_cluster_") as tmp:
        save_mlp(tmp + "/m1", 11)
        save_mlp(tmp + "/m2", 53)
        root = tmp + "/models"
        ckpt.publish_model(root, tmp + "/m1", version=1)
        cluster = ClusterController(
            root, replicas=args.replicas, inprocess=False,
            serving_config=ServingConfig(max_batch_size=4,
                                         batch_timeout_ms=1.0),
            model_poll_s=0.25,
            replica_env=replica_env).start(ready_timeout_s=180)
        print(f"cluster up: {args.replicas} replica processes behind "
              f"{cluster.url}, fault spec '{spec}'", flush=True)

        def worker(wid, count):
            for i in range(count):
                rid = f"chaos-{wid}-{i}"
                body = json.dumps({"inputs": {"x": xbatch.tolist()},
                                   "deadline_ms": 30000}).encode()
                req = urllib.request.Request(
                    cluster.url + "/v1/infer", data=body,
                    headers={"Content-Type": "application/json",
                             "X-Request-Id": rid})
                t0 = time.perf_counter()
                try:
                    resp = urllib.request.urlopen(req, timeout=60)
                    doc = json.loads(resp.read())
                except urllib.error.HTTPError as e:
                    with lock:
                        results[rid] = f"HTTP {e.code}"
                    continue
                except Exception as e:
                    with lock:
                        results[rid] = f"{type(e).__name__}"
                    continue
                ms = (time.perf_counter() - t0) * 1e3
                with lock:
                    prev = results.get(rid, 0)
                    results[rid] = prev + 1 if isinstance(prev, int) \
                        else prev
                    latencies.append(ms)
                    if doc.get("model_version") is not None:
                        versions_seen.add(doc["model_version"])

        share = n_requests // workers
        threads = [threading.Thread(target=worker, args=(w, share),
                                    name=f"pt-chaos-cluster-{w}",
                                    daemon=True) for w in range(workers)]
        t_load0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(0.3)
        victim = cluster.replicas[0]
        victim.kill()
        print(f"SIGKILLed {victim.name} (pid {victim.proc.pid}) "
              f"mid-load", flush=True)
        time.sleep(0.3)
        ckpt.publish_model(root, tmp + "/m2", version=2)
        print("published model v2 mid-load (rolling hot swap)", flush=True)
        for t in threads:
            t.join()
        load_s = time.perf_counter() - t_load0

        # let the rolling swap finish, then prove the fleet serves v2
        swap_ok = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if cluster.current_version == 2:
                body = json.dumps(
                    {"inputs": {"x": xbatch.tolist()}}).encode()
                try:
                    doc = json.loads(urllib.request.urlopen(
                        urllib.request.Request(
                            cluster.url + "/v1/infer", data=body,
                            headers={"Content-Type": "application/json"}),
                        timeout=30).read())
                    if doc.get("model_version") == 2:
                        versions_seen.add(2)
                        swap_ok = True
                        break
                except Exception:
                    pass
            time.sleep(0.25)
        stats = cluster.stats()
        cluster.close()

    counters = telemetry.counters()
    served = sum(1 for v in results.values() if v == 1)
    multi = {k: v for k, v in results.items()
             if isinstance(v, int) and v > 1}
    failed = {k: v for k, v in results.items() if not isinstance(v, int)}
    lat = sorted(latencies)
    p50 = lat[int(0.50 * (len(lat) - 1))] if lat else 0.0
    p99 = lat[int(0.99 * (len(lat) - 1))] if lat else 0.0

    print("-- cluster chaos tally " + "-" * 26)
    for key in ("faults.injected", "router.requests", "router.retries",
                "router.failovers", "router.rejects",
                "router.dispatch_errors", "router.dedup_hits",
                "router.replica_deaths", "router.replica_restarts",
                "router.swaps", "router.swap_errors",
                "router.deadline_exceeded", "trace.spans"):
        print(f"{key:28s} {int(counters.get(key, 0))}")
    inj = faults.counts()["injected"]
    for site, n in sorted(inj.items()):
        print(f"  injected@{site:18s} {n}  (router-side)")
    print(f"requests: {served} served exactly-once / {len(multi)} "
          f"duplicated / {len(failed)} failed, load wall {load_s:.1f}s")
    print(f"latency ms: p50 {p50:.1f}  p99 {p99:.1f}  "
          f"(bound {args.p99_bound:.0f})")
    print(f"versions seen in responses: {sorted(versions_seen)}; "
          f"fleet on v{stats.get('current_version')}")

    if failed:
        sample = list(failed.items())[:5]
        print(f"CHAOS FAIL: {len(failed)} accepted requests never got a "
              f"successful response (lost): {sample}")
        return 2
    if multi:
        print(f"CHAOS FAIL: duplicated responses (exactly-once broken): "
              f"{list(multi.items())[:5]}")
        return 2
    if served != workers * share:
        print(f"CHAOS FAIL: {served} != {workers * share} responses")
        return 2
    if p99 > args.p99_bound:
        print(f"CHAOS FAIL: p99 {p99:.1f} ms above bound "
              f"{args.p99_bound:.0f} ms")
        return 2
    if not counters.get("router.replica_deaths", 0):
        print("CHAOS FAIL: the SIGKILL was never observed by the monitor")
        return 2
    if not swap_ok:
        print("CHAOS FAIL: the mid-load model swap never completed to v2")
        return 2
    if args.fault_spec and not counters.get("faults.injected", 0):
        print("CHAOS WARN: router-side fault spec never fired (run too "
              "short for the trigger?)")
    print(f"CHAOS OK: {served} requests exactly-once through SIGKILL + "
          f"hot swap, {int(counters.get('router.failovers', 0))} "
          f"failovers, {int(counters.get('router.swaps', 0))} replica "
          f"swaps, p99 {p99:.1f} ms")
    return 0


def run_fleet(args) -> int:
    """--fleet mode: the fleet-observatory gate (core/fleetobs.py), in
    two phases over one live cluster of replica PROCESSES:

    1. clean — the aggregator scrapes every member for a few passes;
       every member must be OK with fresh scrape ages and ZERO fleet
       SLO rule trips (false-positive gate);
    2. kill — one replica is SIGKILLed mid-scrape; the aggregator must
       mark exactly that member STALE without wedging (the surviving
       members' scrape ages stay fresh, passes keep advancing), the
       fleet_member_stale rule must trip EXACTLY once for the whole
       episode, and tools/fleet_report.py must still render the plane
       (live members > 0 -> exit 0).
    """
    import tempfile
    import time

    import paddle_tpu as pt
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu import io, layers
    from paddle_tpu.core import telemetry
    from paddle_tpu.serving import ClusterController, ServingConfig

    if args.telemetry_log:
        telemetry.configure(args.telemetry_log)
    # fast scrape/staleness clocks so the gate runs in seconds; respawn
    # disabled (max_restarts=0) so the SIGKILLed replica STAYS dead and
    # the staleness episode persists
    pt.set_flags({"FLAGS_fleet_scrape_interval_s": 0.2,
                  "FLAGS_fleet_stale_after_s": 1.0})

    def save_mlp(d, seed):
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup):
            x = layers.data("x", [16])
            y = layers.fc(x, 4, param_attr=pt.ParamAttr(
                name="fl_w0", initializer=pt.initializer.Xavier(seed=seed)))
        scope = pt.Scope()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        io.save_inference_model(d, ["x"], [y], main_program=main_p,
                                scope=scope)

    with tempfile.TemporaryDirectory(prefix="pt_chaos_fleet_") as tmp:
        save_mlp(tmp + "/m1", 29)
        root = tmp + "/models"
        ckpt.publish_model(root, tmp + "/m1", version=1)
        cluster = ClusterController(
            root, replicas=args.replicas, inprocess=False,
            serving_config=ServingConfig(max_batch_size=4,
                                         batch_timeout_ms=1.0),
            max_restarts=0, auto_swap=False,
            fleet=True).start(ready_timeout_s=180)
        agg = cluster.fleet_aggregator
        print(f"cluster up: {args.replicas} replica processes + router "
              f"behind {cluster.url}, fleet scrape every "
              f"{agg.interval_s}s", flush=True)

        # -- phase 1: clean ------------------------------------------------
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = agg.status()
            if st["passes"] >= 5 and all(
                    m["state"] == "OK" for m in st["members"]):
                break
            time.sleep(0.2)
        st = agg.status()
        members = {m["name"]: m for m in st["members"]}
        clean_trips = st["rules"]["trips"]
        print(f"clean phase: {st['passes']} scrape passes, "
              f"{len(members)} members "
              f"{sorted(members)}, rule trips {clean_trips}", flush=True)
        if len(members) != args.replicas + 1:     # replicas + router
            print(f"CHAOS FAIL: fleet sees {len(members)} members, "
                  f"expected {args.replicas + 1}")
            cluster.close()
            return 2
        not_ok = [n for n, m in members.items() if m["state"] != "OK"]
        if not_ok:
            print(f"CHAOS FAIL: members not OK in the clean phase: "
                  f"{not_ok}")
            cluster.close()
            return 2
        if clean_trips:
            print(f"CHAOS FAIL: clean fleet tripped {clean_trips} "
                  f"rule(s): {st['rules']['firing']} (false positive)")
            cluster.close()
            return 2

        # -- phase 2: SIGKILL one replica mid-scrape -----------------------
        victim = cluster.replicas[0]
        victim.kill()
        print(f"SIGKILLed {victim.name} (pid {victim.proc.pid}) "
              f"mid-scrape", flush=True)
        passes_at_kill = agg.status()["passes"]
        stale_seen = False
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = agg.status()
            m = {x["name"]: x for x in st["members"]}.get(victim.name)
            if m is not None and m["state"] == "STALE":
                stale_seen = True
                break
            time.sleep(0.2)
        if not stale_seen:
            print(f"CHAOS FAIL: {victim.name} never went STALE after "
                  f"the SIGKILL")
            cluster.close()
            return 2
        # let several more passes run: the loop must stay live and the
        # stale rule must hold at exactly one trip for the episode
        settle = agg.status()["passes"] + 5
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                agg.status()["passes"] < settle:
            time.sleep(0.2)
        st = agg.status()
        members = {m["name"]: m for m in st["members"]}
        survivors = [m for n, m in members.items() if n != victim.name]
        stale_rule = st["rules"]["rules"].get("fleet_member_stale") or {}
        trips = int(stale_rule.get("trips") or 0)
        fresh = [m for m in survivors
                 if m["state"] == "OK"
                 and (m["scrape_age_s"] or 99) < 5 * agg.interval_s
                 + agg.stale_after_s]
        print(f"kill phase: passes {passes_at_kill} -> {st['passes']}, "
              f"{victim.name} {members[victim.name]['state']} "
              f"(consecutive failures "
              f"{members[victim.name]['consecutive_failures']}), "
              f"{len(fresh)}/{len(survivors)} survivors fresh, "
              f"fleet_member_stale trips {trips}", flush=True)

        # the router still renders the plane for the CLI
        sys.path.insert(0, REPO_ROOT)
        from tools import fleet_report
        report_rc = fleet_report.main(["--url", cluster.url])

        stats = cluster.stats()
        cluster.close()

    counters = telemetry.counters()
    print("-- fleet chaos tally " + "-" * 28)
    for key in ("fleet.scrapes", "fleet.scrape_failures",
                "fleet.members_registered", "fleet.members_went_stale",
                "slo.trips", "incidents.reported"):
        print(f"{key:28s} {int(counters.get(key, 0))}")
    print(f"fleet stats section: {json.dumps(stats.get('fleet'))[:200]}")

    if st["passes"] <= passes_at_kill:
        print("CHAOS FAIL: the scrape loop wedged after the SIGKILL")
        return 2
    if len(fresh) != len(survivors):
        print(f"CHAOS FAIL: surviving members went stale with the loop "
              f"up: {[m['name'] for m in survivors if m not in fresh]}")
        return 2
    if trips != 1:
        print(f"CHAOS FAIL: fleet_member_stale tripped {trips} times, "
              f"expected exactly 1 for one persistent STALE episode")
        return 2
    if "fleet_member_stale" not in st["rules"]["firing"]:
        print("CHAOS FAIL: the stale episode is not held firing while "
              "the member stays dead")
        return 2
    if not counters.get("fleet.members_went_stale", 0):
        print("CHAOS FAIL: fleet.members_went_stale never counted")
        return 2
    if report_rc != 0:
        print(f"CHAOS FAIL: fleet_report exited {report_rc} on a live "
              f"plane")
        return 2
    print(f"CHAOS OK: SIGKILL mid-scrape -> {victim.name} STALE without "
          f"wedging the loop, fleet_member_stale tripped exactly once, "
          f"{int(counters.get('fleet.scrapes', 0))} member scrapes, "
          f"fleet_report renders the plane")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="run a short PS training loop under fault injection "
                    "and assert convergence")
    ap.add_argument("--fault-spec", default="",
                    help="core/faults.py spec, e.g. 'ps.rpc.send:0.1' "
                         "(empty = fault-free control run)")
    ap.add_argument("--serving", action="store_true",
                    help="chaos-test the micro-batching serving engine "
                         "(serving.handler site) instead of the PS loop")
    ap.add_argument("--decode", action="store_true",
                    help="chaos-test the generative decode engine "
                         "(decode.step / decode.kv_alloc sites): "
                         "mid-generation faults must become per-request "
                         "errors with the KV page pool accounting back "
                         "to baseline")
    ap.add_argument("--prefix", action="store_true",
                    help="chaos-test the prefix-sharing KV store + "
                         "disaggregated prefill plane (kv.prefix_lookup "
                         "/ disagg.ship sites): per-request errors only, "
                         "zero leaked pages via pool.audit, and a "
                         "corrupted-CRC shipment rejected and locally "
                         "re-prefilled — never served")
    ap.add_argument("--checkpoint", action="store_true",
                    help="chaos-test the crash-consistent checkpoint "
                         "protocol (ckpt.save.write/commit + "
                         "ckpt.restore.read sites) with an elastic "
                         "kill/restart instead of the PS loop")
    ap.add_argument("--slo", action="store_true",
                    help="gate the flight-recorder + SLO watchdog plane "
                         "(core/incidents.py): per-fault-class legs "
                         "must trip the matching rule exactly once with "
                         "one incident dump; the clean leg must trip "
                         "zero (false-positive gate)")
    ap.add_argument("--slo-class", default="",
                    help="--slo mode: comma-separated fault classes to "
                         "run (default: all + clean); classes: "
                         "step_time, mfu_drop, serving_queue, "
                         "decode_queue, pallas_gemm, pallas_attn, "
                         "router_failover, ckpt_verify, clean")
    ap.add_argument("--cluster", action="store_true",
                    help="chaos-test the cluster serving control plane "
                         "(replica processes + router): SIGKILL a "
                         "replica and hot-swap the model mid-load under "
                         "router.dispatch/serving.handler faults, assert "
                         "exactly-once responses and bounded p99")
    ap.add_argument("--fleet", action="store_true",
                    help="chaos-test the fleet observatory (core/"
                         "fleetobs.py): SIGKILL a replica mid-scrape — "
                         "the aggregator must mark it STALE without "
                         "wedging, the fleet_member_stale rule must "
                         "trip exactly once, the clean phase zero")
    ap.add_argument("--resize", action="store_true",
                    help="gate the elastic-resize protocol (distributed/"
                         "scaler.py + elastic.py): kill a trainer "
                         "mid-run, scale down on the heartbeat verdict, "
                         "scale back up from the checkpoint when it "
                         "rejoins — the loss trajectory must be bitwise "
                         "identical to an uninterrupted run, with "
                         "exactly one scale incident per transition and "
                         "zero leaked KV rows across a server-count "
                         "resize")
    ap.add_argument("--orchestrator", action="store_true",
                    help="gate process-level crash survival "
                         "(distributed/launch.py + decode-session "
                         "failover): SIGKILL every role once — "
                         "trainer, pserver, prefill replica, decode "
                         "replica, the router's mid-generation "
                         "affinity target — and assert zero lost "
                         "rows/requests, exactly one incident per "
                         "death, and bitwise-identical resumed output "
                         "in all four identity legs")
    ap.add_argument("--replicas", type=int, default=2,
                    help="--cluster/--fleet mode: replica process count")
    ap.add_argument("--p99-bound", type=float, default=5000.0,
                    help="--cluster mode: fail if client-observed p99 "
                         "latency exceeds this many ms")
    ap.add_argument("--requests", type=int, default=24,
                    help="--serving/--cluster mode: total client requests")
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-injection seed (FLAGS_fault_seed)")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="FLAGS_trace_sample_rate for the run — with a "
                         "--telemetry-log, span records land in the log "
                         "(render with tools/trace_view.py) and the "
                         "trace.spans tally is printed alongside the "
                         "fault counts")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--servers", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--rpc-timeout", type=float, default=20.0,
                    help="FLAGS_ps_rpc_timeout for the run")
    ap.add_argument("--max-retries", type=int, default=16)
    ap.add_argument("--backoff", type=float, default=0.01)
    ap.add_argument("--telemetry-log", default="",
                    help="also write the JSONL run log here")
    args = ap.parse_args()
    if args.cluster and args.requests == 24:
        args.requests = 400   # the serving default is too short to span
        # a kill + a rolling swap; --requests still overrides
    if args.slo:
        sys.exit(run_slo(args))
    if args.serving:
        sys.exit(run_serving(args))
    if args.decode:
        sys.exit(run_decode(args))
    if args.prefix:
        sys.exit(run_prefix(args))
    if args.checkpoint:
        sys.exit(run_checkpoint(args))
    if args.cluster:
        sys.exit(run_cluster(args))
    if args.fleet:
        sys.exit(run_fleet(args))
    if args.resize:
        sys.exit(run_resize(args))
    if args.orchestrator:
        sys.exit(run_orchestrator(args))
    sys.exit(run(args))


if __name__ == "__main__":
    main()
