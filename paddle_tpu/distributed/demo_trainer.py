"""Deterministic trainer/pserver child for the launch.py orchestrator.

This is the workload side of the process-level crash-survival story
(tests/test_orchestrator.py, tools/chaos_check.py --orchestrator): a
small fc net trained with a deterministic data stream, speaking the
orchestrator's full child contract —

* env-carried identity: PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM (set by
  Orchestrator via distributed/parallel.cluster_env), PADDLE_ROLE;
* control channel: one ``PT_ORCH_READY`` announce once serving, one
  ``PT_ORCH_HB {"step": n}`` heartbeat per step;
* SIGTERM = drain: rank 0 runs under ElasticRunner with
  install_signal_handlers(), so the drain command force-checkpoints and
  BOUND-joins the async writer before exit 0 (the orchestrator's
  SIGKILL escalation is the backstop, not the plan);
* crash-restart resume: every rank restores the newest VERIFIED
  checkpoint from the shared --ckpt-dir at startup, so a respawned or
  relaunched-at-new-world child continues the step sequence.

Every rank computes the FULL global batch (mirrored data parallelism),
which makes the parameter trajectory — and therefore the ``LOSS <step>
<value>`` rows rank 0 appends to --out — invariant to world size: the
2→3→2 resize gate diffs those rows bitwise against an uninterrupted
single-process run. --crash-at K SIGKILLs the process at step K every
life, turning this child into the deterministic crash-loop the
restart-budget-exhaustion test needs; --step-delay-ms widens the
mid-step kill window for chaos.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time

import numpy as np


def build_model():
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.initializer import Xavier

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [16], stop_gradient=True)
        label = layers.data("label", [1], dtype="int64",
                            stop_gradient=True)
        h = layers.fc(x, 32, act="relu",
                      param_attr=pt.ParamAttr(name="w0",
                                              initializer=Xavier(seed=7)),
                      bias_attr=pt.ParamAttr(name="b0"))
        logits = layers.fc(h, 4,
                           param_attr=pt.ParamAttr(name="w1",
                                                   initializer=Xavier(
                                                       seed=8)),
                           bias_attr=pt.ParamAttr(name="b1"))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
        opt = pt.optimizer.SGDOptimizer(0.25)
        opt.minimize(loss)
    return main, startup, loss


def batch_for(step: int):
    """The FULL global batch for one step — identical on every rank, so
    the parameter trajectory is world-size invariant."""
    rng = np.random.RandomState(1000 + step)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 4, (16, 1)).astype(np.int64)
    return x, y


def run_trainer(args) -> int:
    import paddle_tpu as pt
    from paddle_tpu.distributed.elastic import ElasticRunner
    from paddle_tpu.distributed.launch import announce_ready, heartbeat

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    main, startup, loss = build_model()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)

    out_f = None
    if args.out and rank == 0:
        # O_APPEND + per-row flush: a SIGKILL never loses a committed
        # row, and a respawned life appends after its predecessor's
        out_f = open(args.out, "a", buffering=1)

    def step_fn(step: int):
        if args.crash_at >= 0 and step == args.crash_at:
            os.kill(os.getpid(), signal.SIGKILL)
        if args.step_delay_ms > 0:
            time.sleep(args.step_delay_ms / 1e3)
        x, y = batch_for(step)
        out = exe.run(main, feed={"x": x, "label": y},
                      fetch_list=[loss], scope=scope)
        value = float(np.asarray(out[0]).reshape(-1)[0])
        if out_f is not None:
            out_f.write(f"LOSS {step} {value:.6f}\n")
        heartbeat(step=step)
        return value

    if rank == 0:
        # the saving rank: ElasticRunner owns restore-at-start, the
        # periodic async save, and the SIGTERM drain (force save +
        # bounded writer join)
        runner = ElasticRunner(args.ckpt_dir, program=main, scope=scope,
                               save_interval_steps=args.save_interval,
                               max_restarts=0, world_size=world)
        runner.install_signal_handlers()
        announce_ready(role="trainer", rank=rank, world=world)
        try:
            runner.run(step_fn, args.steps)
        finally:
            runner.close()
            if out_f is not None:
                out_f.close()
        return 0

    # follower ranks: restore to the shared trajectory, run the mirrored
    # step loop, exit 0 on SIGTERM (nothing of theirs needs saving)
    from paddle_tpu.checkpoint import CheckpointManager

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda _s, _f: stop.set())
    signal.signal(signal.SIGINT, lambda _s, _f: stop.set())
    step = CheckpointManager(args.ckpt_dir).restore_latest(main, scope)
    announce_ready(role="trainer", rank=rank, world=world)
    while step < args.steps and not stop.is_set():
        step_fn(step)
        step += 1
    return 0


def run_pserver(args) -> int:
    """A real RPC service child (distributed/ps/rpc.RPCServer) holding a
    kv table — the orchestrator provisions, heartbeats, and respawns it
    exactly like a trainer; chaos_check SIGKILLs it."""
    from paddle_tpu.distributed.launch import announce_ready, heartbeat
    from paddle_tpu.distributed.ps.rpc import RPCServer

    table = {}

    def handler(method, name, arr, aux):
        if method in ("send", "push", "send_grad"):
            table[name] = np.asarray(arr).copy()
            return None, aux
        got = table.get(name)
        if got is None:
            got = np.zeros(1, dtype=np.float32)
        return got, aux

    server = RPCServer("127.0.0.1:0", handler)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda _s, _f: stop.set())
    signal.signal(signal.SIGINT, lambda _s, _f: stop.set())
    announce_ready(role="pserver", endpoint=server.endpoint)
    while not stop.wait(0.5):
        heartbeat(keys=len(table))
    server.shutdown()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="deterministic orchestrator child (trainer or "
                    "pserver role)")
    ap.add_argument("--role", default="",
                    choices=("", "trainer", "pserver"),
                    help="default: PADDLE_ROLE env (the orchestrator "
                         "sets it), else trainer")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--ckpt-dir", default="",
                    help="shared checkpoint dir (required for trainers)")
    ap.add_argument("--out", default="",
                    help="rank 0 appends 'LOSS <step> <value>' rows here")
    ap.add_argument("--save-interval", type=int, default=1)
    ap.add_argument("--step-delay-ms", type=float, default=0.0,
                    help="pace steps (widens the chaos kill window)")
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="SIGKILL self at this step, every life — the "
                         "deterministic crash loop for budget tests")
    args = ap.parse_args(argv)
    role = args.role or os.environ.get("PADDLE_ROLE", "trainer")
    if role == "pserver":
        return run_pserver(args)
    if not args.ckpt_dir:
        ap.error("--ckpt-dir is required for trainer role")
    return run_trainer(args)


if __name__ == "__main__":
    sys.exit(main())
