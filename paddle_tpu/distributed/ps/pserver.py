"""Parameter-server runtime: the listen-and-serve loop.

Capability mirror of the reference pserver
(operators/distributed_ops/listen_and_serv_op.cc:367 RunImpl — RPC server
loop executing optimizer blocks on received grads;
operators/distributed/communicator.h sync semantics). TPU-native twist:
the pserver executes its optimizer sub-program with the framework's OWN
interpreting executor on host CPU — the same op lowerings that run on
device run the update, so optimizer semantics (sgd/momentum/adam/...)
are identical to local training by construction.

Sync mode (reference SyncCommunicator / DistributeTranspiler sync_mode):
  each param applies its update once ALL trainers' grads for the step
  arrived (mean), bumping the param's version; trainers block in recv
  until the version they expect is published.
Async mode (reference AsyncCommunicator, Downpour-style): every received
  grad applies immediately (scaled 1/trainers); recv returns the current
  value, no barriers.

Fault tolerance: sync-mode recv waits are bounded by
FLAGS_ps_sync_barrier_timeout (BarrierTimeoutError passed back to the
trainer); with FLAGS_ps_degrade_to_survivors, a trainer the
HeartBeatMonitor declares dead is dropped from the barrier — updates
become the mean over survivors (ps.barrier_degraded telemetry) and a
revived trainer is re-admitted at the next version. Checkpoint saves
pass the `ps.checkpoint.save` fault-injection site first.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import numpy as np

from ...core import faults, telemetry
from ...core import flags as _flags
from ...core.analysis import lockdep
from ..errors import BarrierTimeoutError
from .rpc import RPCServer


class ParamState:
    __slots__ = ("pending", "version", "cond")

    def __init__(self):
        self.pending: Dict[int, np.ndarray] = {}
        self.version = 0
        self.cond = lockdep.condition("ps.param_state")


class HeartBeatMonitor:
    """Worker-liveness watchdog (reference:
    operators/distributed/heart_beat_monitor.h:51 — the pserver-side
    monitor that watches trainer pings and flags silent workers).
    Trainers ping implicitly with every send_grad/recv_param (and
    explicitly via the 'heartbeat' RPC); a background thread marks a
    trainer dead after `timeout` seconds of silence and invokes
    `on_dead` (default: log). The PS protocol survives a dead trainer in
    async mode; in sync mode the monitor is what tells the operator WHY
    a barrier stalled."""

    def __init__(self, num_trainers: int, timeout: float = 60.0,
                 interval: float = 5.0, on_dead=None):
        self.timeout = float(timeout)
        self.interval = float(interval)
        self.on_dead = on_dead
        self.last_seen: Dict[int, float] = {}
        self.num_trainers = int(num_trainers)
        self.dead: set = set()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch,
                                        name="pt-ps-heartbeat-monitor",
                                        daemon=True)

    def start(self):
        import time

        # pre-register every expected trainer (reference initialises the
        # full worker table up front) so one that DIES BEFORE its first
        # contact is still flagged
        now = time.monotonic()
        for tid in range(self.num_trainers):
            self.last_seen.setdefault(tid, now)
        self._thread.start()
        return self

    def ping(self, trainer_id: int):
        import time

        tid = int(trainer_id)
        self.last_seen[tid] = time.monotonic()
        if tid in self.dead:
            # re-admission: the next barrier requires this trainer again
            self.dead.discard(tid)
            telemetry.counter_add("ps.trainer_revived", 1, trainer=tid)
            telemetry.counter_add("ps.barrier_regrown", 1, trainer=tid,
                                  cause="revived")

    def _watch(self):
        import logging
        import time

        while not self._stop.wait(self.interval):
            now = time.monotonic()
            for tid, seen in list(self.last_seen.items()):
                if tid not in self.dead and now - seen > self.timeout:
                    self.dead.add(tid)
                    if self.on_dead is not None:
                        self.on_dead(tid)
                    else:
                        logging.getLogger("paddle_tpu.ps").warning(
                            "trainer %d silent for %.0fs — marked DEAD",
                            tid, now - seen)

    def stop(self):
        self._stop.set()


class PServer:
    """One parameter-server process.

    pserver_program: a Program whose ops are the optimizer ops for the
    params this server owns (built by DistributeTranspiler);
    startup_program initialises those params + accumulators + lr vars.
    """

    def __init__(self, endpoint: str, pserver_program, startup_program,
                 num_trainers: int, sync_mode: bool = True,
                 grad_to_param: Optional[Dict[str, str]] = None,
                 grad_to_ops: Optional[Dict[str, list]] = None,
                 common_ops: Optional[list] = None,
                 heartbeat_timeout: float = 0.0,
                 mode: Optional[str] = None, merge_size: int = 0):
        """mode: 'sync' | 'async' | 'half_async' (overrides the legacy
        sync_mode bool). half_async (reference communicator.h:343
        HalfAsyncCommunicator): no cross-trainer barriers, but received
        grads BUFFER and apply as the MEAN of `merge_size` contributions
        (default num_trainers) — async liveness with sync-like merged
        updates."""
        import paddle_tpu as pt

        self.num_trainers = int(num_trainers)
        self.mode = mode or ("sync" if sync_mode else "async")
        self.sync_mode = self.mode == "sync"
        self.merge_size = int(merge_size or num_trainers)
        self.program = pserver_program
        self.scope = pt.Scope()
        self.exe = pt.Executor(pt.CPUPlace())
        self.exe.run(startup_program, scope=self.scope, use_compiled=False)
        self.grad_to_param = grad_to_param or {}
        self.grad_to_ops = grad_to_ops or {}
        # LR-schedule / counter ops shared by every param on this server
        # (transpiler._common_ops) — run once per GLOBAL step, not once
        # per parameter apply
        self.common_ops = list(common_ops or [])
        self._apply_count: Dict[str, int] = {}
        self._global_step = 0
        self.states: Dict[str, ParamState] = {
            g: ParamState() for g in self.grad_to_param}
        # one update at a time: connection threads race on the shared
        # scope (items() iteration vs insertion) and on the step counters
        self._apply_lock = lockdep.lock("ps.apply")
        self.monitor = None
        if heartbeat_timeout > 0:
            self.monitor = HeartBeatMonitor(
                num_trainers, timeout=heartbeat_timeout,
                interval=min(heartbeat_timeout / 4, 5.0),
                on_dead=self._on_trainer_dead).start()
        # sparse KV tables served from THIS host's memory (reference:
        # large_scale_kv.h server tables; see kv_service.py)
        from .kv_service import KVTables

        self.kv = KVTables()
        self.server = RPCServer(endpoint, self._handle)
        self.endpoint = self.server.endpoint

    # -- update machinery ----------------------------------------------------
    def _apply(self, grad_name: str, grad: np.ndarray):
        """Run this grad's optimizer ops through the interpreting executor
        (op-by-op, host CPU — the reference's executor.cc loop role)."""
        from ...core.executor import run_op

        with self._apply_lock:
            env = {}
            for name, val in self.scope.items():
                env[name] = val

            def persist(ops):
                for op in ops:
                    for out in op.output_names():
                        if out in env:
                            self.scope.set(out, np.asarray(env[out]))

            # the Nth apply of any grad belongs to global step N-1; the
            # fastest-advancing grad opens the new step, running the
            # common/LR-schedule ops (e.g. the increment on
            # @LR_DECAY_COUNTER@) exactly ONCE per step — a server
            # hosting K params must not decay K× per step
            count = self._apply_count.get(grad_name, 0) + 1
            self._apply_count[grad_name] = count
            step = np.int32(count - 1)
            if count > self._global_step:
                self._global_step = count
                for op in self.common_ops:
                    run_op(op, env, step=step)
                persist(self.common_ops)
                # observability only (nothing reads it back): global
                # steps applied, inspectable from tests/monitoring
                self.scope.set("@PS_STEP@", np.int32(self._global_step))
            env[grad_name] = grad
            for op in self.grad_to_ops[grad_name]:
                run_op(op, env, step=step)
            persist(self.grad_to_ops[grad_name])

    # -- sync-barrier policy -------------------------------------------------
    def _barrier_set(self, st: "ParamState") -> set:
        """Trainer ids whose grads complete the current sync barrier.
        Default: everyone. With FLAGS_ps_degrade_to_survivors and a
        heartbeat monitor, the barrier shrinks to the LIVE set (anyone
        whose grad already arrived counts as live regardless of the
        monitor's view) — the update becomes the mean over survivors
        instead of stalling to the barrier timeout."""
        everyone = set(range(self.num_trainers))
        if self.monitor is None or \
                not _flags.flag("ps_degrade_to_survivors"):
            return everyone
        return (everyone - set(self.monitor.dead)) | set(st.pending)

    def _maybe_apply_sync(self, grad_name: str, st: "ParamState"):
        """Apply the mean grad + bump the version once every barrier
        member contributed. Caller holds st.cond."""
        need = self._barrier_set(st)
        if not st.pending or not need <= set(st.pending):
            return
        if len(need) < self.num_trainers:
            telemetry.counter_add("ps.barrier_degraded", 1,
                                  grad=grad_name, survivors=len(need))
        vals = list(st.pending.values())
        mean = np.mean(vals, axis=0)
        try:
            self._apply(grad_name, mean.astype(vals[0].dtype))
        finally:
            # a failed apply must not leave this step's grads pending —
            # the NEXT step's first send would complete the barrier with
            # a stale mix
            st.pending.clear()
        st.version += 1
        st.cond.notify_all()

    def _on_trainer_dead(self, tid: int):
        """HeartBeatMonitor callback: a trainer went silent. Under the
        degradation policy, any barrier now satisfied by the survivors
        alone completes immediately instead of waiting out the stall."""
        import logging

        logging.getLogger("paddle_tpu.ps").warning(
            "trainer %d silent past %.1fs — marked DEAD%s", tid,
            self.monitor.timeout,
            " (degrading barriers to survivors)"
            if _flags.flag("ps_degrade_to_survivors") else "")
        telemetry.counter_add("ps.trainer_dead", 1, trainer=tid)
        if not _flags.flag("ps_degrade_to_survivors"):
            return
        for grad_name, st in self.states.items():
            with st.cond:
                if self.sync_mode:
                    self._maybe_apply_sync(grad_name, st)

    def _admit_trainer(self, tid: int):
        """Elastic admission (scale-UP half of the barrier contract): a
        trainer id the server has never seen announces itself via its
        first send_grad/heartbeat, and the barrier REGROWS to include it
        — the complement of the degrade-to-survivors shrink path. Gated
        by FLAGS_ps_elastic_admission so fixed-world deployments keep
        treating unknown ids as a config error."""
        with self._apply_lock:
            if tid < self.num_trainers:
                return
            old = self.num_trainers
            self.num_trainers = tid + 1
            if self.monitor is not None:
                import time

                now = time.monotonic()
                for t in range(old, self.num_trainers):
                    self.monitor.last_seen.setdefault(t, now)
                self.monitor.num_trainers = self.num_trainers
        telemetry.counter_add("ps.barrier_regrown", 1, trainer=tid,
                              cause="joined")

    def _handle(self, method, name, arr, aux):
        # every contact is a liveness signal; recv_param's aux is a
        # version (not a trainer id), so sync-blocked trainers ping via
        # their preceding sends + explicit heartbeats
        if method in ("send_grad", "heartbeat"):
            if int(aux) >= self.num_trainers and \
                    _flags.flag("ps_elastic_admission"):
                self._admit_trainer(int(aux))
            if self.monitor is not None:
                self.monitor.ping(aux)
        if method == "heartbeat":
            if name:
                # the beat's name field carries the trainer's metrics
                # URL (rpc.start_heartbeat metrics_url): hand it to the
                # fleet observatory when one is running here
                try:
                    from ...core import fleetobs
                    fleetobs.announce(f"trainer-{aux}", name)
                except Exception:
                    pass
            return None, 0
        if method.startswith("kv_"):
            # under the apply lock: checkpoint snapshots take the same
            # lock, so dense params and KV rows form one consistent cut
            with self._apply_lock:
                return self.kv.handle(method, name, arr, aux)
        if method == "send_grad":
            st = self.states[name]
            with st.cond:
                if self.sync_mode:
                    st.pending[aux] = arr     # aux = trainer_id
                    self._maybe_apply_sync(name, st)
                elif self.mode == "half_async":
                    # buffer by arrival order (duplicates from one fast
                    # trainer merge too — reference HalfAsync's queue
                    # semantics), apply the MEAN per merge_size batch
                    st.pending[len(st.pending)] = arr
                    if len(st.pending) >= self.merge_size:
                        mean = np.mean(list(st.pending.values()), axis=0)
                        try:
                            self._apply(name, mean.astype(arr.dtype))
                        finally:
                            st.pending.clear()
                        st.version += 1
                        st.cond.notify_all()
                else:
                    self._apply(name, (arr / self.num_trainers)
                                .astype(arr.dtype))
                    st.version += 1
            return None, st.version
        if method == "recv_param":
            # aux = minimum version the trainer expects (sync); 0 = latest.
            # Returns the published version so the client can track it.
            grad_name = self._grad_of(name)
            ver = 0
            if grad_name is not None:
                st = self.states[grad_name]
                if self.sync_mode and aux > 0:
                    timeout = _flags.flag("ps_sync_barrier_timeout")
                    with st.cond:
                        ok = st.cond.wait_for(lambda: st.version >= aux,
                                              timeout=timeout)
                    if not ok:
                        # surface the stalled barrier instead of silently
                        # serving a stale parameter (the RPC layer returns
                        # this to the trainer as an error status)
                        dead = (sorted(self.monitor.dead)
                                if self.monitor else None)
                        telemetry.counter_add("ps.barrier_timeouts", 1,
                                              param=name)
                        raise BarrierTimeoutError(
                            f"sync barrier timed out after {timeout:.0f}s:"
                            f" '{name}' at version {st.version}, trainer "
                            f"expects >= {aux}"
                            + (f"; dead trainers: {dead}" if dead else ""))
                ver = st.version
            val = self.scope.find_var(name)
            return np.asarray(val), ver
        if method == "barrier":
            return None, 0
        if method == "checkpoint":
            # name carries "dirname|tag" — tag is the notifier-assigned
            # server index, stable across restarts (endpoints are not:
            # port-0 servers rebind)
            dirname, _, tag = name.partition("|")
            self.save_checkpoint(dirname, tag or None)
            return None, 0
        if method == "checkpoint_load":
            # wire: "dirname|tag" or "dirname|tag|index/count" — the
            # third field asks for a KV rebalance into a server set of
            # `count` endpoints of which this server is `index`
            dirname, _, rest = name.partition("|")
            tag, _, shard = rest.partition("|")
            rebalance = None
            if shard:
                idx, _, cnt = shard.partition("/")
                rebalance = (int(idx), int(cnt))
            self.load_checkpoint(dirname, tag or None, rebalance=rebalance)
            return None, 0
        raise ValueError(f"unknown PS method '{method}'")

    # -- checkpoint/restore (reference: checkpoint_notify_op.cc flow) -------
    def _ckpt_tag(self) -> str:
        return self.endpoint.replace(":", "_").replace(".", "-")

    def save_checkpoint(self, dirname: str, tag: str = None):
        """Snapshot params + optimizer accumulators (the whole scope),
        the step counters, and every KV table. Taken under the apply
        lock so the snapshot is a consistent cut, committed through the
        atomic checkpoint protocol (paddle_tpu/checkpoint.py: staged
        write + fsync + COMMIT manifest with per-array checksums +
        rename) so a server killed mid-snapshot leaves the previous
        snapshot intact and verifiable."""
        from ... import checkpoint as ckpt

        # fault site: a checkpoint that dies BEFORE writing must leave
        # the previous snapshot intact (nothing is touched before here)
        faults.maybe_fail("ps.checkpoint.save", dirname=dirname)
        os.makedirs(dirname, exist_ok=True)
        tag = tag or self._ckpt_tag()
        with self._apply_lock:
            arrays = {n: np.asarray(v) for n, v in self.scope.items()}
            meta = {"global_step": self._global_step,
                    "apply_count": dict(self._apply_count)}
            # still inside the lock: kv_* RPCs also serialise on it, so
            # the table snapshot pairs with the dense cut above
            self.kv.save_all(dirname, tag)
        ckpt.write_checkpoint_dir(
            os.path.join(dirname, f"pserver_{tag}"), arrays,
            extras={"ps": meta}, step=self._global_step)
        telemetry.counter_add("ps.checkpoints", 1, tag=tag)

    def load_checkpoint(self, dirname: str, tag: str = None,
                        rebalance=None):
        """Verified restore: the snapshot's manifest (file sha256 +
        per-array CRC32) must check out before any byte enters the
        server scope — a torn snapshot raises CheckpointCorruptError
        (returned to the notifier as an RPC error) instead of silently
        serving wrong parameters.

        rebalance=(server_index, num_servers): restore into a CHANGED
        server count. KV rows re-shard by id across the new set
        (KVTables.load_all reads every saved server's snapshot, keeps
        the rows `id % num_servers == server_index` routes here); the
        dense part stays per-tag — a brand-new server whose tag has no
        snapshot keeps its startup-initialised params."""
        from ... import checkpoint as ckpt

        tag = tag or self._ckpt_tag()
        dense_dir = os.path.join(dirname, f"pserver_{tag}")
        arrays, meta = {}, {}
        if rebalance is None or os.path.isdir(dense_dir):
            arrays, manifest = ckpt.read_checkpoint_dir(dense_dir)
            meta = (manifest.get("extras") or {}).get("ps") or {}
        with self._apply_lock:
            for k, v in arrays.items():
                self.scope.set(k, v)
            if meta:
                self._global_step = int(meta.get("global_step", 0))
                self._apply_count = {
                    k: int(v) for k, v in (meta.get("apply_count")
                                           or {}).items()}
            # inside the lock, like save: a kv RPC between the dense
            # restore and the table restore would see a torn state
            if rebalance is None:
                self.kv.load_all(dirname, tag)
            else:
                self.kv.load_all(dirname, tag,
                                 num_servers=int(rebalance[1]),
                                 server_index=int(rebalance[0]))

    def _grad_of(self, param_name):
        for g, p in self.grad_to_param.items():
            if p == param_name:
                return g
        return None

    def run(self):
        """Block until a trainer sends __stop__ (reference:
        ListenAndServOp::RunImpl loop)."""
        self.server.wait()

    def shutdown(self):
        if self.monitor is not None:
            self.monitor.stop()
        self.server.shutdown()
