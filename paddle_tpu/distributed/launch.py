"""Distributed launcher CLI + supervising orchestrator (reference:
python/paddle/distributed/launch.py:221 — spawns one process per GPU
with PADDLE_TRAINER_ID/... env; heart_beat_monitor.h + the
listen_and_serv respawn paths are its supervision story).

TPU-native: one process per HOST (each owns all local chips); multi-host
rendezvous via jax.distributed's coordination service. ``--nproc N`` > 1
is for CPU children (``JAX_PLATFORMS=cpu`` in their environment): on a
host with an accelerator it is refused with a typed
core/chips.ChipContentionError before anything is spawned, as is any
spawn from a launcher process that has itself initialised the
accelerator backend. Usage:

  python -m paddle_tpu.distributed.launch train.py args...            # local
  python -m paddle_tpu.distributed.launch --nproc 2 train.py ...      # multi-proc (CPU testing)
  python -m paddle_tpu.distributed.launch --nproc 2 --supervise \\
      train.py ...                                                    # crash-surviving
  PADDLE_TRAINER_ID=k PADDLE_TRAINERS_NUM=N PADDLE_COORDINATOR_ADDR=host:port \\
      python -m paddle_tpu.distributed.launch train.py               # pod slice

``--supervise`` replaces fire-and-forget spawning with the
:class:`Orchestrator`: trainers (and optional pserver-tier children)
run as supervised subprocesses with env-carried identity
(distributed/parallel.cluster_env), a stdout control channel
(``PT_ORCH_READY`` announce + ``PT_ORCH_HB`` heartbeats, the
serving/replica.py pattern), SIGTERM-drain as the stop command
(distributed/elastic.ElasticRunner.install_signal_handlers on the child
side), crash detection with the PR 17 windowed restart budget
(elastic.RestartBudget — ``orch.*`` counters, one rate-limit-EXEMPT
``kind:"incident"`` record per child death), and ``execute_scale``:
checkpoint → drain → terminate → relaunch at the new world size, where
the children's cross-world restore (PR 17) continues the uninterrupted
loss trajectory. ``tests/test_orchestrator.py`` SIGKILLs children
mid-step against all of it; ``tools/chaos_check.py --orchestrator`` is
the standing gate.
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..core import chips
from ..core import telemetry

# a child's respawn budget, counted over a sliding window of seconds
# (0 = its lifetime; RestartBudget refunds as elastic_restart_window_s does):
# exhaustion raises RestartBudgetExhaustedError instead of respawn-looping
MAX_RESTARTS = 3
RESTART_WINDOW_S = 0.0
# seconds to wait for a child's PT_ORCH_READY line before the spawn counts
# as failed (<= 0 supervises from spawn), and between the SIGTERM drain
# command and SIGKILL: the child's window for its bounded final checkpoint
READY_TIMEOUT_S = 30.0
DRAIN_TIMEOUT_S = 15.0
READY_MARK = "PT_ORCH_READY"
HB_MARK = "PT_ORCH_HB"


def announce_ready(**attrs):
    """Child-side helper: print the one machine-readable readiness line
    the orchestrator's control channel parses."""
    print(f"{READY_MARK} " + json.dumps(
        dict(attrs, pid=os.getpid())), flush=True)


def heartbeat(step: Optional[int] = None, **attrs):
    """Child-side helper: one heartbeat line (per step, or periodic)."""
    doc = dict(attrs)
    if step is not None:
        doc["step"] = int(step)
    print(f"{HB_MARK} " + json.dumps(doc), flush=True)


class Child:
    """One supervised subprocess: spawn, drain stdout on a daemon
    thread (parsing the control channel), expose liveness/readiness/
    heartbeat state, and stop via SIGTERM-drain with SIGKILL
    escalation."""

    def __init__(self, name: str, role: str, rank: int, argv: List[str],
                 env: Dict[str, str],
                 on_line: Optional[Callable[[str, str], None]] = None):
        self.name = name
        self.role = role
        self.rank = int(rank)
        self.argv = list(argv)
        self.env = dict(env)
        self.on_line = on_line
        self.proc: Optional[subprocess.Popen] = None
        self.ready = threading.Event()
        self.announce: Dict[str, Any] = {}
        self._hb_lock = threading.Lock()
        self.last_hb: float = 0.0
        self.last_step: int = -1
        self.retired = False          # drained on purpose: not a crash
        self.done = False             # exited 0: finished its work
        self._drain_thread: Optional[threading.Thread] = None

    def spawn(self) -> "Child":
        env = dict(self.env)
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            self.argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1)
        with self._hb_lock:
            self.last_hb = time.monotonic()
        self._drain_thread = threading.Thread(
            target=self._drain, name=f"pt-orch-stdout-{self.name}",
            daemon=True)
        self._drain_thread.start()
        return self

    def _drain(self):
        assert self.proc is not None and self.proc.stdout is not None
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            if line.startswith(READY_MARK):
                try:
                    self.announce = json.loads(
                        line[len(READY_MARK):].strip() or "{}")
                except ValueError:
                    self.announce = {}
                with self._hb_lock:
                    self.last_hb = time.monotonic()
                self.ready.set()
                continue
            if line.startswith(HB_MARK):
                with self._hb_lock:
                    self.last_hb = time.monotonic()
                try:
                    doc = json.loads(line[len(HB_MARK):].strip() or "{}")
                    self.last_step = int(doc.get("step", self.last_step))
                except (ValueError, TypeError):
                    pass
                continue
            if self.on_line is not None:
                self.on_line(self.name, line)
        self.proc.stdout.close()

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def returncode(self) -> Optional[int]:
        return self.proc.poll() if self.proc is not None else None

    def heartbeat_age(self, now: Optional[float] = None) -> float:
        if now is None:
            now = time.monotonic()
        with self._hb_lock:
            return max(0.0, now - self.last_hb)

    def signal(self, sig: int):
        if self.alive():
            try:
                self.proc.send_signal(sig)
            except OSError:
                pass

    def stop(self, drain_timeout_s: float = 15.0) -> Optional[int]:
        """SIGTERM (the drain command: children checkpoint + exit 0),
        escalating to SIGKILL past the deadline. Returns the exit code."""
        self.retired = True
        if self.proc is None:
            return None
        self.signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=max(0.1, drain_timeout_s))
        except subprocess.TimeoutExpired:
            telemetry.counter_add("orch.drain_kills", 1, child=self.name)
            self.signal(signal.SIGKILL)
            return self.proc.wait(timeout=10)


class Orchestrator:
    """Supervising launcher: a pserver tier + a trainer world as real
    subprocesses, crash detection under a windowed restart budget, and
    world-size-changing resize by checkpoint → drain → relaunch.

        orch = Orchestrator([sys.executable, "train.py"], world=2)
        orch.start()
        rc = orch.run()         # supervises until all trainers exit 0

    Identity is env-carried (cluster_env: PADDLE_TRAINER_ID /
    PADDLE_TRAINERS_NUM / ...; pservers additionally get PADDLE_ROLE /
    PADDLE_PSERVER_ID, and trainers see the ready-announced pserver
    endpoints in PADDLE_PSERVER_ENDPOINTS). A child death lands exactly
    one rate-limit-exempt incident record (exit code, signal, last
    heartbeat age) and one respawn charge; when the budget is spent the
    orchestrator drains the survivors and raises
    RestartBudgetExhaustedError instead of respawn-looping."""

    def __init__(self, trainer_argv: List[str], world: int,
                 coordinator: str = "127.0.0.1:12355",
                 pserver_argv: Optional[List[str]] = None,
                 n_pservers: int = 0,
                 env: Optional[Dict[str, str]] = None,
                 max_restarts: int = MAX_RESTARTS,
                 restart_window_s: float = RESTART_WINDOW_S,
                 ready_timeout_s: float = READY_TIMEOUT_S,
                 drain_timeout_s: float = DRAIN_TIMEOUT_S,
                 schedule=None,
                 on_line: Optional[Callable[[str, str], None]] = None):
        from .elastic import RestartBudget

        self.trainer_argv = list(trainer_argv)
        self.world = int(world)
        self.coordinator = coordinator
        self.pserver_argv = list(pserver_argv) if pserver_argv else None
        self.n_pservers = int(n_pservers) if pserver_argv else 0
        self.env = dict(os.environ if env is None else env)
        self.max_restarts = int(max_restarts)
        self.restart_window_s = float(restart_window_s)
        self.ready_timeout_s = float(ready_timeout_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.budget = RestartBudget(
            self.max_restarts, self.restart_window_s,
            on_refund=lambda n: telemetry.counter_add(
                "orch.restart_budget_refunds", n))
        self.schedule = schedule      # scaler.ResizeSchedule or None
        self.on_line = on_line
        self.trainers: List[Child] = []
        self.pservers: List[Child] = []
        self.respawns = 0
        self.scale_events = 0
        self._stop = threading.Event()
        self._lock = threading.Lock()   # guards the child lists

    # -- spawning ------------------------------------------------------------
    def _pserver_endpoints(self) -> str:
        return ",".join(c.announce.get("endpoint", "")
                        for c in self.pservers)

    def _spawn_pserver(self, idx: int) -> Child:
        env = dict(self.env)
        env["PADDLE_ROLE"] = "pserver"
        env["PADDLE_PSERVER_ID"] = str(idx)
        env["PADDLE_TRAINERS_NUM"] = str(self.world)
        child = Child(f"pserver-{idx}", "pserver", idx, self.pserver_argv,
                      env, on_line=self.on_line).spawn()
        telemetry.counter_add("orch.spawns", 1, role="pserver")
        return child

    def _spawn_trainer(self, rank: int, world: int) -> Child:
        from .parallel import cluster_env

        env = dict(self.env)
        env.update(cluster_env(rank, world, self.coordinator))
        env["PADDLE_ROLE"] = "trainer"
        eps = self._pserver_endpoints()
        if eps:
            env["PADDLE_PSERVER_ENDPOINTS"] = eps
        child = Child(f"trainer-{rank}", "trainer", rank,
                      self.trainer_argv, env,
                      on_line=self.on_line).spawn()
        telemetry.counter_add("orch.spawns", 1, role="trainer")
        return child

    def _wait_ready(self, children: List[Child]):
        deadline = time.monotonic() + self.ready_timeout_s
        for child in children:
            remaining = deadline - time.monotonic()
            if not child.ready.wait(timeout=max(0.1, remaining)):
                if not child.alive():
                    raise RuntimeError(
                        f"orchestrator: {child.name} died before "
                        f"announcing ready "
                        f"(exit {child.returncode()})")
                raise TimeoutError(
                    f"orchestrator: {child.name} never announced ready "
                    f"within {self.ready_timeout_s:.0f}s")

    def start(self) -> "Orchestrator":
        """Provision the pserver tier first (trainers need the
        announced endpoints), then the trainer world; block until every
        child has announced ready."""
        chips.check_spawn(self.world, self.env,
                          f"Orchestrator({self.world} trainers)")
        with self._lock:
            for idx in range(self.n_pservers):
                self.pservers.append(self._spawn_pserver(idx))
        self._wait_ready(self.pservers)
        with self._lock:
            for rank in range(self.world):
                self.trainers.append(self._spawn_trainer(rank, self.world))
        self._wait_ready(self.trainers)
        return self

    # -- supervision ---------------------------------------------------------
    def max_step(self) -> int:
        with self._lock:
            steps = [c.last_step for c in self.trainers]
        return max(steps) if steps else -1

    def _handle_death(self, child: Child, roster: List[Child]):
        """Exactly one incident + one budget charge + (maybe) one
        respawn per death. Raises RestartBudgetExhaustedError once the
        windowed budget is spent."""
        from ..core import incidents
        from .elastic import RestartBudgetExhaustedError

        rc = child.returncode()
        hb_age = round(child.heartbeat_age(), 3)
        telemetry.counter_add("orch.child_deaths", 1, child=child.name,
                              role=child.role, exit_code=rc)
        # the satellite contract: every child death lands ONE
        # kind:"incident" record, exempt from the rate-limit window like
        # oom/stall — back-to-back deaths must all be in the ledger
        incidents.report_incident(
            "orchestrator", "child_death", 1.0,
            context={"child": child.name, "role": child.role,
                     "rank": child.rank, "exit_code": rc,
                     "signal": -rc if isinstance(rc, int) and rc < 0
                     else None,
                     "heartbeat_age_s": hb_age,
                     "last_step": child.last_step},
            rate_limit=False)
        used = self.budget.note()
        if used > self.max_restarts:
            telemetry.counter_add("orch.budget_exhausted", 1,
                                  child=child.name)
            self.stop()
            raise RestartBudgetExhaustedError(
                used, self.max_restarts, self.restart_window_s,
                last_error=f"{child.name} exit {rc}")
        self.respawns += 1
        telemetry.counter_add("orch.respawns", 1, child=child.name,
                              role=child.role)
        incidents.report_scale_event(
            "orch", "restart", self.world, self.world,
            reason=f"{child.role}_death",
            attrs={"child": child.name, "exit_code": rc,
                   "restarts": used})
        if child.role == "pserver":
            fresh = self._spawn_pserver(child.rank)
        else:
            fresh = self._spawn_trainer(child.rank, self.world)
        fresh.last_step = child.last_step
        with self._lock:
            roster[roster.index(child)] = fresh
        self._wait_ready([fresh])

    def _poll_once(self):
        with self._lock:
            rosters = [(list(self.trainers), self.trainers),
                       (list(self.pservers), self.pservers)]
        for snapshot, roster in rosters:
            for child in snapshot:
                if self._stop.is_set():
                    return
                if child.retired or child.done or child.alive():
                    continue
                if child.returncode() == 0:
                    child.done = True
                    continue
                self._handle_death(child, roster)

    def run(self, poll_s: float = 0.1) -> int:
        """Supervise until every trainer exits 0. Executes scheduled
        resizes between polls. Returns 0; raises
        RestartBudgetExhaustedError when the crash budget is spent."""
        try:
            while not self._stop.is_set():
                self._poll_once()
                with self._lock:
                    trainers = list(self.trainers)
                if trainers and all(c.done for c in trainers):
                    break
                if self.schedule is not None:
                    target = self.schedule.next_target(self.max_step())
                    if target is not None and target != self.world:
                        self.execute_scale(target, reason="schedule")
                time.sleep(poll_s)
        finally:
            self.stop()
        return 0

    # -- elastic resize ------------------------------------------------------
    def execute_scale(self, new_world: int, reason: str = "manual"):
        """The real process-level resize: drain every trainer (SIGTERM →
        the child's ElasticRunner force-checkpoints, bound-joins its
        async writer, exits 0; SIGKILL past the deadline), then relaunch
        the full trainer world at ``new_world`` — each relaunched child
        restores the newest verified checkpoint into the new world (the
        PR 17 cross-world resume), continuing the loss trajectory."""
        from ..core import incidents

        new_world = int(new_world)
        old_world = self.world
        if new_world < 1 or new_world == old_world:
            return
        chips.check_spawn(new_world, self.env,
                          f"Orchestrator.execute_scale({new_world})")
        telemetry.counter_add("orch.drains", 1, world=old_world)
        with self._lock:
            draining = list(self.trainers)
        for child in draining:
            child.retired = True
        for child in draining:
            child.signal(signal.SIGTERM)
        deadline = time.monotonic() + self.drain_timeout_s
        for child in draining:
            if child.proc is None:
                continue
            try:
                child.proc.wait(
                    timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                telemetry.counter_add("orch.drain_kills", 1,
                                      child=child.name)
                child.signal(signal.SIGKILL)
                child.proc.wait(timeout=10)
        self.world = new_world
        with self._lock:
            self.trainers = [self._spawn_trainer(rank, new_world)
                             for rank in range(new_world)]
            fresh = list(self.trainers)
        self._wait_ready(fresh)
        self.scale_events += 1
        telemetry.counter_add("orch.scale_events", 1,
                              old_world=old_world, new_world=new_world)
        incidents.report_scale_event("orch", "resize", old_world,
                                     new_world, reason=reason)

    def stop(self):
        """Drain everything: trainers first (they may still be flushing
        state to the pserver tier), then pservers."""
        self._stop.set()
        with self._lock:
            trainers, pservers = list(self.trainers), list(self.pservers)
        for child in trainers + pservers:
            child.stop(self.drain_timeout_s)


def main(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nproc", type=int, default=1,
                        help="processes to spawn locally (CPU/testing; on "
                             "TPU hardware keep 1 per host)")
    parser.add_argument("--coordinator", default="127.0.0.1:12355")
    parser.add_argument("--supervise", action="store_true",
                        help="supervise children: crash detection + "
                             "respawn under the windowed restart budget, "
                             "SIGTERM-drain stop, scheduled resizes")
    parser.add_argument("--max-restarts", type=int, default=MAX_RESTARTS,
                        help="crash budget of each child")
    parser.add_argument("--restart-window-s", type=float,
                        default=RESTART_WINDOW_S,
                        help="sliding budget window (0 = lifetime)")
    parser.add_argument("--resize-schedule", default="",
                        help="'step:world,step:world' — execute_scale to "
                             "WORLD once any trainer reports STEP "
                             "(scaler.ResizeSchedule)")
    parser.add_argument("--npserver", type=int, default=0,
                        help="pserver-tier children to provision before "
                             "the trainers (requires --pserver-script)")
    parser.add_argument("--pserver-script", default="",
                        help="script run as each pserver child")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.nproc <= 1 and not args.supervise:
        sys.argv = [args.script] + args.script_args
        runpy.run_path(args.script, run_name="__main__")
        return 0

    trainer_argv = [sys.executable, args.script] + args.script_args
    if args.supervise:
        from .scaler import ResizeSchedule

        schedule = ResizeSchedule(args.resize_schedule) \
            if args.resize_schedule else None
        orch = Orchestrator(
            trainer_argv, world=args.nproc, coordinator=args.coordinator,
            pserver_argv=[sys.executable, args.pserver_script]
            if args.pserver_script else None,
            n_pservers=args.npserver,
            max_restarts=args.max_restarts,
            restart_window_s=args.restart_window_s,
            schedule=schedule,
            on_line=lambda name, line: print(f"[{name}] {line}",
                                             flush=True))
        orch.start()
        return orch.run()

    from .parallel import cluster_env

    chips.check_spawn(args.nproc, os.environ,
                      f"launch --nproc {args.nproc}")
    procs = []
    for rank in range(args.nproc):
        env = dict(os.environ)
        env.update(cluster_env(rank, args.nproc, args.coordinator))
        procs.append(subprocess.Popen(trainer_argv, env=env))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
