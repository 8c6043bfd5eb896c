"""Elastic training: checkpoint-restart failure recovery + elastic resize.

Capability mirror of the reference's failure-detection story (SURVEY.md
§5): the reference has a pserver-side HeartBeatMonitor
(operators/distributed/heart_beat_monitor.h:51) and a placeholder
`DistributedStrategy.elastic` flag but NO in-tree trainer recovery —
"checkpoint-restart based recovery is the realistic TPU equivalent".
This module provides that equivalent: a supervised step loop that
checkpoints periodically and, when a step raises a recoverable error,
restores the newest VERIFIED checkpoint and resumes, up to max_restarts.

    runner = ElasticRunner(ckpt_dir, program, scope,
                           save_interval_steps=10)
    runner.run(step_fn, num_steps)   # step_fn(step) -> loss

Exact resume: each checkpoint carries the global RNG state (restored by
the manager) and, when a `reader` with ``state_dict()``/``set_state()``
is attached (the double-buffer _GeneratorLoader grew that surface), the
reader cursor — a restored run re-reads exactly the batch that was in
flight when the step failed. The step loop runs under try/finally
``wait_until_finished()`` so teardown can't truncate an in-flight async
save; checkpoint-save failures (e.g. injected ``ckpt.save.*`` faults)
are themselves recoverable, not fatal.

Restart budget: with ``FLAGS_elastic_restart_window_s`` > 0 only the
restarts inside that sliding window count against ``max_restarts`` —
sustained progress refunds the crash budget instead of a lifetime
counter bleeding it dry (``elastic.restart_budget_refunds``). Every
restart lands a ``kind:"scale"`` record in the incident ring
(core/incidents.report_scale_event).

Elastic resize: attach a ``scaler`` (distributed/scaler.ScalerPolicy)
and an ``on_scale`` callback and the runner executes ScaleUp/ScaleDown
decisions between steps as checkpoint → barrier-drain → relaunch-at-
new-world: the current step is force-checkpointed, the async writer is
drained, and ``on_scale(decision)`` rebuilds the world (program, scope,
step_fn, reader) at the target size — the runner then restores the
checkpoint INTO the new world (world-size-changing resume: dense arrays
re-lay out at the next compile, ZeRO state regroups via
parallel/zero_regroup, the reader cursor re-splits across the new
trainer set) and continues the step loop.

On a multi-host job the same script re-launched by the cluster manager
lands in restore_latest() and continues — the reference's
checkpoint_notify flow without the pserver middleman.
"""

from __future__ import annotations

import logging
import signal as _signal
import threading
import time
from collections import deque
from typing import Callable, Optional, Tuple

from ..core import flags as _flags
from ..core import telemetry
from .errors import RpcError

_LOG = logging.getLogger("paddle_tpu.elastic")


class RestartBudgetExhaustedError(RuntimeError):
    """The windowed restart budget is spent: ``used`` restarts landed
    inside ``window_s`` (or lifetime, with no window) against a budget
    of ``max_restarts``. A supervisor that sees this must STOP
    respawning — the failure is systematic, not transient."""

    def __init__(self, used: int, max_restarts: int, window_s: float,
                 last_error: str = ""):
        self.used = int(used)
        self.max_restarts = int(max_restarts)
        self.window_s = float(window_s)
        self.last_error = last_error
        window = f" inside {window_s:.0f}s" if window_s > 0 else ""
        detail = f" (last: {last_error})" if last_error else ""
        super().__init__(
            f"restart budget exhausted: {used} restarts{window} against "
            f"max_restarts={max_restarts}{detail}")


class RestartBudget:
    """Sliding-window crash budget, shared by ElasticRunner (in-process
    restore-restart) and the launch.py orchestrator (child respawn).
    With ``window_s`` <= 0 the budget is a lifetime counter; otherwise
    only restarts inside the window count — pruning expired entries IS
    the refund for sustained progress (reported to ``on_refund`` so
    each owner counts refunds on its own metric name)."""

    def __init__(self, max_restarts: int, window_s: float = 0.0,
                 on_refund: Optional[Callable[[int], None]] = None):
        self.max_restarts = int(max_restarts)
        self.window_s = float(window_s)
        self.on_refund = on_refund
        self.times: deque = deque()
        self.lifetime = 0

    def used(self, now: Optional[float] = None) -> int:
        if self.window_s <= 0:
            return self.lifetime
        if now is None:
            now = time.monotonic()
        cut = now - self.window_s
        refunded = 0
        while self.times and self.times[0] < cut:
            self.times.popleft()
            refunded += 1
        if refunded and self.on_refund is not None:
            self.on_refund(refunded)
        return len(self.times)

    def note(self, now: Optional[float] = None) -> int:
        """Charge one restart; returns the post-charge used count."""
        if now is None:
            now = time.monotonic()
        self.lifetime += 1
        self.times.append(now)
        return self.used(now)

    def exhausted(self, now: Optional[float] = None) -> bool:
        return self.used(now) > self.max_restarts

    def check(self, now: Optional[float] = None, last_error: str = ""):
        """Raise RestartBudgetExhaustedError when over budget."""
        used = self.used(now)
        if used > self.max_restarts:
            raise RestartBudgetExhaustedError(
                used, self.max_restarts, self.window_s,
                last_error=last_error)

# error types worth a restart: transport failures (RpcError covers
# RpcDeadlineError/RpcRemoteError — retries exhausted, deadlines blown,
# barrier stalls reported by a pserver) and the OS-level network/device
# errors underneath them. Plain RuntimeError is deliberately NOT here —
# it swallowed programming errors; raise one of these (or subclass) from
# custom step_fns that want a restart. In particular core.verify's
# ProgramVerifyError (a RuntimeError) names a corrupt PROGRAM: restoring
# a checkpoint and re-running the same program would fail identically
# forever, so it must re-raise (tests/test_verify.py pins this).
# bound on joining the async checkpoint writer when a runner drains under
# SIGTERM: a wedged writer cannot stall termination past the supervisor's
# kill escalation (the atomic rename commit still means no torn checkpoint
# is ever restored)
DRAIN_TIMEOUT_S = 30.0
RECOVERABLE = (RpcError, ConnectionError, OSError, TimeoutError)


class ElasticRunner:
    def __init__(self, ckpt_dir: str, program=None, scope=None,
                 save_interval_steps: int = 10, max_to_keep: int = 3,
                 max_restarts: int = 3,
                 recoverable: Tuple[type, ...] = RECOVERABLE,
                 reader=None, async_save: bool = True,
                 restart_window_s: Optional[float] = None,
                 world_size: int = 1, scaler=None,
                 on_scale: Optional[Callable] = None):
        from ..checkpoint import CheckpointManager

        self.program = program
        self.scope = scope
        self.max_restarts = int(max_restarts)
        self.recoverable = tuple(recoverable)
        self.save_interval = int(save_interval_steps)
        self.reader = reader
        self.mgr = CheckpointManager(ckpt_dir, max_to_keep=max_to_keep,
                                     save_interval_steps=save_interval_steps,
                                     async_save=async_save)
        self.restarts = 0              # lifetime total (observability)
        self.restart_window_s = float(
            _flags.flag("elastic_restart_window_s")
            if restart_window_s is None else restart_window_s)
        self._budget = RestartBudget(
            self.max_restarts, self.restart_window_s,
            on_refund=lambda n: telemetry.counter_add(
                "elastic.restart_budget_refunds", n))
        # alias, not a copy: tests (and budget_used) poke the deque
        self._restart_times = self._budget.times
        self.world_size = int(world_size)
        self.scaler = scaler
        self.on_scale = on_scale
        self.scale_events = 0
        # cooperative drain (orchestrator SIGTERM path): the loop
        # force-saves at the next step boundary, bound-joins the async
        # writer, and returns instead of raising
        self._drain = threading.Event()
        self.drained_at: Optional[int] = None

    def _recoverable_exc(self, e: BaseException) -> bool:
        """True if e — or anything on its explicit cause chain — is a
        recoverable type. The interpreting executor wraps op failures in
        ExecutionError `from` the original, so a transport RpcError
        surfacing through a send/recv op still counts; a wrapped
        TypeError still re-raises."""
        seen = set()
        while e is not None and id(e) not in seen:
            if isinstance(e, self.recoverable):
                return True
            seen.add(id(e))
            e = e.__cause__
        return False

    # -- windowed restart budget ---------------------------------------------
    def budget_used(self, now: Optional[float] = None) -> int:
        """Restarts currently charged against max_restarts: all of them
        (legacy) or only those inside FLAGS_elastic_restart_window_s —
        pruning expired entries IS the refund for sustained progress."""
        if self.restart_window_s <= 0:
            return self.restarts
        return self._budget.used(now)

    def _note_restart(self, step: int, exc: BaseException) -> int:
        """Count one restart against the budget; returns the charged
        count. Each restart is a scale-plane event: a kind:"scale"
        record lands in the incident ring."""
        from ..core import incidents

        now = time.monotonic()
        self.restarts += 1
        self._budget.note(now)
        telemetry.counter_add("elastic.restarts", 1, step=step,
                              exc=type(exc).__name__)
        incidents.report_scale_event(
            "elastic", "restart", self.world_size, self.world_size,
            reason=type(exc).__name__,
            attrs={"step": int(step), "restarts": self.restarts})
        return self.budget_used(now)

    # -- cooperative drain ---------------------------------------------------
    def request_drain(self):
        """Ask the step loop to stop at the NEXT step boundary: force-
        checkpoint, bound-join the async writer, return cleanly. Safe
        from signal handlers and other threads (one Event.set)."""
        self._drain.set()

    @property
    def draining(self) -> bool:
        return self._drain.is_set()

    def install_signal_handlers(self, signals=(_signal.SIGTERM,
                                               _signal.SIGINT)):
        """Wire SIGTERM/SIGINT to request_drain() — the orchestrator's
        graceful-stop contract for trainer children. Main thread only
        (signal.signal's own constraint). Returns self."""
        for sig in signals:
            _signal.signal(sig, lambda _s, _f: self.request_drain())
        return self

    def _execute_drain(self, step: int) -> bool:
        """Force-save and BOUND-join the async writer
        (DRAIN_TIMEOUT_S): a SIGTERM'd trainer must make its checkpoint
        durable before the supervisor's kill-escalation deadline, and a
        wedged writer must not turn a drain into a hang. Returns True
        when the writer fully drained."""
        try:
            self.mgr.save(step, self.program, self.scope,
                          extras=self._extras(), force=True)
        except self.recoverable as e:
            _LOG.warning("elastic: drain checkpoint at step %d failed: "
                         "%r", step, e)
        ok = self.mgr.wait_until_finished(timeout=DRAIN_TIMEOUT_S)
        if not ok:
            telemetry.counter_add("elastic.drain_timeouts", 1, step=step)
            _LOG.error("elastic: async writer still busy after %.1fs "
                       "drain timeout at step %d", DRAIN_TIMEOUT_S, step)
        telemetry.counter_add("elastic.drains", 1, step=step)
        self.drained_at = int(step)
        return ok

    # -- exact-resume extras -------------------------------------------------
    def _extras(self) -> dict:
        ex = {}
        if self.reader is not None and hasattr(self.reader, "state_dict"):
            ex["reader"] = self.reader.state_dict()
        if self.world_size > 1:
            ex["world"] = {"size": int(self.world_size)}
        return ex

    def _apply_restored_extras(self):
        ex = self.mgr.last_restore_extras
        if self.reader is not None and hasattr(self.reader, "set_state") \
                and "reader" in ex:
            self.reader.set_state(ex["reader"])

    def _save_baseline(self):
        """Baseline checkpoint of the INITIAL weights: a failure before
        the first periodic save must restore to step 0's state, not keep
        the partially-trained scope and re-run from step 0. Saved
        synchronously (durable before any step can fail and need it),
        with one retry against injected/transient save faults."""
        for attempt in (1, 2):
            try:
                self.mgr.save(0, self.program, self.scope,
                              extras=self._extras(), force=True)
                self.mgr.wait_until_finished()
                return
            except ValueError:
                return   # nothing persistable yet -> nothing to restore
            except self.recoverable as e:
                _LOG.warning("elastic: baseline checkpoint attempt %d "
                             "failed: %r", attempt, e)

    # -- scale-decision execution --------------------------------------------
    def _maybe_scale(self, step: int, step_fn):
        """Poll the policy; on a decision, execute checkpoint →
        barrier-drain → relaunch-at-new-world. Returns the (possibly
        replaced) step_fn."""
        if self.scaler is None or self.on_scale is None:
            return step_fn
        decision = self.scaler.decide(self.world_size)
        if decision is None:
            return step_fn
        return self.execute_scale(decision, step, step_fn)

    def execute_scale(self, decision, step: int, step_fn):
        """The scale-event protocol, in order:

        1. force-checkpoint the current step (the relaunch resumes here);
        2. barrier-drain: join the async writer so the checkpoint is
           durable before any part of the old world is torn down;
        3. ``on_scale(decision)`` rebuilds the world at decision.target —
           it returns None to veto, or a dict with any of
           ``step_fn`` / ``program`` / ``scope`` / ``reader`` /
           ``world_size`` replaced;
        4. restore the checkpoint INTO the new world (the world-size-
           changing resume) and emit the ``kind:"scale"`` ring record.
        """
        from ..core import incidents

        self.mgr.save(step, self.program, self.scope,
                      extras=self._extras(), force=True)
        self.mgr.wait_until_finished()          # the barrier-drain
        swapped = self.on_scale(decision)
        if swapped is None:
            _LOG.warning("elastic: on_scale vetoed %s -> %d",
                         decision.direction, decision.target)
            return step_fn
        old_world = self.world_size
        self.program = swapped.get("program", self.program)
        self.scope = swapped.get("scope", self.scope)
        self.reader = swapped.get("reader", self.reader)
        self.world_size = int(swapped.get("world_size", decision.target))
        step_fn = swapped.get("step_fn", step_fn)
        restored = self.mgr.restore_latest(self.program, self.scope)
        self._apply_restored_extras()
        self.scale_events += 1
        telemetry.counter_add("elastic.scale_events", 1,
                              direction=decision.direction,
                              old_world=old_world,
                              new_world=self.world_size)
        incidents.report_scale_event(
            "elastic", "resize", old_world, self.world_size,
            reason=decision.reason,
            attrs={"step": int(restored),
                   "direction": decision.direction,
                   "signals": decision.signals})
        _LOG.info("elastic: resized world %d -> %d at step %d (%s)",
                  old_world, self.world_size, restored, decision.reason)
        return step_fn

    def run(self, step_fn: Callable[[int], object], num_steps: int,
            on_restart: Optional[Callable[[int, BaseException], None]] = None):
        """Run step_fn(step) for num_steps with failure recovery.

        Returns the last step_fn result. Restores from the newest
        verified checkpoint on a recoverable exception (from the step OR
        from the checkpoint save itself); re-raises after max_restarts
        (or immediately for non-recoverable types)."""
        step = self.mgr.restore_latest(self.program, self.scope)
        if step:
            self._apply_restored_extras()
            _LOG.info("elastic: resumed from checkpoint step %d", step)
        else:
            self._save_baseline()
        result = None
        try:
            while step < num_steps:
                if self._drain.is_set():
                    self._execute_drain(step)
                    break
                try:
                    result = step_fn(step)
                    step += 1
                    self.mgr.save(step, self.program, self.scope,
                                  extras=self._extras())
                    step_fn = self._maybe_scale(step, step_fn)
                except Exception as e:
                    if not self._recoverable_exc(e):
                        raise
                    used = self._note_restart(step, e)
                    if used > self.max_restarts:
                        _LOG.error("elastic: step %d failed after %d "
                                   "restarts%s", step, used,
                                   f" inside {self.restart_window_s:.0f}s"
                                   if self.restart_window_s > 0 else "")
                        raise
                    restored = self.mgr.restore_latest(self.program,
                                                       self.scope)
                    self._apply_restored_extras()
                    _LOG.warning(
                        "elastic: step %d raised %r — restart %d/%d from "
                        "checkpoint step %d", step, e, used,
                        self.max_restarts, restored)
                    if on_restart is not None:
                        on_restart(step, e)
                    step = restored
        finally:
            # teardown join: process exit must not truncate an in-flight
            # async save (the checkpoint module's atexit hook is the
            # last-resort backstop; this is the orderly path). A drain
            # already bound-joined; don't let a wedged writer hang the
            # drain exit unboundedly on top of that.
            if self._drain.is_set():
                self.mgr.wait_until_finished(
                    timeout=DRAIN_TIMEOUT_S)
            else:
                self.mgr.wait_until_finished()
        return result

    def close(self):
        self.mgr.close()
