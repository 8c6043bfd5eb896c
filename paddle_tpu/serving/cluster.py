"""Cluster serving control plane — replicated engines behind one router,
with supervised respawn and zero-downtime rolling model swaps.

This is ROADMAP item 2: the composition of the robustness subsystems
into one deployment. The pieces and where they came from:

* N **replicas** — each a PR 4 ServingEngine process
  (serving/replica.py) with the health.py liveness/readiness machine;
* a **router** (router.py) balancing on live per-replica telemetry and
  failing over on the shared core/retry.py schedule (PR 2 heritage);
* a **model watcher** (checkpoint.ModelWatcher) polling a published-
  models root for new verified COMMIT manifests (PR 5 protocol); a new
  version triggers the **rolling swap**: one replica at a time, the
  controller POSTs /v1/admin/swap — the replica goes not-ready, warms
  every bucket on the new predictor while the OLD one keeps serving,
  flips atomically, and returns ready. At most one replica is swapping
  at any moment, so N-1 replicas carry traffic throughout: zero
  downtime, zero dropped requests, never a cold-bucket response;
* a **monitor** thread supervising replica processes: a death is
  counted (router.replica_deaths), the handle is marked down (the
  router already failed over by then), and the slot is respawned on a
  core/retry.py backoff schedule up to FLAGS_cluster_max_restarts.

Two replica backends share every code path above:

* ``inprocess=False`` (default) — real OS processes via
  ``python -m paddle_tpu.serving.replica``; what production and the
  chaos gate (tools/chaos_check.py --cluster, SIGKILL mid-load) use;
* ``inprocess=True`` — engine + HTTP server threads in THIS process;
  same wire surface on real sockets, a fraction of the startup cost —
  what most tier-1 tests use, and the way to run N replicas on one
  accelerator host today.

One process per chip: a chip belongs to one process, and an unpinned
replica process claims every chip of its host. On a host with an
accelerator the subprocess backend therefore refuses (typed
core/chips.ChipContentionError, before anything is launched) a replica
set of more than one process, and any spawn from a controller process
that has itself initialised the accelerator backend. Replicas whose
environment pins ``JAX_PLATFORMS=cpu`` are never limited.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .. import checkpoint as _ckpt
from ..core import chips, fleetobs, retry, telemetry
from ..core.analysis import lockdep
from ..core.flags import flag as _flag
from .router import Router, RouterHTTPServer, _http_json


# seconds between polls of the published-models root
# (checkpoint.ModelWatcher): a new verified COMMIT manifest starts the
# rolling swap
MODEL_POLL_S = 0.5


class ClusterError(RuntimeError):
    """Control-plane failure (replica never came up, swap never took)."""


# ---------------------------------------------------------------------------
# replica backends
# ---------------------------------------------------------------------------

class ReplicaProcess:
    """One supervised replica OS process."""

    def __init__(self, name: str, model_root: str,
                 env: Optional[Dict[str, str]] = None,
                 serving_config=None, telemetry_log: str = "",
                 ready_timeout_s: float = 120.0, role: str = "unified",
                 decode_model_dir: Optional[str] = None,
                 prefill_urls: str = "", prefix_cache: bool = False,
                 journal_url: str = "", **_ignored):
        self.name = name
        self.model_root = model_root
        self.env = env
        self.serving_config = serving_config
        self.telemetry_log = telemetry_log
        self.ready_timeout_s = ready_timeout_s
        # disaggregated-serving tier (serving/disagg.py); forwarded to
        # the replica process and the router's affinity pick
        self.role = str(role or "unified")
        # generative replica (serving/decode.py): serve --decode-model-dir
        # over /v1/generate instead of a predictor over /v1/infer
        self.decode_model_dir = decode_model_dir
        self.prefill_urls = prefill_urls
        self.prefix_cache = bool(prefix_cache)
        self.journal_url = journal_url
        self.proc: Optional[subprocess.Popen] = None
        self.url: Optional[str] = None
        self.version: Optional[int] = None
        self.log_tail: "deque[str]" = deque(maxlen=200)
        self._drain_thread: Optional[threading.Thread] = None

    def spawn(self):
        """Launch and block until the PT_REPLICA_READY announce line."""
        env = dict(os.environ if self.env is None else self.env)
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        if self.decode_model_dir:
            cmd = [sys.executable, "-m", "paddle_tpu.serving.replica",
                   "--decode-model-dir", self.decode_model_dir,
                   "--port", "0"]
            if self.prefill_urls:
                cmd += ["--prefill-urls", self.prefill_urls]
            if self.prefix_cache:
                cmd += ["--prefix-cache"]
            if self.journal_url and self.role != "prefill":
                cmd += ["--journal-url", self.journal_url]
        else:
            cmd = [sys.executable, "-m", "paddle_tpu.serving.replica",
                   "--model-root", self.model_root, "--port", "0"]
            if self.serving_config is not None:
                cmd += ["--max-batch-size",
                        str(self.serving_config.max_batch_size),
                        "--batch-timeout-ms",
                        str(self.serving_config.batch_timeout_ms)]
        if self.telemetry_log:
            cmd += ["--telemetry-log", self.telemetry_log]
        if self.role != "unified":
            cmd += ["--role", self.role]
        self.proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1)
        deadline = time.monotonic() + self.ready_timeout_s
        announce = None
        assert self.proc.stdout is not None
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                break
            self.log_tail.append(line.rstrip())
            if line.startswith("PT_REPLICA_READY "):
                announce = json.loads(line[len("PT_REPLICA_READY "):])
                break
            if line.startswith("PT_REPLICA_FAIL"):
                break
        if announce is None:
            rc = self.proc.poll()
            raise ClusterError(
                f"replica {self.name} never announced readiness "
                f"(exit={rc}); last output: "
                f"{list(self.log_tail)[-5:]}")
        self.url = announce["url"]
        self.version = announce.get("version")
        # keep draining stdout so the pipe never fills and wedges the child
        self._drain_thread = threading.Thread(
            target=self._drain, name=f"pt-replica-log-{self.name}",
            daemon=True)
        self._drain_thread.start()
        return self

    def _drain(self):
        try:
            assert self.proc is not None and self.proc.stdout is not None
            for line in self.proc.stdout:
                self.log_tail.append(line.rstrip())
        except (OSError, ValueError):
            pass

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self, sig: int = signal.SIGKILL):
        """Chaos/test helper: the ungraceful death."""
        if self.alive():
            assert self.proc is not None
            self.proc.send_signal(sig)

    def stop(self, timeout: float = 30.0):
        """Graceful stop: SIGTERM (replica drains), then SIGKILL."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=5)
        if self._drain_thread is not None:
            self._drain_thread.join(timeout=2)


class InprocReplica:
    """Engine + HTTP server threads in this process: the same wire
    surface as ReplicaProcess at a fraction of the startup cost."""

    def __init__(self, name: str, model_root: str, serving_config=None,
                 role: str = "unified",
                 decode_model_dir: Optional[str] = None,
                 prefill_urls: str = "", prefix_cache: bool = False,
                 journal_sink=None, **_ignored):
        self.name = name
        self.model_root = model_root
        self.serving_config = serving_config
        self.role = str(role or "unified")
        self.decode_model_dir = decode_model_dir
        self.prefill_urls = prefill_urls
        self.prefix_cache = bool(prefix_cache)
        # in-process replicas journal straight into the router's
        # SessionJournal — same records, no HTTP hop
        self.journal_sink = journal_sink
        self.engine = None
        self.server = None
        self.url: Optional[str] = None
        self.version: Optional[int] = None
        self._stopped = False

    def spawn(self):
        from .server import ServingHTTPServer

        if self.decode_model_dir:
            from .decode import DecodeConfig, decode_engine_from_dir

            config = DecodeConfig(role=self.role,
                                  prefill_urls=self.prefill_urls,
                                  prefix_cache=self.prefix_cache or None)
            self.engine = decode_engine_from_dir(self.decode_model_dir,
                                                 config=config)
            if self.journal_sink is not None and self.role != "prefill":
                self.engine.journal_sink = self.journal_sink
            self.server = ServingHTTPServer(
                None, decode_engine=self.engine).start()
            self.url = self.server.url
            self.version = self.engine.version
            self.engine.start(warmup=True)
            self._stopped = False
            return self
        from ..inference import AnalysisConfig, create_predictor
        from .engine import ServingEngine

        newest = _ckpt.ModelWatcher(self.model_root).latest()
        if newest is None:
            raise ClusterError(f"no verified published model under "
                               f"{self.model_root}")
        version, model_dir = newest
        self.engine = ServingEngine(
            create_predictor(AnalysisConfig(model_dir)),
            config=self.serving_config, version=version)
        self.server = ServingHTTPServer(self.engine).start()
        self.url = self.server.url
        self.version = version
        self.engine.start(warmup=True)
        self._stopped = False
        return self

    def alive(self) -> bool:
        return not self._stopped

    def kill(self, sig: int = signal.SIGKILL):
        """Abrupt death: tear the socket down and fail the backlog —
        in-flight router dispatches see reset/refused, like a SIGKILL."""
        self._stopped = True
        if self.server is not None:
            self.server.shutdown()
        if self.engine is not None:
            self.engine.close(drain=False, timeout=5)

    def stop(self, timeout: float = 30.0):
        if self._stopped:
            return
        self._stopped = True
        if self.engine is not None:
            self.engine.close(drain=True, timeout=timeout)
        if self.server is not None:
            self.server.shutdown()


# ---------------------------------------------------------------------------
# the controller
# ---------------------------------------------------------------------------

class ClusterController:
    """Launch N replicas over a published-models root, front them with a
    router, supervise deaths, and roll the fleet onto newly published
    model versions with zero downtime.

        cluster = ClusterController(models_root, replicas=3).start()
        ... POST cluster.url + "/v1/infer" ...
        checkpoint.publish_model(models_root, new_model_dir)   # auto-rolls
        cluster.close()
    """

    def __init__(self, model_root: str, replicas: int = 2,
                 inprocess: bool = False,
                 serving_config=None,
                 replica_env: Optional[Dict[str, str]] = None,
                 router: Optional[Router] = None,
                 host: str = "127.0.0.1", router_port: int = 0,
                 model_poll_s: float = MODEL_POLL_S,
                 max_restarts: Optional[int] = None,
                 replica_telemetry_dir: str = "",
                 auto_swap: bool = True,
                 fleet: Optional[bool] = None,
                 roles: Optional[List[str]] = None,
                 decode_model_dir: Optional[str] = None,
                 role_counts: Optional[Dict[str, int]] = None,
                 prefix_cache: bool = False):
        self.model_root = os.path.abspath(model_root) if model_root else ""
        self.n_replicas = int(replicas)
        self.inprocess = bool(inprocess)
        self.serving_config = serving_config
        self.replica_env = replica_env
        self.model_poll_s = float(model_poll_s)
        self.max_restarts = int(
            _flag("cluster_max_restarts") if max_restarts is None
            else max_restarts)
        self.replica_telemetry_dir = replica_telemetry_dir
        self.auto_swap = bool(auto_swap)
        # disaggregated-serving topology (serving/disagg.py): roles are
        # cycled across replica slots (e.g. ["prefill", "decode"]) and
        # drive the router's role-aware prefix-affinity pick; default is
        # an all-unified fleet
        self.roles = [str(r) for r in roles] if roles else []
        # generative cluster (serving/decode.py): replicas serve
        # /v1/generate from this servable dir instead of running
        # predictors over model_root; decode-role replicas are wired to
        # journal sessions to the router and pull prefill shipments
        # through it (forward_prefill), so a respawned survivor can
        # resume any journaled session
        self.decode_model_dir = os.path.abspath(decode_model_dir) \
            if decode_model_dir else None
        self.prefix_cache = bool(prefix_cache)
        # role_counts is the TIER view of the fleet ({"prefill": 1,
        # "decode": 2}): it fixes the initial role plan AND gives
        # scale_tier() a per-role target that survives respawns. A
        # plain roles=[...] list keeps the legacy cycling behaviour.
        self.role_counts: Optional[Dict[str, int]] = \
            {str(k): int(v) for k, v in role_counts.items()} \
            if role_counts else None
        if self.role_counts is not None:
            plan: List[str] = []
            for r in sorted(self.role_counts):
                plan.extend([r] * self.role_counts[r])
            self.roles = plan
            self.n_replicas = len(plan)
        # slot → role registry: a respawn keeps the role its slot was
        # provisioned with even after tier scaling reshapes the modulo
        # cycling that assigned it
        self._slot_roles: Dict[int, str] = {}
        self.router = router or Router()
        self.router_server = RouterHTTPServer(self.router, host=host,
                                              port=router_port)
        self.replicas: List[Any] = []
        self._handles: Dict[str, Any] = {}
        self._restarts: Dict[str, int] = {}
        # monotonic name source: a slot retired by scale_to is never
        # renamed onto a later replica (router/fleet slots key by name)
        self._next_index = 0
        self._retired: set = set()
        self._scaler = None
        self._watcher: Optional[_ckpt.ModelWatcher] = None
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # serialises rolling swaps (and guards current_version): held
        # across a whole fleet roll on purpose — swaps must not overlap
        self._swap_lock = lockdep.lock("cluster.swap")
        self._counted_dead: set = set()
        self.current_version: Optional[int] = None
        # fleet observatory (core/fleetobs.py): opt-in per cluster or
        # fleet-wide via FLAGS_fleet_enable — scrapes every member's
        # /metrics into merged fleet windows + /fleet/* on the router
        self.fleet_enabled = bool(_flag("fleet_enable")) if fleet is None \
            else bool(fleet)
        self.fleet_aggregator: Optional[fleetobs.FleetAggregator] = None

    # -- lifecycle -----------------------------------------------------------
    @property
    def url(self) -> str:
        return self.router_server.url

    def _check_chips(self, n_replicas: int):
        """One process per chip (core/chips.py): fail loudly instead of
        letting every replica process claim the same chip."""
        if not self.inprocess:
            chips.check_spawn(
                n_replicas,
                os.environ if self.replica_env is None
                else self.replica_env,
                f"ClusterController({n_replicas} subprocess replicas)")

    def _make_replica(self, index: int, role: Optional[str] = None):
        name = f"replica-{index}"
        log = ""
        if self.replica_telemetry_dir:
            log = os.path.join(self.replica_telemetry_dir,
                               f"{name}.jsonl")
        cls = InprocReplica if self.inprocess else ReplicaProcess
        if role is None:
            role = self._slot_roles.get(index)
        if role is None:
            role = self.roles[index % len(self.roles)] if self.roles \
                else "unified"
        self._slot_roles[index] = role
        extra: Dict[str, Any] = {}
        if self.decode_model_dir:
            extra["decode_model_dir"] = self.decode_model_dir
            extra["prefix_cache"] = self.prefix_cache
            if role == "decode":
                # pull shipments THROUGH the router (forward_prefill):
                # the replica never needs to track prefill-tier
                # membership — respawns and tier scaling stay invisible
                extra["prefill_urls"] = self.url
            if role != "prefill":
                # RouterHTTPServer binds its port at construction, so
                # the journal endpoint is known before any spawn
                extra["journal_url"] = self.url + "/v1/session/journal"
                extra["journal_sink"] = self.router.sessions.update
        return cls(name, self.model_root, env=self.replica_env,
                   serving_config=self.serving_config,
                   telemetry_log=log, role=role, **extra)

    def start(self, ready_timeout_s: float = 120.0) -> "ClusterController":
        if self.decode_model_dir:
            # generative fleet: the servable dir IS the model — no
            # published-versions root, no rolling-swap watcher
            self.auto_swap = False
        else:
            self._watcher = _ckpt.ModelWatcher(self.model_root)
            newest = self._watcher.poll()
            if newest is None:
                raise ClusterError(f"no verified published model under "
                                   f"{self.model_root} — publish_model() "
                                   f"one before starting the cluster")
            # current_version is owned by the swap lock: the monitor/
            # watch threads (spawned below) read and roll it under the
            # same lock
            with self._swap_lock:
                self.current_version = newest[0]
        self._check_chips(self.n_replicas)
        for _ in range(self.n_replicas):
            replica = self._make_replica(self._next_index)
            self._next_index += 1
            replica.spawn()
            self.replicas.append(replica)
            self._restarts[replica.name] = 0
            self._handles[replica.name] = self.router.add_replica(
                replica.name, replica.url,
                role=getattr(replica, "role", "unified"))
        self.router.start()
        self.router_server.start()
        self._wait_ready(ready_timeout_s)
        if self.fleet_enabled:
            self.fleet_aggregator = fleetobs.FleetAggregator()
            self.fleet_aggregator.register("router", self.url,
                                           kind="router")
            for replica in self.replicas:
                self.fleet_aggregator.register(replica.name, replica.url)
            self.router.attach_fleet(self.fleet_aggregator)
            self.fleet_aggregator.start()
        mon = threading.Thread(target=self._monitor_loop,
                               name="pt-cluster-monitor", daemon=True)
        mon.start()
        self._threads.append(mon)
        if self.auto_swap:
            watch = threading.Thread(target=self._watch_loop,
                                     name="pt-cluster-modelwatch",
                                     daemon=True)
            watch.start()
            self._threads.append(watch)
        return self

    def _wait_ready(self, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            for handle in self.router.handles():
                self.router.probe(handle)
            if all(h.ready for h in self.router.handles()):
                return
            time.sleep(0.1)
        not_ready = [h.name for h in self.router.handles() if not h.ready]
        raise ClusterError(f"replicas never became ready: {not_ready}")

    def close(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        if self.fleet_aggregator is not None:
            self.fleet_aggregator.stop()
        self.router_server.shutdown()
        self.router.close()
        for replica in self.replicas:
            replica.stop()

    # -- supervision ---------------------------------------------------------
    def _monitor_loop(self):
        while not self._stop.wait(0.25):
            for replica in list(self.replicas):
                if self._stop.is_set():
                    return
                if id(replica) in self._retired:
                    continue   # scale_to drained it on purpose
                if replica.alive():
                    self._counted_dead.discard(id(replica))
                    continue
                handle = self._handles.get(replica.name)
                if handle is not None:
                    handle.mark_down("process_died")
                if id(replica) not in self._counted_dead:
                    self._counted_dead.add(id(replica))
                    telemetry.counter_add("router.replica_deaths", 1,
                                          replica=replica.name)
                    # exactly ONE incident record per death, exempt from
                    # the rate-limit window like oom/stall — two replicas
                    # dying back-to-back must both land in the ledger
                    from ..core import incidents as _incidents

                    rc = getattr(getattr(replica, "proc", None),
                                 "returncode", None)
                    _incidents.report_incident(
                        "cluster", "replica_death", 1.0,
                        context={"replica": replica.name,
                                 "role": getattr(replica, "role",
                                                 "unified"),
                                 "exit_code": rc,
                                 "signal": -rc if isinstance(rc, int)
                                 and rc < 0 else None},
                        rate_limit=False)
                if self.inprocess:
                    continue   # tests kill in-proc replicas on purpose
                if self._restarts[replica.name] >= self.max_restarts:
                    telemetry.counter_add("router.replica_abandoned", 1,
                                          replica=replica.name)
                    continue
                self._restarts[replica.name] += 1
                telemetry.counter_add("router.replica_restarts", 1,
                                      replica=replica.name)
                sched = retry.RetryPolicy(
                    max_retries=3, backoff=0.2, deadline=60.0).start()
                while not self._stop.is_set():
                    try:
                        fresh = self._make_replica(
                            int(replica.name.rsplit("-", 1)[-1]))
                        fresh.spawn()
                    except ClusterError:
                        outcome, delay = sched.note_failure()
                        if outcome != retry.RETRY:
                            telemetry.counter_add(
                                "router.replica_abandoned", 1,
                                replica=replica.name)
                            break
                        time.sleep(delay)
                        continue
                    # locate by identity: a concurrent scale_to may have
                    # shifted list positions (or retired this slot)
                    slot = next((j for j, r in enumerate(self.replicas)
                                 if r is replica), None)
                    if slot is None:
                        fresh.stop()
                        break
                    self.replicas[slot] = fresh
                    if handle is not None:
                        handle.rebind(fresh.url)
                        self.router.probe(handle)
                    role = getattr(fresh, "role", "unified")
                    if role in ("decode", "prefill"):
                        # tier membership changed: the router's prefix-
                        # affinity hash now maps some sessions elsewhere
                        telemetry.counter_add("router.affinity_remaps",
                                              1, role=role,
                                              reason="respawn")
                    if self.fleet_aggregator is not None:
                        # a respawn keeps its fleet slot — re-point the
                        # scrape at the fresh endpoint
                        self.fleet_aggregator.register(replica.name,
                                                       fresh.url)
                    # a respawn comes up on the NEWEST published version;
                    # converge it if the fleet is ahead/behind
                    if self.current_version is not None and \
                            fresh.version != self.current_version:
                        newest = _ckpt.ModelWatcher(
                            self.model_root).latest()
                        if newest is not None and \
                                newest[0] == self.current_version:
                            self._swap_one(fresh, newest[0], newest[1])
                    break

    # -- rolling model swap --------------------------------------------------
    def _watch_loop(self):
        while not self._stop.wait(self.model_poll_s):
            assert self._watcher is not None
            newest = self._watcher.poll()
            if newest is not None:
                version, path = newest
                try:
                    self.roll_to(version, path)
                except ClusterError as e:
                    telemetry.counter_add("router.swap_errors", 1,
                                          version=version,
                                          reason=type(e).__name__)
                    print(f"[cluster] rolling swap to v{version} "
                          f"failed: {e}", file=sys.stderr)

    def _swap_one(self, replica, version: int, path: str) -> bool:
        """Swap ONE replica (POST /v1/admin/swap), with retries. Returns
        success; the replica keeps serving its old version on failure."""
        sched = retry.RetryPolicy(max_retries=2, backoff=0.1,
                                  deadline=120.0).start()
        while True:
            try:
                code, doc = _http_json(
                    "POST", replica.url, "/v1/admin/swap",
                    body=json.dumps({"model_dir": path,
                                     "version": version}).encode(),
                    timeout=sched.remaining(default=90.0) or 90.0)
            except (ConnectionError, OSError) as e:
                code, doc = -1, {"error": repr(e)}
            if code == 200:
                telemetry.counter_add("router.swaps", 1,
                                      replica=replica.name,
                                      version=version)
                replica.version = version
                return True
            telemetry.counter_add("router.swap_errors", 1,
                                  replica=replica.name, version=version,
                                  status=code)
            outcome, delay = sched.note_failure()
            if outcome != retry.RETRY:
                return False
            time.sleep(delay)

    def _await_peer_ready(self, name: str, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and not self._stop.is_set():
            for handle in self.router.handles():
                if handle.name != name:
                    self.router.probe(handle)
            if any(h.ready for h in self.router.handles()
                   if h.name != name):
                return
            time.sleep(0.1)

    def roll_to(self, version: int, path: str):
        """Rolling zero-downtime swap: one replica at a time — readiness
        drops while it warms/flips, the router routes around it, and the
        next replica only starts once this one is ready again."""
        with self._swap_lock:
            failed = []
            for replica in list(self.replicas):
                if not replica.alive():
                    continue
                # never take the LAST ready replica offline: if a death/
                # respawn window has degraded the fleet, wait for a peer
                # to be ready before making this one not-ready. (If no
                # peer recovers, proceed anyway — the router's swapping-
                # fallback still dispatches to a warming replica, which
                # serves its OLD version until the flip.)
                # pt-lint: disable=blocking-call-under-lock(the swap lock exists to serialise whole fleet rolls; waiting for a ready peer under it is the zero-downtime invariant, and only swap paths contend)
                self._await_peer_ready(replica.name, timeout_s=30.0)
                # pt-lint: disable=blocking-call-under-lock(one replica swap at a time IS the rolling-swap contract; nothing but another roll waits on this lock)
                if not self._swap_one(replica, version, path):
                    failed.append(replica.name)
                    continue
                # wait for readiness to return before touching the next
                # replica: N-1 ready replicas at all times
                handle = self._handles.get(replica.name)
                deadline = time.monotonic() + 60.0
                while handle is not None and time.monotonic() < deadline:
                    self.router.probe(handle)
                    if handle.ready:
                        break
                    time.sleep(0.05)  # pt-lint: disable=blocking-call-under-lock(readiness poll between per-replica swaps, still inside the serialised fleet roll; bounded by the 60 s deadline)
            self.current_version = version
            if failed:
                raise ClusterError(
                    f"rolling swap to v{version}: replicas {failed} "
                    f"failed to swap (still serving their old version)")

    # -- elastic replica scaling --------------------------------------------
    def scale_to(self, n: int, reason: str = "manual",
                 ready_timeout_s: float = 60.0) -> int:
        """Grow or shrink the replica fleet to exactly ``n``, with zero
        dropped in-flight requests.

        Grow: spawn fresh replicas (on the newest published model),
        router-register them, and wait for readiness. Shrink: pick the
        most recently added replicas, wait for a READY peer (never take
        the last ready replica offline), remove each from the router so
        no NEW dispatch lands on it, then stop it gracefully — the
        engine drains its queue before the socket closes. Each call is
        ONE scale transition: exactly one incidents.report_scale_event.
        Returns the new replica count."""
        from ..core import incidents as _incidents

        n = int(n)
        if n < 1:
            raise ClusterError("scale_to: need at least 1 replica")
        with self._swap_lock:
            old = len(self.replicas)
            if n == old:
                return old
            if n > old:
                self._check_chips(n)
                for _ in range(n - old):
                    replica = self._make_replica(self._next_index)
                    self._next_index += 1
                    replica.spawn()
                    self.replicas.append(replica)
                    self._restarts[replica.name] = 0
                    self._handles[replica.name] = self.router.add_replica(
                        replica.name, replica.url,
                        role=getattr(replica, "role", "unified"))
                    if self.fleet_aggregator is not None:
                        self.fleet_aggregator.register(replica.name,
                                                       replica.url)
                    # converge the newcomer onto the fleet's version if
                    # a roll moved it past the newest-published default
                    if self.current_version is not None and \
                            replica.version != self.current_version:
                        newest = _ckpt.ModelWatcher(
                            self.model_root).latest()
                        if newest is not None and \
                                newest[0] == self.current_version:
                            self._swap_one(replica, newest[0], newest[1])  # pt-lint: disable=blocking-call-under-lock(scale transitions serialise with rolls on purpose; bounded by the swap timeout)
                deadline = time.monotonic() + ready_timeout_s
                while time.monotonic() < deadline:
                    for handle in self.router.handles():
                        if not handle.ready:
                            self.router.probe(handle)
                    if all(h.ready for h in self.router.handles()):
                        break
                    time.sleep(0.05)  # pt-lint: disable=blocking-call-under-lock(scale transitions serialise with rolls on purpose; bounded by ready_timeout_s)
            else:
                for _ in range(old - n):
                    victim = self.replicas[-1]
                    # pt-lint: disable=blocking-call-under-lock(the zero-downtime invariant: a peer must be ready before this replica leaves the fleet)
                    self._await_peer_ready(victim.name, timeout_s=30.0)
                    self._retired.add(id(victim))
                    self.replicas.remove(victim)
                    self._handles.pop(victim.name, None)
                    # router first: no NEW dispatch can land while the
                    # engine drains its in-flight queue below
                    self.router.remove_replica(victim.name)
                    victim.stop()
                    if self.fleet_aggregator is not None:
                        self.fleet_aggregator.deregister(victim.name)
            self.n_replicas = len(self.replicas)
        telemetry.counter_add(
            "router.scale_events", 1,
            direction="up" if n > old else "down", replicas=n)
        _incidents.report_scale_event(
            "cluster", "resize", old, n, reason=reason)
        return n

    def tier_members(self, role: str) -> List[Any]:
        """Live replicas provisioned into ``role`` (slot registry order)."""
        return [r for r in self.replicas
                if getattr(r, "role", "unified") == str(role)]

    def scale_tier(self, role: str, n: int, reason: str = "manual",
                   ready_timeout_s: float = 60.0) -> int:
        """Grow or shrink ONE role tier (prefill / decode / unified) to
        exactly ``n`` replicas, leaving the other tiers untouched — the
        serving-side analogue of a per-tier resize. New slots are
        provisioned with the requested role and keep it across respawns
        (the slot registry), so a prefill tier is supervised exactly
        like decode replicas. Returns the tier's new size."""
        from ..core import incidents as _incidents

        role = str(role)
        n = int(n)
        if n < 0:
            raise ClusterError("scale_tier: need n >= 0")
        with self._swap_lock:
            members = self.tier_members(role)
            old = len(members)
            if n == old:
                return old
            if n > old:
                self._check_chips(len(self.replicas) + n - old)
                for _ in range(n - old):
                    replica = self._make_replica(self._next_index,
                                                 role=role)
                    self._next_index += 1
                    replica.spawn()
                    self.replicas.append(replica)
                    self._restarts[replica.name] = 0
                    self._handles[replica.name] = self.router.add_replica(
                        replica.name, replica.url, role=role)
                    if self.fleet_aggregator is not None:
                        self.fleet_aggregator.register(replica.name,
                                                       replica.url)
                deadline = time.monotonic() + ready_timeout_s
                while time.monotonic() < deadline:
                    for handle in self.router.handles():
                        if not handle.ready:
                            self.router.probe(handle)
                    if all(h.ready for h in self.router.handles()):
                        break
                    time.sleep(0.05)  # pt-lint: disable=blocking-call-under-lock(tier transitions serialise with rolls on purpose; bounded by ready_timeout_s)
            else:
                for _ in range(old - n):
                    victim = self.tier_members(role)[-1]
                    # pt-lint: disable=blocking-call-under-lock(the zero-downtime invariant: a peer must be ready before this replica leaves the fleet)
                    self._await_peer_ready(victim.name, timeout_s=30.0)
                    self._retired.add(id(victim))
                    self.replicas.remove(victim)
                    self._handles.pop(victim.name, None)
                    self.router.remove_replica(victim.name)
                    victim.stop()
                    if self.fleet_aggregator is not None:
                        self.fleet_aggregator.deregister(victim.name)
            self.n_replicas = len(self.replicas)
            if self.role_counts is not None:
                self.role_counts[role] = n
        telemetry.counter_add(
            "router.scale_events", 1,
            direction="up" if n > old else "down", tier=role, replicas=n)
        if role in ("decode", "prefill"):
            telemetry.counter_add("router.affinity_remaps", 1, role=role,
                                  reason="scale_tier")
        _incidents.report_scale_event(
            "cluster", f"resize_{role}", old, n, reason=reason)
        return n

    def attach_scaler(self, policy) -> "ClusterController":
        """Drive replica count from a distributed.scaler.ScalerPolicy —
        the SAME policy engine the training-side ElasticRunner uses,
        pointed at serving signals (router load / queue saturation via
        the fleet observatory)."""
        self._scaler = policy
        return self

    def autoscale_tick(self, now: Optional[float] = None):
        """One policy evaluation + (maybe) one scale transition.
        Deterministic entry point — tests and external control loops
        call this instead of racing a background thread. Returns the
        executed ScaleDecision or None."""
        if self._scaler is None:
            return None
        decision = self._scaler.decide(len(self.replicas), now=now,
                                       fleet=self.fleet_aggregator)
        if decision is None:
            return None
        self.scale_to(decision.target, reason=decision.reason)
        return decision

    def start_autoscaler(self, interval_s: float = 5.0):
        """Background autoscale loop (production path; tests prefer
        autoscale_tick)."""
        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.autoscale_tick()
                except ClusterError:
                    telemetry.counter_add("router.scale_errors", 1)
        t = threading.Thread(target=loop, name="pt-cluster-autoscale",
                             daemon=True)
        t.start()
        self._threads.append(t)
        return self

    # -- introspection -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = self.router.stats()
        out["current_version"] = self.current_version
        out["restarts"] = dict(self._restarts)
        out["replica_backend"] = "inprocess" if self.inprocess \
            else "process"
        if self.fleet_aggregator is not None:
            out["fleet"] = {
                "members": self.fleet_aggregator.members(),
                "stragglers": self.fleet_aggregator.straggler_names()}
        return out
