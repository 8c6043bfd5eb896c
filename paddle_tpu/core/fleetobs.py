"""Fleet observatory — cross-process metrics aggregation.

Capability mirror of the reference's fleet monitoring tier
(operators/distributed/heart_beat_monitor.h liveness, platform/monitor.h
stat aggregation, pserver barrier stats): PRs 1/6/10/14 built strictly
per-process observability — the router sees only queue_depth, the
ClusterController only alive/dead, and nobody could answer "what is
fleet p99?". This module is the missing sensor layer (the scaffolding
ROADMAP items 1 and 5 — disaggregated serving placement and
signal-driven autoscaling — both stand on):

* **Membership**: replicas/routers register via
  :meth:`FleetAggregator.register` (serving/cluster.py does it for the
  whole fleet when ``FLAGS_fleet_enable`` / ``fleet=True``); trainers
  and pservers :func:`announce` the URL of their
  ``telemetry.start_metrics_server`` through the PS heartbeat path
  (distributed/ps/rpc.py forwards it, pserver.py lands it here).

* **Scraping**: a daemon loop GETs every member's ``/metrics``
  (Prometheus text — parsed by :func:`parse_prometheus`) and, where the
  member serves one, ``/v1/stats``. A scrape failure marks the member
  STALE after ``FLAGS_fleet_stale_after_s`` — its last-known load is
  RETAINED (never zeroed into "least loaded" evidence) and the loop
  moves on; one dead member can never wedge the pass.

* **Exact percentile merging**: members expose cumulative
  ``pt_*_bucket{le=...}`` series over the shared fixed
  ``telemetry.HIST_BUCKET_BOUNDS``, so fleet percentiles come from
  POOLED bucket counts (``merged_buckets`` + ``telemetry.
  bucket_quantile``) — not from averaging per-member quantiles, which
  is wrong the moment load skews.

* **Straggler detection**: per-member dispatch/step latency (windowed
  mean from ``_sum``/``_count`` deltas between scrapes) is z-scored
  against the fleet median; outliers past
  ``FLAGS_fleet_straggler_zscore`` are flagged — the router's
  ``pick()`` deprioritises them, and the ``fleet_straggler_replica``
  rule trips.

* **Fleet SLO rules**: the PR 14 rule engine (core/incidents.py
  ``Rule``/``Watchdog``) re-used verbatim over the ``fleet.*`` gauges
  this aggregator publishes into its local registry — aggregate QPS
  floor, fleet queue saturation, straggler-replica, member-stale-burst
  — with trips flowing into the same ``report_incident`` pipeline as
  every other anomaly.

* **Surfaces**: ``/fleet/status`` (per-member table + stragglers +
  goodput breakdown) and ``/fleet/metrics`` (merged bucket series +
  fleet gauges) on the router front end (serving/router.py) or a
  standalone :func:`start_fleet_server`. tools/fleet_report.py renders
  either; ``tools/chaos_check.py --fleet`` is the kill-a-replica gate.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

from . import flags as _flags
from . import incidents, telemetry

# ---------------------------------------------------------------------------
# Prometheus text parsing (the scrape side of telemetry.prometheus_text)
# ---------------------------------------------------------------------------

_LINE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'        # metric name
    r'(?:\{([^}]*)\})?'                   # optional labels
    r'\s+(\+Inf|-Inf|NaN|[0-9.eE+\-]+)\s*$')
_LE_RE = re.compile(r'le="([^"]+)"')


def _num(tok: str) -> float:
    if tok == "+Inf":
        return float("inf")
    if tok == "-Inf":
        return float("-inf")
    return float(tok)


def parse_prometheus(text: str) -> Dict[str, Any]:
    """Parse one /metrics exposition into
    ``{"counters": {name_total: v}, "gauges": {name: v},
       "hists": {base: {"buckets": [(le, cum)], "sum": s, "count": n}}}``.
    Bucket lists keep exposition order (le-ascending, +Inf last).
    Unknown/labelled series it does not understand are skipped — a
    foreign exporter must not break the scrape."""
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, Dict[str, Any]] = {}

    def hist(base: str) -> Dict[str, Any]:
        return hists.setdefault(base, {"buckets": [], "sum": 0.0,
                                       "count": 0})

    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _LINE_RE.match(line)
        if m is None:
            continue
        name, labels, tok = m.group(1), m.group(2), m.group(3)
        try:
            value = _num(tok)
        except ValueError:
            continue
        if name.endswith("_bucket") and labels:
            le = _LE_RE.search(labels)
            if le is None:
                continue
            try:
                le_v = _num(le.group(1))
            except ValueError:
                continue
            hist(name[:-len("_bucket")])["buckets"].append(
                (le_v, int(value)))
        elif name.endswith("_sum") and not labels:
            hist(name[:-len("_sum")])["sum"] = value
        elif name.endswith("_count") and not labels:
            hist(name[:-len("_count")])["count"] = int(value)
        elif name.endswith("_total") and not labels:
            counters[name] = value
        elif not labels:
            gauges[name] = value
    return {"counters": counters, "gauges": gauges, "hists": hists}


def counts_from_cumulative(buckets: List[Tuple[float, int]]) -> List[int]:
    """Cumulative (le, count) pairs -> per-bucket counts aligned to
    telemetry.HIST_BUCKET_BOUNDS (+ overflow). Tolerates reordered
    input by sorting on le."""
    ordered = sorted(buckets, key=lambda b: b[0])
    out = [0] * (len(telemetry.HIST_BUCKET_BOUNDS) + 1)
    prev = 0
    for le, cum in ordered:
        delta = max(0, int(cum) - prev)
        prev = int(cum)
        if delta == 0:
            continue
        if le == float("inf"):
            out[-1] += delta
        else:
            out[telemetry.bucket_index(le)] += delta
    return out


# latency histograms tried in order as a member's straggler / step-time
# evidence: the first one it exposes wins
STRAGGLER_METRICS = ("serving.request_ms", "router.dispatch_ms",
                     "executor.run_ms", "executor.run_steps_ms")
# share of the per-replica admission bound (FLAGS_serving_max_queue_depth)
# the fleet-AVERAGE queue depth may reach before fleet_queue_saturation trips
QUEUE_SATURATION = 0.9


def detect_stragglers(latency_by_member: Dict[str, float],
                      zscore: Optional[float] = None,
                      min_members: Optional[int] = None) -> List[str]:
    """Members whose latency z-score vs the fleet median exceeds the
    threshold. Pure function (unit-testable): returns [] below
    ``min_members`` or when the fleet has no spread."""
    if zscore is None:
        zscore = float(_flags.flag("fleet_straggler_zscore"))
    if min_members is None:
        min_members = int(_flags.flag("fleet_min_members"))
    vals = sorted(latency_by_member.values())
    n = len(vals)
    if n < max(2, min_members):
        return []
    median = vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1]
                                               + vals[n // 2])
    mean = sum(vals) / n
    var = sum((v - mean) ** 2 for v in vals) / n
    std = var ** 0.5
    if std <= 1e-9:
        return []
    return sorted(name for name, v in latency_by_member.items()
                  if (v - median) / std > zscore)


def fleet_rules() -> List[incidents.Rule]:
    """The fleet-level SLO rule set (PR 14 Rule engine over the fleet.*
    gauges this aggregator publishes). Evaluated by the aggregator's OWN
    Watchdog — the per-process default rule set stays untouched."""
    return [
        # any member past the staleness horizon (a stale burst after a
        # kill/partition; the episode clears when the member recovers
        # or is deregistered, so one kill trips exactly once)
        incidents.Rule("fleet_member_stale", "fleet.members_stale",
                       kind="gauge", threshold=0, direction="above",
                       cooldown_s=60.0),
        # a replica flagged a latency outlier vs the fleet median
        incidents.Rule("fleet_straggler_replica", "fleet.stragglers",
                       kind="gauge", threshold=0, direction="above",
                       cooldown_s=60.0),
        # fleet-average queue depth saturating the admission bound
        incidents.Rule("fleet_queue_saturation", "fleet.queue_frac",
                       kind="gauge",
                       threshold=QUEUE_SATURATION,
                       direction="above", cooldown_s=60.0),
    ]


# ---------------------------------------------------------------------------
# membership + the aggregator
# ---------------------------------------------------------------------------

class FleetMember:
    """One scraped member: endpoint(s) + last-known state. A failed
    scrape RETAINS the last good metrics/stats (staleness is surfaced,
    load is never zeroed)."""

    def __init__(self, name: str, url: str, kind: str = "replica",
                 stats_url: Optional[str] = None):
        self.name = name
        self.url = url.rstrip("/")
        self.kind = kind
        self.metrics_url = self.url + "/metrics"
        if stats_url is None and kind in ("replica", "router"):
            stats_url = self.url + "/v1/stats"
        self.stats_url = stats_url
        self.state = "UNKNOWN"           # UNKNOWN | OK | STALE
        self.scrapes = 0
        self.failures = 0                # consecutive
        self.last_ok_t = 0.0             # monotonic
        self.last_attempt_t = 0.0
        self.last_error: Optional[str] = None
        self.metrics: Optional[Dict[str, Any]] = None   # last parsed
        self.prev: Optional[Tuple[float, Dict[str, Any]]] = None
        self.stats: Optional[Dict[str, Any]] = None
        self.latency_ms: Optional[float] = None
        self.straggler = False

    def scrape_age_s(self, now: Optional[float] = None) -> Optional[float]:
        if not self.last_ok_t:
            return None
        return round((time.monotonic() if now is None else now)
                     - self.last_ok_t, 3)

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        out = {"name": self.name, "kind": self.kind, "url": self.url,
               "state": self.state,
               "scrape_age_s": self.scrape_age_s(now),
               "scrapes": self.scrapes,
               "consecutive_failures": self.failures,
               "straggler": self.straggler,
               "latency_ms": self.latency_ms}
        if self.last_error:
            out["last_error"] = self.last_error
        if isinstance(self.stats, dict):
            for key in ("queue_depth", "model_version", "status"):
                if key in self.stats:
                    out[key] = self.stats[key]
        return out


def _fetch(url: str, timeout: float) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


class FleetAggregator:
    """Scrape every member into merged fleet-level rolling windows,
    publish ``fleet.*`` gauges/counters into the LOCAL registry, flag
    stragglers, and evaluate the fleet SLO rule set.

        agg = FleetAggregator()
        agg.register("replica-0", url)          # cluster.py does this
        agg.start()
        agg.status()                            # /fleet/status body
        agg.metrics_text()                      # /fleet/metrics body
    """

    def __init__(self, interval_s: Optional[float] = None,
                 stale_after_s: Optional[float] = None,
                 rules: Optional[List[incidents.Rule]] = None):
        self.interval_s = float(
            _flags.flag("fleet_scrape_interval_s") if interval_s is None
            else interval_s)
        self.stale_after_s = float(
            _flags.flag("fleet_stale_after_s") if stale_after_s is None
            else stale_after_s)
        # plain lock (never lockdep, never held across HTTP): the scrape
        # loop copies the member list, fetches OUTSIDE, updates under it
        self._lock = threading.Lock()
        self._members: Dict[str, FleetMember] = {}
        self._watchdog = incidents.Watchdog(
            fleet_rules() if rules is None else rules)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._passes = 0

    # -- membership ----------------------------------------------------------
    def register(self, name: str, url: str, kind: str = "replica",
                 stats_url: Optional[str] = None) -> FleetMember:
        """Add (or re-point — a respawned replica keeps its slot) one
        member."""
        member = FleetMember(name, url, kind=kind, stats_url=stats_url)
        with self._lock:
            self._members[name] = member
        telemetry.counter_quiet("fleet.members_registered")
        return member

    def deregister(self, name: str):
        with self._lock:
            self._members.pop(name, None)

    def members(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        with self._lock:
            members = list(self._members.values())
        return [m.snapshot(now) for m in members]

    def straggler_names(self) -> List[str]:
        with self._lock:
            return sorted(m.name for m in self._members.values()
                          if m.straggler)

    # -- the scrape pass -----------------------------------------------------
    def _scrape_member(self, member: FleetMember, now_mono: float):
        """One member's /metrics (+/v1/stats) fetch+parse. Updates the
        member in place; never raises."""
        timeout = max(0.2, min(self.interval_s, 2.0))
        member.last_attempt_t = now_mono
        try:
            parsed = parse_prometheus(
                _fetch(member.metrics_url, timeout).decode(
                    "utf-8", "replace"))
            if member.stats_url:
                try:
                    member.stats = json.loads(
                        _fetch(member.stats_url, timeout))
                except (OSError, ValueError, urllib.error.URLError):
                    pass   # stats are garnish; /metrics decides health
        except (OSError, ValueError, urllib.error.URLError) as e:
            member.failures += 1
            member.last_error = type(e).__name__
            telemetry.counter_quiet("fleet.scrape_failures")
            # staleness is SURFACED, load is retained: member.metrics /
            # member.stats keep their last good values
            if member.state != "STALE" and (
                    not member.last_ok_t
                    or now_mono - member.last_ok_t > self.stale_after_s):
                member.state = "STALE"
                telemetry.counter_add("fleet.members_went_stale", 1,
                                      member=member.name,
                                      error=member.last_error)
            return
        if member.metrics is not None:
            member.prev = (member.last_ok_t, member.metrics)
        member.metrics = parsed
        member.scrapes += 1
        member.failures = 0
        member.last_error = None
        member.last_ok_t = now_mono
        if member.state != "OK":
            member.state = "OK"
        telemetry.counter_quiet("fleet.scrapes")

    def _member_latency(self, member: FleetMember) -> Optional[float]:
        """Windowed mean latency (ms) of the first straggler metric the
        member exposes: _sum/_count delta between the last two scrapes
        (falling back to lifetime mean on the first)."""
        if member.metrics is None:
            return None
        prev_h = (member.prev[1]["hists"] if member.prev else {})
        for name in STRAGGLER_METRICS:
            key = "pt_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)
            h = member.metrics["hists"].get(key)
            if not h or not h["count"]:
                continue
            p = prev_h.get(key)
            if p and h["count"] > p["count"]:
                return (h["sum"] - p["sum"]) / (h["count"] - p["count"])
            return h["sum"] / h["count"]
        return None

    def scrape_once(self, now: Optional[float] = None) -> Dict[str, Any]:
        """One full pass: scrape every member, recompute the fleet view,
        publish fleet.* into the local registry, evaluate the fleet SLO
        rules. Returns the fleet summary. Never raises."""
        now_mono = time.monotonic()
        with self._lock:
            members = list(self._members.values())
        for member in members:
            if self._stop.is_set():
                break
            self._scrape_member(member, now_mono)
        summary = self._publish(members, now=now)
        try:
            self._watchdog.evaluate(now=now)
        except Exception:
            telemetry.counter_quiet("fleet.rule_eval_errors")
        self._passes += 1
        return summary

    def _publish(self, members: List[FleetMember],
                 now: Optional[float] = None) -> Dict[str, Any]:
        ok = [m for m in members if m.state == "OK"]
        stale = [m for m in members if m.state == "STALE"]
        # aggregate QPS: sum of per-member request-counter deltas over
        # each member's own scrape interval (routers re-count their
        # replicas' requests — prefer the replica-side counter)
        qps = 0.0
        for m in ok:
            if m.metrics is None or m.prev is None:
                continue
            prev_t, prev = m.prev
            dt = m.last_ok_t - prev_t
            if dt <= 0:
                continue
            for ctr in ("pt_serving_requests_total",
                        "pt_decode_requests_total"):
                cur = m.metrics["counters"].get(ctr)
                old = prev["counters"].get(ctr)
                if cur is not None and old is not None and cur >= old:
                    qps += (cur - old) / dt
                    break
        # fleet queue: sum + saturation fraction vs the admission bound
        depths = [int(m.stats.get("queue_depth", 0)) for m in ok
                  if isinstance(m.stats, dict)
                  and isinstance(m.stats.get("queue_depth"), (int, float))]
        q_sum = sum(depths)
        q_bound = max(1, int(_flags.flag("serving_max_queue_depth")))
        q_frac = (q_sum / len(depths) / q_bound) if depths else 0.0
        # stragglers: windowed latency z-score vs the fleet median
        lat = {}
        for m in ok:
            v = self._member_latency(m)
            m.latency_ms = round(v, 4) if v is not None else None
            if v is not None:
                lat[m.name] = v
        flagged = set(detect_stragglers(lat))
        for m in members:
            m.straggler = m.name in flagged
        # fleet percentile from exactly-merged bucket counts
        p99 = None
        merged = self.merged_buckets()
        for name in STRAGGLER_METRICS:
            key = "pt_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)
            if key in merged and sum(merged[key]) > 0:
                p99 = telemetry.bucket_quantile(merged[key], 0.99)
                break
        telemetry.gauge_set("fleet.members", len(members))
        telemetry.gauge_set("fleet.members_ok", len(ok))
        telemetry.gauge_set("fleet.members_stale", len(stale))
        telemetry.gauge_set("fleet.stragglers", len(flagged))
        telemetry.gauge_set("fleet.qps", round(qps, 4))
        telemetry.gauge_set("fleet.queue_depth", q_sum)
        telemetry.gauge_set("fleet.queue_frac", round(q_frac, 4))
        if p99 is not None:
            telemetry.gauge_set("fleet.p99_ms", round(p99, 4))
        return {"members": len(members), "ok": len(ok),
                "stale": len(stale), "stragglers": sorted(flagged),
                "qps": round(qps, 4), "queue_depth": q_sum,
                "queue_frac": round(q_frac, 4), "p99_ms": p99}

    # -- merged views --------------------------------------------------------
    def merged_buckets(self) -> Dict[str, List[int]]:
        """Per-histogram bucket counts POOLED across every member's last
        good scrape (exact merge: count addition under the shared fixed
        bounds). Keys are prometheus names (pt_*)."""
        with self._lock:
            members = list(self._members.values())
        out: Dict[str, List[int]] = {}
        for m in members:
            if m.metrics is None:
                continue
            for name, h in m.metrics["hists"].items():
                if not h["buckets"]:
                    continue
                counts = counts_from_cumulative(h["buckets"])
                if name in out:
                    out[name] = telemetry.merge_bucket_counts(
                        [out[name], counts])
                else:
                    out[name] = counts
        return out

    def fleet_quantile(self, metric: str, q: float) -> Optional[float]:
        """Fleet-level quantile of one histogram (telemetry name or
        pt_-name) from the pooled bucket counts."""
        key = metric if metric.startswith("pt_") else \
            "pt_" + re.sub(r"[^a-zA-Z0-9_]", "_", metric)
        counts = self.merged_buckets().get(key)
        if not counts or sum(counts) == 0:
            return None
        return telemetry.bucket_quantile(counts, q)

    # -- surfaces ------------------------------------------------------------
    def status(self) -> Dict[str, Any]:
        """The /fleet/status body: per-member table, fleet gauges,
        stragglers, watchdog health, local goodput breakdown."""
        g = telemetry.gauges()
        fleet = {k.split(".", 1)[1]: v for k, v in g.items()
                 if k.startswith("fleet.")}
        out: Dict[str, Any] = {
            "ts": time.time(),
            "interval_s": self.interval_s,
            "stale_after_s": self.stale_after_s,
            "passes": self._passes,
            "members": self.members(),
            "stragglers": self.straggler_names(),
            "fleet": fleet,
            "rules": self._watchdog.health(),
        }
        try:
            from . import goodput as _goodput

            out["goodput"] = _goodput.breakdown()
        except Exception:
            pass
        return out

    def metrics_text(self) -> str:
        """The /fleet/metrics body: merged cumulative bucket series
        (``pt_fleet_<base>_bucket{le=...}``) + the fleet gauges."""
        lines = []
        g = telemetry.gauges()
        for name in sorted(k for k in g if k.startswith("fleet.")):
            v = g[name]
            if not isinstance(v, (int, float)):
                continue
            m = "pt_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)
            lines.append(f"# TYPE {m} gauge")
            lines.append(f"{m} {v}")
        merged = self.merged_buckets()
        for name in sorted(merged):
            counts = merged[name]
            total = sum(counts)
            base = "pt_fleet_" + name[len("pt_"):]
            running = 0
            for bound, c in zip(telemetry.HIST_BUCKET_BOUNDS, counts):
                running += c
                lines.append(f'{base}_bucket{{le="{bound}"}} {running}')
            lines.append(f'{base}_bucket{{le="+Inf"}} {total}')
            lines.append(f"{base}_count {total}")
        return "\n".join(lines) + "\n"

    def watchdog(self) -> incidents.Watchdog:
        return self._watchdog

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "FleetAggregator":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="pt-fleet-scrape", daemon=True)
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.scrape_once()
            except Exception:
                # the loop must survive anything a member throws at it
                telemetry.counter_quiet("fleet.scrape_pass_errors")


# ---------------------------------------------------------------------------
# process-default aggregator + the heartbeat announce hook
# ---------------------------------------------------------------------------

_default_lock = threading.Lock()
_default: Optional[FleetAggregator] = None


def aggregator(create: bool = False) -> Optional[FleetAggregator]:
    """The process's default aggregator (the one heartbeat announces
    land in). ``create=True`` builds+starts it on first use."""
    global _default
    with _default_lock:
        if _default is None and create:
            _default = FleetAggregator().start()
        return _default


def set_aggregator(agg: Optional[FleetAggregator]):
    global _default
    with _default_lock:
        _default = agg


def announce(name: str, url: str, kind: str = "trainer"):
    """Membership announce from the heartbeat path (distributed/ps):
    a trainer/pserver that started a metrics server registers its URL
    with the default aggregator. No-op without one — announcing must
    never cost the training loop anything."""
    agg = aggregator()
    if agg is None or not url:
        return
    with agg._lock:
        known = agg._members.get(name)
        if known is not None and known.url == url.rstrip("/"):
            return
    agg.register(name, url, kind=kind, stats_url=None)


def reset():
    """Tests: drop the default aggregator."""
    global _default
    with _default_lock:
        agg, _default = _default, None
    if agg is not None:
        agg.stop()


# ---------------------------------------------------------------------------
# standalone HTTP surface (when no router front end is running)
# ---------------------------------------------------------------------------

class FleetHTTPServer:
    """Stdlib server for /fleet/status + /fleet/metrics (+/healthz) —
    the scrape surface of the scraper, for trainer-side deployments
    with no router to piggyback on."""

    def __init__(self, agg: FleetAggregator, host: str = "127.0.0.1",
                 port: int = 0):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        self.aggregator = agg

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/fleet/status":
                    self._send(200, json.dumps(agg.status(),
                                               default=str).encode(),
                               "application/json")
                elif path == "/fleet/metrics":
                    self._send(200, agg.metrics_text().encode(),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif path == "/healthz":
                    self._send(200, b'{"status": "ok"}',
                               "application/json")
                else:
                    self._send(404, b'{"error": "no route"}',
                               "application/json")

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="pt-fleet-http",
            daemon=True)
        self._thread.start()

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def shutdown(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def start_fleet_server(agg: Optional[FleetAggregator] = None,
                       host: str = "127.0.0.1",
                       port: int = 0) -> FleetHTTPServer:
    """Serve /fleet/status + /fleet/metrics for ``agg`` (default: the
    process aggregator, created+started on demand)."""
    if agg is None:
        agg = aggregator(create=True)
    return FleetHTTPServer(agg, host=host, port=port)
