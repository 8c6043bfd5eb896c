"""Flight recorder + SLO watchdog plane (PR 14, core/incidents.py).

Pins the ISSUE acceptance criteria:

* the flight recorder is ALWAYS on (records land with no JSONL sink),
  bounded by FLAGS_blackbox_max_records, pruned to
  FLAGS_blackbox_seconds, and 0 disables it;
* rule trip/cooldown semantics: a sustained breach trips EXACTLY once
  (firing latch), a cleared episode + elapsed cooldown re-trips,
  ratio rules learn their baseline from the warmup window;
* a clean executor run under the default rule set trips ZERO rules
  (the false-positive gate);
* the unified kind:"incident" record bundles ring + ledger + traces +
  rule context, is globally rate-limited, and the legacy
  oom/stall/thread_error records keep their exact old shape (mem_report
  and the PR 10/11 readers stay green);
* /v1/stats grows a "health" section and /metrics grows pt_slo_*
  firing gauges;
* CLI smoke: tools/incident_report.py renders timeline + counter
  deltas + correlated spans; tools/slo_check.py exits 0/1/2;
  tools/trace_view.py marks incidents as instant events;
  tools/chaos_check.py --slo legs pass.
"""

import io
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import costmodel, incidents, telemetry, trace
from paddle_tpu.core.flags import flag as _flag
from paddle_tpu.core.flags import set_flags

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane():
    telemetry.configure(None)
    telemetry.reset()
    costmodel.reset()
    incidents.reset()
    set_flags({"blackbox_max_records": 2048, "blackbox_seconds": 120.0,
               "slo_watchdog": "auto", "slo_rules": "",
               "incident_rate_limit_s": 30.0, "slo_eval_s": 5.0,
               "trace_sample_rate": 0.0})
    yield
    telemetry.configure(None)
    telemetry.reset()
    costmodel.reset()
    incidents.reset()
    set_flags({"blackbox_max_records": 2048, "blackbox_seconds": 120.0,
               "slo_watchdog": "auto", "slo_rules": "",
               "incident_rate_limit_s": 30.0, "slo_eval_s": 5.0,
               "trace_sample_rate": 0.0})


def _read(path):
    telemetry.flush_sink()
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _failover_rule(**kw):
    kw.setdefault("window_s", 30.0)
    kw.setdefault("threshold", 3)
    kw.setdefault("cooldown_s", 60.0)
    return incidents.Rule("router_failover_burst", "router.failovers",
                          kind="counter", stat="delta", **kw)


# -- flight recorder ----------------------------------------------------------


class TestFlightRecorder:
    def test_always_on_without_sink(self):
        """The ring sees counters/gauges/hists/events with NO JSONL sink
        configured — the black-box property."""
        assert not telemetry.enabled()
        telemetry.counter_add("router.failovers", 2)
        telemetry.gauge_set("serving.queue_depth", 7)
        telemetry.observe("executor.run_ms", 3.25, kind="timer")
        telemetry.event("compile", "executor", 12.5, {"cause": "program"})
        snap = incidents.flight_recorder().snapshot()
        kinds = [(r["kind"], r["name"]) for r in snap]
        assert ("counter", "router.failovers") in kinds
        assert ("gauge", "serving.queue_depth") in kinds
        assert ("timer", "executor.run_ms") in kinds
        assert ("compile", "executor") in kinds

    def test_ring_bounded_keeps_newest(self):
        set_flags({"blackbox_max_records": 8})
        for i in range(50):
            telemetry.counter_add("router.failovers", 1, i=i)
        rec = incidents.flight_recorder()
        assert len(rec) == 8
        snap = rec.snapshot()
        assert len(snap) == 8
        assert [r["attrs"]["i"] for r in snap] == list(range(42, 50))
        assert rec.dropped > 0

    def test_zero_disables(self):
        set_flags({"blackbox_max_records": 0})
        incidents.flight_recorder().clear()
        telemetry.counter_add("router.failovers", 1)
        assert len(incidents.flight_recorder()) == 0

    def test_snapshot_prunes_by_time_and_caps(self):
        telemetry.counter_add("router.failovers", 1)
        rec = incidents.flight_recorder()
        now = time.time()
        # a record older than the horizon is pruned at snapshot time
        assert rec.snapshot(window_s=60.0, now=now)
        assert rec.snapshot(window_s=60.0, now=now + 120.0) == []
        for _ in range(10):
            telemetry.counter_add("router.failovers", 1)
        assert len(rec.snapshot(limit=4)) == 4


# -- rule semantics -----------------------------------------------------------


class TestRuleSemantics:
    def test_counter_rule_trips_once_latched(self):
        """A sustained breach trips exactly once: the firing latch
        absorbs every later evaluation of the same episode."""
        wd = incidents.Watchdog([_failover_rule()])
        telemetry.counter_add("router.failovers", 5)
        now = time.time()
        assert wd.evaluate(now=now) == ["router_failover_burst"]
        for i in range(5):
            assert wd.evaluate(now=now + i * 0.1) == []
        (rule,) = wd.rules
        assert rule.trips == 1 and rule.firing
        assert telemetry.counter_get("slo.trips") == 1

    def test_cooldown_gates_retrigger(self):
        """After the episode clears, a new breach re-trips only once the
        cooldown elapsed."""
        wd = incidents.Watchdog([_failover_rule(cooldown_s=60.0)])
        telemetry.counter_add("router.failovers", 5)
        now = time.time()
        assert wd.evaluate(now=now) == ["router_failover_burst"]
        (rule,) = wd.rules
        # signal leaves the window -> episode clears
        assert wd.evaluate(now=now + 100.0) == []
        assert not rule.firing
        # new breach inside the cooldown: suppressed (but latched)
        telemetry.counter_add("router.failovers", 5)
        assert wd.evaluate(now=now + 0.1) == []
        assert rule.firing
        # same breach once the cooldown HAS elapsed: trips again
        rule.firing = False
        rule.last_trip_ts = now - 100.0
        assert wd.evaluate(now=now + 0.2) == ["router_failover_burst"]
        assert rule.trips == 2

    def test_hist_baseline_learning_and_regression(self):
        """Ratio rules: the first window satisfying min_samples freezes
        the baseline; a later p99 above baseline*ratio trips."""
        rule = incidents.Rule("step_time_p99", "executor.run_ms",
                              kind="hist", stat="p99", window_s=60.0,
                              ratio=2.0, min_samples=20, cooldown_s=300.0)
        wd = incidents.Watchdog([rule])
        for _ in range(25):
            telemetry.observe("executor.run_ms", 5.0, kind="timer")
        now = time.time()
        assert wd.evaluate(now=now) == []          # learns, no trip
        assert rule.baseline == pytest.approx(5.0)
        assert rule.state() == "ok"
        assert wd.evaluate(now=now + 0.1) == []    # clean stays clean
        for _ in range(25):
            telemetry.observe("executor.run_ms", 50.0, kind="timer")
        assert wd.evaluate(now=now + 0.2) == ["step_time_p99"]
        assert rule.last_value > 2.0 * rule.baseline

    def test_gauge_below_rule_mfu_drop(self):
        rule = incidents.Rule("live_mfu_drop", "cost.live_mfu",
                              kind="gauge", ratio=0.5, direction="below",
                              min_samples=3, cooldown_s=300.0)
        wd = incidents.Watchdog([rule])
        telemetry.gauge_set("cost.live_mfu", 0.4)
        now = time.time()
        assert wd.evaluate(now=now) == []
        assert wd.evaluate(now=now) == []
        assert wd.evaluate(now=now) == []          # 3rd: baseline frozen
        assert rule.baseline == pytest.approx(0.4)
        telemetry.gauge_set("cost.live_mfu", 0.05)
        assert wd.evaluate(now=now + 1) == ["live_mfu_drop"]

    def test_threshold_gauge_queue_saturation(self):
        wd = incidents.Watchdog([incidents.Rule(
            "serving_queue_saturation", "serving.queue_depth",
            kind="gauge", threshold=0.9 * _flag("serving_max_queue_depth"),
            cooldown_s=60.0)])
        telemetry.gauge_set("serving.queue_depth", 4)
        assert wd.evaluate() == []
        telemetry.gauge_set(
            "serving.queue_depth",
            int(0.95 * _flag("serving_max_queue_depth")))
        assert wd.evaluate() == ["serving_queue_saturation"]

    def test_declarative_spec_overrides(self):
        spec = json.dumps([{"name": "my_rule", "metric": "foo.bar",
                            "kind": "counter", "threshold": 7,
                            "window_s": 10, "cooldown_s": 1}])
        rules = incidents.rules_from_spec(spec)
        assert len(rules) == 1
        assert rules[0].name == "my_rule"
        assert rules[0].threshold == 7
        assert rules[0].window_s == 10.0
        with pytest.raises((ValueError, json.JSONDecodeError)):
            incidents.rules_from_spec("{not json")
        with pytest.raises(ValueError):
            incidents.rules_from_spec(json.dumps(
                [{"name": "x", "metric": "m", "kind": "nope",
                  "threshold": 1}]))
        # empty spec -> the built-in set, which covers the ISSUE list
        names = {r.name for r in incidents.rules_from_spec("")}
        assert {"step_time_p99", "live_mfu_drop",
                "serving_queue_saturation", "decode_queue_saturation",
                "pallas_gemm_fallback_spike", "router_failover_burst",
                "ckpt_verify_failures"} <= names

    def test_clean_executor_run_trips_zero_rules(self, scope, tmp_path):
        """ACCEPTANCE (false-positive gate): a real, fault-free
        instrumented executor run under the DEFAULT rule set trips
        nothing."""
        telemetry.configure(str(tmp_path / "run.jsonl"))
        wd = incidents.arm()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4], stop_gradient=True)
            loss = layers.mean(layers.fc(x, 8, act="relu"))
            pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        xv = np.ones((4, 4), np.float32)
        trips = []
        for _ in range(5):
            exe.run(main, feed={"x": xv}, fetch_list=[loss], scope=scope)
            trips += wd.evaluate()
        assert trips == []
        assert telemetry.counter_get("incidents.reported") == 0
        assert not [r for r in _read(tmp_path / "run.jsonl")
                    if r["kind"] == "incident"]
        # ...and the run's signals DID reach the window the rules read
        assert telemetry.windowed(60.0)["hists"].get("executor.run_ms")

    def test_executor_tick_drives_evaluation(self, scope, tmp_path):
        """incidents.tick() on the executor hot path evaluates while
        armed (throttled by FLAGS_slo_eval_s) and is inert disarmed."""
        set_flags({"slo_eval_s": 0.0})
        telemetry.configure(str(tmp_path / "run.jsonl"))
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4], stop_gradient=True)
            loss = layers.mean(layers.fc(x, 8))
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        xv = np.ones((2, 4), np.float32)
        exe.run(main, feed={"x": xv}, fetch_list=[loss], scope=scope)
        assert telemetry.counter_get("slo.evaluations") == 0  # disarmed
        incidents.arm()
        exe.run(main, feed={"x": xv}, fetch_list=[loss], scope=scope)
        assert telemetry.counter_get("slo.evaluations") >= 1


# -- incident pipeline --------------------------------------------------------


class TestIncidentPipeline:
    def test_incident_record_schema(self, tmp_path):
        """ACCEPTANCE: one trip -> one kind:'incident' record bundling
        ring snapshot, ledger, active traces, counters and the rule
        context."""
        set_flags({"trace_sample_rate": 1.0})
        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))
        with trace.span("serving.request"):
            telemetry.counter_add("router.failovers", 5)
        wd = incidents.Watchdog([_failover_rule()])
        assert wd.evaluate() == ["router_failover_burst"]
        (inc,) = [r for r in _read(log) if r["kind"] == "incident"]
        assert inc["name"] == "slo.router_failover_burst"
        a = inc["attrs"]
        assert a["source"] == "slo"
        assert a["id"].startswith("inc-")
        assert a["rule"]["name"] == "router_failover_burst"
        assert a["rule"]["threshold"] == 3
        assert a["rule"]["value"] == 5.0
        assert isinstance(a["ledger"], dict)
        assert a["counters"]["router.failovers"] == 5
        # the ring snapshot carries the events leading to the trip,
        # including the sampled span whose trace id is in `traces`
        ring_kinds = {(r["kind"], r["name"]) for r in a["ring"]}
        assert ("counter", "router.failovers") in ring_kinds
        assert ("span", "serving.request") in ring_kinds
        span_rec = next(r for r in a["ring"] if r["kind"] == "span")
        assert span_rec["attrs"]["trace"] in a["traces"]
        assert telemetry.counter_get("incidents.reported") == 1
        assert telemetry.counter_get("slo.trips") == 1

    def test_global_rate_limit(self, tmp_path):
        """Two rules tripping back-to-back: the second dump is
        rate-limited (counted, not written); legacy records are not."""
        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))
        telemetry.counter_add("router.failovers", 5)
        telemetry.counter_add("ckpt.verify_failures", 1)
        wd = incidents.Watchdog([
            _failover_rule(),
            incidents.Rule("ckpt_verify_failures",
                           "ckpt.verify_failures", kind="counter",
                           stat="delta", window_s=120.0, threshold=0,
                           cooldown_s=60.0)])
        trips = wd.evaluate()
        assert sorted(trips) == ["ckpt_verify_failures",
                                 "router_failover_burst"]
        assert telemetry.counter_get("slo.trips") == 2
        incs = [r for r in _read(log) if r["kind"] == "incident"]
        assert len(incs) == 1
        assert telemetry.counter_get("incidents.reported") == 1
        assert telemetry.counter_get("incidents.rate_limited") == 1

    def test_oom_flows_through_pipeline_legacy_intact(self, tmp_path):
        """The PR 10 OOM dump rides the unified pipeline: the legacy
        kind:'oom' record keeps its exact fields (mem_report reads it),
        plus one incident record with source 'oom'."""
        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))
        err = costmodel.oom_forensics(
            "prog9v1", RuntimeError("RESOURCE_EXHAUSTED: oom"),
            where="executor.dispatch")
        assert isinstance(err, costmodel.OutOfMemoryError)
        recs = _read(log)
        (oom,) = [r for r in recs if r["kind"] == "oom"]
        assert oom["name"] == "costmodel.oom"
        assert oom["attrs"]["where"] == "executor.dispatch"
        assert oom["attrs"]["program"] == "prog9v1"
        assert "ledger" in oom["attrs"] and "top_programs" in oom["attrs"]
        (inc,) = [r for r in recs if r["kind"] == "incident"]
        assert inc["attrs"]["source"] == "oom"
        assert inc["attrs"]["context"]["where"] == "executor.dispatch"
        # mem_report still renders the legacy record
        from tools.mem_report import summarize_mem

        s = summarize_mem(recs)
        assert len(s["ooms"]) == 1
        assert s["ooms"][0]["program"] == "prog9v1"

    def test_thread_death_flows_through_pipeline(self, tmp_path):
        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))

        def boom():
            raise RuntimeError("worker died")

        t = threading.Thread(target=boom, name="pt-test-dying",
                             daemon=True)
        t.start()
        t.join(timeout=10)
        recs = _read(log)
        (te,) = [r for r in recs if r["kind"] == "thread_error"]
        assert te["name"] == "pt-test-dying"
        assert te["attrs"]["exc"] == "RuntimeError"
        assert "traceback" in te["attrs"]
        (inc,) = [r for r in recs if r["kind"] == "incident"]
        assert inc["attrs"]["source"] == "thread_error"
        assert inc["attrs"]["context"]["exc"] == "RuntimeError"

    def test_stall_flows_through_pipeline(self, tmp_path):
        """The PR 11 stall dump keeps its legacy shape and gains the
        incident twin (driven directly — wedging a real lock for
        FLAGS_lock_stall_s is a slow-test concern)."""
        from paddle_tpu.core.analysis import lockdep

        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))
        ident = threading.get_ident()
        lockdep._dump_stall(ident, {"lock": "router.dedup",
                                    "t0": time.monotonic() - 31.0,
                                    "thread": "pt-test"}, 31.0)
        recs = _read(log)
        (st,) = [r for r in recs if r["kind"] == "stall"]
        assert st["name"] == "lockdep.stall"
        assert st["attrs"]["lock"] == "router.dedup"
        assert st["attrs"]["threads"]          # all-thread stacks
        (inc,) = [r for r in recs if r["kind"] == "incident"]
        assert inc["attrs"]["source"] == "stall"
        assert inc["attrs"]["context"]["lock"] == "router.dedup"

    def test_health_and_prometheus_surfaces(self, tmp_path):
        telemetry.configure(str(tmp_path / "run.jsonl"))
        telemetry.counter_add("router.failovers", 5)
        incidents.arm([_failover_rule()])
        incidents.watchdog().evaluate()
        h = incidents.health()
        assert h["watchdog_armed"]
        assert h["incidents_reported"] == 1
        assert h["slo_trips"] == 1
        assert h["rules"]["router_failover_burst"]["state"] == "firing"
        assert h["firing"] == ["router_failover_burst"]
        assert h["last_incident"]["rule"] == "router_failover_burst"
        text = telemetry.prometheus_text()
        assert "pt_slo_router_failover_burst_firing 1" in text

    def test_v1_stats_health_section(self, tmp_path):
        """/v1/stats carries the health section (ACCEPTANCE: the stats
        surface exposes watchdog state)."""
        import urllib.request

        from paddle_tpu.serving.server import ServingHTTPServer
        from tests.test_serving import _engine, _save_mlp

        engine = _engine(_save_mlp(tmp_path)).start(warmup=False)
        srv = ServingHTTPServer(engine).start()
        try:
            assert incidents.armed()     # 'auto' armed by the server
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/v1/stats", timeout=10).read())
            assert "health" in doc
            assert doc["health"]["watchdog_armed"] is True
            assert "incidents_reported" in doc["health"]
        finally:
            srv.shutdown()
            engine.close()
        assert not incidents.armed()     # disarmed on shutdown


# -- CLI surfaces -------------------------------------------------------------


def _make_incident_log(tmp_path):
    set_flags({"trace_sample_rate": 1.0})
    log = tmp_path / "run.jsonl"
    telemetry.configure(str(log))
    with trace.span("serving.request"):
        telemetry.counter_add("router.failovers", 5)
    incidents.Watchdog([_failover_rule()]).evaluate()
    telemetry.flush_sink()
    telemetry.configure(None)
    return log


class TestCLIs:
    def test_incident_report_renders_postmortem(self, tmp_path):
        """ACCEPTANCE: the postmortem carries timeline, counter deltas
        and correlated spans."""
        log = _make_incident_log(tmp_path)
        from tools.incident_report import (load_incidents,
                                           render_incident,
                                           summarize_incident)
        from tools.perf_report import load_counted

        recs, _ = load_counted(str(log))
        (inc,) = load_incidents(recs)
        s = summarize_incident(inc)
        assert s["source"] == "slo"
        assert s["counter_deltas"]
        assert s["spans"] and s["spans"][0]["name"] == "serving.request"
        buf = io.StringIO()
        render_incident(s, out=buf)
        text = buf.getvalue()
        for section in ("-- tripped rule --", "-- counter deltas",
                        "-- correlated spans", "-- timeline around"):
            assert section in text, f"missing {section}"

    def test_incident_report_cli_smoke(self, tmp_path):
        log = _make_incident_log(tmp_path)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "incident_report.py"),
             str(log)], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "incident" in r.stdout
        # a log without incidents exits 2
        clean = tmp_path / "clean.jsonl"
        clean.write_text(json.dumps(
            {"ts": 1.0, "kind": "counter", "name": "x", "value": 1,
             "attrs": {}}) + "\n")
        r2 = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "incident_report.py"),
             str(clean)], capture_output=True, text=True, timeout=60)
        assert r2.returncode == 2

    def test_trace_view_incident_markers(self, tmp_path):
        """Incidents render as chrome instant events on the swimlane of
        a span sharing their trace id."""
        log = _make_incident_log(tmp_path)
        from tools import trace_view

        out = tmp_path / "trace.json"
        rc = trace_view.main([str(log), "--out", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        inst = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
        assert len(inst) == 1
        assert inst[0]["name"].startswith("INCIDENT slo.")
        assert inst[0]["args"]["rule"] == "router_failover_burst"
        span_ev = next(e for e in doc["traceEvents"]
                       if e.get("cat") == "span")
        assert inst[0]["pid"] == span_ev["pid"]
        assert inst[0]["tid"] == span_ev["tid"]

    def test_perf_report_incidents_section(self, tmp_path):
        log = _make_incident_log(tmp_path)
        from tools.perf_report import load_counted, render, summarize_log

        recs, malformed = load_counted(str(log))
        s = summarize_log(recs, malformed=malformed)
        ic = s["incidents"]
        assert ic["reported"] == 1
        assert ic["slo_trips"] == 1
        assert ic["rules_firing"]["router_failover_burst"] == 1
        assert ic["incidents"][0]["rule"] == "router_failover_burst"
        buf = io.StringIO()
        render(s, out=buf)
        assert "-- incidents & SLO" in buf.getvalue()
        assert "STILL FIRING" in buf.getvalue()

    def test_slo_check_exit_codes(self, tmp_path):
        from tools import slo_check

        prior = tmp_path / "BENCH_r01.json"
        prior.write_text(json.dumps({"parsed": {
            "metric": "m1", "value": 100.0, "unit": "tokens/s",
            "extra": {"mfu": 0.5, "ms_per_step": 10.0}}}))
        good = tmp_path / "good.json"
        good.write_text(json.dumps({
            "metric": "m1", "value": 101.0, "unit": "tokens/s",
            "extra": {"mfu": 0.51, "ms_per_step": 9.5}}))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({
            "metric": "m1", "value": 60.0, "unit": "tokens/s",
            "extra": {"mfu": 0.3, "ms_per_step": 17.0}}))
        glob_arg = str(tmp_path / "BENCH_r*.json")
        assert slo_check.main([str(good), "--prior", glob_arg]) == 0
        assert slo_check.main([str(bad), "--prior", glob_arg]) == 1
        garbage = tmp_path / "garbage.json"
        garbage.write_text("{\"nope\": 1}")
        assert slo_check.main([str(garbage)]) == 2
        # no comparable prior rows -> pass (no_baseline), not a failure
        other = tmp_path / "other.json"
        other.write_text(json.dumps({"metric": "m2", "value": 1.0,
                                     "unit": "x/s"}))
        assert slo_check.main([str(other), "--prior", glob_arg]) == 0
        # the embedded verdict bench rows carry
        v = slo_check.slo_verdict(json.loads(bad.read_text()),
                                  [json.loads(prior.read_text())["parsed"]])
        assert v["verdict"] == "regress"
        assert any(not c["ok"] for c in v["checks"])

    def test_slo_check_cli_smoke(self, tmp_path):
        """The CLI judges a row against a history that holds its equal:
        PASS; against the repo root, which commits no history: PASS as
        no_baseline, not a failure."""
        row = {"metric": "m1", "value": 100.0, "unit": "tokens/s",
               "extra": {"mfu": 0.5, "ms_per_step": 10.0}}
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps({"parsed": row}))
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(row))
        cli = [sys.executable, os.path.join(REPO_ROOT, "tools",
                                            "slo_check.py"), str(fresh)]
        r = subprocess.run(
            cli + ["--prior", str(tmp_path / "BENCH_r*.json")],
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "1 prior row(s): PASS" in r.stdout
        r = subprocess.run(cli, capture_output=True, text=True,
                           timeout=60, cwd=REPO_ROOT)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "0 prior row(s): NO_BASELINE" in r.stdout

    @pytest.mark.chaos
    def test_chaos_slo_fault_and_clean_legs(self):
        """ACCEPTANCE: the chaos --slo gate — one fault class leg (trips
        exactly once) + the clean false-positive leg (zero trips)."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "chaos_check.py"),
             "--slo", "--slo-class",
             "router_failover,ckpt_verify,clean", "--steps", "4"],
            capture_output=True, text=True, timeout=300, cwd=REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "CHAOS OK" in r.stdout
        assert "tripped exactly once" in r.stdout
        assert "0 trips, 0 incidents" in r.stdout
