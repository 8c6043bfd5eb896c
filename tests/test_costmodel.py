"""Cost & memory observability plane tests (PR 10 tier-1 gate).

Contracts under test (paddle_tpu/core/costmodel.py + the wiring):
* every fresh executor compile captures XLA cost/memory analyses keyed
  by the compile-cache entry (flops/bytes at level 'cost', plus peak/
  argument/output/temp bytes at level 'full'), and the HBM ledger
  gauges (mem.param_bytes / mem.opt_state_bytes / mem.peak_temp_bytes /
  mem.hbm_total_bytes) + live MFU gauge land on the metrics plane;
* 'auto' capture costs nothing in uninstrumented runs and turns on when
  a telemetry sink or metrics server is active;
* a backend without the analysis APIs degrades by COUNTING
  (costmodel.unavailable) — executor, predictor and serving engine all
  stay green (ISSUE satellite);
* an allocation failure dumps an OOM-forensics record (ledger snapshot
  + top cached programs + the offending program) and raises a typed
  OutOfMemoryError;
* serving warmup captures per-bucket footprints into /v1/stats and
  mem.serving.bucket<B>_peak_bytes gauges;
* BENCH rows embed extra.model_flops + extra.live_mfu;
* tools/mem_report.py renders the ledger + per-program table from a
  run log, and --smoke self-checks (ISSUE satellite);
* no emitted cost.*/mem.*/costmodel.*/sharding.*state_bytes* metric is
  silently orphaned — every one is rendered by perf_report or
  mem_report (ISSUE satellite: metric-name drift guard).
"""

import json
import os
import re
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import costmodel, flags, telemetry
from paddle_tpu.core.flags import set_flags

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane():
    telemetry.configure(None)
    telemetry.reset()
    costmodel.reset()
    set_flags({"cost_capture": "auto"})
    # the CPU is in no peak table: the verdict/MFU cases score against a
    # peak they name themselves (the v5e figures)
    with flags.overrides(device_peak_flops=197e12, device_peak_bw=819e9):
        yield
    set_flags({"cost_capture": "auto"})
    telemetry.configure(None)
    telemetry.reset()
    costmodel.reset()


def _mlp_program(hidden=8):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], stop_gradient=True)
        y = layers.fc(x, hidden, act="relu")
        loss = layers.mean(y)
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


def _run_steps(scope, n=3, log=None, level="full"):
    if log is not None:
        telemetry.configure(str(log))
    set_flags({"cost_capture": level})
    main, startup, loss = _mlp_program()
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    x = np.ones((4, 4), np.float32)
    out = None
    for _ in range(n):
        out = exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
    return exe, float(np.asarray(out[0]).reshape(-1)[0])


def _read(path):
    telemetry.flush_sink()
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class TestCaptureExecutor:
    def test_full_capture_program_record_and_ledger(self, scope, tmp_path):
        """Acceptance core: a full-capture run records flops + memory
        stats per compile-cache entry and composes the HBM ledger."""
        log = tmp_path / "run.jsonl"
        _run_steps(scope, n=3, log=log)
        recs = costmodel.programs()
        assert len(recs) == 1
        rec = recs[0]
        assert rec.kind == "executor"
        assert rec.flops > 0 and rec.bytes_accessed > 0
        assert rec.source == "compiled"
        assert rec.temp_bytes > 0 and rec.arg_bytes > 0
        assert rec.peak_bytes >= rec.temp_bytes
        assert rec.roofline() in ("compute_bound", "memory_bound")
        g = telemetry.gauges()
        assert g["mem.param_bytes"] > 0          # fc weights
        assert g["mem.opt_state_bytes"] > 0      # lr counter etc.
        assert g["mem.peak_temp_bytes"] == rec.temp_bytes
        led = costmodel.ledger()
        assert led["total_bytes"] == (led["param_bytes"] +
                                      led["opt_state_bytes"] +
                                      led["peak_temp_bytes"] +
                                      led.get("serving_kv_pool_bytes", 0))
        assert g["mem.hbm_total_bytes"] == led["total_bytes"]
        # dispatch accounting + live MFU gauge (set on first dispatch)
        assert telemetry.counter_get("cost.dispatch_flops") >= 3 * rec.flops
        assert costmodel.live_mfu() > 0
        assert g["cost.live_mfu"] > 0
        # the run log carries the per-compile cost record
        cost_recs = [r for r in _read(log) if r["kind"] == "cost"]
        assert len(cost_recs) == 1
        attrs = cost_recs[0]["attrs"]
        assert attrs["flops"] == rec.flops
        assert attrs["temp_bytes"] == rec.temp_bytes
        assert attrs["roofline"] == rec.roofline()
        assert attrs["key"] == rec.key_id

    def test_cost_level_skips_memory_stats(self, scope, tmp_path):
        """'cost' level: flops/bytes from the lowered module only — no
        second XLA compile, no temp bytes."""
        _run_steps(scope, n=1, log=tmp_path / "r.jsonl", level="cost")
        (rec,) = costmodel.programs()
        assert rec.source == "lowered"
        assert rec.flops > 0
        assert rec.temp_bytes == 0 and rec.peak_bytes == 0

    def test_auto_is_off_when_uninstrumented(self, scope):
        """No sink, no metrics server → 'auto' captures nothing (bare CI
        runs pay zero)."""
        assert costmodel.capture_mode() == "off"
        _run_steps(scope, n=1, log=None, level="auto")
        assert costmodel.programs() == []
        assert telemetry.counter_get("cost.captures") == 0

    def test_auto_is_on_with_sink(self, scope, tmp_path):
        telemetry.configure(str(tmp_path / "r.jsonl"))
        assert costmodel.capture_mode() == "cost"
        _run_steps(scope, n=1, log=None, level="auto")
        assert telemetry.counter_get("cost.captures") == 1

    def test_run_steps_capture_covers_the_fused_scan(self, scope, tmp_path):
        """K-step fusion: the captured program IS the scan — flops scale
        ~k× the single-step program and the record names k."""
        telemetry.configure(str(tmp_path / "r.jsonl"))
        set_flags({"cost_capture": "cost"})
        main, startup, loss = _mlp_program()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        x = np.ones((4, 4), np.float32)
        exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        stacked = {"x": np.stack([x] * 4)}
        exe.run_steps(main, feed=stacked, fetch_list=[loss], k=4,
                      scope=scope)
        recs = {r.steps_per_dispatch: r for r in costmodel.programs()}
        assert set(recs) == {1, 4}
        # XLA cost analysis counts the scan body ONCE — the per-dispatch
        # figure scales it by k
        assert recs[4].flops_per_dispatch() >= 3 * recs[1].flops_per_dispatch()
        assert recs[4].flops == pytest.approx(recs[1].flops, rel=0.25)

    def test_peak_flops_override(self):
        with flags.overrides(device_peak_flops=123.0):
            assert costmodel.peak_device_flops() == 123.0

    def test_unknown_device_kind_has_no_peak(self, scope, tmp_path):
        """A device_kind the table lacks — the CPU is not a v5e — raises
        a typed error; the in-loop callers omit their figure instead of
        raising out of Executor.run."""
        with flags.overrides(device_peak_flops=0.0, device_peak_bw=0.0):
            with pytest.raises(costmodel.UnknownDevicePeakError,
                               match="cpu"):
                costmodel.peak_device_flops()
            with pytest.raises(costmodel.UnknownDevicePeakError):
                costmodel.peak_device_bandwidth()
            _run_steps(scope, n=2, log=tmp_path / "run.jsonl")
            rec = costmodel.programs()[0]
            assert rec.flops > 0 and rec.roofline() == "unknown"
            assert costmodel.live_mfu() is None
            assert "cost.live_mfu" not in telemetry.gauges()

    @pytest.mark.parametrize("kind,flops", [
        ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
        ("TPU v4", 275e12), ("TPU v6 lite", 918e12)])
    def test_device_table_rows(self, monkeypatch, kind, flops):
        import types

        import jax

        monkeypatch.setattr(jax, "devices", lambda *a: [
            types.SimpleNamespace(device_kind=kind)])
        with flags.overrides(device_peak_flops=0.0, device_peak_bw=0.0):
            assert costmodel.peak_device_flops() == flops

    def test_normalize_cost_analysis_shapes(self):
        """One place knows XLA's key spelling — list-vs-dict and the
        'bytes accessed' name (satellite: audit_hlo rebases on this)."""
        flat = costmodel.normalize_cost_analysis(
            {"flops": 2.0, "bytes accessed": 3.0, "transcendentals": 1.0,
             "bytes accessed0{}": 99.0})
        assert flat == {"flops": 2.0, "bytes_accessed": 3.0,
                        "transcendentals": 1.0}
        assert costmodel.normalize_cost_analysis(
            [{"flops": 5.0}])["flops"] == 5.0
        assert costmodel.normalize_cost_analysis(None) == {}
        assert costmodel.normalize_cost_analysis("nope") == {}


class TestDegradation:
    """ISSUE satellite: a backend without cost_analysis/memory_analysis
    degrades by counting — executor/predictor/serving all stay green."""

    def test_executor_green_without_analysis_apis(self, scope, tmp_path,
                                                  monkeypatch):
        import jax

        def boom(self, *a, **kw):
            raise NotImplementedError("no analysis on this backend")

        monkeypatch.setattr(jax.stages.Lowered, "cost_analysis", boom)
        monkeypatch.setattr(jax.stages.Lowered, "compile", boom)
        _exe, loss = _run_steps(scope, n=2, log=tmp_path / "r.jsonl")
        assert np.isfinite(loss)                 # run unaffected
        assert costmodel.programs() == []        # nothing captured
        assert telemetry.counter_get("costmodel.unavailable") >= 1
        assert telemetry.counter_get("cost.captures") == 0

    def test_memory_analysis_only_missing(self, scope, tmp_path,
                                          monkeypatch):
        """cost_analysis works, memory_analysis raises → partial record
        (flops yes, temp bytes no), unavailable counted once."""
        import jax

        def boom(self, *a, **kw):
            raise NotImplementedError("CompiledMemoryStats unavailable")

        monkeypatch.setattr(jax.stages.Compiled, "memory_analysis", boom)
        _run_steps(scope, n=1, log=tmp_path / "r.jsonl")
        (rec,) = costmodel.programs()
        assert rec.flops > 0 and rec.temp_bytes == 0
        assert telemetry.counter_get("costmodel.unavailable") == 1

    def test_serving_green_without_analysis_apis(self, tmp_path,
                                                 monkeypatch):
        import jax

        def boom(self, *a, **kw):
            raise NotImplementedError("no analysis")

        monkeypatch.setattr(jax.stages.Lowered, "cost_analysis", boom)
        monkeypatch.setattr(jax.stages.Lowered, "compile", boom)
        telemetry.configure(str(tmp_path / "r.jsonl"))
        set_flags({"cost_capture": "full"})
        from tests.test_serving import _engine, _save_mlp

        engine = _engine(_save_mlp(tmp_path)).start(warmup=True)
        try:
            out, = engine.infer(
                {"x": np.ones((2, 6), np.float32)}, timeout=30)
            assert out.shape == (2, 4)
            assert engine.stats().get("memory") is None
            assert telemetry.counter_get("costmodel.unavailable") >= 1
        finally:
            engine.close()


class TestOOMForensics:
    def test_is_oom_error_markers(self):
        assert costmodel.is_oom_error(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        assert costmodel.is_oom_error(MemoryError("Out of memory"))
        assert not costmodel.is_oom_error(ValueError("bad shape"))

    def test_oom_forensics_record_contents(self, scope, tmp_path):
        """The forensics record carries the ledger + top programs by
        peak bytes + the offending program id, and mem.oom_events is
        counted."""
        log = tmp_path / "run.jsonl"
        _run_steps(scope, n=1, log=log)
        err = costmodel.oom_forensics(
            "prog7v1", RuntimeError("RESOURCE_EXHAUSTED: oom"),
            where="executor.dispatch")
        assert isinstance(err, costmodel.OutOfMemoryError)
        assert "prog7v1" in str(err)
        assert telemetry.counter_get("mem.oom_events") == 1
        ooms = [r for r in _read(log) if r["kind"] == "oom"]
        assert len(ooms) == 1
        attrs = ooms[0]["attrs"]
        assert attrs["program"] == "prog7v1"
        assert attrs["where"] == "executor.dispatch"
        assert attrs["ledger"]["total_bytes"] > 0
        assert attrs["top_programs"] and \
            attrs["top_programs"][0]["peak_bytes"] > 0

    def test_executor_dispatch_wraps_oom(self, scope, tmp_path):
        """An allocation failure out of the jitted dispatch surfaces as
        the typed OutOfMemoryError with the forensics landed."""
        log = tmp_path / "run.jsonl"
        telemetry.configure(str(log))
        set_flags({"cost_capture": "full"})
        main, startup, loss = _mlp_program()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        x = np.ones((4, 4), np.float32)
        exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        (entry,) = exe._cache.values()

        def exhausted(*a, **kw):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating 1234 bytes")

        entry.jitted = exhausted
        with pytest.raises(costmodel.OutOfMemoryError):
            exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        ooms = [r for r in _read(log) if r["kind"] == "oom"]
        assert len(ooms) == 1
        assert ooms[0]["attrs"]["where"] == "executor.dispatch"
        assert str(main.uid) in str(ooms[0]["attrs"]["program"])


class TestLiveMetricsPlane:
    def test_metrics_server_exposes_cost_and_mem_gauges(self, scope):
        """Acceptance: /metrics exposes pt_cost_*/pt_mem_* mid-run. A
        running metrics server alone (no sink) turns 'auto' capture on."""
        srv = telemetry.start_metrics_server(port=0)
        try:
            assert telemetry.metrics_server_active()
            assert costmodel.capture_mode() == "cost"
            set_flags({"cost_capture": "full"})
            _run_steps(scope, n=2, log=None, level="full")
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            assert "pt_cost_captures_total" in text
            assert "pt_cost_live_mfu" in text
            assert "pt_mem_param_bytes" in text
            assert "pt_mem_hbm_total_bytes" in text
            assert "pt_cost_dispatch_flops_total" in text
        finally:
            srv.shutdown()
        assert not telemetry.metrics_server_active()


class TestServingBuckets:
    def test_warmup_captures_bucket_footprints(self, tmp_path):
        """Per-bucket cost/memory footprints land in /v1/stats and on
        mem.serving.bucket<B>_peak_bytes gauges at engine warmup."""
        telemetry.configure(str(tmp_path / "r.jsonl"))
        set_flags({"cost_capture": "full"})
        from tests.test_serving import _engine, _save_mlp

        engine = _engine(_save_mlp(tmp_path)).start(warmup=True)
        try:
            stats = engine.stats()
            mem = stats["memory"]
            # pow2 buckets up to max_batch_size=8 → 1, 2, 4, 8
            assert set(mem["buckets"]) == {"1", "2", "4", "8"}
            for rec in mem["buckets"].values():
                assert rec["peak_bytes"] > 0
                assert rec["flops"] > 0
            assert mem["ledger"]["param_bytes"] > 0
            g = telemetry.gauges()
            assert g["mem.serving.bucket8_peak_bytes"] > 0
            assert g["mem.serving.bucket8_peak_bytes"] >= \
                g["mem.serving.bucket1_peak_bytes"]
        finally:
            engine.close()


class TestBenchEmbedding:
    def test_bench_row_embeds_model_flops_and_live_mfu(self, tmp_path):
        """Acceptance: a BENCH row carries extra.model_flops (analytic)
        + extra.live_mfu (runtime gauge) — self-attributing rows."""
        telemetry.configure(str(tmp_path / "bench.jsonl"))
        set_flags({"cost_capture": "full"})
        sys.path.insert(0, REPO_ROOT)
        from tools.bench_models import bench_mnist, finalize_bench_result

        row = finalize_bench_result(bench_mnist(steps=4, batch=16))
        ex = row["extra"]
        assert ex["model_flops"] > 0
        assert "live_mfu" in ex and ex["live_mfu"] >= 0
        assert ex["cost_captures"] >= 1
        assert ex["cost_dispatch_flops"] > 0
        assert ex["mem_hbm_total_bytes"] > 0


class TestMemReportCLI:
    def _produce_log(self, scope, tmp_path):
        log = tmp_path / "run.jsonl"
        _run_steps(scope, n=3, log=log)
        costmodel.oom_forensics("progX", RuntimeError(
            "RESOURCE_EXHAUSTED: oom"), where="executor.dispatch")
        telemetry.flush()
        return log

    def test_cli_renders_ledger_and_cost_table(self, scope, tmp_path):
        """Acceptance: mem_report renders the HBM ledger (param/opt/peak
        temp bytes) + per-program cost table + OOM forensics from a real
        LeNet/MLP-harness run log."""
        log = self._produce_log(scope, tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join("tools", "mem_report.py"),
             str(log)],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        assert "-- HBM ledger --" in out
        assert "params" in out and "optimizer state" in out
        assert "peak program scratch" in out
        assert "-- per-program cost table" in out
        assert "executor" in out
        assert "-- OOM forensics" in out
        assert "-- capture health --" in out

    def test_cli_json_summary(self, scope, tmp_path):
        log = self._produce_log(scope, tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join("tools", "mem_report.py"),
             str(log), "--json"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 0, proc.stderr
        s = json.loads(proc.stdout)
        assert s["ledger"]["param_bytes"] > 0
        assert s["ledger"]["peak_temp_bytes"] > 0
        assert len(s["programs"]) == 1
        assert s["programs"][0]["flops"] > 0
        assert len(s["ooms"]) == 1

    def test_smoke_self_check(self):
        """ISSUE satellite: `mem_report --smoke` (synthetic log →
        nonzero exit on missing sections) in the tools smoke path."""
        proc = subprocess.run(
            [sys.executable, os.path.join("tools", "mem_report.py"),
             "--smoke"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout

    def test_smoke_fails_when_renderer_loses_a_section(self, monkeypatch):
        """The smoke must actually bite: drop a section from the
        renderer and --smoke exits nonzero."""
        sys.path.insert(0, REPO_ROOT)
        from tools import mem_report

        real_render = mem_report.render

        def lossy(s, out=sys.stdout):
            import io

            buf = io.StringIO()
            real_render(s, out=buf)
            out.write(buf.getvalue().replace("-- HBM ledger --", ""))

        monkeypatch.setattr(mem_report, "render", lossy)
        assert mem_report.smoke() == 2

    def test_perf_report_memcost_section(self, scope, tmp_path):
        """perf_report gains a 'Memory & cost' section for instrumented
        runs."""
        log = self._produce_log(scope, tmp_path)
        from tools.perf_report import load_counted, render, summarize_log
        import io

        recs, malformed = load_counted(str(log))
        s = summarize_log(recs, malformed=malformed)
        mc = s["memcost"]
        assert mc["captures"] == 1
        assert mc["programs"] == 1
        assert mc["param_bytes"] > 0
        assert mc["oom_events"] == 1
        assert mc["roofline"]
        buf = io.StringIO()
        render(s, out=buf)
        assert "-- memory & cost" in buf.getvalue()


# -- metric-name drift guard (ISSUE satellite) -------------------------------

_EMIT_RE = re.compile(
    r"(?:counter_add|counter_quiet|counter_set|gauge_set|observe)\(\s*"
    r"f?\"([a-zA-Z0-9_.{}]+)\"")


def _emitted_metric_names():
    """Every cost.*/mem.*/costmodel.*/pallas.*/sharding.*state_bytes*
    metric name the framework emits, scraped from the source (f-string
    placeholders truncate the name at '{' — the renderer must reference
    the static prefix)."""
    names = set()
    roots = [os.path.join(REPO_ROOT, "paddle_tpu"),
             os.path.join(REPO_ROOT, "tools")]
    for root in roots:
        for dirpath, _dirs, files in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, fn)) as f:
                    src = f.read()
                for m in _EMIT_RE.finditer(src):
                    name = m.group(1).split("{", 1)[0]
                    if name.startswith(("cost.", "mem.", "costmodel.",
                                        "pallas.", "incidents.",
                                        "slo.",
                                        "goodput.", "fleet.",
                                        "scaler.", "elastic.",
                                        "kv.", "disagg.",
                                        "orch.", "session.")) or \
                            (name.startswith("sharding.")
                             and ("state_bytes" in name
                                  or "zero_regroup" in name)):
                        names.add(name)
    return names


class TestMetricDriftGuard:
    def test_every_cost_mem_metric_is_rendered(self):
        """No silently-orphaned telemetry: every cost.*/mem.*/
        costmodel.*/pallas.*/incidents.*/slo.*/sharding.*state_bytes*
        metric the code emits must be referenced by perf_report.py or
        mem_report.py."""
        names = _emitted_metric_names()
        # the plane exists: the guard must be looking at real names
        assert "cost.captures" in names
        assert "mem.param_bytes" in names
        assert "costmodel.unavailable" in names
        assert any(n.startswith("mem.serving.bucket") for n in names)
        assert "sharding.optimizer_state_bytes" in names
        # the Pallas serving kernels count every dispatch and fallback
        assert "pallas.int8_gemm_dispatches" in names
        assert "pallas.paged_attn_dispatches" in names
        assert "pallas.int8_gemm_fallbacks" in names
        assert "pallas.paged_attn_fallbacks" in names
        # the incident pipeline + SLO watchdog (core/incidents.py)
        assert "incidents.reported" in names
        assert "incidents.rate_limited" in names
        assert "slo.trips" in names
        assert "slo.evaluations" in names
        # the goodput ledger (core/goodput.py) — badput_<phase> emits
        # via an f-string, so the scraped name is the static prefix
        assert "goodput.productive_ms" in names
        assert "goodput.wall_ms" in names
        assert "goodput.ratio" in names
        assert "goodput.badput_" in names
        # the elastic resize / autoscaling plane (distributed/scaler.py
        # policy engine + distributed/elastic.py runner)
        assert "scaler.evaluations" in names
        assert "scaler.decisions" in names
        assert "scaler.scale_up" in names
        assert "scaler.scale_down" in names
        assert "scaler.suppressed_cooldown" in names
        assert "scaler.clamped" in names
        assert "elastic.restarts" in names
        assert "elastic.scale_events" in names
        assert "elastic.restart_budget_refunds" in names
        assert "incidents.scale_events" in names
        assert "sharding.zero_regroup_events" in names
        # the content-addressed prefix store + disaggregated prefill
        # plane (serving/prefix_store.py + disagg.py)
        assert "kv.prefix_hits" in names
        assert "kv.prefix_misses" in names
        assert "kv.bytes_saved" in names
        assert "kv.cow_forks" in names
        assert "kv.reclaims" in names
        assert "kv.audit_failures" in names
        assert "kv.prefix_blocks" in names
        assert "mem.serving.kv_prefix_saved_bytes" in names
        assert "disagg.ships" in names
        assert "disagg.ship_bytes" in names
        assert "disagg.installs" in names
        assert "disagg.crc_rejects" in names
        assert "disagg.fallback_prefills" in names
        # the process-level crash-survival plane: the launch.py
        # orchestrator and the decode-session failover journal
        # (serving/session.py)
        assert "orch.spawns" in names
        assert "orch.child_deaths" in names
        assert "orch.respawns" in names
        assert "orch.budget_exhausted" in names
        assert "orch.drains" in names
        assert "orch.drain_kills" in names
        assert "orch.scale_events" in names
        assert "orch.restart_budget_refunds" in names
        assert "session.journaled" in names
        assert "session.evicted" in names
        assert "session.resumed" in names
        assert "session.resumed_tokens" in names
        assert "session.journal_errors" in names
        assert "session.failovers" in names
        assert "elastic.drains" in names
        assert "elastic.drain_timeouts" in names
        # the fleet observatory (core/fleetobs.py)
        assert "fleet.scrapes" in names
        assert "fleet.scrape_failures" in names
        assert "fleet.members_went_stale" in names
        assert "fleet.stragglers" in names
        assert "fleet.qps" in names
        renderers = ""
        for tool in ("perf_report.py", "mem_report.py"):
            with open(os.path.join(REPO_ROOT, "tools", tool)) as f:
                renderers += f.read()
        orphaned = sorted(n for n in names if n not in renderers)
        assert not orphaned, \
            f"metrics emitted but rendered nowhere: {orphaned}"
