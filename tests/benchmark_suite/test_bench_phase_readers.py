"""The readers of the engine's and the executor's phase timers, and the
split of the device's idle time by the program's own spans."""

import math
import time

import pytest

from benchmark import run, trace_reduce
from benchmark.manifest import Manifest
from benchmark.readers import _idle_split
from benchmark.runners import result

from . import toy

REAL = Manifest(toy.REPO)
SERVE_TIMERS = {                      # metric -> (histogram, field)
    "engine_loop_ms_p50": ("decode.loop_ms", "p50"),
    "engine_feed_ms_p50": ("decode.feed_ms", "p50"),
    "engine_fetch_ms_p50": ("decode.fetch_ms", "p50"),
    "engine_sample_ms_p50": ("decode.sample_ms", "p50"),
    "engine_other_ms_p50": ("decode.other_ms", "p50"),
    "token_gap_p99_ms": ("decode.token_gap_ms", "p99"),
}
TRAIN_TIMERS = {
    "exe_feed_ms_p50.train": "executor.feed_ms",
    "exe_state_ms_p50.train": "executor.state_ms",
    "exe_call_ms_p50.train": "executor.call_ms",
}
IDLE = ("idle_in_sample_share.serve", "idle_in_fetch_share.serve",
        "idle_unattributed_share.serve")
NEW = tuple(SERVE_TIMERS) + IDLE + tuple(TRAIN_TIMERS)


def test_the_manifest_is_sound_with_the_twelve_entries():
    assert REAL.problems() == []
    for name in NEW:
        entry = toy.entry(REAL, "per_layer", name)
        kind, = {REAL.config_doc(REAL.cell(cell)["config"])["kind"]
                 for cell in entry["workloads"]}
        assert kind == ("train" if name.endswith(".train") else "serve")
        assert entry["moves"] == (
            "train_tokens_per_s" if kind == "train" else "tpot_p90_ms")


@pytest.mark.parametrize("metric", sorted(SERVE_TIMERS))
def test_a_serving_timer_reader_reads_its_histogram(metric):
    name, field = SERVE_TIMERS[metric]
    read = REAL.reader(metric)
    summary = {"count": 3, "p50": 1.25, "p99": 7.5}
    ctx = result(kind="serve", telemetry={"hists": {name: summary}})
    assert read(ctx) == summary[field]
    # the parent commit has no such histogram; a training run no telemetry
    assert read(result(kind="serve", telemetry={"hists": {}})) is None
    assert read(result(kind="serve", telemetry={
        "hists": {name: dict(summary, count=0)}})) is None
    assert read(result(kind="train")) is None


@pytest.mark.parametrize("metric", sorted(TRAIN_TIMERS))
def test_a_training_timer_reader_reads_the_programs_registry(metric):
    from paddle_tpu.core import telemetry

    name = TRAIN_TIMERS[metric]
    read = REAL.reader(metric)
    saved = telemetry.TelemetryRegistry.instance()._hists.pop(name, None)
    try:
        assert read(result(kind="train")) is None       # the parent commit
        for ms in (2.0, 4.0, 9.0):
            telemetry.observe(name, ms, kind="timer")
        assert read(result(kind="train")) == 4.0
        assert read(result(kind="serve")) is None
    finally:
        hists = telemetry.TelemetryRegistry.instance()._hists
        hists.pop(name, None)
        if saved is not None:
            hists[name] = saved


# -- the split ---------------------------------------------------------------

def test_innermost_names_every_instant_for_the_span_that_opened_last():
    spans = [("decode.loop_ms", 0, 100), ("decode.step_ms", 10, 60),
             ("decode.fetch_ms", 40, 60), ("decode.sample_ms", 60, 90)]
    assert _idle_split.innermost(spans, -20, 120) == [
        (-20, 0, None), (0, 10, "decode.loop_ms"),
        (10, 40, "decode.step_ms"), (40, 60, "decode.fetch_ms"),
        (60, 90, "decode.sample_ms"), (90, 100, "decode.loop_ms"),
        (100, 120, None)]
    # a span cut by the window's edge still names its part inside
    assert _idle_split.innermost([("decode.loop_ms", -5, 7)], 0, 10) == [
        (0, 7, "decode.loop_ms"), (7, 10, None)]


def test_a_gap_is_split_among_the_spans_it_crosses():
    spans = [("decode.loop_ms", 0, 100), ("decode.step_ms", 10, 60),
             ("decode.fetch_ms", 40, 60), ("decode.sample_ms", 60, 90)]
    # the device runs 10-50, so one gap crosses fetch, sample and the loop's
    # tail, another lies before the step, and 100-120 is under no span
    device = {"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.2", 30, 50)]}
    shares = _idle_split.idle_shares(device, spans, (0, 120))
    assert shares == pytest.approx({
        "decode.loop_ms": 100 * 20 / 120, "decode.fetch_ms": 100 * 10 / 120,
        "decode.sample_ms": 100 * 30 / 120,
        _idle_split.NO_SPAN: 100 * 20 / 120})
    reduced = trace_reduce.reduce_events(device, [], window=(0, 120))
    assert sum(shares.values()) == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))


def test_two_device_planes_are_averaged_and_add_up_to_the_idle_share():
    spans = [("executor.state_ms", 0, 40), ("executor.call_ms", 40, 80)]
    device = {"/device:TPU:0": [("a", 0, 80)],            # idle 80-100
              "/device:TPU:1": [("a", 20, 40), ("b", 60, 100)]}
    shares = _idle_split.idle_shares(device, spans, (0, 100))
    assert shares == pytest.approx({
        "executor.state_ms": 10.0, "executor.call_ms": 10.0,
        _idle_split.NO_SPAN: 10.0})
    reduced = trace_reduce.reduce_events(device, [], window=(0, 100))
    assert sum(shares.values()) == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))


def test_split_walks_both_lists_once():
    idle = [(0, 5), (8, 30), (40, 41)]
    segments = [(0, 10, "a"), (10, 20, None), (20, 50, "b")]
    assert _idle_split.split(idle, segments) == {"a": 7, None: 10, "b": 11}


# -- finding the run's trace -------------------------------------------------

def _trace_a_window(trace_dir, *annotations):
    """A real CPU trace with a bench.window span around the given program
    spans; returns the window's length as trace_reduce reports it."""
    import jax
    import jax.numpy as jnp

    trace_reduce.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        jnp.ones((64, 64)).sum().block_until_ready()
        for name in annotations:
            with jax.profiler.TraceAnnotation(name):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return trace_reduce.reduce_trace(trace_dir, "host", "cpu")


def _ctx(trace, kind="serve"):
    return result(kind=kind, trace=trace,
                  device={"platform": "cpu", "kind": "cpu", "count": 1})


def test_the_idle_readers_find_the_runs_own_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path))
    mine = _trace_a_window(str(tmp_path / "cell_a" / "trace"),
                           "decode.sample_ms", "decode.fetch_ms")
    # a newer trace of another cell, as another test worker would leave it
    other = _trace_a_window(str(tmp_path / "cell_b" / "trace"),
                            "decode.sample_ms")
    assert other["window_s"] != mine["window_s"]
    values = {m: REAL.reader(m)(_ctx(mine)) for m in IDLE}
    assert all(v is not None and v >= 0 for v in values.values())
    assert values["idle_in_sample_share.serve"] > 0
    assert values["idle_in_fetch_share.serve"] > 0
    shares = _idle_split._shares_of_run("cpu", mine["window_s"])
    assert sum(shares.values()) == pytest.approx(
        100 * (1 - mine["busy_s"] / mine["window_s"]), abs=1e-6)
    # in the other cell's trace the device never idled under a fetch span
    assert REAL.reader("idle_in_fetch_share.serve")(_ctx(other)) == 0.0


@pytest.mark.parametrize("metric", IDLE)
def test_an_idle_reader_returns_nothing_without_spans_or_a_trace(
        metric, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path))
    read = REAL.reader(metric)
    assert read(result(kind="serve")) is None                  # untraced
    assert read(_ctx({"window_s": 1.5, "busy_s": 1.0})) is None  # no file
    # the parent commit: a trace with the window and no program span
    bare = _trace_a_window(str(tmp_path / "cell_p" / "trace"))
    assert read(_ctx(bare)) is None
    assert read(_ctx(bare, kind="train")) is None


# -- the CPU rehearsal -------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root with cell names of this file's own: the work directory
    is <checkout>/.bench_work/<cell>, and test_bench_runners.py may be
    running toy_open on another worker."""
    root = toy.keep_cells(
        toy.make_root(str(tmp_path_factory.mktemp("phase_root"))),
        {"toy_train": "phase_train", "toy_closed": "phase_closed"})
    assert Manifest(root).problems() == []
    return root


def test_the_serving_rehearsal_reports_all_nine_and_they_add_up(root):
    out = run.run_cell(root, "phase_closed", seed=2 ** 31 + 5, seconds=2.0,
                       trace=True, require_platform=None)
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in tuple(SERVE_TIMERS) + IDLE:
        assert math.isfinite(got[name]), name
    assert got["engine_fetch_ms_p50"] <= got["decode_step_ms_p50"]
    assert got["engine_loop_ms_p50"] >= got["decode_step_ms_p50"]
    shares = _idle_split._shares_of_run("cpu", out["device"]["window_s"])
    assert sum(shares.values()) == pytest.approx(
        got["device_idle_share.serve"], abs=1e-6)
    assert shares["decode.sample_ms"] == got["idle_in_sample_share.serve"]
    # PR 24's eight spans, and room for any the engine opens since: the
    # device idles under a span of the program's or under none
    assert {"decode." + p for p in (
        "loop_ms", "admit_ms", "prefill_ms", "feed_ms", "step_ms",
        "fetch_ms", "sample_ms", "retire_ms")} <= set(shares)
    assert all(name == _idle_split.NO_SPAN or name.startswith("decode.")
               for name in shares)


def test_the_training_rehearsal_reports_the_executors_phases(root):
    out = run.run_cell(root, "phase_train", seed=7, seconds=1.0, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    for name in TRAIN_TIMERS:
        assert math.isfinite(out["metrics"][name]["value"]), name
    assert not set(out["metrics"]) & (set(SERVE_TIMERS) | set(IDLE))
