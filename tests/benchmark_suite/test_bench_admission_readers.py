"""The five readers of what an admission costs the decode engine: the wait
every slot stands still for a prefill, the padded tokens a prefill computes,
the device's idle time between two prefills, the engine thread's CPU time
and the wall time of its loop that neither it nor a wait for the device
explains.

BENCHMARK.json lists them since PR 42, at the end of `per_layer` as it then
stood, over the four served cells: every engine observes what they read.
The readers are held here against hand-made results and through the harness
on the toy root, whose metric lists are the repo's own."""

import math
import time

import pytest

from benchmark import run, trace_reduce
from benchmark.manifest import Manifest
from benchmark.readers import _idle_split
from benchmark.runners import result

from . import toy

REAL = Manifest(toy.REPO)
CELLS = ["xglm_1p7b_serve_closed_c16",
         "trinity_large_tp8ep8_serve_closed_c96",
         "kimi_k2_dp_ep32_serve_closed_c96",
         "falcon_h1_34b_pp12_serve_closed_c96"]
SOURCES = {                                     # metric -> source
    "prefill_wait_share.serve": "program_span",
    "prefill_padded_token_share.serve": "program_counter",
    "idle_between_prefills_share.serve": "device_trace",
    "engine_cpu_share.serve": "program_span",
    "engine_wait_unexplained_share.serve": "program_span",
}
WINDOW_SHARES = {                               # metric -> histogram
    "prefill_wait_share.serve": "decode.prefill_wait_ms",
    "engine_cpu_share.serve": "decode.cpu_ms",
}
ENTRIES = [{"name": name, "unit": "%", "better": "lower", "source": source,
            "layer": "decode engine", "moves": "serve_tokens_per_s"}
           for name, source in SOURCES.items()]        # less `workloads`


def _hist(total, count=4):
    return {"count": count, "total": total, "avg": total / max(count, 1)}


def _serve(hists=None, counters=None, window_s=50.0, **fields):
    return result(kind="serve", window_s=window_s, telemetry={
        "hists": hists or {}, "counters": counters or {}}, **fields)


def holds(man):
    for wanted in ENTRIES:
        entry = toy.entry(man, "per_layer", wanted["name"])
        assert {k: v for k, v in entry.items() if k != "workloads"} == wanted
        assert set(CELLS) <= set(entry["workloads"])
        assert callable(man.reader(entry["name"]))
        for cell in entry["workloads"]:
            assert man.config_doc(man.cell(cell)["config"])["kind"] == "serve"
            assert "serve_tokens_per_s" in toy.reported(man, cell)


def test_the_real_manifest_lists_the_five_over_the_served_cells():
    assert REAL.problems() == []
    holds(REAL)


@pytest.mark.parametrize("metric", sorted(WINDOW_SHARES))
def test_a_window_share_is_the_histograms_sum_over_the_window(metric):
    read, name = REAL.reader(metric), WINDOW_SHARES[metric]
    # 12.5 s of a 50 s window
    assert read(_serve({name: _hist(12_500.0)})) == pytest.approx(25.0)
    assert read(_serve({name: _hist(0.0)})) == 0.0
    # the parent commit has no such histogram; a training run no telemetry
    assert read(_serve({})) is None
    assert read(_serve({name: _hist(0.0, count=0)})) is None
    assert read(_serve({name: _hist(1.0)}, window_s=None)) is None
    assert read(result(kind="train", window_s=50.0)) is None
    assert read(result(kind="train", window_s=50.0, telemetry={
        "hists": {name: _hist(5.0)}, "counters": {}})) is None


def test_the_wait_share_reads_under_the_whole_prefill_spans_share():
    hists = {"decode.prefill_ms": _hist(33_000.0, count=300),
             "decode.prefill_wait_ms": _hist(27_500.0, count=300)}
    whole = REAL.reader("prefill_time_share.serve")(_serve(hists))
    wait = REAL.reader("prefill_wait_share.serve")(_serve(hists))
    assert (whole, wait) == (pytest.approx(66.0), pytest.approx(55.0))


def test_the_padded_share_is_one_less_asked_over_computed():
    read = REAL.reader("prefill_padded_token_share.serve")
    counters = {"decode.prefill_tokens": 3_000,
                "decode.prefill_bucket_tokens": 4_096}
    assert read(_serve(counters=counters)) == pytest.approx(
        100.0 * 1_096 / 4_096)
    assert read(_serve(counters={"decode.prefill_tokens": 512,
                                 "decode.prefill_bucket_tokens": 512})) == 0.0
    # the parent commit counts the prompts' tokens alone
    assert read(_serve(counters={"decode.prefill_tokens": 3_000})) is None
    assert read(_serve(counters={"decode.prefill_bucket_tokens": 0,
                                 "decode.prefill_tokens": 0})) is None
    assert read(result(kind="train")) is None
    assert read(result(kind="train", telemetry={
        "hists": {}, "counters": counters})) is None


def test_the_unexplained_share_is_loop_less_cpu_less_the_two_waits():
    read = REAL.reader("engine_wait_unexplained_share.serve")
    hists = {"decode.loop_ms": _hist(50_000.0),
             "decode.cpu_ms": _hist(10_000.0),
             "decode.fetch_ms": _hist(20_000.0),
             "decode.prefill_wait_ms": _hist(15_000.0)}
    assert read(_serve(hists)) == pytest.approx(10.0)
    # nothing is clamped: CPU used inside a wait reads below zero
    spin = dict(hists, **{"decode.cpu_ms": _hist(17_500.0)})
    assert read(_serve(spin)) == pytest.approx(-5.0)
    for missing in hists:                           # the parent commit
        assert read(_serve({k: v for k, v in hists.items()
                            if k != missing})) is None
    assert read(result(kind="train", window_s=50.0)) is None


# -- the idle share between two prefills -------------------------------------

def _trace_a_window(trace_dir, *annotations):
    """A real CPU trace with a bench.window span around the given program
    spans (name, stats), as test_bench_phase_readers.py makes one."""
    import jax
    import jax.numpy as jnp

    trace_reduce.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        jnp.ones((64, 64)).sum().block_until_ready()
        for name, stats in annotations:
            with jax.profiler.TraceAnnotation(name, **stats):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return trace_reduce.reduce_trace(trace_dir, "host", "cpu")


def _traced(trace, hists):
    return _serve(hists, trace=trace,
                  device={"platform": "cpu", "kind": "cpu", "count": 1})


def test_the_idle_between_prefills_is_the_idle_under_the_admissions_spans(
        tmp_path, monkeypatch):
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path))
    read = REAL.reader("idle_between_prefills_share.serve")
    seat = {"decode.seat_ms": _hist(2.0)}
    trace = _trace_a_window(
        str(tmp_path / "cell_a" / "trace"),
        ("decode.admit_ms", dict(part="prefill_feed", rid=7)),
        ("decode.prefill_ms", dict(rid=7, bucket=16, tokens=9)),
        ("decode.admit_ms", dict(part="seat", rid=7)))
    value = read(_traced(trace, seat))
    shares = _idle_split._shares_of_run("cpu", trace["window_s"])
    # a span's stats leave its name alone: both parts are one entry
    assert value == shares["decode.admit_ms"] > 0
    assert shares["decode.prefill_ms"] > 0
    assert value >= 100.0 * 2 * 0.02 / trace["window_s"] * 0.9
    # the parent commit: the same spans in the trace and no part observed
    assert read(_traced(trace, {})) is None
    assert read(_serve(seat)) is None                          # untraced
    assert read(result(kind="train", trace=trace, telemetry={
        "hists": seat, "counters": {}},
        device={"platform": "cpu", "kind": "cpu", "count": 1})) is None
    # the device never idled in an admission: zero, not nothing
    other = _trace_a_window(str(tmp_path / "cell_b" / "trace"),
                            ("decode.sample_ms", {}))
    assert read(_traced(other, seat)) == 0.0


# -- the CPU rehearsal -------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toy.keep_cells(
        toy.make_root(str(tmp_path_factory.mktemp("admission_root"))),
        {"toy_closed": "admission_closed"})
    assert Manifest(root).problems() == []
    return root


def test_the_serving_rehearsal_reports_all_five(root):
    out = run.run_cell(root, "admission_closed", seed=2 ** 31 + 40,
                       seconds=2.0, trace=True, require_platform=None)
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in SOURCES:
        assert math.isfinite(got[name]), name
    assert 0 < got["prefill_wait_share.serve"] \
        <= got["prefill_time_share.serve"]
    # the toy ladder is 16, 32 over prompts of 3-30 tokens
    assert 0 < got["prefill_padded_token_share.serve"] < 100
    assert 0 < got["engine_cpu_share.serve"] < 120
    shares = _idle_split._shares_of_run("cpu", out["device"]["window_s"])
    assert got["idle_between_prefills_share.serve"] \
        == shares.get("decode.admit_ms", 0.0)
    assert got["engine_wait_unexplained_share.serve"] < 100
