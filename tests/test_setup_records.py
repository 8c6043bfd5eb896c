"""Set-up records (core/telemetry.py CompileRecord): one a program that the
executor compiles, interprets for the first time or the decode engine
brings to its first run, with its seconds split so that the parts tile the
total; kept past ``telemetry.reset()`` for a reader that runs after the
window a reset opens."""

import io
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import telemetry

PARTS = ("build_s", "trace_s", "lower_s", "compile_s", "cache_read_s",
         "capture_s", "first_run_s")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture(autouse=True)
def _no_records():
    telemetry.configure(None)
    telemetry.reset()
    telemetry.clear_compile_records()
    yield
    telemetry.configure(None)
    telemetry.clear_compile_records()


def _small_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], stop_gradient=True)
        loss = layers.mean(layers.fc(x, 8, act="relu"))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


def _engine():
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    return demo_engine(DecodeConfig(
        max_slots=4, buckets=[2, 4], kv_pages=64, page_size=4,
        max_new_tokens=8, prefill_buckets=[16]))


def check_tiles(record):
    assert record["t0"] <= record["t1"] <= time.perf_counter()
    assert record["total_s"] > 0
    assert all(record[p] >= 0 for p in PARTS)
    assert sum(record[p] for p in PARTS) == pytest.approx(
        record["total_s"], rel=0.02)
    assert record["total_s"] <= record["t1"] - record["t0"] + 1e-9


def test_an_executor_run_leaves_one_record_a_program_and_its_parts_tile(
        scope):
    main, startup, loss = _small_program()
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    x = np.ones((4, 4), np.float32)
    exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
    start, step = telemetry.compile_records()
    assert (start["kind"], step["kind"]) == ("interpreted", "executor")
    assert start["name"] == f"{startup.uid}v{startup.version}"
    assert step["name"] == f"{main.uid}v{main.version}"
    assert start["ops"] == len(startup.global_block().ops)
    assert step["ops"] == len(main.global_block().ops)
    assert start["t1"] <= step["t0"]
    for record in (start, step):
        check_tiles(record)
    # jax traced, lowered and compiled the step inside its first call
    assert step["trace_s"] > 0 and step["lower_s"] > 0
    assert step["compile_s"] > 0 and step["backend_compiles"] == 1
    assert step["build_s"] > 0 and step["capture_s"] == 0
    assert "pallas_kernels" in step
    # a second run of the same key compiles nothing and leaves none
    exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
    assert len(telemetry.compile_records()) == 2
    # a new key (another fetch list) is a new program
    exe.run(main, feed={"x": x}, fetch_list=[], scope=scope)
    assert len(telemetry.compile_records()) == 3


def test_a_loop_over_an_interpreted_program_leaves_one_record(scope):
    main, startup, loss = _small_program()
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    x = np.ones((4, 4), np.float32)
    for _ in range(3):
        exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope,
                use_compiled=False)
    kinds = [r["kind"] for r in telemetry.compile_records()]
    assert kinds == ["interpreted", "interpreted"]
    # the small compiles of an op-by-op run landed in the record's parts
    first = telemetry.compile_records()[1]
    assert first["name"] == f"{main.uid}v{main.version}"
    check_tiles(first)


def test_the_engines_warmup_leaves_a_record_a_program_under_its_name():
    engine = _engine()
    assert engine.warmup() == 3
    records = telemetry.compile_records()
    assert [r["name"] for r in records] == [
        "decode_step_b2", "decode_step_b4", "prefill_p16"]
    for record, key in zip(records, (("step", 2), ("step", 4),
                                     ("prefill", 16))):
        # the name jax gives the program in the profiler's trace
        assert engine._entries[key].__name__ == record["name"]
        assert record["kind"] == "decode" and record["ops"] > 0
        check_tiles(record)
        # the Program's construction is inside the record: a shape
        # inference an op, all of it a part of build_s
        assert 0 < record["infer_shape_s"] <= record["build_s"]
        assert record["infer_shape_calls"] >= record["ops"]
    assert all(a["t1"] <= b["t0"] for a, b in zip(records, records[1:]))
    # a warm engine builds nothing
    assert engine.warmup() == 0
    assert len(telemetry.compile_records()) == 3
    warm = engine.stats()["warmup"]
    assert warm["programs"] == records
    assert warm["total"]["total_s"] == pytest.approx(
        sum(r["total_s"] for r in records), abs=2e-3)
    engine.close()


def test_stats_show_the_warmup_and_the_compile_event_carries_the_record(
        tmp_path):
    from paddle_tpu.serving.server import ServingHTTPServer

    log = tmp_path / "run.jsonl"
    telemetry.configure(str(log))
    engine = _engine().start(warmup=True)
    server = ServingHTTPServer(None, decode_engine=engine).start()
    try:
        stats = json.loads(urllib.request.urlopen(
            server.url + "/v1/stats", timeout=10).read())
    finally:
        server.shutdown()
        engine.close(drain=False, timeout=30)
    programs = stats["decode"]["warmup"]["programs"]
    assert [p["name"] for p in programs] == [
        "decode_step_b2", "decode_step_b4", "prefill_p16"]
    telemetry.flush_sink()
    with open(log) as f:
        events = [r for r in map(json.loads, f) if r["kind"] == "compile"]
    assert len(events) == 3
    for event, program in zip(events, programs):
        attrs = event["attrs"]
        # the keys the event had, and the record's
        assert attrs["cause"] == "decode_bucket" and "bucket" in attrs
        assert attrs["name"] == program["name"]
        assert {k: attrs[k] for k in PARTS} == {
            k: program[k] for k in PARTS}
        # with a sink the cost capture runs, and is its own part
        assert attrs["capture_s"] > 0
        check_tiles(attrs)
    from tools.perf_report import render, summarize_log

    text = io.StringIO()
    render(summarize_log(events), out=text)
    assert "set-up by program (s): 3" in text.getvalue()
    assert "decode_step_b4" in text.getvalue()


def test_reset_keeps_the_records_and_clear_drops_them():
    with telemetry.CompileRecord("executor", "a") as record:
        record.close()
    telemetry.note_infer_shape(0.25)
    seconds, calls = telemetry.infer_shape_totals()
    telemetry.reset()
    assert [r["name"] for r in telemetry.compile_records()] == ["a"]
    assert telemetry.infer_shape_totals() == (seconds, calls)
    telemetry.clear_compile_records()
    assert telemetry.compile_records() == []
    assert telemetry.infer_shape_totals() == (seconds, calls)


def test_append_op_times_its_shape_inference_into_the_open_record():
    before = telemetry.infer_shape_totals()
    with telemetry.CompileRecord("decode", "built") as record:
        with record.phase("build_s"):
            _small_program()
        doc = record.close()
    seconds, calls = telemetry.infer_shape_totals()
    assert calls - before[1] == doc["infer_shape_calls"] > 0
    assert seconds - before[0] == pytest.approx(doc["infer_shape_s"])
    # jax traces for a shape are the build's own seconds, not trace_s
    assert doc["trace_s"] == 0 and doc["infer_shape_s"] <= doc["build_s"]
    check_tiles(doc)
    # with no record open the process's pair still counts
    _small_program()
    assert telemetry.infer_shape_totals()[1] > calls
    assert len(telemetry.compile_records()) == 1


def test_a_duration_on_a_thread_with_no_record_open_is_dropped():
    import jax.monitoring

    def elsewhere():
        jax.monitoring.record_event_duration_secs(TRACE, 5.0)
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")

    with telemetry.CompileRecord("executor", "mine") as record:
        other = threading.Thread(target=elsewhere)
        other.start()
        other.join()
        time.sleep(0.02)
        jax.monitoring.record_event_duration_secs(TRACE, 0.01)
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        doc = record.close()
    assert doc["trace_s"] == pytest.approx(0.01)
    assert doc["cache_hit"] is True
    # and with no record open anywhere
    jax.monitoring.record_event_duration_secs(TRACE, 5.0)
    assert [r["name"] for r in telemetry.compile_records()] == ["mine"]


def test_nested_durations_are_stored_as_disjoint_parts():
    import jax.monitoring

    say = jax.monitoring.record_event_duration_secs
    with telemetry.CompileRecord("executor", "nests") as record:
        time.sleep(0.03)
        say(TRACE, 0.01)            # a jitted function inside the trace
        time.sleep(0.01)
        say(TRACE, 0.04)            # the trace that held it
        time.sleep(0.03)
        say(RETRIEVAL, 0.02)
        say(BACKEND, 0.03)          # taken around the retrieval
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        doc = record.close()
    assert doc["trace_s"] == pytest.approx(0.04, abs=1e-3)
    assert doc["cache_read_s"] == pytest.approx(0.02, abs=1e-3)
    assert doc["compile_s"] == pytest.approx(0.01, abs=1e-3)
    assert doc["backend_compiles"] == 1
    assert doc["cache_hit"] is False      # one program of two missed
    check_tiles(doc)


def test_a_record_inside_another_takes_the_seconds():
    import jax.monitoring

    with telemetry.CompileRecord("interpreted", "outer") as outer:
        with outer.phase("build_s"):
            time.sleep(0.01)
            with telemetry.CompileRecord("executor", "inner") as inner:
                time.sleep(0.02)
                jax.monitoring.record_event_duration_secs(TRACE, 0.01)
                inner_doc = inner.close()
        outer_doc = outer.close()
    assert [r["name"] for r in telemetry.compile_records()] == [
        "inner", "outer"]
    assert inner_doc["trace_s"] == pytest.approx(0.01)
    assert outer_doc["trace_s"] == 0
    # the outer's total and its build leave the inner's seconds out
    wall = outer_doc["t1"] - outer_doc["t0"]
    assert outer_doc["total_s"] == pytest.approx(
        wall - (inner_doc["t1"] - inner_doc["t0"]))
    assert outer_doc["build_s"] < outer_doc["total_s"] + 1e-9
    assert outer_doc["total_s"] + inner_doc["total_s"] <= wall + 1e-9
    for doc in (inner_doc, outer_doc):
        check_tiles(doc)


def test_a_program_that_fails_leaves_no_record_and_no_record_open():
    with pytest.raises(RuntimeError):
        with telemetry.CompileRecord("executor", "fails"):
            raise RuntimeError("no such program")
    assert telemetry.compile_records() == []
    with telemetry.CompileRecord("executor", "next") as record:
        doc = record.close()
    assert doc["total_s"] == pytest.approx(doc["t1"] - doc["t0"])


def test_the_list_keeps_the_newest_512():
    for i in range(520):
        with telemetry.CompileRecord("executor", f"p{i}") as record:
            record.close()
    names = [r["name"] for r in telemetry.compile_records()]
    assert len(names) == 512 and names[0] == "p8" and names[-1] == "p519"
