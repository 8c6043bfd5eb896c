"""`telemetry.timer` as a profiler annotation, and the two hot loops it
splits into phases: `DecodeEngine._loop` and `Executor.run`.

* a timer's region is in the `.xplane.pb` of a running jax profiler trace
  under the histogram's name, as long as the histogram's sample;
* a process that never imports jax does not start to because it timed
  something;
* every decode iteration that runs a step records phases that add up to
  it exactly; tokens have gaps, requests have a queue wait, and a batch
  answers what each request gets alone;
* a steady-state `Executor.run` fills its five phase histograms once, a
  run that compiles fills none;
* `/v1/generate` answers with each token's time since submit.
"""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_PARTS = ("decode.admit_ms", "decode.feed_ms", "decode.step_ms",
              "decode.sample_ms", "decode.retire_ms", "decode.other_ms")
EXECUTOR_PHASES = ("executor.feed_ms", "executor.state_ms",
                   "executor.call_ms", "executor.book_ms",
                   "executor.writeback_ms")


def _samples(name):
    from paddle_tpu.core import telemetry

    h = telemetry.TelemetryRegistry.instance()._hists.get(name)
    return list(h.samples) if h else []


def test_a_timer_is_an_annotation_of_the_same_name_in_a_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from paddle_tpu.core import telemetry

    telemetry.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.timer("phase_test.sleep_ms"):
            time.sleep(0.05)
        into = {}
        with telemetry.timer("phase_test.deferred_ms", into=into):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {ev.name: ev.duration_ns / 1e6
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("phase_test.")}
    sample, = _samples("phase_test.sleep_ms")
    assert sample >= 50.0
    assert abs(found["phase_test.sleep_ms"] - sample) <= 0.2 * sample
    # a deferred timer is in the trace too, and in no histogram until the
    # caller observes it
    assert abs(found["phase_test.deferred_ms"]
               - into["phase_test.deferred_ms"]) <= 0.2 * 10.0
    assert _samples("phase_test.deferred_ms") == []


def test_a_deferred_timer_adds_up_under_one_name():
    from paddle_tpu.core import telemetry

    into = {}
    for _ in range(2):
        with telemetry.timer("phase_test.twice_ms", into=into):
            time.sleep(0.005)
    assert into["phase_test.twice_ms"] >= 10.0


def test_a_quiet_observation_fills_the_histogram_and_no_record(tmp_path):
    from paddle_tpu.core import telemetry

    log = tmp_path / "run.jsonl"
    telemetry.configure(str(log))
    try:
        telemetry.observe("phase_test.loud_ms", 1.5, kind="timer")
        telemetry.observe_quiet("phase_test.quiet_ms", 2.5)
        telemetry.flush_sink()
    finally:
        telemetry.configure(None)
    names = [json.loads(line)["name"] for line in log.read_text().splitlines()]
    assert "phase_test.loud_ms" in names
    assert "phase_test.quiet_ms" not in names
    hists = telemetry.snapshot()["hists"]
    assert hists["phase_test.quiet_ms"]["count"] == 1
    assert hists["phase_test.quiet_ms"]["max"] == 2.5
    window = telemetry.windowed()["hists"]
    assert "phase_test.loud_ms" in window
    assert "phase_test.quiet_ms" not in window    # nothing for it to sort
    assert "pt_phase_test_quiet_ms_count 1" in telemetry.prometheus_text()


def test_timing_does_not_import_jax():
    """The telemetry module alone (a pserver, a report tool), loaded the way
    such a process would if the package did not import jax for it."""
    child = r'''
import importlib, os, sys, types
for name, path in (("paddle_tpu", "paddle_tpu"),
                   ("paddle_tpu.core", "paddle_tpu/core")):
    mod = types.ModuleType(name)
    mod.__path__ = [os.path.join(sys.argv[1], path)]
    sys.modules[name] = mod
telemetry = importlib.import_module("paddle_tpu.core.telemetry")
with telemetry.timer("child.timed_ms"):
    pass
assert telemetry.snapshot()["hists"]["child.timed_ms"]["count"] == 1
assert "jax" not in sys.modules, sorted(m for m in sys.modules if "jax" in m)
print("NO_JAX_OK")
'''
    r = subprocess.run([sys.executable, "-c", child, REPO],
                       capture_output=True, text=True, timeout=120)
    assert "NO_JAX_OK" in r.stdout, (r.stdout[-500:], r.stderr[-1500:])


@pytest.fixture(scope="module")
def seeded_run():
    """Ten seeded, sampled requests through a four-slot toy engine, with the
    registry cleared first: (requests, their tokens, snapshot)."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    config = dict(max_slots=4, kv_pages=64, page_size=4, max_new_tokens=16)
    rng = np.random.RandomState(0)
    asks = [(rng.randint(1, 50, rng.randint(3, 12)).astype(np.int32),
             int(rng.randint(2, 10))) for _ in range(10)]

    def generate(engine, which):
        reqs = [engine.submit(asks[i][0], max_new_tokens=asks[i][1],
                              temperature=0.8, seed=100 + i,
                              stop_at_eos=False) for i in which]
        return reqs, [r.result(60) for r in reqs]

    engine = demo_engine(DecodeConfig(**config)).start(warmup=True)
    try:
        telemetry.reset()
        reqs, tokens = generate(engine, range(10))
    finally:
        engine.close()
    snap = telemetry.snapshot()
    samples = {name: _samples(name) for name in snap["hists"]}
    # the same requests one at a time: nothing else in the slot array
    alone = demo_engine(DecodeConfig(**config)).start(warmup=True)
    try:
        one_by_one = [generate(alone, [i])[1][0] for i in range(10)]
    finally:
        alone.close()
    return reqs, tokens, one_by_one, snap, samples


def test_each_stepping_iteration_records_phases_that_add_up(seeded_run):
    _, _, _, snap, samples = seeded_run
    steps = snap["counters"]["decode.steps"]
    assert steps > 0
    for name in ("decode.loop_ms", "decode.fetch_ms") + LOOP_PARTS:
        assert snap["hists"][name]["count"] == steps, name
    for i, loop in enumerate(samples["decode.loop_ms"]):
        parts = sum(samples[name][i] for name in LOOP_PARTS)
        assert abs(parts - loop) < 1e-6, (i, parts, loop)
        assert samples["decode.fetch_ms"][i] <= samples["decode.step_ms"][i]


def test_tokens_have_gaps_and_requests_a_queue_wait(seeded_run):
    reqs, tokens, _, snap, samples = seeded_run
    n_tokens = sum(len(t) for t in tokens)
    assert snap["hists"]["decode.token_gap_ms"]["count"] \
        == n_tokens - len(reqs)
    assert snap["hists"]["decode.queue_wait_ms"]["count"] == len(reqs)
    assert min(samples["decode.token_gap_ms"]) >= 0.0
    assert min(samples["decode.queue_wait_ms"]) >= 0.0


def test_a_batch_answers_what_each_request_gets_alone(seeded_run):
    """A row's token is a function of its logits and its request's own RNG:
    the phases around sampling and retiring change no token."""
    _, tokens, one_by_one, _, _ = seeded_run
    for got, want in zip(tokens, one_by_one):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_executor_run_fills_its_phases_in_steady_state_only():
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import telemetry

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [16])
        y = layers.data("y", [1], dtype="int64")
        logits = layers.fc(layers.fc(x, 32, act="relu"), 4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    feed = {"x": np.random.RandomState(0).randn(8, 16).astype(np.float32),
            "y": np.zeros((8, 1), np.int64)}
    telemetry.reset()

    def counts():
        hists = telemetry.snapshot()["hists"]
        return [hists.get(name, {"count": 0})["count"]
                for name in EXECUTOR_PHASES]

    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)   # compiles
    assert counts() == [0] * 5
    assert telemetry.counter_get("executor.compiles") == 1
    for n in (1, 2, 3):
        t0 = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        wall_ms = (time.perf_counter() - t0) * 1e3
        assert counts() == [n] * 5
    hists = telemetry.snapshot()["hists"]
    assert "executor.device_ms" not in hists
    assert "executor.host_dispatch_ms" not in hists
    assert hists["executor.run_ms"]["count"] == 3
    # the phases lie inside the call they split
    assert sum(_samples(name)[-1] for name in EXECUTOR_PHASES) <= wall_ms


def test_generate_answers_with_each_tokens_time_since_submit():
    from paddle_tpu.serving import ServingHTTPServer
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    engine = demo_engine(DecodeConfig(
        max_slots=2, kv_pages=32, page_size=4)).start(warmup=True)
    server = ServingHTTPServer(None, decode_engine=engine).start()
    try:
        body = json.dumps({"prompt_ids": [5, 6, 7, 8], "max_new_tokens": 6,
                           "stop_at_eos": False}).encode()
        doc = json.loads(urllib.request.urlopen(urllib.request.Request(
            server.url + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"}),
            timeout=60).read())
    finally:
        server.shutdown()
        engine.close()
    assert len(doc["token_ms"]) == doc["num_tokens"] == 6
    assert doc["token_ms"][0] == doc["ttft_ms"]
    assert doc["token_ms"] == sorted(doc["token_ms"])
    assert doc["token_ms"][-1] <= doc["latency_ms"]


# -- the hot paths know telemetry and nothing built on it -------------------

PLANES_ABOVE = ("paddle_tpu.core.trace", "paddle_tpu.core.incidents",
                "paddle_tpu.core.goodput", "paddle_tpu.profiler")


def _imports(path, package):
    """(absolute module name, enclosing function or None) of every import
    in the file, at module or function level."""
    import ast

    tree = ast.parse(open(path).read())
    found = []

    def visit(node, func):
        for child in ast.iter_child_nodes(node):
            inside = child.name if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else func
            if isinstance(child, ast.Import):
                found.extend((a.name, func) for a in child.names)
            elif isinstance(child, ast.ImportFrom):
                base = package.split(".")
                base = base[:len(base) - (child.level - 1)] \
                    if child.level else []
                mod = ".".join(base + ([child.module] if child.module
                                       else []))
                found.append((mod, func))
                # `from . import a, b` names modules, not attributes
                found.extend((f"{mod}.{a.name}", func) for a in child.names)
            visit(child, inside)

    visit(tree, None)
    return found


@pytest.mark.parametrize("rel,package", [
    ("paddle_tpu/core/executor.py", "paddle_tpu.core"),
    ("paddle_tpu/serving/decode.py", "paddle_tpu.serving")])
def test_the_hot_paths_import_none_of_the_planes_built_on_them(rel, package):
    """core/executor.py and serving/decode.py reach trace spans, profiler
    events, the SLO watchdog and the goodput ledger through telemetry's
    timer(span=) and tick(), never by name. run_op's per-op RecordEvent on
    the interpreted path is the reference's profiler capability and keeps
    its import."""
    found = _imports(os.path.join(REPO, rel), package)
    assert ("paddle_tpu.core.telemetry", None) in found
    banned = [(mod, func) for mod, func in found
              if mod in PLANES_ABOVE and func != "run_op"]
    assert banned == []
    text = open(os.path.join(REPO, rel)).read()
    for needle in ("trace.span(", "trace.record(", "trace.current(",
                   'RecordEvent("executor', "incidents.", "goodput.tick"):
        assert needle not in text


@pytest.mark.parametrize("armed", [True, False])
def test_one_tick_a_step_reaches_whoever_subscribed(armed):
    """An armed watchdog rule set and an open goodput window see an
    executor step and a decode step through telemetry.tick(); in a
    process that armed nothing, a step adds no record of either."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import goodput, incidents, telemetry
    from paddle_tpu.core.flags import flag, set_flags
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    before = {k: flag(k) for k in ("slo_eval_s", "goodput_publish_s")}
    telemetry.reset()
    incidents.reset()
    goodput.reset()
    set_flags({"slo_eval_s": 0.0, "goodput_publish_s": 0.0})
    try:
        if armed:
            incidents.arm()
            goodput.start_run()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4], stop_gradient=True)
            loss = layers.mean(layers.fc(x, 8))
        scope, exe = pt.Scope(), pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        xv = np.ones((2, 4), np.float32)
        for _ in range(3):          # one run that compiles, two that do not
            exe.run(main, feed={"x": xv}, fetch_list=[loss], scope=scope)
        after_exe = telemetry.counter_get("slo.evaluations")
        assert ("goodput.ratio" in telemetry.gauges()) == armed
        assert (after_exe >= 3) == armed and (after_exe == 0) != armed

        engine = demo_engine(DecodeConfig(
            max_slots=2, kv_pages=32, page_size=4)).start(warmup=True)
        try:
            engine.generate([5, 6, 7], max_new_tokens=4, stop_at_eos=False,
                            timeout=60)
        finally:
            engine.close()
        after_decode = telemetry.counter_get("slo.evaluations")
        assert (after_decode > after_exe) == armed
        assert (after_decode == 0) != armed
    finally:
        incidents.reset()
        goodput.reset()
        set_flags(before)
