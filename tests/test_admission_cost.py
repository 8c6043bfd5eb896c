"""What an admission costs, as the decode engine records it
(paddle_tpu/serving/decode.py):

* one ``decode.prefill_wait_ms`` observation a prefill, on the whole-prompt
  and on the chunked path, never above that prefill's ``decode.prefill_ms``;
* ``decode.prefill_bucket_tokens`` is the tokens the prefill programs
  computed (the buckets chosen; the chunks run x the chunk) beside
  ``decode.prefill_tokens``, the tokens asked for;
* ``decode.prefill_feed_ms`` and ``decode.seat_ms`` are observed once a
  prefill, also for the second request of one poll (the ``_drain`` path),
  and ``decode.loop_ms`` is still the sum of its six phases;
* ``decode.cpu_ms`` is recorded once an accepted step and is at most
  ``decode.loop_ms``;
* in a profiler trace a request's spans carry one ``rid``, two requests
  two, a prefill's its ``bucket`` and ``tokens``, a step's none;
* ``stats()`` and tools/perf_report.py show the shares, and
  ``tokens_device_sampled`` is still the tokens the step program chose.
"""

import glob
import io

import numpy as np
import pytest

pytestmark = pytest.mark.serving

LADDER = [8, 16, 32]
LOOP_PARTS = ("decode.admit_ms", "decode.feed_ms", "decode.step_ms",
              "decode.sample_ms", "decode.retire_ms", "decode.other_ms")
PER_PREFILL = ("decode.prefill_ms", "decode.prefill_wait_ms",
               "decode.prefill_feed_ms", "decode.seat_ms")


def _engine(**kw):
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    return demo_engine(DecodeConfig(**dict(dict(
        max_slots=4, buckets=[4], kv_pages=64, page_size=4,
        max_new_tokens=32, prefill_buckets=LADDER), **kw)))


def _samples(name):
    from paddle_tpu.core import telemetry

    h = telemetry.TelemetryRegistry.instance()._hists.get(name)
    return list(h.samples) if h else []


def _prompt(length, seed=0):
    return np.random.RandomState(seed + length).randint(3, 200, length)


def _run(engine, lengths, new_tokens=4, queued_first=0):
    """Answer one request a length; the first ``queued_first`` are in the
    queue before the engine starts, so one poll admits them together."""
    from paddle_tpu.core import telemetry

    engine.warmup()
    telemetry.reset()
    asks = [dict(prompt=_prompt(n), max_new_tokens=new_tokens,
                 stop_at_eos=False) for n in lengths]
    reqs = [engine.submit(**ask) for ask in asks[:queued_first]]
    engine.start()
    try:
        for ask in asks[queued_first:]:
            reqs.append(engine.submit(**ask))
            reqs[-1].result(60)
        tokens = [req.result(60) for req in reqs]
    finally:
        engine.close()
    stats = engine.stats()        # the loop has ended: nothing is added
    assert [len(t) for t in tokens] == [new_tokens] * len(lengths)
    return reqs, stats


def test_a_whole_prompt_prefill_counts_its_bucket_and_times_its_wait():
    from paddle_tpu.core import telemetry

    lengths = [3, 8, 9, 16, 17, 30, 5]
    _, stats = _run(_engine(), lengths)
    counters = telemetry.counters()
    buckets = [next(b for b in LADDER if b >= n) for n in lengths]
    assert buckets == [8, 8, 16, 16, 32, 32, 8]
    assert counters["decode.prefills"] == len(lengths)
    assert counters["decode.prefill_tokens"] == sum(lengths)
    assert counters["decode.prefill_bucket_tokens"] == sum(buckets)
    for name in PER_PREFILL:
        assert len(_samples(name)) == len(lengths), name
    # the wait is the second half of the prefill's own span
    for wait, whole in zip(_samples("decode.prefill_wait_ms"),
                           _samples("decode.prefill_ms")):
        assert 0.0 <= wait <= whole
    assert stats["prefill_bucket_tokens"] == sum(buckets)
    for key in ("prefill_feed_ms", "prefill_wait_ms", "seat_ms"):
        assert stats[key]["count"] == len(lengths), key
    assert stats["prefill_padded_token_share"] == pytest.approx(
        100.0 * (1 - sum(lengths) / sum(buckets)), abs=0.01)


def test_a_chunked_prefill_counts_the_chunks_it_ran():
    from paddle_tpu.core import telemetry

    page, length = 4, 10
    engine = _engine(prefix_cache=True, prefill_buckets=[16])
    engine.warmup()
    telemetry.reset()
    engine.start()
    try:
        prompt = _prompt(length)
        for _ in range(2):        # the second finds the first's full pages
            engine.generate(prompt, max_new_tokens=3, stop_at_eos=False,
                            timeout=60)
    finally:
        engine.close()
    counters = telemetry.counters()
    assert counters["decode.prefills"] == 2
    assert counters["kv.prefix_hits"] >= 1
    computed = counters["decode.prefill_bucket_tokens"]
    asked = counters["decode.prefill_tokens"]
    # a cold prompt of 10 runs 3 chunks of 4; one that finds k pages cached
    # runs 3 - k chunks for 10 - 4 k tokens: the padding is the last
    # chunk's either way
    assert computed % page == 0 and 12 < computed < 24
    assert computed - asked == 2 * (3 * page - length)
    for name in PER_PREFILL:
        assert len(_samples(name)) == 2, name
    for wait, whole in zip(_samples("decode.prefill_wait_ms"),
                           _samples("decode.prefill_ms")):
        assert 0.0 <= wait <= whole


def test_the_second_request_of_one_poll_has_its_feed_and_seat_observed():
    from paddle_tpu.core import telemetry

    lengths = [6, 12, 20]
    _run(_engine(), lengths, new_tokens=6, queued_first=3)
    counters = telemetry.counters()
    # all three came in one poll: the second and third drained the pipe
    assert counters["decode.prefills"] == 3
    assert counters["decode.steps"] - counters["decode.steps_ahead"] >= 1
    for name in PER_PREFILL:
        assert len(_samples(name)) == 3, name
    # the parts of an admission lie inside decode.admit_ms and are no
    # phases of their own: the loop is still the sum of its six
    loops = _samples("decode.loop_ms")
    assert loops
    parts = {name: _samples(name) for name in LOOP_PARTS}
    for i, loop in enumerate(loops):
        assert abs(sum(parts[p][i] for p in LOOP_PARTS) - loop) < 1e-6
    assert sum(_samples("decode.prefill_feed_ms")) \
        + sum(_samples("decode.seat_ms")) \
        <= sum(_samples("decode.loop_ms"))


def test_the_threads_cpu_time_is_recorded_with_every_accepted_step():
    from paddle_tpu.core import telemetry

    _, stats = _run(_engine(), [5, 9, 13], new_tokens=12)
    loops, cpu = _samples("decode.loop_ms"), _samples("decode.cpu_ms")
    assert len(cpu) == len(loops) == telemetry.counters()["decode.steps"]
    # a thread's clock may tick coarsely (10 ms on the chip's machine, where
    # one sample reads 0 or 10): only the sums compare, with a tick's room
    assert all(used >= 0.0 for used in cpu)
    assert sum(cpu) <= sum(loops) + 10.0
    assert 0.0 < stats["prefill_wait_share"] < 100.0
    assert stats["engine_cpu_share"] == pytest.approx(
        100.0 * sum(cpu) / sum(loops), abs=0.02)
    assert stats["prefill_wait_share"] == pytest.approx(
        100.0 * sum(_samples("decode.prefill_wait_ms")) / sum(loops),
        abs=0.02)


def test_stats_keeps_tokens_device_sampled_without_a_counter_of_its_own():
    from paddle_tpu.core import telemetry

    reqs, stats = _run(_engine(), [4, 7], new_tokens=5)
    chosen_by_the_step = sum(len(r.tokens) - 1 for r in reqs)
    assert stats["tokens_device_sampled"] == chosen_by_the_step
    assert stats["tokens_host_sampled"] == len(reqs)
    assert "decode.tokens_device_sampled" not in telemetry.counters()


def test_a_requests_spans_share_its_rid_in_a_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from paddle_tpu.core import telemetry

    engine = _engine()
    engine.warmup()
    telemetry.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    engine.start()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        reqs = [engine.submit(_prompt(n), max_new_tokens=3,
                              stop_at_eos=False) for n in (5, 11)]
        for req in reqs:
            req.result(60)
    finally:
        jax.profiler.stop_trace()
        engine.close()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("decode.")]
    assert reqs[0].rid != reqs[1].rid
    for req, bucket in zip(reqs, (8, 16)):
        mine = sorted((name, stats.get("part", ""))
                      for name, stats in spans if stats.get("rid") == req.rid)
        assert mine == [("decode.admit_ms", "prefill_feed"),
                        ("decode.admit_ms", "seat"),
                        ("decode.prefill_ms", ""), ("decode.prefill_ms", ""),
                        ("decode.retire_ms", "")]
        halves = [stats for name, stats in spans
                  if name == "decode.prefill_ms"
                  and stats.get("rid") == req.rid]
        assert [(h["bucket"], h["tokens"]) for h in halves] \
            == [(bucket, int(req.prompt.size))] * 2
    # a step belongs to every row: its spans stay bare, as the loop's do
    step_spans = [stats for name, stats in spans if name in (
        "decode.loop_ms", "decode.feed_ms", "decode.step_ms",
        "decode.fetch_ms", "decode.sample_ms")]
    assert step_spans and not any(step_spans)
    # and no span has a name the device could not idle under before
    assert {name for name, _ in spans} <= {"decode." + p for p in (
        "loop_ms", "admit_ms", "prefill_ms", "feed_ms", "step_ms",
        "fetch_ms", "sample_ms", "retire_ms")}


def test_perf_report_shows_what_admissions_cost():
    from tools.perf_report import render, summarize_log

    def counter(name, value):
        return {"ts": 1.0, "kind": "counter", "name": name, "value": value,
                "attrs": {"delta": value}}

    recs = [counter("decode.prefills", 4), counter("decode.tokens", 30),
            counter("decode.steps", 10),
            counter("decode.prefill_tokens", 30),
            counter("decode.prefill_bucket_tokens", 40),
            {"ts": 2.0, "kind": "snapshot", "name": "telemetry",
             "value": None, "attrs": {"counters": {}, "gauges": {}, "hists": {
                 "decode.loop_ms": {"count": 10, "total": 200.0},
                 "decode.cpu_ms": {"count": 10, "total": 50.0},
                 "decode.prefill_wait_ms": {"count": 4, "total": 80.0}}}}]
    dc = summarize_log(recs)["decode"]
    assert (dc["prefill_wait_share"], dc["engine_cpu_share"],
            dc["prefill_padded_token_share"]) == (40.0, 25.0, 25.0)
    buf = io.StringIO()
    render(summarize_log(recs), out=buf)
    assert "every slot waited for a prefill 40.0% of the loop's time" \
        in buf.getvalue()
    # a log that ends without its snapshot has the counters' share alone
    bare = summarize_log(recs[:-1])["decode"]
    assert "prefill_wait_share" not in bare
    assert bare["prefill_padded_token_share"] == 25.0
