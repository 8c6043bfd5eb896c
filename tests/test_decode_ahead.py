"""The decode loop runs one step ahead (paddle_tpu/serving/decode.py): step
k+1 is dispatched before step k's tokens are fetched, a continuing row's
input token stays on the device (``last_tokens[slot]``), and the host's
feed, launch, fetch and accept hide under the step program.

* a mixed batch (greedy and sampled rows, staggered admissions, different
  lengths, two step buckets) gives every request exactly the tokens it gets
  alone through a loop that never has a second step in flight and feeds
  every token from the host: the parent's loop, rebuilt here from the
  engine's own launch and fetch;
* a request that ends on ``eos_id`` with a step in flight delivers nothing
  past the EOS, its row of that step is thrown away and counted, its pages
  are freed once;
* a deadline that expires with a step in flight fails that request alone;
* a ``decode.step`` fault with a step in flight fails the live rows and the
  engine serves the next request;
* ``decode.steps_ahead`` is ``decode.steps`` less the steps dispatched into
  an empty pipe, and no continuing row's token is fed from the host;
* an admission queues its prefill behind the step in flight and the next
  step behind the prefill, accepts that step's tokens, and only then waits
  for the prefill's logits: the pipe stays full;
* a journal record's ``rng_state`` is the seed advanced one draw per
  accepted token while the live stream is one draw further.
"""

import time

import numpy as np
import pytest

pytestmark = pytest.mark.serving

ENGINE_KW = dict(max_slots=4, buckets=[2, 4], kv_pages=64, page_size=4,
                 max_new_tokens=32, prefill_buckets=[16])


def _engine(model_cfg=None, **kw):
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    return demo_engine(DecodeConfig(**dict(ENGINE_KW, **kw)),
                       model_cfg=model_cfg)


@pytest.fixture(scope="module")
def engine():
    eng = _engine().start(warmup=True)
    yield eng
    eng.close()
    assert eng.kv.audit(owned=[], owned_ring=[]) == []


@pytest.fixture(scope="module")
def serial():
    """An engine whose loop is the parent's: a step is fetched and accepted
    before the next is built, and every row's token is fed from the host."""
    eng = _engine(max_slots=1, buckets=[1])

    def run_step(it):
        rows = list(eng._active)
        for req in rows:
            req.carried = False
        eng._finish(eng._launch(rows, it), it)
        assert eng._inflight is None

    eng._run_step = run_step
    eng.start(warmup=True)
    yield eng
    eng.close()


def _asks(n, seed):
    rng = np.random.RandomState(seed)
    return [dict(prompt=rng.randint(3, 200, rng.randint(3, 13)),
                 max_new_tokens=int(rng.randint(2, 15)), stop_at_eos=False,
                 temperature=0.0 if i % 3 == 1 else 0.9, seed=700 + i)
            for i in range(n)]


def _wait(condition, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


def test_a_mixed_batch_answers_what_each_request_gets_from_the_serial_loop(
        engine, serial):
    from paddle_tpu.core import telemetry

    asks = _asks(12, 0)
    for ask in asks[:3]:
        ask["max_new_tokens"] += 8        # still decoding when more arrive
    step_buckets = set()
    feed = engine._feed

    def noting(phase, bucket, parts):
        if phase == "step":
            step_buckets.add(bucket)
        return feed(phase, bucket, parts)

    engine._feed = noting
    telemetry.reset()
    try:
        # staggered: three, four more once the first has decoded a while,
        # the rest as slots come free, so every slot turns over
        reqs = [engine.submit(**ask) for ask in asks[:3]]
        _wait(lambda: len(reqs[0].tokens) >= 2, "no step ran")
        reqs += [engine.submit(**ask) for ask in asks[3:7]]
        _wait(lambda: any(r.done() for r in reqs), "nothing finished")
        reqs += [engine.submit(**ask) for ask in asks[7:]]
        got = [r.result(60) for r in reqs]
        # a last request alone, so the small bucket's program runs too
        asks.append(dict(asks[0], seed=99))
        got.append(engine.generate(timeout=60, **asks[-1]))
    finally:
        engine._feed = feed
    counters = telemetry.counters()
    assert counters["decode.steps_ahead"] > 0
    assert "decode.rows_discarded" not in counters
    assert step_buckets == {2, 4}
    for ask, tokens in zip(asks, got):
        alone = serial.generate(timeout=60, **ask)
        assert len(tokens) == ask["max_new_tokens"]
        assert tokens.dtype == alone.dtype and np.array_equal(tokens, alone)


def test_an_eos_with_a_step_in_flight_delivers_nothing_past_it(engine):
    from paddle_tpu.core import telemetry
    from paddle_tpu.models.decoder_lm import DecoderLMConfig

    ask = dict(prompt=np.arange(5, 14), max_new_tokens=12, temperature=0.9,
               seed=31)
    free_run = engine.generate(timeout=60, stop_at_eos=False, **ask)
    # a token first seen at a step (not the prefill's), with room behind it
    at = next(j for j in range(1, 10) if free_run[j] not in free_run[:j])
    stopping = _engine(model_cfg=DecoderLMConfig(eos_id=int(free_run[at])))
    stopping.start(warmup=True)
    try:
        telemetry.reset()
        got = stopping.generate(timeout=60, stop_at_eos=True, **ask)
    finally:
        # the step after the EOS was dispatched before the EOS was fetched;
        # the loop fetches it, and throws its row away, before it ends
        stopping.close()
    assert stopping._inflight is None and stopping._active == []
    assert np.array_equal(got, free_run[:at + 1])
    counters = telemetry.counters()
    assert counters["decode.rows_discarded"] == 1
    assert counters["decode.steps"] == at + 1       # one past the EOS
    assert counters["decode.tokens"] == at == counters["decode.steps_ahead"]
    assert counters["decode.retired"] == 1
    assert counters["decode.kv_pages_freed"] \
        == counters["decode.kv_pages_allocated"]
    assert stopping.kv.audit(owned=[], owned_ring=[]) == []
    assert sorted(stopping._free_slots) == list(range(4))


def test_a_deadline_with_a_step_in_flight_fails_that_request_alone(
        engine, serial):
    from paddle_tpu.core import telemetry
    from paddle_tpu.core.flags import flag, set_flags
    from paddle_tpu.serving import DeadlineExceededError

    stays = dict(prompt=np.arange(20, 27), max_new_tokens=14,
                 temperature=0.9, seed=41, stop_at_eos=False)
    before = flag("decode_step_delay_ms")
    set_flags({"decode_step_delay_ms": 20.0})
    try:
        telemetry.reset()
        kept = engine.submit(**stays)
        late = engine.submit(np.arange(40, 46), max_new_tokens=60,
                             stop_at_eos=False, deadline_ms=400.0)
        with pytest.raises(DeadlineExceededError):
            late.result(60)
        tokens = kept.result(60)
    finally:
        set_flags({"decode_step_delay_ms": before})
    assert 1 < len(late.tokens) < 60
    # the row was in the step in flight when the scan retired it: the
    # caller hears of the deadline first, the loop then fetches that step
    _wait(lambda: "decode.rows_discarded" in telemetry.counters(),
          "the step in flight was never fetched")
    counters = telemetry.counters()
    assert counters["decode.rows_discarded"] == 1
    assert counters["decode.deadline_expired"] == 1
    assert counters["decode.tokens"] == len(late.tokens) - 1 + 13
    assert np.array_equal(tokens, serial.generate(timeout=60, **stays))
    assert engine.pool.stats()["pages_used"] == 0


@pytest.mark.chaos
def test_a_step_fault_with_a_step_in_flight_fails_the_live_rows(serial):
    from paddle_tpu.core import faults, telemetry
    from paddle_tpu.serving import ServingError

    asks = _asks(3, 5)
    for ask in asks:
        ask["max_new_tokens"] = 10
    engine = _engine()
    # polled together: two are seated before the first launch, the third's
    # prefill ends under it and it joins the second
    reqs = [engine.submit(**ask) for ask in asks]
    telemetry.reset()
    faults.configure("decode.step:@3")   # the third launch: the second flies
    try:
        engine.start(warmup=True)
        for req in reqs:
            with pytest.raises(ServingError):
                req.result(60)
        faults.configure("")
        # one step's tokens were accepted; the second step's went with it
        assert [len(r.tokens) for r in reqs] == [2, 2, 1]
        counters = telemetry.counters()
        assert counters["decode.steps"] == 1 == counters["decode.steps_ahead"]
        assert counters["decode.errors"] == 3
        for ask in asks[:2]:
            assert np.array_equal(engine.generate(timeout=60, **ask),
                                  serial.generate(timeout=60, **ask))
    finally:
        faults.configure("")
        engine.close()
    assert engine._inflight is None and engine._active == []
    assert engine.pool.stats()["pages_used"] == 0
    assert sorted(engine._free_slots) == list(range(4))


def test_steps_ahead_is_steps_less_the_empty_pipes_and_no_token_comes_back():
    """Two requests polled together run 9 steps through one pipe (the
    second's prefill ends under the first step, so it joins the second); a
    third, submitted when they are done, fills an empty pipe again."""
    from paddle_tpu.core import telemetry

    engine = _engine()
    fed = []
    feed = engine._feed

    def spy(phase, bucket, parts):
        if phase == "step":
            fed.append((parts["tokens"].copy(), parts["carry"].copy()))
        return feed(phase, bucket, parts)

    pair = [engine.submit(np.arange(5, 11), max_new_tokens=n,
                          stop_at_eos=False, temperature=0.9, seed=n)
            for n in (10, 4)]
    engine.warmup()
    engine._feed = spy
    telemetry.reset()
    engine.start()
    try:
        got = [r.result(60) for r in pair]
        got.append(engine.generate(np.arange(30, 35), max_new_tokens=6,
                                   stop_at_eos=False, timeout=60))
    finally:
        engine.close()
    assert [len(t) for t in got] == [10, 4, 6]
    counters = telemetry.counters()
    assert counters["decode.steps"] == 9 + 5
    assert counters["decode.steps_ahead"] == 14 - 2
    assert counters["decode.tokens"] == 9 + 3 + 5
    assert "decode.rows_discarded" not in counters
    # rows by step: the short request is left out once its count is reached
    assert [int((carry[:, 0] < 4).sum()) for _, carry in fed] \
        == [1, 2, 2, 2] + [1] * 5 + [1] * 5
    first_fed = []
    for tokens, carry in fed:
        live = carry[:, 0] < 4               # a padding row names no slot
        assert not carry[~live][:, 1].any() and not tokens[~live].any()
        carried = carry[:, 1] > 0
        # a continuing row's token is the device's: the host feeds none
        assert not tokens[carried].any()
        first_fed += [int(t) for t in tokens[live & ~carried]]
    assert first_fed == [int(t[0]) for t in got]      # once a request
    # a seated request keeps its slot while the rows beside it come and go
    long_slot = {int(carry[0, 0]) for _, carry in fed[:9]}
    short_slot = {int(carry[1, 0]) for _, carry in fed[1:4]}
    assert len(long_slot) == len(short_slot) == 1 and long_slot != short_slot


def test_an_admission_leaves_the_pipe_full():
    """An admission with a step in flight: the prefill is queued behind that
    step, the next step behind the prefill, the step's tokens are accepted,
    and only then does the host wait for the prefill's logits row and seat
    the request, under the step it launched: no step starts in an empty
    pipe, and no finished step's tokens wait behind a prefill."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.core.flags import flag, set_flags

    engine = _engine()
    engine.warmup()
    events = []
    bucket, = engine.config.prefill_buckets
    prefill = engine._entries[("prefill", bucket)]
    launch, finish, seat = engine._launch, engine._finish, engine._seat

    def dispatching(*args):
        events.append("prefill")
        return prefill(*args)

    def launching(rows, it):
        events.append(("launch", len(rows)))
        return launch(rows, it)

    def finishing(flight, it):
        events.append(("finish", len(first.tokens)))
        return finish(flight, it)

    def seating(req, logits_row):
        events.append(("seat", engine._inflight is not None))
        return seat(req, logits_row)

    engine._entries[("prefill", bucket)] = dispatching
    engine._launch, engine._finish = launching, finishing
    engine._seat = seating
    before = flag("decode_step_delay_ms")
    set_flags({"decode_step_delay_ms": 10.0})    # the first outlasts the wait
    telemetry.reset()
    engine.start()
    try:
        first = engine.submit(np.arange(5, 12), max_new_tokens=30,
                              stop_at_eos=False)
        _wait(lambda: len(first.tokens) >= 3, "no step ran")
        at = len(events)
        second = engine.submit(np.arange(60, 66), max_new_tokens=3,
                               stop_at_eos=False)
        second.result(60)
        first.result(60)
    finally:
        set_flags({"decode_step_delay_ms": before})
        engine.close()
    assert events[:3] == ["prefill", ("seat", False), ("launch", 1)]
    i = events.index("prefill", at)
    (_, tokens_before), = [e for e in events[i + 1:i + 3] if e[0] == "finish"]
    assert events[i + 1:i + 5] == [
        ("launch", 1), ("finish", tokens_before), ("seat", True),
        ("launch", 2)]
    # the step's token was on the request when the second got its first
    assert first.token_walls[tokens_before] <= second.token_walls[0]
    # one step went into an empty pipe, the first request's first
    counters = telemetry.counters()
    assert counters["decode.steps"] == 29
    assert counters["decode.steps_ahead"] == 28


def test_a_journal_record_leaves_out_the_draw_made_ahead(engine):
    from paddle_tpu.serving.session import unpack_rng_state

    def advanced(seed, draws):
        rng = np.random.RandomState(seed)
        rng.random_sample(draws)
        return rng.get_state()

    def same(a, b):
        return a[0] == b[0] and np.array_equal(a[1], b[1]) and a[2:] == b[2:]

    seen = []

    def sink(records):
        for rec in records:
            seen.append((len(rec["accepted"]), rec["rng_state"],
                         req._rng.get_state(), req.ahead))

    engine.journal_sink = sink
    try:
        req = engine.submit(np.arange(50, 58), max_new_tokens=9, seed=77,
                            temperature=0.8, stop_at_eos=False,
                            request_id="ahead")
        req.result(60)
    finally:
        engine.journal_sink = None
    # cut after every accepted token but the last, which retires the request
    assert [n for n, _, _, _ in seen] == list(range(1, 9))
    for n, packed, live, ahead in seen:
        # the next step's draw was made before this token was fetched
        assert ahead == 1
        assert same(live, advanced(77, n + 1))
        assert same(unpack_rng_state(packed).get_state(), advanced(77, n))


def test_a_slot_is_taken_at_admission_and_given_back_by_a_failed_prefill():
    """Every model's request takes its slot before its prefill (a model
    with per-slot state writes the slot there); a prefill that fails, or a
    request that ends on its first token, gives it back."""
    from paddle_tpu.serving.admission import ServingError

    eng = _engine()
    entry_of = eng._entry
    taken = []

    def entry(phase, bucket):
        fn = entry_of(phase, bucket)
        if phase != "prefill":
            return fn

        def prefill(params, pools, feed):
            taken.append(len(eng._free_slots))
            if len(taken) == 1:
                raise RuntimeError("planted")
            return fn(params, pools, feed)

        return prefill

    eng.start(warmup=True)
    eng._entry = entry
    try:
        with pytest.raises(ServingError, match="prefill failed"):
            eng.generate(np.arange(5, 14), max_new_tokens=4, timeout=60)
        assert sorted(eng._free_slots) == list(range(4))
        assert len(eng.generate(np.arange(5, 14), max_new_tokens=1,
                                timeout=60)) == 1
        assert len(eng.generate(np.arange(5, 14), max_new_tokens=4,
                                timeout=60)) == 4
    finally:
        eng.close()
    assert taken == [3, 3, 3]       # one slot held while each prefill ran
    assert sorted(eng._free_slots) == list(range(4))
    assert eng.pool.stats()["pages_used"] == 0
