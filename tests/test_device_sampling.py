"""The decode step chooses its tokens on the device
(paddle_tpu/serving/sampling.py, called at the end of the jitted step
program of serving/decode.py).

The sampler, against a float64 numpy oracle that states what
``GenerationRequest.sample`` states:

* a sampled row's token ``t`` satisfies ``cdf64[t-1] - 1e-5 < u <=
  cdf64[t] + 1e-5`` always, and equals the oracle's token in at least 99.9%
  of 10,000 draws at V = 128 and, on rows as peaked as a confident trained
  model's (softmax entropy about 1.8 nats: 9,998 of 10,000), at V =
  256,008. The flatter the row, the lower the share: 9,993 of 10,000 at
  2.9 nats, 99.3% on FLAT rows of 256,008, because float32 rounds the
  uniform itself by up to 3e-8 and a flat row has a CDF boundary every
  4e-6, so no float32 sampler can do better there; the distance to the
  boundary (1.3e-7 at the worst) is what is held on every row;
* greedy rows are ``np.argmax``, lowest index on ties; greedy and sampled
  rows mix in one batch; the uniform's ends clamp into the vocabulary;
* a row's token does not depend on its bucket, its position or its
  neighbours.

The engine: the step entry returns int32 ``[bucket]`` and nothing with a
vocabulary axis; the journal's ``rng_state`` is ``RandomState(seed)``
advanced one draw per accepted token; the two counters add up to the tokens
returned; the loop's phases still add up to the loop.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.serving

BIG_V = 256008


@pytest.fixture(scope="module")
def sampler():
    import jax

    from paddle_tpu.serving.sampling import sample_tokens

    jitted = jax.jit(sample_tokens)

    def run(logits, temperature, uniform):
        return np.asarray(jitted(np.asarray(logits, np.float32),
                                 np.asarray(temperature, np.float32),
                                 np.asarray(uniform, np.float32)))

    return run


def _cdf64(row, temperature):
    z = row.astype(np.float64) / float(temperature)
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    return np.cumsum(p)


def _oracle(cdf, u):
    return min(int(np.searchsorted(cdf, u)), len(cdf) - 1)


def _held_to_the_boundary(cdf, u, t):
    below = cdf[t - 1] if t > 0 else 0.0
    return below - 1e-5 < u <= cdf[t] + 1e-5


@pytest.mark.parametrize("vocab,scale,batch,rounds,draws,least_equal", [
    (128, 1.0, 100, 4, 25, 0.999),          # flat, small vocabulary
    (BIG_V, 8.0, 50, 8, 25, 0.999),         # peaked like a trained model
    (BIG_V, 1.0, 50, 2, 10, 0.98),          # flat: a boundary every 4e-6
], ids=["v128", "v256008_peaked", "v256008_flat"])
def test_sampled_rows_against_the_float64_oracle(sampler, vocab, scale,
                                                 batch, rounds, draws,
                                                 least_equal):
    rng = np.random.RandomState(vocab % 1000 + int(scale))
    total = equal = 0
    for _ in range(rounds):
        logits = (rng.randn(batch, vocab) * scale).astype(np.float32)
        temperature = rng.uniform(0.5, 1.5, batch).astype(np.float32)
        cdfs = [_cdf64(logits[i], temperature[i]) for i in range(batch)]
        for _ in range(draws):
            u = rng.random_sample(batch)
            got = sampler(logits, temperature, u)
            assert got.dtype == np.int32 and got.shape == (batch,)
            for i in range(batch):
                t = int(got[i])
                assert 0 <= t < vocab
                assert _held_to_the_boundary(cdfs[i], u[i], t), \
                    (i, t, u[i])
                equal += t == _oracle(cdfs[i], u[i])
                total += 1
    assert total == batch * rounds * draws
    assert equal >= least_equal * total, (equal, total)


@pytest.mark.parametrize("vocab", [128, 5000, BIG_V])
def test_greedy_rows_are_argmax_lowest_index_on_ties(sampler, vocab):
    rng = np.random.RandomState(3)
    logits = rng.randn(8, vocab).astype(np.float32)
    # planted ties: the maximum twice in a row, in one block and across two
    logits[1, [vocab // 3, vocab // 3 + 7]] = 9.0
    logits[2, [5, vocab - 1]] = 11.0
    logits[3, :] = 0.25                       # every entry ties
    got = sampler(logits, np.zeros(8), rng.random_sample(8))
    assert np.array_equal(got, np.argmax(logits, axis=-1))
    assert got[1] == vocab // 3 and got[2] == 5 and got[3] == 0
    # a negative temperature is greedy too, as on the host
    assert np.array_equal(sampler(logits, -np.ones(8), np.zeros(8)), got)


def test_a_batch_mixes_greedy_and_sampled_rows(sampler):
    rng = np.random.RandomState(4)
    vocab = 3000
    logits = (rng.randn(8, vocab) * 3).astype(np.float32)
    temperature = np.array([0, 0.8, 0, 1.2, 0.5, 0, 0.8, 0], np.float32)
    u = rng.random_sample(8)
    got = sampler(logits, temperature, u)
    for i in range(8):
        if temperature[i] <= 0:
            assert got[i] == np.argmax(logits[i])
        else:
            assert got[i] == _oracle(_cdf64(logits[i], temperature[i]),
                                     u[i])


@pytest.mark.parametrize("vocab", [128, 1030, BIG_V])
def test_the_uniforms_ends_clamp_into_the_vocabulary(sampler, vocab):
    rng = np.random.RandomState(5)
    logits = rng.randn(4, vocab).astype(np.float32)
    logits[1, -3:] = -np.inf                  # a tail with no mass
    logits[2, :2] = -np.inf                   # a head with no mass
    ones = np.ones(4, np.float32)
    last = np.nextafter(np.float32(1), np.float32(0))
    # u = 0: no CDF entry lies below it, as np.searchsorted(cdf, 0.0) says
    assert np.array_equal(sampler(logits, ones, np.zeros(4)), np.zeros(4))
    for top in (last, np.float32(1)):         # 1.0: a float64 draw rounded
        got = sampler(logits, ones, np.full(4, top))
        for i in range(4):
            cdf = _cdf64(logits[i], 1.0)
            assert 0 <= got[i] < vocab
            assert cdf[got[i]] > 1 - 1e-5     # the end of the mass
    # the tail without mass is never chosen short of the rounding to 1.0
    assert sampler(logits, ones, np.full(4, last))[1] < vocab - 3


@pytest.mark.parametrize("vocab", [5000, BIG_V])
def test_a_rows_token_is_its_own(sampler, vocab):
    """Alone in a bucket of 1, and at every position of a bucket of 8 among
    rows that change: the same token, greedy or sampled."""
    rng = np.random.RandomState(6)
    draws = 6 if vocab == BIG_V else 40
    for d in range(draws):
        row = (rng.randn(vocab) * (1 + d % 4)).astype(np.float32)
        temperature = 0.0 if d % 5 == 4 else 0.5 + (d % 3) * 0.4
        u = rng.random_sample()
        alone = int(sampler(row[None], [temperature], [u])[0])
        for pos in range(8) if vocab != BIG_V else (d % 8,):
            logits = (rng.randn(8, vocab) * 2).astype(np.float32)
            temps = rng.choice([0.0, 0.7, 1.3], 8)
            us = rng.random_sample(8)
            logits[pos], temps[pos], us[pos] = row, temperature, u
            assert int(sampler(logits, temps, us)[pos]) == alone, (d, pos)


# -- the engine ---------------------------------------------------------------

ENGINE_KW = dict(max_slots=4, kv_pages=64, page_size=4, max_new_tokens=16)


@pytest.fixture(scope="module")
def journaled_run():
    """Seeded, sampled and greedy requests through a four-slot toy engine
    that journals every token; the step entry is wrapped to note what it
    returns."""
    import jax

    from paddle_tpu.core import telemetry
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    engine = demo_engine(DecodeConfig(**ENGINE_KW))
    records, returned = [], []
    engine.journal_sink = records.extend
    engine.warmup()
    bucket, = engine.config.buckets
    entry = engine._entries[("step", bucket)]
    shapes = jax.eval_shape(entry, engine._params, dict(engine._pools),
                            engine._zero_feed("step", bucket),
                            engine._last_tokens)

    def noting(params, pools, feed, last_tokens):
        out = entry(params, pools, feed, last_tokens)
        returned.append(jax.tree_util.tree_map(
            lambda a: (tuple(a.shape), str(a.dtype)), out))
        return out

    engine._entries[("step", bucket)] = noting
    rng = np.random.RandomState(0)
    asks = [dict(prompt=rng.randint(1, 50, rng.randint(3, 12)),
                 max_new_tokens=int(rng.randint(2, 12)), stop_at_eos=False,
                 temperature=0.0 if i % 3 == 2 else 0.8, seed=200 + i,
                 request_id=f"s{i}") for i in range(9)]
    telemetry.reset()
    engine.start()
    try:
        reqs = [engine.submit(**ask) for ask in asks]
        tokens = [r.result(60) for r in reqs]
    finally:
        engine.close()
    hists = telemetry.TelemetryRegistry.instance()._hists
    samples = {name: list(h.samples) for name, h in hists.items()}
    return dict(engine=engine, bucket=bucket, shapes=shapes,
                returned=returned, records=records, asks=asks,
                tokens=tokens, counters=dict(telemetry.counters()),
                samples=samples)


def test_the_step_entry_returns_tokens_and_no_vocabulary_axis(journaled_run):
    run = journaled_run
    bucket, vocab = run["bucket"], run["engine"].model_cfg.vocab_size
    slots = run["engine"].config.max_slots
    chosen, pools, last_tokens = run["shapes"]
    assert chosen.shape == (bucket,) and str(chosen.dtype) == "int32"
    assert last_tokens.shape == (slots,) \
        and str(last_tokens.dtype) == "int32"
    assert run["returned"], "no step ran"
    for first, new_pools, last in run["returned"]:
        assert first == ((bucket,), "int32")
        assert last == ((slots,), "int32")
        # what the loop can fetch is what the entry returns: the tokens,
        # the KV pools and every slot's latest token, nothing [bucket, vocab]
        assert sorted(new_pools) == sorted(run["engine"]._pools)
        assert all(vocab not in shape for shape, _ in new_pools.values())


def test_the_journals_rng_state_is_the_seed_advanced_a_draw_a_token(
        journaled_run):
    from paddle_tpu.serving.session import unpack_rng_state

    run = journaled_run
    by_id = {ask["request_id"]: ask for ask in run["asks"]}
    seen = set()
    for rec in run["records"]:
        ask = by_id[rec["request_id"]]
        if ask["temperature"] <= 0:
            assert rec["rng_state"] is None
            continue
        n = len(rec["accepted"])
        want = np.random.RandomState(ask["seed"])
        want.random_sample(n)
        got = unpack_rng_state(rec["rng_state"]).get_state()
        assert got[0] == want.get_state()[0]
        assert np.array_equal(got[1], want.get_state()[1])
        assert got[2:] == want.get_state()[2:]
        seen.add((rec["request_id"], n))
    # every sampled request was journaled mid-stream, past its first token
    sampled = [a for a in run["asks"] if a["temperature"] > 0
               and a["max_new_tokens"] > 2]
    assert all(any(rid == a["request_id"] and n >= 2 for rid, n in seen)
               for a in sampled)


def test_the_two_counters_add_up_to_the_tokens_returned(journaled_run):
    run = journaled_run
    counters, tokens = run["counters"], run["tokens"]
    n_tokens = sum(len(t) for t in tokens)
    assert [len(t) for t in tokens] \
        == [a["max_new_tokens"] for a in run["asks"]]
    assert counters["decode.tokens_host_sampled"] == len(tokens)
    assert counters["decode.tokens"] == n_tokens - len(tokens)
    # the step program chose every token it delivered: /v1/stats keeps the
    # key, from decode.tokens, and no second counter says the same
    assert "decode.tokens_device_sampled" not in counters
    stats = run["engine"].stats()
    assert stats["tokens_device_sampled"] == n_tokens - len(tokens)
    assert stats["tokens_device_sampled"] + stats["tokens_host_sampled"] \
        == n_tokens


def test_the_loops_phases_still_add_up_to_the_loop(journaled_run):
    samples = journaled_run["samples"]
    parts = ("decode.admit_ms", "decode.feed_ms", "decode.step_ms",
             "decode.sample_ms", "decode.retire_ms", "decode.other_ms")
    loops = samples["decode.loop_ms"]
    assert len(loops) == len(journaled_run["returned"])
    for i, loop in enumerate(loops):
        assert abs(sum(samples[p][i] for p in parts) - loop) < 1e-6
        assert samples["decode.fetch_ms"][i] <= samples["decode.step_ms"][i]
