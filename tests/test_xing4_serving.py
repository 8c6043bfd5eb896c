"""models/xing4.py behind `DecodeEngine` at toy widths on the CPU, its draft
module DRAFTING: a step feeds a slot's last accepted token and the module's
draft, verifies both positions and delivers one or two tokens.

(a) the engine against benchmark/reference_xing4.py on seeded weights:
    prefill, then drafted steps through the cache; the logits of both
    positions and the module's, greedy and sampled rows in one batch; a
    greedy row rejects nearly every draft, and the step after a rejection
    must not see the rejected draft's latent row: its logits and the rows
    its pages hold at the end say so;
(b) the acceptance rule (serving/sampling.py): q = p accepts every draft;
    over a toy vocabulary the delivered tokens are distributed as the
    one-token sampler's, and a rule that redraws from p is not;
(c) a request ends exactly at `max_new_tokens` and at `eos_id`, whichever
    position of a step reaches it, the surplus counted, and its tokens are
    its seed's whatever else is in the batch;
(d) a journal record of a drafting session resumes it.
"""

import numpy as np
import pytest

from benchmark import reference_xing4 as rx
from benchmark.families import xing4 as family
from paddle_tpu.core import telemetry
from paddle_tpu.models import xing4
from paddle_tpu.serving import sampling
from paddle_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                       GenerationRequest)
from paddle_tpu.serving.session import resume_args

TEMPS = (0.0, 0.8, 2.5, 2.5)
LENGTHS = (5, 12, 20, 9)
NEW = 20


def toy_cfg():
    return xing4.Xing4Config(max_seq_len=128, n_layers=3, first_k_dense=1,
                             dtype="float32", experts_held=(0, 32))


def toy_params(cfg, seed=1):
    params = xing4.xing4_params(cfg, seed)
    params["x4_tok_emb"] = np.random.RandomState(5).normal(
        0, 1, params["x4_tok_emb"].shape).astype(np.float32)
    return params


@pytest.fixture(scope="module")
def engine():
    cfg = toy_cfg()
    eng = DecodeEngine(cfg, toy_params(cfg), DecodeConfig(
        max_slots=4, page_size=8, kv_pages=4 * 17 + 1, max_new_tokens=64,
        prefill_buckets=[16, 32, 64], prefix_cache=False))
    eng.start(warmup=True)
    yield eng
    eng.close()


def prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(3, 512, n) for n in LENGTHS]


@pytest.fixture(scope="module")
def batch(engine):
    """Four requests in one batch, greedy and sampled, each keeping what a
    check reads."""
    reqs = [engine.submit(p, max_new_tokens=NEW, temperature=t, seed=7 + i,
                          stop_at_eos=False, keep_first_logits=True,
                          keep_final_pages=True, keep_step_outputs=True)
            for i, (p, t) in enumerate(zip(prompts(), TEMPS))]
    return reqs, [r.result(120) for r in reqs]


# -- (a) ---------------------------------------------------------------------

def test_the_engine_is_the_reference_through_prefill_and_drafted_steps(
        engine, batch):
    cfg = engine.model_cfg
    ref = rx.Reference(toy_params(cfg), family.reference_config(cfg))
    accepted = rejected = 0
    for req, prompt, out in zip(batch[0], prompts(), batch[1]):
        seq = np.concatenate([prompt, out])
        first = prompt.size - 1
        logits, draft_logits, _, _, latents = ref.rows(seq, 64, first,
                                                       NEW + 1)
        assert rx.logit_error(req.first_logits, logits[0]) < 1e-4
        before = 1
        assert not req.step_outputs[0]["had_draft"]
        for step in req.step_outputs:
            pos = prompt.size + before - 1
            assert step["position"] == pos
            assert rx.logit_error(step["logits"][0],
                                  logits[pos - first]) < 1e-4
            count = len(step["tokens"])
            accepted += count - 1
            rejected += step["had_draft"] and count == 1
            if count == 2:      # the draft accepted: it is the sequence's
                assert step["tokens"][0] == step["draft"]
                assert rx.logit_error(step["logits"][1],
                                      logits[pos + 1 - first]) < 1e-4
            if step["delivered"] == count:
                at = pos + count - 1
                assert rx.logit_error(step["draft_logits"],
                                      draft_logits[at - first]) < 1e-4
            before += step["delivered"]
        assert before == NEW
        # the rows its pages hold: every one that was once a rejected
        # draft's was overwritten by the step after
        fed = seq.size - 1
        for layer in range(cfg.n_layers + 1):
            rows = np.asarray(req.final_pages[f"kv_c_{layer}"],
                              np.float32).reshape(-1, cfg.latent_row_width)
            assert rx.latent_error(rows[:fed], latents[layer][:fed]) < 1e-5
    assert accepted > 5 and rejected > 20
    # a greedy row's tokens are the model's own argmaxes, draft or none
    greedy, out = batch[0][0], batch[1][0]
    took = [t for s in greedy.step_outputs
            for t in s["tokens"][:s["delivered"]]]
    assert list(out[1:]) == took
    for step in greedy.step_outputs:
        assert step["tokens"][0] == int(np.argmax(step["logits"][0]))


def test_a_rejected_second_position_is_the_reference_fed_the_draft(
        engine, batch):
    cfg = engine.model_cfg
    ref = rx.Reference(toy_params(cfg), family.reference_config(cfg))
    req, prompt, out = batch[0][0], prompts()[0], batch[1][0]
    seq = np.concatenate([prompt, out])
    before, seen = 1, 0
    for step in req.step_outputs:
        pos = prompt.size + before - 1
        if step["had_draft"] and len(step["tokens"]) == 1 and seen < 2:
            seen += 1
            fed = np.concatenate([seq[:pos + 1], [step["draft"]]])
            row = ref.rows(fed, 64, pos + 1, NEW + 1)[0][0]
            assert rx.logit_error(step["logits"][1], row) < 1e-4
        before += step["delivered"]
    assert seen == 2


@pytest.mark.parametrize("chunk", [32, 1024])
def test_the_paged_kernel_attends_two_positions_with_one_read(monkeypatch,
                                                              chunk):
    """Interpreted on the CPU: a row's heads of position pos and of pos + 1
    in ONE call are the two single-position calls' (the second sees one
    row more), and the kernel is its stock lowering's; positions at a
    page's and at a chunk's edge among them."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_mla_attention as pma

    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(pma, "CHUNK_TOKENS", chunk)
    rng = np.random.RandomState(2)
    b, n, width, v, page, mp, pages = 5, 8, 256, 128, 16, 12, 80
    pool = jnp.asarray(rng.randn(pages, page, width), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pages - 1)[:b * mp].reshape(b, mp)
                        + 1, jnp.int32)
    pos = jnp.asarray([0, 15, 31, 100, mp * page - 2], jnp.int32)
    q = jnp.asarray(rng.randn(b, 2 * n * width), jnp.float32)
    telemetry.reset()
    got = np.asarray(pma.paged_mla_decode_attention(
        q, pool, table, pos, n, v, 0.05, queries=2))
    want = np.asarray(pma.stock_paged_mla_attention(
        q, pool, table, pos, 2 * n, v, 0.05, queries=2))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    c = telemetry.snapshot()["counters"]
    assert c.get("pallas.paged_attn_dispatches") == 1
    assert not c.get("pallas.paged_attn_fallbacks")
    halves = q.reshape(b, 2, n * width)
    for j in range(2):
        alone = np.asarray(pma.stock_paged_mla_attention(
            halves[:, j], pool, table, pos + j, n, v, 0.05))
        np.testing.assert_allclose(
            want.reshape(b, 2, n * v)[:, j], alone, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="ring"):
        pma.paged_mla_decode_attention(q, pool, table, pos, n, v, 0.05,
                                       window=16, queries=2)


def test_the_maps_kernel_clamps_the_residual_logits(monkeypatch):
    """`mhc_pre` with a clamp of H_res's logits and an epsilon in the
    Sinkhorn denominators: the kernel (interpreted) is its stock lowering,
    a clamp that bites changes the maps, and without either the call is
    models/motif3.py's."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import mhc_mix

    rng = np.random.RandomState(1)
    n, c, t = 4, 128, 16
    x = jnp.asarray(rng.randn(t, n * c), jnp.float32)
    gamma = jnp.ones((n * c,), jnp.float32)
    phi = jnp.asarray(rng.randn(n * c, 24) * 0.2, jnp.float32)
    scale = jnp.asarray([1.0, 1.0, 3.0], jnp.float32)
    bias = jnp.asarray(rng.randn(24), jnp.float32)
    kw = dict(n=n, iters=20, eps=1e-6)
    clamped = dict(kw, res_clamp=(-2.0, 2.0), sinkhorn_eps=1e-6)
    want = mhc_mix.stock_mhc_pre(x, gamma, phi, scale, bias, **clamped)
    monkeypatch.setenv("PT_PALLAS", "interpret")
    got = mhc_mix.mhc_pre(x, gamma, phi, scale, bias, **clamped)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
    free = mhc_mix.stock_mhc_pre(x, gamma, phi, scale, bias, **kw)
    assert np.abs(np.asarray(free[1]) - np.asarray(want[1])).max() > 0.01
    res = np.asarray(want[1])[:, 8:24].reshape(t, n, n)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-4)


def test_tile_streams_copies_a_row_into_every_stream():
    """The op that hands the draft module's input to its streams, as
    `embed_streams` hands an embedding row."""
    import paddle_tpu.ops  # noqa: F401
    from paddle_tpu.core import registry

    x = np.arange(12, dtype=np.float32).reshape(2, 6)
    out = np.asarray(registry.get("tile_streams").forward(
        {"X": [x]}, {"n_streams": 4})["Out"])
    assert out.shape == (2, 24) and out.dtype == np.float32
    for i in range(4):
        np.testing.assert_array_equal(out[:, i * 6:(i + 1) * 6], x)


# -- (b) ---------------------------------------------------------------------

VOCAB, DRAWS = 8, 40000
# an empirical share of 40,000 draws lies within 4.5 standard deviations
# of its probability (2.4e-3 at 1/8); a wrong rule is tens of them off
SIGMAS = 4.5


def rule_inputs():
    rng = np.random.RandomState(3)
    logits = np.tile(rng.normal(0, 1.5, (1, VOCAB)), (DRAWS, 1))
    after = np.tile(rng.normal(0, 1.5, (1, VOCAB)), (DRAWS, 1))
    q_logits = np.tile(rng.normal(0, 1.5, (1, VOCAB)), (DRAWS, 1))
    u = rng.random_sample((DRAWS, 4)).astype(np.float32)
    return (logits.astype(np.float32), after.astype(np.float32),
            q_logits.astype(np.float32), u)


def within(counts, p):
    share = counts / counts.sum()
    sd = np.sqrt(p * (1 - p) / counts.sum())
    return np.abs(share - p).max() <= SIGMAS * sd.max(), \
        np.abs(share - p).max() / sd.max()


def test_the_delivered_tokens_are_distributed_as_the_one_token_samplers():
    logits, after, q_logits, u = rule_inputs()
    temp = np.full((DRAWS,), 1.3, np.float32)
    draft, q = sampling.draft_tokens(q_logits, temp, u[:, 3])
    tokens, count = sampling.verify_tokens(
        logits, after, q, draft, np.ones((DRAWS,), bool), temp, u[:, :3])
    tokens, count = np.asarray(tokens), np.asarray(count)
    p = rx.probabilities(logits[0], 1.3)
    p_after = rx.probabilities(after[0], 1.3)
    qq = rx.probabilities(q_logits[0], 1.3)
    # the draft is drawn FROM q; the first token of a step is p's
    ok, _ = within(np.bincount(np.asarray(draft), minlength=VOCAB), qq)
    assert ok
    ok, _ = within(np.bincount(tokens[:, 0], minlength=VOCAB), p)
    assert ok
    # accepted with probability sum min(p, q), and then followed by p_after
    accept = np.minimum(p, qq).sum()
    assert abs((count == 2).mean() - accept) < SIGMAS * np.sqrt(
        accept * (1 - accept) / DRAWS)
    ok, _ = within(np.bincount(tokens[count == 2, 1], minlength=VOCAB),
                   p_after)
    assert ok
    # the same inputs through the plain rule, row by row: the same tokens
    for i in range(0, 400):
        want = rx.accept(p, p_after, qq, int(draft[i]), u[i, :3])
        assert list(tokens[i, :count[i]]) == want
    # a rule that redraws from p and not from norm(max(p - q, 0)) is not
    # the one-token sampler: the same check fails it
    wrong = np.array([rx.accept(p, p_after, qq, int(draft[i]), u[i, :3],
                                redraw_from_p=True)[0]
                      for i in range(DRAWS)])
    ok, sds = within(np.bincount(wrong, minlength=VOCAB), p)
    assert not ok and sds > 3 * SIGMAS


def test_a_draft_from_the_models_own_distribution_is_always_accepted():
    logits, after, _, u = rule_inputs()
    temp = np.full((DRAWS,), 0.9, np.float32)
    draft, q = sampling.draft_tokens(logits, temp, u[:, 3])
    tokens, count = sampling.verify_tokens(
        logits, after, q, draft, np.ones((DRAWS,), bool), temp, u[:, :3])
    assert np.all(np.asarray(count) == 2)
    assert np.array_equal(np.asarray(tokens)[:, 0], np.asarray(draft))
    # no draft: one token, from p itself, at the redraw's uniform
    tokens, count = sampling.verify_tokens(
        logits, after, q, draft, np.zeros((DRAWS,), bool), temp, u[:, :3])
    assert np.all(np.asarray(count) == 1)
    assert np.array_equal(
        np.asarray(tokens)[:, 0],
        np.asarray(sampling.sample_tokens(logits, temp, u[:, 1])))
    # greedy rows: accepted where the draft is p's argmax
    cold = np.zeros((DRAWS,), np.float32)
    best = np.argmax(logits[0])
    for d, n in ((best, 2), ((best + 1) % VOCAB, 1)):
        tokens, count = sampling.verify_tokens(
            logits[:4], after[:4], q[:4], np.full((4,), d, np.int32),
            np.ones((4,), bool), cold[:4], u[:4, :3])
        assert np.all(np.asarray(count) == n)
        assert np.all(np.asarray(tokens)[:, 0] == best)


# -- (c) ---------------------------------------------------------------------

def test_a_request_ends_exactly_on_its_count_and_on_eos(engine, batch):
    reqs, outs = batch
    req, prompt, full = reqs[2], prompts()[2], outs[2]
    two = [s for s in req.step_outputs if s["delivered"] == 2]
    assert len(two) >= 2
    before = telemetry.counter_get("decode.tokens_discarded")
    # every count: some end on a step's first position, some on its second
    for n in range(3, 10):
        out = engine.submit(prompt, max_new_tokens=n, temperature=TEMPS[2],
                            seed=9, stop_at_eos=False).result(120)
        assert np.array_equal(out, full[:n])
    assert telemetry.counter_get("decode.tokens_discarded") > before
    # eos at a step's first position (its second token is thrown away) and
    # at a step's second position
    cfg = engine.model_cfg
    old = cfg.eos_id
    try:
        for at in (0, 1):
            step = next(s for s in two
                        if list(full).index(s["tokens"][at])
                        == list(full).index(s["tokens"][0]) + at > 0)
            cfg.eos_id = step["tokens"][at]
            end = list(full).index(cfg.eos_id)
            out = engine.submit(prompt, max_new_tokens=NEW,
                                temperature=TEMPS[2], seed=9).result(120)
            assert np.array_equal(out, full[:end + 1])
    finally:
        cfg.eos_id = old


def test_a_requests_tokens_are_its_seeds_whatever_else_is_in_the_batch(
        engine, batch):
    for i, (prompt, t) in enumerate(zip(prompts(), TEMPS)):
        alone = engine.submit(prompt, max_new_tokens=NEW, temperature=t,
                              seed=7 + i, stop_at_eos=False).result(120)
        assert np.array_equal(alone, batch[1][i])


def test_the_counters_tell_drafts_from_tokens(engine, batch):
    c = telemetry.counters()
    assert c["decode.draft_proposed"] >= c["decode.draft_accepted"] > 0
    assert c["decode.tokens"] <= c["decode.rows_stepped"] \
        + c["decode.draft_accepted"]
    stats = engine.stats()
    assert 0 < stats["draft_accept_share"] < 100
    assert 1 <= stats["tokens_per_row_step"]["avg"] <= 2
    # a row's context counts once a step over the six latent layers
    assert c["decode.kv_tokens_attended"] % 4 == 0


def test_the_temperature_sets_the_share_of_drafts_accepted(engine):
    """Seeded weights: module and model agree as far as the temperature
    flattens both (benchmark/readings_xing4.py's sweep)."""
    from benchmark.readings_xing4 import accept_share

    check = {"beside": {"requests": 4, "prompt_tokens": [6, 11],
                        "new_tokens": 24}}
    rng = np.random.RandomState(2)
    cold, warm = [accept_share(engine, engine.model_cfg, check, t, rng)
                  for t in (0.5, 4.0)]
    assert cold["accept_share"] < warm["accept_share"] <= 100
    assert 1 < cold["tokens_per_row_step"] < warm["tokens_per_row_step"] < 2


def test_perf_report_shows_the_drafts_accepted():
    import io

    from tools.perf_report import render, summarize_log

    def counter(name, value):
        return {"ts": 1.0, "kind": "counter", "name": name, "value": value,
                "attrs": {"delta": value}}

    recs = [counter("decode.prefills", 4), counter("decode.tokens", 179),
            counter("decode.steps", 10), counter("decode.rows_stepped", 100),
            counter("decode.draft_proposed", 96),
            counter("decode.draft_accepted", 72),
            counter("decode.tokens_discarded", 3)]
    dc = summarize_log(recs)["decode"]
    assert (dc["draft_accept_share"], dc["tokens_per_row_step"],
            dc["tokens_discarded"]) == (75.0, 1.79, 3)
    buf = io.StringIO()
    render(summarize_log(recs), out=buf)
    assert "drafts accepted: 75.0%  tokens a row a step: 1.79" \
        in buf.getvalue()
    # a model without a module: the section is as it was
    plain = summarize_log(recs[:3])["decode"]
    assert "draft_accept_share" not in plain


# -- (d) ---------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 2.5])
def test_a_journal_record_resumes_a_drafting_session(engine, temperature):
    records = []
    engine.journal_sink = records.extend
    try:
        prompt = prompts()[1]
        full = engine.submit(prompt, max_new_tokens=NEW, seed=21,
                             temperature=temperature, stop_at_eos=False,
                             request_id="s1").result(120)
    finally:
        engine.journal_sink = None
    mid = next(r for r in records if 6 <= len(r["accepted"]) < NEW - 4)
    assert mid["last_step_tokens"] in (1, 2)
    assert mid["accepted"] == list(full[:len(mid["accepted"])])
    args = resume_args(mid)
    args["prompt"] = np.asarray(args.pop("prompt_ids"), np.int32)
    args.pop("request_id")
    tails = [engine.submit(**args).result(120) for _ in range(2)]
    assert np.array_equal(tails[0], tails[1])       # the record decides it
    assert len(mid["accepted"]) + tails[0].size == NEW
    if temperature == 0:    # greedy: the model's own argmaxes, as before
        assert np.array_equal(np.concatenate([mid["accepted"], tails[0]]),
                              full)


def test_a_record_of_a_model_without_a_module_is_unchanged():
    req = GenerationRequest(np.arange(3, 9, dtype=np.int32), 4, None,
                            session_id="s")
    req.tokens = [5, 6]
    assert "last_step_tokens" not in req.journal_record(8)
    req.last_step_tokens = 2
    assert req.journal_record(8)["last_step_tokens"] == 2
