"""ops/pallas/draft_tail.py: the drafting step's tail as two kernels, held in
interpret mode against the plain form (serving/sampling.py `verify_tokens`
and `draft_tokens`), and the engine's step record unchanged by them.

* the kernels' tokens are the plain form's except where a uniform lies within
  1e-6 of a CDF boundary, q to 1e-6: sampled, greedy and mixed rows, rows
  without a draft, padding rows, slots in a permuted order, vocabularies that
  are no multiple of `sampling.BLOCK`, buckets that are no multiple of 8;
* q is written in place: only the named slots' rows change;
* the delivered tokens are distributed as `sample_tokens`';
* `keep_step_outputs` gives the same record, key by key, under either form.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_xing4 as rx
from paddle_tpu.models import xing4
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import draft_tail as dt
from paddle_tpu.serving import sampling
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

NEAR = 1e-6


@pytest.fixture
def interpreted(monkeypatch):
    """The tail's kernels in interpret mode, every other kernel as it is."""
    monkeypatch.setattr(dt, "_route", lambda kernel, state: "interpret")


def rows(seed, b, v, slots, temperature, carried=None, slot=None):
    rng = np.random.RandomState(seed)
    logits2 = rng.normal(0, 2.0, (2 * b, v)).astype(np.float32)
    module = rng.normal(0, 2.0, (b, v)).astype(np.float32)
    q = np.asarray(jax.nn.softmax(jnp.asarray(
        rng.normal(0, 2.0, (slots + 1, v)).astype(np.float32)), axis=-1))
    if slot is None:
        slot = rng.permutation(slots)[:b]
    if carried is None:
        carried = rng.rand(b) > 0.3
    # a draft drawn from q, as the engine's are, so that some are accepted
    draft = np.array([rx.inverse_cdf(q[s], u)
                      for s, u in zip(slot, rng.random_sample(b))])
    return {"logits2": jnp.asarray(logits2), "module": jnp.asarray(module),
            "state": dt.state_rows(jnp.asarray(q)), "q": q,
            "slot": jnp.asarray(slot, jnp.int32),
            "draft": jnp.asarray(draft, jnp.int32),
            "carried": jnp.asarray(carried),
            "temperature": jnp.asarray(temperature(rng, b), jnp.float32),
            "u": jnp.asarray(rng.random_sample((b, 4)), jnp.float32)}


def sampled(t):
    return lambda rng, b: np.full(b, t)


def mixed(rng, b):
    return np.where(rng.rand(b) > 0.5, 1.3, 0.0)


CASES = {
    "sampled": dict(b=8, v=8192, slots=8, temperature=sampled(2.8)),
    "sampled_cold": dict(b=8, v=8192, slots=8, temperature=sampled(0.4)),
    "greedy": dict(b=8, v=8192, slots=8, temperature=sampled(0.0)),
    "mixed": dict(b=8, v=16384, slots=12, temperature=mixed),
    "first_step": dict(b=4, v=8192, slots=4, temperature=mixed,
                       carried=np.zeros(4, bool)),
    "padding_rows": dict(b=8, v=8192, slots=5, temperature=sampled(1.0),
                         slot=np.array([3, 0, 4, 5, 5, 5, 5, 5]),
                         carried=np.array([1, 1, 0, 0, 0, 0, 0, 0], bool)),
    "permuted_slots": dict(b=6, v=8192, slots=6, temperature=sampled(1.7),
                           slot=np.array([5, 2, 0, 4, 1, 3])),
    "vocab_1536": dict(b=5, v=1536, slots=6, temperature=mixed),
    "vocab_9000": dict(b=3, v=9000, slots=4, temperature=sampled(1.0)),
    "vocab_500": dict(b=4, v=500, slots=4, temperature=mixed),
    "bucket_1": dict(b=1, v=8192, slots=2, temperature=sampled(0.9)),
}


def verify(r, fn):
    return fn(r["logits2"], r["state"], r["slot"], r["draft"], r["carried"],
              r["temperature"], r["u"][:, :3])


def draw(r, fn):
    return fn(r["module"], r["state"], r["slot"], r["temperature"],
              r["u"][:, 3])


def near_a_boundary(mass, uniform, *tokens):
    """Both tokens lie within NEAR of where `uniform` cuts the CDF."""
    return all(rx.cdf_distance(mass, float(uniform), int(t)) < NEAR
               for t in tokens)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_verify_kernel_gives_the_plain_forms_tokens(interpreted, name):
    r = rows(sorted(CASES).index(name), **CASES[name])
    want, want_n, want_q = verify(r, dt.stock_draft_verify)
    got, got_n, got_q = verify(r, dt.draft_verify)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got_n.shape == want_n.shape and got_n.dtype == want_n.dtype
    # the copy of q the rule read: the state's rows, zero without a draft
    assert np.array_equal(np.asarray(got_q), np.asarray(want_q))
    v = r["logits2"].shape[1]
    two = np.asarray(r["logits2"]).reshape(-1, 2, v)
    for i in range(two.shape[0]):
        t = float(r["temperature"][i])
        if t <= 0:      # the argmaxes, to the letter
            assert list(got[i]) == list(want[i]) and got_n[i] == want_n[i]
            continue
        p = rx.probabilities(two[i, 0], t)
        q = r["q"][int(r["slot"][i])] * bool(r["carried"][i])
        u = np.asarray(r["u"][i])
        if got_n[i] != want_n[i]:       # u q(d) against p(d)
            d = int(r["draft"][i])
            assert abs(u[0] * q[d] - p[d]) < NEAR * p[d]
            continue
        if got[i, 0] != want[i, 0]:
            assert got_n[i] == 1 and near_a_boundary(
                np.maximum(p - q, 0), u[1], got[i, 0], want[i, 0])
        if got[i, 1] != want[i, 1]:
            assert near_a_boundary(rx.probabilities(two[i, 1], t), u[2],
                                   got[i, 1], want[i, 1])
    if name == "first_step":
        assert np.all(np.asarray(got_n) == 1)
        assert not np.asarray(got_q).any()


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_draft_kernel_writes_q_in_place_and_draws_from_it(interpreted,
                                                              name):
    r = rows(100 + sorted(CASES).index(name), **CASES[name])
    want, want_state = draw(r, dt.stock_draft_next)
    got, got_state = draw(r, dt.draft_next)
    assert got.shape == want.shape and got.dtype == want.dtype
    v = r["module"].shape[1]
    slots = r["state"].shape[0] - 1
    named = sorted({int(s) for s in np.asarray(r["slot"]) if s < slots})
    others = [s for s in range(slots) if s not in named]
    # q to 1e-6, the rows of no named slot untouched
    got_state, want_state = np.asarray(got_state), np.asarray(want_state)
    assert np.abs(got_state[named] - want_state[named]).max() < NEAR
    assert np.array_equal(got_state[others], np.asarray(r["state"])[others])
    for i, s in enumerate(np.asarray(r["slot"])):
        t = float(r["temperature"][i])
        q = rx.probabilities(np.asarray(r["module"][i]), t if t > 0 else 1.0)
        if s < slots:
            assert np.abs(dt.vocab_rows(got_state[s], v)
                          - q).max() < NEAR
        if got[i] != want[i]:
            assert t > 0 and near_a_boundary(q, r["u"][i, 3], got[i],
                                             want[i])


def test_the_state_is_the_vocabulary_in_the_kernels_order():
    q = np.random.RandomState(0).rand(3, 9000).astype(np.float32)
    state = dt.state_rows(q)
    assert state.shape == (3, dt.padded_vocab(9000) // 128, 128)
    assert np.array_equal(dt.vocab_rows(state, 9000), q)
    assert np.array_equal(np.asarray(dt.state_rows(jnp.asarray(q))), state)
    assert dt.q_state(4, 9000).shape == (5,) + state.shape[1:]
    assert dt.BLOCK == sampling.BLOCK
    assert dt.draft_tail_fingerprint() in pallas.kernels_fingerprint()


VOCAB, DRAWS, SIGMAS = 8, 2000, 4.5


def within(counts, p):
    share = counts / counts.sum()
    sd = np.sqrt(p * (1 - p) / counts.sum())
    return np.abs(share - p).max() <= SIGMAS * sd.max()


@pytest.mark.parametrize("temperature", [0.8, 1.3])
def test_the_kernels_tokens_are_distributed_as_the_one_token_samplers(
        interpreted, temperature):
    """Every row the same three distributions, a uniform each: the first
    token of a step is p's, an accepted draft is followed by the second
    position's, the draft is q's (tests/test_xing4_serving.py holds the
    plain form to the same)."""
    rng = np.random.RandomState(11)
    row = rng.normal(0, 1.5, (3, VOCAB)).astype(np.float32)
    u = jnp.asarray(rng.random_sample((DRAWS, 4)), jnp.float32)
    temp = jnp.full((DRAWS,), temperature, jnp.float32)
    slot = jnp.arange(DRAWS, dtype=jnp.int32)
    draft, state = dt.draft_next(
        jnp.tile(row[2:], (DRAWS, 1)), dt.q_state(DRAWS, VOCAB), slot, temp,
        u[:, 3])
    logits2 = jnp.tile(row[:2], (DRAWS, 1))
    tokens, count, _ = dt.draft_verify(
        logits2, state, slot, draft, jnp.ones((DRAWS,), bool), temp,
        u[:, :3])
    tokens, count = np.asarray(tokens), np.asarray(count)
    p, p_after, q = (rx.probabilities(x, temperature) for x in row)
    assert within(np.bincount(np.asarray(draft), minlength=VOCAB), q)
    assert within(np.bincount(tokens[:, 0], minlength=VOCAB), p)
    accept = np.minimum(p, q).sum()
    assert abs((count == 2).mean() - accept) < SIGMAS * np.sqrt(
        accept * (1 - accept) / DRAWS)
    assert within(np.bincount(tokens[count == 2, 1], minlength=VOCAB),
                  p_after)
    # and row by row the one-token sampler's own draws
    alone = np.asarray(sampling.sample_tokens(logits2[1::2], temp, u[:, 2]))
    assert (alone != tokens[:, 1]).mean() < 1e-3


# -- the engine's record -------------------------------------------------------
def run_engine():
    cfg = xing4.Xing4Config(max_seq_len=128, n_layers=3, first_k_dense=1,
                            dtype="float32", experts_held=(0, 32))
    params = xing4.xing4_params(cfg, 1)
    params["x4_tok_emb"] = np.random.RandomState(5).normal(
        0, 1, params["x4_tok_emb"].shape).astype(np.float32)
    eng = DecodeEngine(cfg, params, DecodeConfig(
        max_slots=4, page_size=8, kv_pages=4 * 17 + 1, max_new_tokens=64,
        prefill_buckets=[16, 32], prefix_cache=False))
    eng.start(warmup=True)
    try:
        rng = np.random.RandomState(0)
        reqs = [eng.submit(rng.randint(3, 512, n), max_new_tokens=9,
                           temperature=t, seed=7 + i, stop_at_eos=False,
                           keep_step_outputs=True)
                for i, (n, t) in enumerate(((5, 0.0), (12, 0.8), (9, 2.5)))]
        return cfg, [(r.result(120), r.step_outputs) for r in reqs]
    finally:
        eng.close()


@pytest.fixture(scope="module")
def records():
    """The same requests through an engine on the plain form (the mode the
    CPU gives) and on the kernels."""
    plain = run_engine()[1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(dt, "_route", lambda kernel, state: "interpret")
        cfg, kernels = run_engine()
    return cfg, plain, kernels


# a step's record (serving/decode.py `_keep_step`): what
# benchmark/families/xing4.py `judge_prompt` reads
RECORD = {"position": int, "draft": int, "had_draft": bool,
          "logits": (2, None), "q": (None,), "draft_logits": (None,),
          "uniforms": (4,), "tokens": list, "delivered": int}


@pytest.mark.parametrize("key", sorted(RECORD))
def test_a_kept_steps_record_is_the_same_under_either_form(records, key):
    cfg, plain, kernels = records
    kind = RECORD[key]
    for (want_tokens, want), (got_tokens, got) in zip(plain, kernels):
        assert list(got_tokens) == list(want_tokens)
        assert len(got) == len(want) > 3
        for a, b in zip(want, got):
            assert set(a) == set(b) == set(RECORD)
            if isinstance(kind, tuple):
                shape = tuple(cfg.vocab_size if n is None else n
                              for n in kind)
                assert a[key].shape == b[key].shape == shape
                assert a[key].dtype == b[key].dtype == np.float32
                assert np.abs(a[key] - b[key]).max() < NEAR
            else:
                assert type(a[key]) is type(b[key]) is kind
                assert a[key] == b[key]


def test_q_in_a_record_is_what_the_step_before_drew_from(records):
    """`q` is read from the carried state, not handed over: a step's is the
    softmax of the module's logits of the step before, and zero where there
    was no draft."""
    _, _, kernels = records
    for (_, steps), t in zip(kernels, (0.0, 0.8, 2.5)):
        assert not steps[0]["had_draft"] and not steps[0]["q"].any()
        for last, s in zip(steps, steps[1:]):
            want = rx.probabilities(last["draft_logits"], t if t > 0 else 1.0)
            assert s["had_draft"]
            assert np.abs(s["q"] - want).max() < 1e-6
