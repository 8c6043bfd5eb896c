"""models/afmoe.py behind DecodeEngine against benchmark/reference_afmoe.py
on seeded weights at a small size: 4 query heads on 1 K/V head of 16, 8 of
32 experts top-4, window 32 on a ring of 5 pages of 8, 1 dense + 4 MoE
layers (sliding x 3, full).

In float32 the engine and the reference are the same mathematics in
another order, and agree to rounding: prefill logits, then 48 greedy steps
through the cache, for contexts that pass the window and wrap the ring
twice. In bfloat16 the chip check's own limits hold them together
(families/afmoe.judge_prompt), and the same weights rounded to 8 bits fail
those limits."""

import ml_dtypes
import numpy as np
import pytest

from benchmark import reference_afmoe
from benchmark.families import afmoe as family
from paddle_tpu.core import telemetry
from paddle_tpu.models.afmoe import AfmoeConfig, afmoe_params
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

NEW = 48
RING_TOKENS = 40          # (window 32 / page 8 + 1) pages of 8


def engine_for(cfg, params, slots=4):
    return DecodeEngine(cfg, params, DecodeConfig(
        max_slots=slots, page_size=8, kv_pages=slots * 32 + 1,
        kv_ring_pages=slots * 5 + 1, prefill_buckets=[16, 64, 128],
        prefix_cache=False, max_new_tokens=64, buckets=[slots]))


def reference_for(cfg, params):
    import jax.numpy as jnp

    return reference_afmoe.Reference(
        {k: jnp.asarray(v) for k, v in params.items()},
        family.reference_config(cfg))


def check_prompt(engine, ref, prompt, new_tokens, pad_min):
    """One prompt alone in the engine: cut as the chip check cuts it,
    prefilled, decoded greedily through the cache, and judged."""
    sent = family.cut_prompt(ref, prompt, new_tokens, pad_min)
    req = engine.submit(sent, max_new_tokens=new_tokens, stop_at_eos=False,
                        keep_first_logits=True)
    chosen = req.result(600)
    return family.judge_prompt(ref, sent, req.first_logits, chosen, pad_min)


def depth_scaled(params):
    """The post-norm gains as the benchmark's seeded weights have them."""
    return {k: np.full_like(v, family.POST_NORM_GAIN)
            if k.endswith(family.POST_NORMS) else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def f32():
    cfg = AfmoeConfig(dtype="float32")
    params = afmoe_params(cfg, 3)
    engine = engine_for(cfg, params).start(warmup=False)
    yield cfg, engine, reference_for(cfg, params)
    engine.close()


@pytest.mark.parametrize("prompt_len", [5, 30, 45, 100])
def test_prefill_and_decode_agree_with_the_reference(f32, prompt_len):
    """5: inside one page; 30: under the window; 45: past the window, the
    ring wraps while decoding; 100: the prefill itself wraps the ring
    twice and decoding wraps it once more (148 > 3 x 40)."""
    cfg, engine, ref = f32
    rng = np.random.RandomState(prompt_len)
    got = check_prompt(engine, ref,
                       rng.randint(3, cfg.vocab_size, prompt_len), NEW, 192)
    assert got["sent"] + NEW > (3 * RING_TOKENS if prompt_len == 100 else 0)
    assert got["logit_err"] < 1e-4
    assert got["gap"] < 1e-4 and len(got["gaps"]) == NEW
    assert got["undecided"] < NEW


def test_continuous_batching_of_unequal_rows_is_each_rows_own(f32):
    """Seven requests of unequal length over four slots, admitted and
    retired at step boundaries: each gets the tokens it gets alone, and
    those are the reference's within the margin."""
    cfg, engine, ref = f32
    rng = np.random.RandomState(7)
    lengths = [(4, 9), (37, 20), (90, 30), (12, 44), (61, 5), (33, 33),
               (100, 12)]
    prompts = [rng.randint(3, cfg.vocab_size, n) for n, _ in lengths]
    telemetry.reset()
    together = [engine.submit(p, max_new_tokens=new, stop_at_eos=False)
                for p, (_, new) in zip(prompts, lengths)]
    together = [r.result(300) for r in together]
    c = telemetry.counters()
    assert c["decode.moe_pairs_total"] == 4 * 4 * c["decode.tokens"]
    assert 0 < c["decode.moe_pairs_held"] < c["decode.moe_pairs_total"]
    assert 0 < c["decode.moe_experts_hit"] <= c["decode.moe_pairs_held"]
    assert c["decode.rows_past_window"] > 0
    # a ring layer's rows read at most their window
    assert c["decode.kv_tokens_attended"] < 5 * sum(
        new * (n + new) for n, new in lengths)
    for p, (n, new), tokens in zip(prompts, lengths, together):
        alone = engine.generate(p, max_new_tokens=new, stop_at_eos=False,
                                timeout=300)
        assert list(alone) == list(tokens), n
        rows, gap = ref.rows(np.concatenate([p, tokens]), 192, n - 1, new)
        decided = gap[n - 1:n - 1 + new] > reference_afmoe.ROUTE_EPS
        gaps = reference_afmoe.greedy_gaps(rows, tokens)
        assert gaps[decided].max() < 1e-4, n


def test_the_decode_step_returns_its_routing_counts_with_its_tokens(f32):
    """One fetch: [slots] tokens and the three int32 behind them."""
    cfg, engine, _ = f32
    entry = engine._entry("step", 4)
    out, _pools, _last = entry(engine._params, engine.kv.make_arrays(),
                               engine._zero_feed("step", 4),
                               engine._last_tokens)
    out = np.asarray(out)
    assert out.shape == (4 + 3,) and out.dtype == np.int32
    assert list(out[4:]) == [0, 0, 0]            # no live row, no pair
    assert engine._feed_names[("step", 4)] == (
        "tokens", "positions", "page_table", "ring_table", "sampling",
        "carry")


# -- bfloat16 and the lower-precision control --------------------------------

# post-norm gains depth-scaled as the chip's configuration has them
WIDE = dict(hidden_size=128, head_dim=32, intermediate_size=256,
            moe_intermediate_size=64)
CONTROL_PROMPTS = (30, 45, 100)


@pytest.fixture(scope="module")
def bf16():
    cfg = AfmoeConfig(**WIDE)
    assert cfg.dtype == "bfloat16"
    params = depth_scaled(afmoe_params(cfg, 3))
    return cfg, params, reference_for(cfg, params)


def readings(cfg, params, ref):
    engine = engine_for(cfg, params).start(warmup=False)
    try:
        rng = np.random.RandomState(0)
        return [check_prompt(
            engine, ref, rng.randint(3, cfg.vocab_size, n), 40, 192)
            for n in CONTROL_PROMPTS]
    finally:
        engine.close()


def test_bfloat16_weights_and_pages_hold_the_chip_checks_limits(bf16):
    cfg, params, ref = bf16
    assert params["af_l1_ex_w1"].dtype == ml_dtypes.bfloat16
    got = readings(cfg, params, ref)
    assert max(g["logit_err"] for g in got) < reference_afmoe.LOGIT_ERR
    assert max(g["gap"] for g in got) < reference_afmoe.MARGIN


def test_weights_rounded_to_8_bits_fail_the_chip_checks_limits(bf16):
    """The lower-precision control: the engine on the same weights rounded
    to float8 (e4m3), against the reference on the weights as they are,
    is not correct by the limits the chip check uses."""
    cfg, params, ref = bf16
    rounded = {k: (v.astype(ml_dtypes.float8_e4m3fn)
                   .astype(ml_dtypes.bfloat16)
                   if v.dtype == ml_dtypes.bfloat16 else v)
               for k, v in params.items()}
    got = readings(cfg, rounded, ref)
    assert min(g["logit_err"] for g in got) > 2 * reference_afmoe.LOGIT_ERR
    assert max(g["gap"] for g in got) > reference_afmoe.MARGIN
