"""models/lfm2.py behind `DecodeEngine` at a small size on the CPU:
`PagedKVCache` with TAIL-ONLY state layers (a conv tail and no recurrent
state: no state array, the tail's bytes alone), the two gated short
convolution ops against a padded sum and the `ssm_conv_*` pair unchanged
through the shared tail helpers, `routed_experts_share` with the published
`norm_eps`, prefill + decode through pages AND tails against the plain
reference (prompts shorter than the tail, a prompt that ends mid-bucket),
continuous batching over reused slots, the step's logits on a request that
keeps them, and the engine's counters and refusals."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_lfm2 as rl
from benchmark.families import lfm2 as family
from paddle_tpu.core import registry, telemetry
from paddle_tpu.models import lfm2
from paddle_tpu.ops import ssm_ops
from paddle_tpu.parallel.moe import routed_experts_share
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import (LayerCache, PagedKVCache,
                                         state_array_names)

C, K = 16, 3      # channels and taps of the ops' cases


def small(**kw):
    kw.setdefault("dtype", "float32")
    return lfm2.Lfm2Config(max_seq_len=128, **kw)


def engine_for(cfg, params, **kw):
    conf = dict(max_slots=4, page_size=8, kv_pages=4 * 16 + 1,
                prefill_buckets=[16, 32, 64], max_new_tokens=32)
    conf.update(kw)
    return DecodeEngine(cfg, params, DecodeConfig(**conf))


def reference_for(cfg, params, **kw):
    return rl.Reference({k: jnp.asarray(v) for k, v in params.items()},
                        family.reference_config(cfg), **kw)


def run_op(name, ins, attrs=None):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs or {})


def kept(req, cfg):
    """What `family.judge_prompt` reads of a request that kept everything."""
    tails = np.stack([np.asarray(req.final_state[f"conv_tail_{i}"],
                                 np.float32)
                      for i in family.conv_layers(cfg)])
    pages = np.stack([np.stack([
        np.asarray(req.final_pages[f"kv_{p}_{i}"], np.float32).reshape(
            -1, cfg.num_kv_heads * cfg.head_dim) for p in "kv"], axis=1)
        for i in range(cfg.n_layers) if cfg.is_attention(i)])
    steps = np.stack([s["logits"] for s in req.step_outputs])
    return (np.asarray(req.first_logits), req.result(0), steps, tails, pages)


# -- the cache ----------------------------------------------------------------

def test_a_tail_only_layer_is_in_the_state_class_with_its_tails_bytes():
    tail_only = LayerCache(0, conv_tail=(2, 64))
    assert tail_only.state_only and tail_only.tail_only
    assert not LayerCache(0, ssm_state=(2, 4, 4),
                          conv_tail=(2, 64)).tail_only
    assert state_array_names(3, tail_only=True) == ("conv_tail_3",)
    assert state_array_names(3) == ("ssm_state_3", "conv_tail_3")
    kv = PagedKVCache([tail_only, LayerCache(32), tail_only], 8, 33,
                      dtype="bfloat16", slots=4)
    assert kv.has_state and kv.state_layers == [0, 2] == kv.tail_layers
    assert kv.context.layers == [1]
    # two rows of 64 bfloat16 a layer: nothing of a recurrent state
    assert kv.state_slot_bytes == 2 * 2 * 64 * 2
    assert kv.state_pool_bytes == 5 * kv.state_slot_bytes
    arrays = kv.make_arrays()
    assert sorted(arrays) == ["conv_tail_0", "conv_tail_2", "kv_k_1",
                              "kv_v_1"]
    assert arrays["conv_tail_2"].shape == (5, 2, 64)
    assert kv.state_names() == ["conv_tail_0", "conv_tail_2"]
    assert kv.stats()["state"]["layers"] == 2


@pytest.mark.parametrize("kw", [{}, {"window": 8, "conv_tail": (2, 8)},
                                {"latent": True, "conv_tail": (2, 8)}])
def test_a_layer_with_neither_pages_nor_state_is_refused(kw):
    with pytest.raises(ValueError, match="state-only"):
        LayerCache(0, **kw)


def test_a_model_of_tail_only_layers_alone_has_no_context():
    with pytest.raises(ValueError, match="context"):
        PagedKVCache([LayerCache(0, conv_tail=(2, 8))], 8, 9, slots=2)


# -- the ops ------------------------------------------------------------------

def plain_short_conv(bcx, w):
    """[T, 3C] -> (y [T, C], z [T, C]): the padded sum, a sequence."""
    b, c, x = np.split(np.asarray(bcx, np.float64), 3, axis=-1)
    z = b * x
    zp = np.concatenate([np.zeros((K - 1, z.shape[1])), z])
    conv = sum(np.asarray(w, np.float64)[k] * zp[k:k + len(z)]
               for k in range(K))
    return c * conv, z


@pytest.mark.parametrize("length", [1, 2, 5, 11])
def test_the_prefill_op_is_the_padded_sum_and_keeps_the_real_tail(length):
    rng = np.random.RandomState(length)
    bcx = rng.normal(size=(1, 12, 3 * C)).astype(np.float32)
    w = rng.normal(size=(K, C)).astype(np.float32)
    pool = jnp.full((4, K - 1, C), 7.0, jnp.float32)
    out = run_op("gated_short_conv_prefill",
                 {"BCX": jnp.asarray(bcx), "ConvTail": pool,
                  "Slots": jnp.asarray([2], jnp.int32),
                  "Lengths": jnp.asarray([length], jnp.int32),
                  "W": jnp.asarray(w)})
    y, z = plain_short_conv(bcx[0], w)
    np.testing.assert_allclose(out["Y"][0], y, rtol=1e-5, atol=1e-5)
    tail = np.asarray(out["ConvTailOut"])
    # z of the last two REAL tokens, zeros before a prompt shorter than that
    want = np.concatenate([np.zeros((K - 1, C)), z[:length]])[-(K - 1):]
    np.testing.assert_allclose(tail[2], want, rtol=1e-5, atol=1e-6)
    assert (tail[[0, 1, 3]] == 7.0).all()        # no other slot is touched


def test_the_step_op_continues_the_prefill_token_by_token():
    rng = np.random.RandomState(0)
    bcx = rng.normal(size=(9, 3 * C)).astype(np.float32)
    w = rng.normal(size=(K, C)).astype(np.float32)
    y, z = plain_short_conv(bcx, w)
    pool = jnp.zeros((3, K - 1, C), jnp.float32)
    slots = jnp.asarray([1, 2], jnp.int32)       # row 1 is a padding row
    for t in range(9):
        out = run_op("gated_short_conv_update",
                     {"BCX": jnp.asarray(np.stack([bcx[t], bcx[0]])),
                      "ConvTail": pool, "Slots": slots,
                      "W": jnp.asarray(w)})
        pool = out["ConvTailOut"]
        np.testing.assert_allclose(out["Y"][0], y[t], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool)[1], z[-2:], rtol=1e-6)
    assert (np.asarray(pool)[0] == 0).all()


def test_the_tail_is_kept_in_the_pools_dtype():
    rng = np.random.RandomState(1)
    bcx = jnp.asarray(rng.normal(size=(1, 3 * C)), jnp.float32)
    out = run_op("gated_short_conv_update",
                 {"BCX": bcx, "ConvTail": jnp.zeros((2, K - 1, C),
                                                    jnp.bfloat16),
                  "Slots": jnp.asarray([0], jnp.int32),
                  "W": jnp.ones((K, C), jnp.float32)})
    assert out["ConvTailOut"].dtype == jnp.bfloat16
    assert out["Y"].dtype == jnp.float32


SSM = {"n_heads": 2, "head_dim": 4, "n_groups": 1, "d_state": 4}


def test_the_ssm_conv_pair_is_what_it_was_through_the_shared_helpers():
    """`ssm_conv_update` / `ssm_conv_prefill` against their statement before
    the tail moved into helpers: silu after the sum, the bias, the split."""
    rng = np.random.RandomState(2)
    xbc = rng.normal(size=(2, 7, C)).astype(np.float32)
    w = rng.normal(size=(4, C)).astype(np.float32)
    bias = rng.normal(size=(C,)).astype(np.float32)
    lengths = np.asarray([7, 2], np.int32)
    slots = jnp.asarray([1, 0], jnp.int32)
    pool = jnp.zeros((3, 3, C), jnp.float32)
    out = run_op("ssm_conv_prefill",
                 {"XBC": jnp.asarray(xbc), "ConvTail": pool, "Slots": slots,
                  "Lengths": jnp.asarray(lengths), "W": jnp.asarray(w),
                  "Bias": jnp.asarray(bias)}, SSM)
    xp = np.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    y = jax.nn.silu(sum(w[j] * xp[:, j:j + 7] for j in range(4)) + bias)
    np.testing.assert_allclose(
        np.concatenate([out["X"], out["B"], out["C"]], axis=-1), y,
        rtol=1e-5, atol=1e-6)
    tail = np.asarray(out["ConvTailOut"])
    np.testing.assert_array_equal(tail[1], xbc[0, 4:7])
    np.testing.assert_array_equal(tail[0], xp[1, 2:5])   # a zero, then two
    new = rng.normal(size=(2, C)).astype(np.float32)
    step = run_op("ssm_conv_update",
                  {"XBC": jnp.asarray(new), "ConvTail": out["ConvTailOut"],
                   "Slots": slots, "W": jnp.asarray(w),
                   "Bias": jnp.asarray(bias)}, SSM)
    win = np.concatenate([tail[[1, 0]], new[:, None]], axis=1)
    np.testing.assert_allclose(
        np.concatenate([step["X"], step["B"], step["C"]], axis=-1),
        jax.nn.silu(np.sum(win * w[None], axis=1) + bias), rtol=1e-5,
        atol=1e-6)
    np.testing.assert_array_equal(np.asarray(step["ConvTailOut"])[1],
                                  win[0, 1:])
    # the helpers are the pair's own and the short convolution's
    from paddle_tpu.ops import short_conv_ops

    assert short_conv_ops.conv_window is ssm_ops.conv_window
    assert short_conv_ops.conv_tail_write is ssm_ops.conv_tail_write


@pytest.mark.parametrize("eps", [None, 1e-6, 0.5])
def test_routed_experts_share_divides_by_the_kept_sum_and_norm_eps(eps):
    """Against the dense sum over every expert; the default is 1e-20, as
    every accepted program reads it."""
    rng = np.random.RandomState(3)
    t, h, f, e, k = 12, 16, 8, 8, 3
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, size=(e,)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(e, h, f)) * h ** -0.5,
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(e, f, h)) * f ** -0.5, jnp.float32)
    kw = {} if eps is None else {"norm_eps": eps}
    out, counts = routed_experts_share(x, rw, bias, w1, w3, w2, top_k=k,
                                       held_lo=0, **kw)
    s = jax.nn.sigmoid(x @ rw)
    _, idx = jax.lax.top_k(s + bias, k)
    kept_s = jnp.take_along_axis(s, idx, axis=1)
    weight = kept_s / (kept_s.sum(1, keepdims=True)
                       + (1e-20 if eps is None else eps))
    dense = jnp.zeros_like(x)
    for j in range(e):
        wj = jnp.sum(jnp.where(idx == j, weight, 0.0), axis=1)
        dense += wj[:, None] * ((jax.nn.silu(x @ w1[j]) * (x @ w3[j]))
                                @ w2[j])
    np.testing.assert_allclose(out, dense, rtol=2e-4, atol=2e-5)
    assert int(counts[0]) == t * k
    if eps == 0.5:      # the weights no longer sum to one
        base, _ = routed_experts_share(x, rw, bias, w1, w3, w2, top_k=k,
                                       held_lo=0)
        assert float(jnp.max(jnp.abs(base - out))) > 1e-2


# -- the model ----------------------------------------------------------------

def test_mixer_and_feed_forward_are_independent_of_each_other():
    cfg = small(layer_types=("full_attention", "conv", "conv"),
                num_dense_layers=2)
    assert [cfg.is_attention(i) for i in range(3)] == [True, False, False]
    assert [cfg.is_moe(i) for i in range(3)] == [False, False, True]
    specs = lfm2.param_specs(cfg)
    assert "lf_l0_q_w" in specs and "lf_l0_w1" in specs       # attn + dense
    assert "lf_l1_in_w" in specs and "lf_l1_w1" in specs      # conv + dense
    assert "lf_l2_in_w" in specs and "lf_l2_ex_w1" in specs   # conv + routed
    assert specs["lf_l2_expert_bias"][1] == lfm2.EXPERT_BIAS
    # the head is the embedding: no second array
    assert not [n for n in specs if "head" in n]
    layout = cfg.served().cache_layout()
    assert [lc.tail_only for lc in layout] == [False, True, True]
    assert layout[1].conv_tail == (cfg.conv_L_cache - 1, cfg.hidden_size)
    with pytest.raises(ValueError, match="attention layer"):
        small(layer_types=("conv", "conv"))
    with pytest.raises(ValueError, match="layer_types"):
        small(layer_types=("conv", "mamba"))


def test_the_programs_are_the_layers_the_configuration_names():
    cfg = small()
    served_model = cfg.served()
    kv = PagedKVCache(served_model.cache_layout(), 8, 65,
                      dtype=served_model.kv_dtype, slots=4)
    main, feeds, fetches = served_model.build_step_program(4, kv)
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_short_conv_update") == 3
    assert types.count("cached_kv_attention") == 1
    assert types.count("routed_experts") == 3 and types.count("swiglu") == 1
    assert "ssm_conv_update" not in types
    assert feeds == ["tokens", "positions", "state_slots", "page_table"]
    assert fetches == ["logits", "conv_tail_0_out", "conv_tail_1_out",
                       "kv_k_2_out", "kv_v_2_out", "conv_tail_3_out",
                       "step_counts"]
    routed = [op for op in main.global_block().ops
              if op.type == "routed_experts"]
    assert all(op.attrs["norm_eps"] == 1e-6 for op in routed)
    head = [op for op in main.global_block().ops
            if op.type == "linear_acc32"][-1]
    assert head.attrs["transpose_Y"] and "lf_tok_emb" in str(head.inputs)
    main, feeds, fetches = served_model.build_prefill_program(32, kv)
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_short_conv_prefill") == 3
    assert types.count("gqa_prefill_attention") == 1
    with pytest.raises(NotImplementedError, match="chunked prefill"):
        served_model.build_chunk_prefill_program(8, kv)


@pytest.mark.parametrize("heads,prompt,block", [
    (32, 256, 256), (32, 384, 384), (32, 768, 384), (32, 2048, 512),
    (32, 3072, 256), (32, 4096, 256), (4, 16, 16)])
def test_the_prefills_query_block_divides_the_bucket(heads, prompt, block):
    got = lfm2.prefill_block_q(heads, prompt)
    assert got == block and prompt % got == 0
    assert heads * got * prompt <= lfm2.PREFILL_SCORES


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_steps_are_the_references_forward(dtype):
    """Prompts of 1 and 2 tokens (shorter than the tail), one that ends
    mid-bucket and one that fills its bucket: the prefill's logits, the
    logits of every step, the tails after the decode and the pages."""
    cfg = small(dtype=dtype)
    params = lfm2.lfm2_params(cfg, 3)
    ref = reference_for(cfg, params)
    engine = engine_for(cfg, params).start()
    rng = np.random.RandomState(0)
    try:
        for n in (1, 2, 13, 32):
            sent = rng.randint(3, cfg.vocab_size, n).astype(np.int32)
            req = engine.submit(sent, max_new_tokens=7, stop_at_eos=False,
                                temperature=0.0 if n != 13 else 5.0,
                                seed=7 if n == 13 else None,
                                keep_first_logits=True,
                                keep_final_state=True,
                                keep_final_pages=True,
                                keep_step_outputs=True)
            req.result(120)
            assert [s["position"] for s in req.step_outputs] \
                == list(range(n, n + 6))
            assert [s["token"] for s in req.step_outputs] \
                == list(req.result(0)[1:])
            got = family.judge_prompt(ref, sent, kept(req, cfg), 64)
            # the reference rounds where the engine rounds, so in bfloat16
            # the first layer's tail is the engine's to the last bit; at
            # this width a routing choice still turns at some positions
            # (a row there is off by a whole expert): the median row and
            # tail are held
            if dtype == "float32":
                assert max(got["rows"]) < 2e-4, (n, got["rows"])
            assert np.median(got["rows"]) < (2e-4 if dtype == "float32"
                                             else 0.2), (n, got["rows"])
            assert np.median(got["tail_err"]) < (
                2e-5 if dtype == "float32" else 0.05)
            assert got["tail_err"][0] < (2e-5 if dtype == "float32"
                                         else 1e-9)
            assert np.median(got["kv_first"]) < (
                2e-5 if dtype == "float32" else 0.02)
    finally:
        engine.close(drain=False, timeout=30)


def test_continuous_batching_over_reused_slots_is_each_request_alone():
    """Seven requests of different lengths through two slots: every slot is
    handed on to a successor whose tails start from ITS prefill, not from
    the last owner's (a one-token prompt's tail is a zero row and its own
    z). Each request's tokens are, bitwise, what it gets decoded alone."""
    cfg = small()
    params = lfm2.lfm2_params(cfg, 5)
    ref = reference_for(cfg, params)
    rng = np.random.RandomState(0)
    lengths = [5, 40, 1, 9, 30, 2, 22]
    news = [12, 6, 9, 14, 5, 11, 8]
    prompts = [rng.randint(3, cfg.vocab_size, n) for n in lengths]
    telemetry.reset()
    engine = engine_for(cfg, params, max_slots=2,
                        kv_pages=2 * 16 + 1).start()
    try:
        reqs = [engine.submit(p, max_new_tokens=n, stop_at_eos=False,
                              temperature=3.0, seed=11 + i,
                              keep_final_state=True)
                for i, (p, n) in enumerate(zip(prompts, news))]
        together = [r.result(180) for r in reqs]
        assert telemetry.counter_get("decode.state_slots_seated") == 7
        alone = [engine.generate(p, timeout=180, max_new_tokens=n,
                                 stop_at_eos=False, temperature=3.0,
                                 seed=11 + i)
                 for i, (p, n) in enumerate(zip(prompts, news))]
    finally:
        engine.close(drain=False, timeout=30)
    for a, b in zip(together, alone):
        np.testing.assert_array_equal(a, b)
    for prompt, new, req, tokens in zip(prompts, news, reqs, together):
        fed = len(prompt) + new - 1
        _, tails, _ = ref.rows(np.concatenate([prompt, tokens]), 64,
                                  len(prompt) - 1, new, tail_at=fed - 1)
        got = np.stack([np.asarray(req.final_state[f"conv_tail_{i}"])
                        for i in family.conv_layers(cfg)])
        assert max(rl.tail_errors(got, tails)) < 2e-5


def test_the_engine_counts_conv_rows_keys_and_routed_pairs():
    cfg = small()
    telemetry.reset()
    engine = engine_for(cfg, lfm2.lfm2_params(cfg, 1)).start()
    try:
        engine.generate(np.arange(3, 12), timeout=120, max_new_tokens=6,
                        stop_at_eos=False)
        c = telemetry.counters()
        assert c["decode.steps"] == 5
        # three convolution layers a live row, and NO recurrent state
        assert c["decode.conv_rows_updated"] == 5 * 3
        assert "decode.state_rows_updated" not in c
        assert c["decode.state_slots_seated"] == 1
        assert c["decode.kv_tokens_attended"] == sum(range(10, 15))
        assert c["decode.moe_pairs_total"] == 5 * 4 * 3
        assert c["decode.moe_pairs_held"] == c["decode.moe_pairs_total"]
        assert 0 < c["decode.moe_experts_hit"] <= c["decode.moe_pairs_held"]
        stats = engine.stats()
        assert stats["conv_rows_updated"] == 15
        assert stats["kv_cache"]["state"]["layers"] == 3
        gauges = telemetry.snapshot()["gauges"]
        assert gauges["mem.serving.state_pool_bytes"] \
            == 5 * 3 * 2 * cfg.hidden_size * 4
        assert gauges["mem.serving.kv_pool_bytes"] \
            == 2 * 65 * 8 * cfg.num_kv_heads * cfg.head_dim * 4
    finally:
        engine.close(drain=False, timeout=30)


def test_a_request_that_asks_for_nothing_keeps_no_step_logits():
    cfg = small()
    engine = engine_for(cfg, lfm2.lfm2_params(cfg, 1)).start()
    try:
        req = engine.submit(np.arange(3, 8), max_new_tokens=3,
                            stop_at_eos=False)
        req.result(120)
        assert req.step_outputs is False
    finally:
        engine.close(drain=False, timeout=30)


def test_a_program_compiled_mid_service_touches_the_scratch_slot_alone():
    cfg = small()
    engine = engine_for(cfg, lfm2.lfm2_params(cfg, 2))
    for n in engine.kv.state_names():
        engine._pools[n] = engine._pools[n] + 1.0
    engine._entry("step", 4)
    engine._entry("prefill", 16)
    for n in engine.kv.state_names():
        np.testing.assert_array_equal(np.asarray(engine._pools[n])[:4], 1.0)


@pytest.mark.parametrize("kw", [{"prefix_cache": True},
                                {"role": "prefill"}])
def test_the_engine_refuses_the_prefix_store_for_a_tail(kw):
    cfg = small()
    with pytest.raises(ValueError, match="conv tail alone"):
        engine_for(cfg, lfm2.lfm2_params(cfg, 0), **kw)
