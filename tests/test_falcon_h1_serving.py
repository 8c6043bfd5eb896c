"""models/falcon_h1.py behind `DecodeEngine` at a small size on the CPU: the
per-slot state class of `PagedKVCache`, the state-space ops (the chunked
scan against the sequential recurrence, the conv tail of the last real
tokens, one step in place by slot), the state kernel against its stock
lowering, prefill + decode through pages AND state against the plain
reference, continuous batching over reused slots, and the engine's
refusals for a model with recurrent state."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_falcon_h1 as rf
from benchmark.families import falcon_h1 as family
from paddle_tpu.core import costmodel, registry, telemetry
from paddle_tpu.models import falcon_h1
from paddle_tpu.ops.pallas import ssm_state_update as ssu
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import (LayerCache, PagedKVCache,
                                         state_array_names)

H, P, G, N, K = 4, 8, 2, 16, 4
ATTRS = {"n_heads": H, "head_dim": P, "n_groups": G, "d_state": N}
CONV = H * P + 2 * G * N


def small(**kw):
    kw.setdefault("dtype", "float32")
    return falcon_h1.FalconH1Config(max_seq_len=128, **kw)


def engine_for(cfg, params, **kw):
    conf = dict(max_slots=4, page_size=8, kv_pages=4 * 16 + 1,
                prefill_buckets=[16, 32, 64], max_new_tokens=32)
    conf.update(kw)
    return DecodeEngine(cfg, params, DecodeConfig(**conf))


def reference_for(cfg, params, **kw):
    return rf.Reference({k: jnp.asarray(v) for k, v in params.items()},
                        family.reference_config(cfg), **kw)


def run_op(name, ins, attrs):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs)


def engine_state(req, cfg):
    """[layers, heads, head_dim, d_state] of what the request's slot held."""
    return np.swapaxes(np.stack([
        np.asarray(req.final_state[f"ssm_state_{i}"])
        for i in range(cfg.n_layers)]), -1, -2)


# -- the cache ---------------------------------------------------------------

def test_a_state_layer_keeps_a_state_and_a_tail_a_slot_beside_its_pages():
    telemetry.reset()
    layout = [LayerCache(16, ssm_state=(H, N, P), conv_tail=(K - 1, CONV))] * 2
    assert state_array_names(1) == ("ssm_state_1", "conv_tail_1")
    kv = PagedKVCache(layout, page_size=8, context_pages=9, dtype="bfloat16",
                      slots=3)
    assert kv.has_state and kv.state_layers == [0, 1]
    arrays = kv.make_arrays()
    assert sorted(arrays) == ["conv_tail_0", "conv_tail_1", "kv_k_0",
                              "kv_k_1", "kv_v_0", "kv_v_1", "ssm_state_0",
                              "ssm_state_1"]
    # a state for every slot and the scratch slot; d_state on sublanes
    assert arrays["ssm_state_1"].shape == (4, H, N, P)
    assert arrays["ssm_state_1"].dtype == jnp.float32
    assert arrays["conv_tail_0"].shape == (4, K - 1, CONV)
    assert arrays["conv_tail_0"].dtype == jnp.bfloat16
    slot = 2 * (H * P * N * 4 + CONV * (K - 1) * 2)
    assert kv.state_slot_bytes == slot and kv.state_pool_bytes == 4 * slot
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["mem.serving.state_pool_bytes"] == 4 * slot
    assert gauges["mem.serving.state_pool_bytes.used"] == 0
    kv.note_state_slots(2)
    assert telemetry.snapshot()["gauges"][
        "mem.serving.state_pool_bytes.used"] == 2 * slot
    assert kv.stats()["state"] == {
        "layers": 2, "slots": 3, "slot_bytes": slot,
        "pool_bytes": 4 * slot, "used_bytes": 2 * slot}
    # the HBM ledger books the class beside the pages
    ledger = costmodel.ledger()
    assert ledger["serving_state_pool_bytes"] == 4 * slot
    assert ledger["serving_state_used_bytes"] == 2 * slot
    # the pages are the class they were
    assert kv.pool_bytes == 2 * 2 * 9 * 8 * 16 * 2


def test_a_model_without_state_has_no_state_class():
    telemetry.reset()
    kv = PagedKVCache([LayerCache(16)] * 2, page_size=8, context_pages=9)
    assert not kv.has_state and kv.state_pool_bytes == 0
    assert sorted(kv.make_arrays()) == ["kv_k_0", "kv_k_1", "kv_v_0",
                                       "kv_v_1"]
    assert "state" not in kv.stats()
    assert "mem.serving.state_pool_bytes" not in \
        telemetry.snapshot()["gauges"]
    with pytest.raises(ValueError, match="slot count"):
        PagedKVCache([LayerCache(16, ssm_state=(H, N, P),
                                 conv_tail=(K - 1, CONV))], 8, 9)


# -- the ops -----------------------------------------------------------------

def drawn(seed, s):
    rng = np.random.RandomState(seed)
    return {"X": rng.randn(1, s, H * P).astype(np.float32),
            "B": rng.randn(1, s, G * N).astype(np.float32),
            "C": rng.randn(1, s, G * N).astype(np.float32),
            "Dt": rng.randn(1, s, H).astype(np.float32),
            "ALog": np.log(rng.uniform(1, 16, H)).astype(np.float32),
            "D": rng.randn(H).astype(np.float32),
            "DtBias": rng.uniform(-5, -1, H).astype(np.float32)}


def sequential(ins, length):
    """The recurrence token by token in float64 -> (y [length, H*P], the
    state after the last token [H, N, P])."""
    x = ins["X"][0].astype(np.float64).reshape(-1, H, P)
    bm = np.repeat(ins["B"][0].astype(np.float64).reshape(-1, G, N),
                   H // G, axis=1)
    cm = np.repeat(ins["C"][0].astype(np.float64).reshape(-1, G, N),
                   H // G, axis=1)
    dt = np.log1p(np.exp(ins["Dt"][0].astype(np.float64) + ins["DtBias"]))
    a = -np.exp(ins["ALog"].astype(np.float64))
    state = np.zeros((H, N, P))
    ys = []
    for t in range(length):
        state = np.exp(dt[t] * a)[:, None, None] * state \
            + bm[t][:, :, None] * (dt[t][:, None] * x[t])[:, None, :]
        ys.append(np.einsum("hnp,hn->hp", state, cm[t])
                  + ins["D"][:, None] * x[t])
    return np.stack(ys).reshape(length, H * P), state


@pytest.mark.parametrize("length,bucket", [(1, 128), (127, 128), (128, 128),
                                           (129, 256), (200, 512)])
def test_the_chunked_scan_is_the_sequential_recurrence(length, bucket):
    """Chunks of 128 over a padded bucket: every real position's output
    and the state after the last REAL token, written at the slot."""
    ins = drawn(length, bucket)
    pool = jnp.full((3, H, N, P), 7.0, jnp.float32)    # the last owner's
    out = run_op("ssm_chunk_scan",
                 dict(ins, State=pool, Slots=np.asarray([1], np.int32),
                      Lengths=np.asarray([length], np.int32)),
                 dict(ATTRS, chunk=128))
    y, state = sequential(ins, length)
    np.testing.assert_allclose(np.asarray(out["Y"])[0, :length], y,
                               rtol=2e-4, atol=2e-4)
    new = np.asarray(out["StateOut"])
    np.testing.assert_allclose(new[1], state, rtol=2e-4, atol=2e-4)
    assert (new[0] == 7.0).all() and (new[2] == 7.0).all()


def test_the_prefills_conv_tail_is_that_of_the_last_real_tokens():
    rng = np.random.RandomState(0)
    xbc = rng.randn(1, 16, CONV).astype(np.float32)
    w = rng.randn(K, CONV).astype(np.float32)
    bias = rng.randn(CONV).astype(np.float32)
    pool = jnp.full((3, K - 1, CONV), 9.0, jnp.float32)
    for length in (1, 2, 3, 11, 16):
        out = run_op("ssm_conv_prefill",
                     {"XBC": xbc, "ConvTail": pool, "W": w, "Bias": bias,
                      "Slots": np.asarray([2], np.int32),
                      "Lengths": np.asarray([length], np.int32)}, ATTRS)
        tail = np.asarray(out["ConvTailOut"])
        want = np.zeros((K - 1, CONV), np.float32)
        kept = xbc[0, max(0, length - (K - 1)):length]
        want[K - 1 - len(kept):] = kept
        np.testing.assert_array_equal(tail[2], want)
        assert (tail[:2] == 9.0).all()
        # the convolution itself, causal, by hand at one position
        t = length - 1
        window = np.zeros((K, CONV), np.float32)
        seen = xbc[0, max(0, t - K + 1):t + 1]
        window[K - len(seen):] = seen
        pre = (window * w).sum(0) + bias
        got = np.concatenate([np.asarray(out[k])[0, t] for k in "XBC"])
        np.testing.assert_allclose(got, pre / (1 + np.exp(-pre)), rtol=1e-5,
                                   atol=1e-5)


def test_steps_by_slot_continue_what_the_prefill_wrote():
    """A prompt's first 9 tokens through the prefill ops, the rest one step
    a token through the update ops at the slot: the whole prompt's scan."""
    ins = drawn(3, 16)
    rng = np.random.RandomState(1)
    xbc = rng.randn(1, 16, CONV).astype(np.float32)
    w = rng.randn(K, CONV).astype(np.float32)
    bias = np.zeros(CONV, np.float32)
    slot = np.asarray([1], np.int32)
    whole = run_op("ssm_conv_prefill",
                   {"XBC": xbc, "ConvTail": jnp.zeros((3, K - 1, CONV)),
                    "W": w, "Bias": bias, "Slots": slot,
                    "Lengths": np.asarray([16], np.int32)}, ATTRS)
    scan = {k: ins[k] for k in ("Dt", "ALog", "D", "DtBias")}
    scan.update({k: np.asarray(whole[k]) for k in "XBC"})
    y_all, state_all = sequential(scan, 16)
    conv = run_op("ssm_conv_prefill",
                  {"XBC": xbc, "ConvTail": jnp.zeros((3, K - 1, CONV)),
                   "W": w, "Bias": bias, "Slots": slot,
                   "Lengths": np.asarray([9], np.int32)}, ATTRS)
    out = run_op("ssm_chunk_scan",
                 dict(scan, State=jnp.zeros((3, H, N, P)), Slots=slot,
                      Lengths=np.asarray([9], np.int32)),
                 dict(ATTRS, chunk=16))
    tail, state = conv["ConvTailOut"], out["StateOut"]
    # two rows a step: the request's and a padding row on the scratch slot
    slots = np.asarray([1, 2], np.int32)
    for t in range(9, 16):
        c = run_op("ssm_conv_update",
                   {"XBC": np.stack([xbc[0, t], xbc[0, 0]]),
                    "ConvTail": tail, "W": w, "Bias": bias, "Slots": slots},
                   ATTRS)
        tail = c["ConvTailOut"]
        for k in "XBC":
            np.testing.assert_allclose(np.asarray(c[k])[0],
                                       scan[k][0, t], rtol=1e-5, atol=1e-5)
        s = run_op("ssm_state_update",
                   {"X": c["X"], "B": c["B"], "C": c["C"],
                    "Dt": np.stack([ins["Dt"][0, t], ins["Dt"][0, 0]]),
                    "ALog": ins["ALog"], "D": ins["D"],
                    "DtBias": ins["DtBias"], "State": state,
                    "Slots": slots}, ATTRS)
        state = s["StateOut"]
        np.testing.assert_allclose(np.asarray(s["Y"])[0], y_all[t],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state)[1], state_all, rtol=2e-4,
                               atol=2e-4)
    assert (np.asarray(state)[0] == 0).all()        # nobody's slot


def test_the_gated_norm_gates_first_and_norms_each_group_alone():
    rng = np.random.RandomState(0)
    x, z = rng.randn(3, 32).astype(np.float32), rng.randn(3, 32)
    gain = rng.rand(32).astype(np.float32)
    got = run_op("gated_group_rms_norm",
                 {"X": x, "Gate": z.astype(np.float32), "Scale": gain},
                 {"groups": 2, "epsilon": 1e-5})["Y"]
    h = (x * z / (1 + np.exp(-z))).reshape(3, 2, 16)
    want = (h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 32) * gain
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_the_split_scales_by_the_mup_vector():
    cfg = small()
    mup = cfg.mup_vector()
    assert mup.shape == (cfg.in_proj_dim,)
    assert {round(float(v), 5) for v in mup} \
        == {round(v, 5) for v in cfg.ssm_multipliers}
    u = np.ones((2, cfg.in_proj_dim), np.float32)
    out = run_op("ssm_split", {"U": u, "Mup": mup},
                 {"d_ssm": cfg.d_ssm, "conv_dim": cfg.conv_dim})
    assert out["Z"].shape == (2, cfg.d_ssm)
    assert out["XBC"].shape == (2, cfg.conv_dim)
    assert out["Dt"].shape == (2, cfg.mamba_n_heads)
    np.testing.assert_allclose(np.asarray(out["Z"]), cfg.ssm_multipliers[0])
    np.testing.assert_allclose(np.asarray(out["Dt"]), cfg.ssm_multipliers[4])


def test_qk_rope_rotates_whole_heads_and_scales_the_key():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 3, 2 * 8).astype(np.float32)
    k = rng.randn(2, 3, 8).astype(np.float32)
    pos = np.asarray([[0, 5, 9], [1, 2, 3]], np.int32)
    out = run_op("qk_rope", {"Q": q, "K": k, "Positions": pos},
                 {"head_dim": 8, "theta": 100.0, "k_scale": 0.5})
    want = rf.rope(jnp.asarray(q[1]), jnp.asarray(pos[1]), 8, 100.0)
    np.testing.assert_allclose(np.asarray(out["QOut"])[1], want, rtol=1e-5,
                               atol=1e-5)
    want = rf.rope(jnp.asarray(k[0] * 0.5), jnp.asarray(pos[0]), 8, 100.0)
    np.testing.assert_allclose(np.asarray(out["KOut"])[0], want, rtol=1e-5,
                               atol=1e-5)
    # position 0 turns nothing
    np.testing.assert_allclose(np.asarray(out["QOut"])[0, 0], q[0, 0],
                               rtol=1e-6)


# -- the kernel --------------------------------------------------------------

def kernel_operands(seed, rows, heads, groups, n, p, slots):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(slots, heads, n, p), jnp.float32),
            jnp.asarray(rng.randn(rows, heads, p), jnp.float32),
            jnp.asarray(rng.rand(rows, heads), jnp.float32),
            jnp.asarray(rng.randn(rows, groups, n), jnp.float32),
            jnp.asarray(rng.randn(rows, groups, n), jnp.float32))


@pytest.mark.parametrize("heads,groups,rows_at", [
    (16, 2, [2, 0, 3]),             # one head block a group
    (32, 2, [1, 4, 4, 4]),          # two blocks a group; padding rows share
    (8, 1, [0]),                    # the scratch slot
])
def test_the_state_kernel_is_its_stock_lowering(monkeypatch, heads, groups,
                                                rows_at):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    slots = jnp.asarray(rows_at, jnp.int32)
    state, xdt, decay, bm, cm = kernel_operands(
        heads, len(rows_at), heads, groups, 128, 128, 5)
    y0, s0 = ssu.stock_ssm_state_update(state, slots, xdt, decay, bm, cm)
    y1, s1 = jax.jit(ssu.ssm_state_update)(state, slots, xdt, decay, bm, cm)
    assert telemetry.counter_get("pallas.ssm_state_update_dispatches") == 1
    assert telemetry.counter_get("pallas.ssm_state_update_fallbacks") == 0
    owned = [i for i, s in enumerate(rows_at) if rows_at.count(s) == 1]
    np.testing.assert_allclose(np.asarray(y1)[owned], np.asarray(y0)[owned],
                               rtol=1e-5, atol=1e-4)
    own = [rows_at[i] for i in owned]
    np.testing.assert_allclose(np.asarray(s1)[own], np.asarray(s0)[own],
                               rtol=1e-6, atol=1e-6)
    untouched = [s for s in range(5) if s not in rows_at]
    np.testing.assert_array_equal(np.asarray(s1)[untouched],
                                  np.asarray(state)[untouched])


def test_the_state_update_counts_its_fallbacks(monkeypatch):
    telemetry.reset()
    state, xdt, decay, bm, cm = kernel_operands(0, 2, 4, 2, 16, 8, 3)
    slots = jnp.asarray([0, 1], jnp.int32)
    monkeypatch.setenv("PT_PALLAS", "off")
    ssu.ssm_state_update(state, slots, xdt, decay, bm, cm)
    assert telemetry.counter_get("pallas.ssm_state_update_fallbacks") == 1
    # a state held lower than float32 is no case of the kernel's
    monkeypatch.setenv("PT_PALLAS", "interpret")
    ssu.ssm_state_update(state.astype(jnp.bfloat16), slots, xdt, decay, bm,
                         cm)
    assert telemetry.counter_get("pallas.ssm_state_update_fallbacks") == 2
    assert telemetry.counter_get("pallas.ssm_state_update_dispatches") == 0


# -- the model behind the engine -------------------------------------------

def test_seeded_scales_undo_the_multipliers_branch_by_branch():
    cfg = small()
    specs = falcon_h1.param_specs(cfg)
    d = cfg.hidden_size
    assert falcon_h1.init_scale(cfg, "fh_tok_emb", specs["fh_tok_emb"][0]) \
        == pytest.approx(1 / cfg.embedding_multiplier)
    for part, by in (("out_w", cfg.ssm_out_multiplier),
                     ("o_w", cfg.attention_out_multiplier),
                     ("k_w", cfg.key_multiplier),
                     ("gate_w", cfg.mlp_multipliers[0]),
                     ("down_w", cfg.mlp_multipliers[1]),
                     ("up_w", 1.0), ("v_w", 1.0),
                     ("q_w", 1 / falcon_h1.QUERY_GAIN)):
        shape = specs["fh_l0_" + part][0]
        assert falcon_h1.init_scale(cfg, "fh_l1_" + part, shape) \
            == pytest.approx(shape[-2] ** -0.5 / by)
    cols = falcon_h1.init_scale(cfg, "fh_l0_in_w", specs["fh_l0_in_w"][0])
    np.testing.assert_allclose(
        cols * cfg.ssm_in_multiplier * cfg.mup_vector(), d ** -0.5,
        rtol=1e-6)
    params = falcon_h1.falcon_h1_params(cfg, 0)
    a = np.exp(params["fh_l0_a_log"])
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(params["fh_l1_dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1001).all()
    assert (params["fh_l0_d_skip"] == 1).all()
    assert params["fh_l0_in_w"].dtype == np.float32     # this toy's dtype
    assert falcon_h1.falcon_h1_params(
        falcon_h1.FalconH1Config(), 0)["fh_l0_in_w"].dtype.name == "bfloat16"


@pytest.fixture(scope="module")
def served():
    cfg = small()
    params = falcon_h1.falcon_h1_params(cfg, 3)
    engine = engine_for(cfg, params).start(warmup=False)
    yield cfg, params, engine, reference_for(cfg, params)
    engine.close(drain=False, timeout=30)


@pytest.mark.parametrize("length", [1, 15, 16, 17, 33, 50])
def test_prefill_then_decode_through_pages_and_state_is_the_reference(
        served, length):
    """The prefill's logits, every greedy token teacher-forced, and the
    slot's recurrent state after the decode: the reference's full forward
    over prompt + tokens (chunks of 16 here, so 15, 16, 17 and 33 lie
    around a chunk's edge and 50 in a padded bucket of 64)."""
    cfg, _, engine, ref = served
    prompt = np.random.RandomState(length).randint(3, cfg.vocab_size, length)
    new = 10
    req = engine.submit(prompt, max_new_tokens=new, stop_at_eos=False,
                        keep_first_logits=True, keep_final_state=True)
    tokens = req.result(120)
    rows, states = ref.rows(np.concatenate([prompt, tokens]), 64,
                            length - 1, new, state_at=length + new - 2)
    assert rf.logit_error(req.first_logits, rows[0]) < 2e-4
    assert rf.greedy_gaps(rows, tokens).max() < 1e-4
    assert rf.state_error(engine_state(req, cfg), states) < 2e-4
    # the tail the slot held: the inputs of the last three FED tokens
    assert np.asarray(req.final_state["conv_tail_0"]).shape \
        == (cfg.mamba_d_conv - 1, cfg.conv_dim)


def test_the_engine_counts_state_rows_and_seats():
    cfg = small()
    telemetry.reset()
    engine = engine_for(cfg, falcon_h1.falcon_h1_params(cfg, 1)).start()
    try:
        prompt = np.arange(3, 12)
        engine.generate(prompt, timeout=120, max_new_tokens=6,
                        stop_at_eos=False)
        c = telemetry.counters()
        # the first token is the prefill's: 5 steps of one live row
        assert c["decode.steps"] == 5
        assert c["decode.state_rows_updated"] == 5 * cfg.n_layers
        assert c["decode.state_slots_seated"] == 1
        assert c["decode.kv_tokens_attended"] == cfg.n_layers * sum(
            range(10, 15))
        stats = engine.stats()
        assert stats["kv_cache"]["state"]["slots"] == 4
        assert stats["kv_cache"]["state"]["used_bytes"] == 0   # retired
        assert telemetry.snapshot()["gauges"][
            "mem.serving.state_pool_bytes"] == 5 * engine.kv.state_slot_bytes
    finally:
        engine.close(drain=False, timeout=30)


def test_continuous_batching_over_reused_slots_is_each_request_alone():
    """Seven requests of different lengths through two slots: rows of a
    step stand at different positions, and every slot is handed on to a
    successor whose state starts from ITS prefill, not from the last
    owner's. Each request's tokens and final state are the reference's for
    that request alone."""
    cfg = small()
    params = falcon_h1.falcon_h1_params(cfg, 5)
    ref = reference_for(cfg, params)
    telemetry.reset()
    engine = engine_for(cfg, params, max_slots=2,
                        kv_pages=2 * 16 + 1).start()
    try:
        rng = np.random.RandomState(0)
        lengths = [5, 40, 17, 9, 30, 3, 22]
        news = [12, 6, 9, 14, 5, 11, 8]
        prompts = [rng.randint(3, cfg.vocab_size, n) for n in lengths]
        reqs = [engine.submit(p, max_new_tokens=n, stop_at_eos=False,
                              keep_final_state=True)
                for p, n in zip(prompts, news)]
        outs = [r.result(180) for r in reqs]
    finally:
        engine.close(drain=False, timeout=30)
    assert telemetry.counter_get("decode.state_slots_seated") == 7
    for prompt, new, req, tokens in zip(prompts, news, reqs, outs):
        rows, states = ref.rows(np.concatenate([prompt, tokens]), 64,
                                len(prompt) - 1, new,
                                state_at=len(prompt) + new - 2)
        assert rf.greedy_gaps(rows, tokens).max() < 1e-4
        assert rf.state_error(engine_state(req, cfg), states) < 2e-4


def test_a_row_dispatched_past_its_requests_end_leaves_its_successor_clean():
    """A request that ends on `eos_id` has a row in the step already in
    flight, which advances its slot's state once more; the successor that
    takes the slot reads nothing of it: its prefill overwrites the slot."""
    cfg = small()
    params = falcon_h1.falcon_h1_params(cfg, 7)
    ref = reference_for(cfg, params)
    prompt = np.arange(5, 25)
    probe = engine_for(cfg, params, max_slots=1, kv_pages=17).start()
    try:
        alone = probe.generate(prompt, timeout=120, max_new_tokens=8,
                               stop_at_eos=False)
    finally:
        probe.close(drain=False, timeout=30)
    cfg.eos_id = int(alone[3])          # the request ends at its 4th token
    telemetry.reset()
    engine = engine_for(cfg, params, max_slots=1, kv_pages=17).start()
    try:
        first = engine.submit(prompt, max_new_tokens=8, stop_at_eos=True)
        after = np.arange(40, 47)
        second = engine.submit(after, max_new_tokens=9, stop_at_eos=False,
                               keep_final_state=True)
        assert list(first.result(120)) == list(alone[:4])
        tokens = second.result(120)
    finally:
        engine.close(drain=False, timeout=30)
    assert telemetry.counter_get("decode.rows_discarded") >= 1
    rows, states = ref.rows(np.concatenate([after, tokens]), 64,
                            len(after) - 1, 9, state_at=len(after) + 9 - 2)
    assert rf.greedy_gaps(rows, tokens).max() < 1e-4
    assert rf.state_error(engine_state(second, cfg), states) < 2e-4


def test_a_program_compiled_mid_service_touches_the_scratch_slot_alone():
    """A bucket that was not warmed compiles through a throwaway run on
    the engine's own arrays: its rows name the scratch slot, so a seated
    request's state stays what it was."""
    cfg = small()
    params = falcon_h1.falcon_h1_params(cfg, 2)
    engine = engine_for(cfg, params)
    before = {n: np.asarray(v) for n, v in engine._pools.items()
              if n.startswith(("ssm_state", "conv_tail"))}
    engine._entry("step", 4)
    engine._entry("prefill", 16)
    for name, was in before.items():
        now = np.asarray(engine._pools[name])
        np.testing.assert_array_equal(now[:4], was[:4])


# -- what the engine refuses ------------------------------------------------

@pytest.mark.parametrize("conf,why", [
    ({"prefix_cache": True}, "no per-token pages to share"),
    ({"role": "prefill"}, "runs unified"),
    ({"role": "decode"}, "runs unified"),
])
def test_the_engine_refuses_what_a_recurrent_state_cannot_do(conf, why):
    cfg = small()
    with pytest.raises(ValueError, match=why) as e:
        engine_for(cfg, falcon_h1.falcon_h1_params(cfg, 0), **conf)
    assert "recurrent state" in str(e.value)


def test_the_model_builds_no_chunk_program():
    cfg = small()
    kv = PagedKVCache(cfg.served().cache_layout(), 8, 17, slots=2)
    with pytest.raises(NotImplementedError, match="resume the recurrent "
                                                  "state"):
        cfg.served().build_chunk_prefill_program(8, kv)
    main, feeds, fetches = cfg.served().build_step_program(2, kv)
    assert "state_slots" in feeds
    assert {"ssm_state_0_out", "conv_tail_1_out", "kv_k_0_out"} \
        <= set(fetches)
    types = [op.type for op in main.global_block().ops]
    assert types.count("ssm_state_update") == cfg.n_layers
    assert types.count("cached_kv_attention") == cfg.n_layers


def test_mem_report_shows_the_state_pool():
    rows = [{"ts": 1.0, "kind": "gauge", "name": "mem.serving.kv_pool_bytes",
             "value": 1 << 20},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.state_pool_bytes", "value": 3 << 20},
            {"ts": 1.1, "kind": "gauge",
             "name": "mem.serving.state_pool_bytes.used", "value": 1 << 20}]
    import io

    from tools import mem_report

    summary = mem_report.summarize_mem(rows)
    assert summary["ledger"]["serving_state_pool_bytes"] == 3 << 20
    assert summary["ledger"]["serving_state_used_bytes"] == 1 << 20
    assert summary["ledger"]["total_bytes"] == 4 << 20
    text = io.StringIO()
    mem_report.render(summary, out=text)
    assert "state pool (decode)" in text.getvalue()
