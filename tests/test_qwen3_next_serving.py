"""models/qwen3_next.py behind `DecodeEngine` at a small size on the CPU:
`PagedKVCache` with STATE-ONLY layers (no pool array for them, bytes
counted), the Gated-DeltaNet ops (the chunked delta rule against the
token-by-token recurrence, the by-key-head split, the per-head gated norm,
the convolution without a bias, one step in place by slot), partial rotary
positions and gains stored around zero, the state kernel against its stock
lowering, prefill + decode through pages AND state against the plain
reference, continuous batching over reused slots, and the engine's
refusals for a model with state."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_qwen3_next as rq
from benchmark.families import qwen3_next as family
from paddle_tpu.core import registry, telemetry
from paddle_tpu.models import qwen3_next
from paddle_tpu.ops import linear_attention_ops as la
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops.pallas import gated_delta_state_update as gdu
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import LayerCache, PagedKVCache

NK, DK, NV, DV, K = 2, 16, 4, 8, 4
GDN = {"key_heads": NK, "key_dim": DK, "value_heads": NV, "value_dim": DV}
CONV = 2 * NK * DK + NV * DV
CONV_ATTRS = {"n_heads": NK, "head_dim": DK, "n_groups": NK, "d_state": DK}


def small(**kw):
    kw.setdefault("dtype", "float32")
    kw.setdefault("linear_chunk_size", 16)
    return qwen3_next.Qwen3NextConfig(max_seq_len=128, **kw)


def engine_for(cfg, params, **kw):
    conf = dict(max_slots=4, page_size=8, kv_pages=4 * 16 + 1,
                prefill_buckets=[16, 32, 64], max_new_tokens=32)
    conf.update(kw)
    return DecodeEngine(cfg, params, DecodeConfig(**conf))


def reference_for(cfg, params, **kw):
    return rq.Reference({k: jnp.asarray(v) for k, v in params.items()},
                        family.reference_config(cfg), **kw)


def run_op(name, ins, attrs):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs)


def engine_state(req, cfg):
    """[state layers, value heads, dk, dv] of what the request's slot
    held."""
    return np.stack([np.asarray(req.final_state[f"ssm_state_{i}"])
                     for i in family.state_layers(cfg)])


def test_seeded_parameters_are_the_initialisation_the_configuration_states():
    cfg = small()
    params = qwen3_next.qwen3_next_params(cfg, 0)
    # the held value heads on a grid over the family's ranges, the same in
    # every layer and for every seed: memories from hundreds of tokens to one
    a = np.exp(params["qn_l0_a_log"])
    np.testing.assert_allclose(a, 1 + 15 * (np.arange(4) + 0.5) / 4,
                               rtol=1e-5)
    dt = np.log1p(np.exp(params["qn_l1_dt_bias"]))
    np.testing.assert_allclose(dt, 1e-3 * 100 ** ((np.arange(4) + 0.5) / 4),
                               rtol=1e-4)
    other = qwen3_next.qwen3_next_params(cfg, 7)
    np.testing.assert_array_equal(other["qn_l2_a_log"],
                                  params["qn_l0_a_log"])
    assert not (other["qn_l0_qkvz_w"] == params["qn_l0_qkvz_w"]).all()
    # the router's columns ROUTER_GAIN times the other matrices' scale
    assert np.std(params["qn_l0_router_w"]) == pytest.approx(
        qwen3_next.ROUTER_GAIN * cfg.hidden_size ** -0.5, rel=0.1)
    assert np.std(params["qn_l0_sh_w1"]) == pytest.approx(
        cfg.hidden_size ** -0.5, rel=0.1)
    # gains stored around zero, but the gated norm's and the query's
    assert (params["qn_l0_norm_in"] == 0).all()
    assert (params["qn_norm_f"] == 0).all()
    assert (params["qn_l0_gn_w"] == 1).all()
    assert (params["qn_l3_q_norm"] == qwen3_next.QUERY_GAIN - 1).all()
    assert (params["qn_l3_k_norm"] == 0).all()
    # a DeltaNet layer has no attention matrices and the other way round
    assert "qn_l0_q_w" not in params and "qn_l3_qkvz_w" not in params
    assert params["qn_l3_q_w"].shape == (cfg.hidden_size,
                                         2 * cfg.num_heads * cfg.head_dim)
    assert params["qn_l0_sh_gate_w"].shape == (cfg.hidden_size, 1)
    assert params["qn_l0_qkvz_w"].dtype == np.float32   # this toy's dtype
    assert qwen3_next.qwen3_next_params(
        qwen3_next.Qwen3NextConfig(), 0)["qn_l0_qkvz_w"].dtype.name \
        == "bfloat16"
    with pytest.raises(ValueError, match="attention layer"):
        qwen3_next.Qwen3NextConfig(n_layers=3)


# -- the cache ---------------------------------------------------------------

def test_a_state_only_layer_keeps_a_state_and_a_tail_and_no_pages():
    telemetry.reset()
    state_only = LayerCache(0, ssm_state=(NV, DK, DV),
                            conv_tail=(K - 1, CONV))
    assert state_only.state_only and not LayerCache(16).state_only
    layout = [state_only, state_only, LayerCache(16)]
    kv = PagedKVCache(layout, page_size=8, context_pages=9, dtype="bfloat16",
                      slots=3)
    assert kv.has_state and kv.state_layers == [0, 1]
    assert kv.context.layers == [2] and kv.ring is None
    arrays = kv.make_arrays()
    # no pool array for the state-only layers, no state for the other
    assert sorted(arrays) == ["conv_tail_0", "conv_tail_1", "kv_k_2",
                              "kv_v_2", "ssm_state_0", "ssm_state_1"]
    assert arrays["ssm_state_1"].shape == (4, NV, DK, DV)
    assert arrays["ssm_state_1"].dtype == jnp.float32
    assert arrays["conv_tail_0"].shape == (4, K - 1, CONV)
    assert arrays["conv_tail_0"].dtype == jnp.bfloat16
    # bytes counted: the pool is the one attention layer's pages, the state
    # class the two state layers'
    assert kv.pool_bytes == 2 * 9 * 8 * 16 * 2
    assert kv.state_slot_bytes == 2 * (NV * DK * DV * 4 + (K - 1) * CONV * 2)
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["mem.serving.kv_pool_bytes"] == kv.pool_bytes
    assert gauges["mem.serving.state_pool_bytes"] == 4 * kv.state_slot_bytes
    assert kv.stats()["state"]["layers"] == 2
    # what a layer without K/V has to be
    with pytest.raises(ValueError, match="state-only"):
        LayerCache(0)
    with pytest.raises(ValueError, match="state-only"):
        LayerCache(0, window=8, ssm_state=(1, 2, 3), conv_tail=(3, 4))
    with pytest.raises(ValueError, match="context's pages"):
        PagedKVCache([state_only], 8, 9, slots=2)


# -- the ops -----------------------------------------------------------------

def drawn(seed, s):
    rng = np.random.RandomState(seed)
    return {"Q": rng.randn(1, s, NK * DK).astype(np.float32),
            "K": rng.randn(1, s, NK * DK).astype(np.float32),
            "V": rng.randn(1, s, NV * DV).astype(np.float32),
            "A": rng.randn(1, s, NV).astype(np.float32),
            "B": rng.randn(1, s, NV).astype(np.float32),
            "ALog": np.log(rng.uniform(1, 16, NV)).astype(np.float32),
            "DtBias": rng.uniform(-5, -1, NV).astype(np.float32)}


def sequential(ins, length):
    """The rule token by token in float64 -> (o [length, NV*DV], the state
    after the last token [NV, DK, DV])."""
    r = NV // NK

    def unit(x):
        x = x.astype(np.float64).reshape(-1, NK, DK)
        return np.repeat(x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6),
                         r, axis=1)

    q, k = unit(ins["Q"][0]) * DK ** -0.5, unit(ins["K"][0])
    v = ins["V"][0].astype(np.float64).reshape(-1, NV, DV)
    g = -np.exp(ins["ALog"].astype(np.float64)) * np.log1p(np.exp(
        ins["A"][0].astype(np.float64) + ins["DtBias"]))
    beta = 1 / (1 + np.exp(-ins["B"][0].astype(np.float64)))
    state = np.zeros((NV, DK, DV))
    outs = []
    for t in range(length):
        state = np.exp(g[t])[:, None, None] * state
        u = np.einsum("hk,hkv->hv", k[t], state)
        state = state + k[t][:, :, None] \
            * (beta[t][:, None] * (v[t] - u))[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", q[t], state))
    return np.stack(outs).reshape(length, NV * DV), state


@pytest.mark.parametrize("length,bucket", [(1, 64), (63, 64), (64, 64),
                                           (65, 128), (100, 256)])
def test_the_chunked_delta_rule_is_the_token_by_token_recurrence(length,
                                                                 bucket):
    """Chunks of 64 over a padded bucket, at lengths just under and over a
    chunk and with a padded tail: every real position's output and the
    state after the last REAL token, written at the slot."""
    ins = drawn(length, bucket)
    pool = jnp.full((3, NV, DK, DV), 7.0, jnp.float32)   # the last owner's
    telemetry.reset()
    out = run_op("gated_delta_chunk_scan",
                 dict(ins, State=pool, Slots=np.asarray([1], np.int32),
                      Lengths=np.asarray([length], np.int32)),
                 dict(GDN, chunk=64))
    assert telemetry.counter_get(
        "ops.gated_delta_chunk_scan_dispatches") == 1
    o, state = sequential(ins, length)
    np.testing.assert_allclose(np.asarray(out["Y"])[0, :length], o,
                               rtol=2e-4, atol=2e-5)
    new = np.asarray(out["StateOut"])
    np.testing.assert_allclose(new[1], state, rtol=2e-4, atol=2e-5)
    assert (new[0] == 7.0).all() and (new[2] == 7.0).all()


def test_the_chunked_rule_holds_where_consecutive_keys_overlap():
    """Keys that nearly repeat make the triangular system far from the
    identity (entries near beta): forward substitution stays exact where a
    nilpotent series would cancel binomials."""
    rng = np.random.RandomState(0)
    base = rng.randn(NK * DK)
    ins = drawn(1, 64)
    ins["K"] = (base + 0.01 * rng.randn(1, 64, NK * DK)).astype(np.float32)
    ins["B"] = np.full((1, 64, NV), 4.0, np.float32)        # beta ~ 0.98
    ins["A"] = np.full((1, 64, NV), -9.0, np.float32)       # hardly decays
    out = run_op("gated_delta_chunk_scan",
                 dict(ins, State=jnp.zeros((2, NV, DK, DV)),
                      Slots=np.asarray([0], np.int32),
                      Lengths=np.asarray([64], np.int32)),
                 dict(GDN, chunk=64))
    o, state = sequential(ins, 64)
    np.testing.assert_allclose(np.asarray(out["Y"])[0], o, rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(out["StateOut"])[0], state,
                               rtol=1e-3, atol=1e-4)


def test_a_conv_without_a_bias_keeps_the_tail_of_the_last_real_tokens():
    rng = np.random.RandomState(0)
    qkv = rng.randn(1, 16, CONV).astype(np.float32)
    w = rng.randn(K, CONV).astype(np.float32)
    pool = jnp.full((3, K - 1, CONV), 9.0, jnp.float32)
    for length in (1, 3, 11, 16):
        out = run_op("ssm_conv_prefill",
                     {"XBC": qkv, "ConvTail": pool, "W": w,
                      "Slots": np.asarray([2], np.int32),
                      "Lengths": np.asarray([length], np.int32)},
                     CONV_ATTRS)
        tail = np.asarray(out["ConvTailOut"])
        want = np.zeros((K - 1, CONV), np.float32)
        kept = qkv[0, max(0, length - (K - 1)):length]
        want[K - 1 - len(kept):] = kept
        np.testing.assert_array_equal(tail[2], want)
        assert (tail[:2] == 9.0).all()
        # the three parts are q, k and v, in the convolution's own order
        assert out["X"].shape == out["B"].shape == (1, 16, NK * DK)
        assert out["C"].shape == (1, 16, NV * DV)
        t = length - 1
        window = np.zeros((K, CONV), np.float32)
        seen = qkv[0, max(0, t - K + 1):t + 1]
        window[K - len(seen):] = seen
        pre = (window * w).sum(0)
        got = np.concatenate([np.asarray(out[k])[0, t] for k in "XBC"])
        np.testing.assert_allclose(got, pre / (1 + np.exp(-pre)), rtol=1e-5,
                                   atol=1e-5)
    # with a zero bias it is the op it was
    with_bias = run_op("ssm_conv_prefill",
                       {"XBC": qkv, "ConvTail": pool, "W": w,
                        "Bias": np.zeros(CONV, np.float32),
                        "Slots": np.asarray([2], np.int32),
                        "Lengths": np.asarray([16], np.int32)}, CONV_ATTRS)
    np.testing.assert_array_equal(np.asarray(with_bias["X"]),
                                  np.asarray(out["X"]))


def test_steps_by_slot_continue_what_the_prefill_wrote():
    """A prompt's first 9 tokens through the prefill ops, the rest one step
    a token through the update ops at the slot: the whole prompt's rule."""
    ins = drawn(3, 16)
    rng = np.random.RandomState(1)
    qkv = rng.randn(1, 16, CONV).astype(np.float32)
    w = rng.randn(K, CONV).astype(np.float32)
    slot = np.asarray([1], np.int32)

    def conv_prefill(length):
        return run_op("ssm_conv_prefill",
                      {"XBC": qkv, "ConvTail": jnp.zeros((3, K - 1, CONV)),
                       "W": w, "Slots": slot,
                       "Lengths": np.asarray([length], np.int32)},
                      CONV_ATTRS)

    whole = conv_prefill(16)
    rule = {k: ins[k] for k in ("A", "B", "ALog", "DtBias")}
    rule.update(Q=np.asarray(whole["X"]), K=np.asarray(whole["B"]),
                V=np.asarray(whole["C"]))
    o_all, state_all = sequential(rule, 16)
    out = run_op("gated_delta_chunk_scan",
                 dict(rule, State=jnp.zeros((3, NV, DK, DV)), Slots=slot,
                      Lengths=np.asarray([9], np.int32)),
                 dict(GDN, chunk=16))
    tail, state = conv_prefill(9)["ConvTailOut"], out["StateOut"]
    # two rows a step: the request's and a padding row on the scratch slot
    slots = np.asarray([1, 2], np.int32)
    for t in range(9, 16):
        c = run_op("ssm_conv_update",
                   {"XBC": np.stack([qkv[0, t], qkv[0, 0]]),
                    "ConvTail": tail, "W": w, "Slots": slots}, CONV_ATTRS)
        tail = c["ConvTailOut"]
        s = run_op("gated_delta_state_update",
                   {"Q": c["X"], "K": c["B"], "V": c["C"],
                    "A": np.stack([ins["A"][0, t], ins["A"][0, 0]]),
                    "B": np.stack([ins["B"][0, t], ins["B"][0, 0]]),
                    "ALog": ins["ALog"], "DtBias": ins["DtBias"],
                    "State": state, "Slots": slots}, GDN)
        state = s["StateOut"]
        np.testing.assert_allclose(np.asarray(s["Y"])[0], o_all[t],
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state)[1], state_all, rtol=2e-4,
                               atol=2e-5)
    assert (np.asarray(state)[0] == 0).all()        # nobody's slot


def test_the_split_reads_the_projections_by_key_head():
    """in_proj_qkvz: each key head its q, k, its r value heads' v and z;
    in_proj_ba: its b and a. Labelled columns come out where the layout
    says."""
    r = NV // NK
    width = 2 * DK + 2 * r * DV
    qkvz = np.zeros((3, NK * width), np.float32)
    ba = np.zeros((3, NK * 2 * r), np.float32)
    for h in range(NK):
        at = h * width
        qkvz[:, at:at + DK] = 100 + h                          # q of head h
        qkvz[:, at + DK:at + 2 * DK] = 200 + h                 # k
        for j in range(r):
            v_at = at + 2 * DK + j * DV
            qkvz[:, v_at:v_at + DV] = 300 + h * r + j          # v, value head
            z_at = at + 2 * DK + r * DV + j * DV
            qkvz[:, z_at:z_at + DV] = 400 + h * r + j          # z
            ba[:, h * 2 * r + j] = 500 + h * r + j             # b
            ba[:, h * 2 * r + r + j] = 600 + h * r + j         # a
    out = run_op("gdn_split", {"QKVZ": qkvz, "BA": ba}, GDN)
    qkv = np.asarray(out["QKV"])
    assert qkv.shape == (3, CONV)
    np.testing.assert_array_equal(
        qkv[0, :NK * DK].reshape(NK, DK)[:, 0], 100 + np.arange(NK))
    np.testing.assert_array_equal(
        qkv[0, NK * DK:2 * NK * DK].reshape(NK, DK)[:, 0],
        200 + np.arange(NK))
    np.testing.assert_array_equal(
        qkv[0, 2 * NK * DK:].reshape(NV, DV)[:, 0], 300 + np.arange(NV))
    np.testing.assert_array_equal(
        np.asarray(out["Z"])[0].reshape(NV, DV)[:, -1], 400 + np.arange(NV))
    np.testing.assert_array_equal(np.asarray(out["B"])[0],
                                  500 + np.arange(NV))
    np.testing.assert_array_equal(np.asarray(out["A"])[0],
                                  600 + np.arange(NV))
    # a query projection that gives each head its query, then its gate
    pairs = np.arange(2 * 3 * 2 * 4, dtype=np.float32).reshape(2, 3 * 8)
    halves = run_op("split_head_pairs", {"X": pairs}, {"head_dim": 4})
    np.testing.assert_array_equal(
        np.asarray(halves["First"])[0],
        np.concatenate([np.arange(4) + 8 * h for h in range(3)]))
    np.testing.assert_array_equal(
        np.asarray(halves["Second"])[0],
        np.concatenate([np.arange(4, 8) + 8 * h for h in range(3)]))


def test_the_gated_head_norm_norms_each_head_and_then_gates():
    rng = np.random.RandomState(0)
    x, z = rng.randn(3, 32).astype(np.float32), rng.randn(3, 32)
    gain = rng.rand(8).astype(np.float32)
    got = run_op("gated_head_rms_norm",
                 {"X": x, "Gate": z.astype(np.float32), "Scale": gain},
                 {"head_dim": 8, "epsilon": 1e-6})["Y"]
    h = x.reshape(3, 4, 8)
    want = (h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-6) * gain
            ).reshape(3, 32) * (z / (1 + np.exp(-z)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_partial_rotary_leaves_the_rest_of_a_head_unturned():
    """rotary_dim 8 of a head of 32: dimensions 8-31 come out as the norm
    gave them, whatever the position; the first 8 turn as the reference's;
    the gains are 1 + w."""
    rng = np.random.RandomState(0)
    hd, rd = 32, 8
    q = rng.randn(2, 3, 2 * hd).astype(np.float32)
    k = rng.randn(2, 3, hd).astype(np.float32)
    wq, wk = rng.randn(hd).astype(np.float32), rng.randn(hd).astype(
        np.float32)
    pos = np.asarray([[0, 5, 9000], [1, 2, 3]], np.int32)
    attrs = {"head_dim": hd, "epsilon": 1e-6, "rope": True, "theta": 1e7,
             "rotary_dim": rd, "scale_offset": 1.0}
    out = run_op("qk_norm_rope", {"Q": q, "K": k, "QScale": wq, "KScale": wk,
                                  "Positions": pos}, attrs)
    still = run_op("qk_norm_rope",
                   {"Q": q, "K": k, "QScale": wq, "KScale": wk,
                    "Positions": np.zeros_like(pos)}, attrs)
    got = np.asarray(out["QOut"]).reshape(2, 3, 2, hd)
    base = np.asarray(still["QOut"]).reshape(2, 3, 2, hd)
    np.testing.assert_array_equal(got[..., rd:], base[..., rd:])
    assert np.abs(got[0, 2, :, :rd] - base[0, 2, :, :rd]).max() > 0.1
    for b in range(2):
        normed = rq.norm(jnp.asarray(k[b]).reshape(3, 1, hd), wk, 1e-6)
        want = rq.rope(normed, jnp.asarray(pos[b]), 1e7, rd)
        np.testing.assert_allclose(
            np.asarray(out["KOut"])[b], np.asarray(want).reshape(3, hd),
            rtol=1e-5, atol=1e-5)
    # rms_norm's gain offset: 1 + w
    x = rng.randn(4, 16).astype(np.float32)
    w = rng.randn(16).astype(np.float32)
    y = run_op("rms_norm", {"X": x, "Scale": w},
               {"epsilon": 1e-6, "scale_offset": 1.0})["Y"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(rq.norm(
        jnp.asarray(x), w, 1e-6)), rtol=1e-5, atol=1e-6)
    # and without the attribute the ops are what they were
    plain = run_op("rms_norm", {"X": x, "Scale": w}, {"epsilon": 1e-6})["Y"]
    np.testing.assert_allclose(
        np.asarray(plain),
        x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w, rtol=1e-5)


def test_a_router_without_a_selection_bias_selects_by_its_scores():
    rng = np.random.RandomState(0)
    ins = {"X": rng.randn(6, 16).astype(np.float32),
           "RouterW": rng.randn(16, 8).astype(np.float32),
           "W1": rng.randn(4, 16, 8).astype(np.float32),
           "W3": rng.randn(4, 16, 8).astype(np.float32),
           "W2": rng.randn(4, 8, 16).astype(np.float32)}
    attrs = {"top_k": 2, "held_lo": 0, "score_func": "softmax"}
    bare = run_op("routed_experts", ins, attrs)
    zero = run_op("routed_experts",
                  dict(ins, SelectBias=np.zeros(8, np.float32)), attrs)
    np.testing.assert_array_equal(np.asarray(bare["Out"]),
                                  np.asarray(zero["Out"]))
    np.testing.assert_array_equal(np.asarray(bare["Chosen"]),
                                  np.asarray(zero["Chosen"]))


# -- the kernel --------------------------------------------------------------

def kernel_operands(seed, rows, heads, kd, vd, slots):
    rng = np.random.RandomState(seed)
    unit = rng.randn(rows, heads, kd)
    unit /= np.linalg.norm(unit, axis=-1, keepdims=True)
    return (jnp.asarray(rng.randn(slots, heads, kd, vd), jnp.float32),
            jnp.asarray(rng.randn(rows, heads, kd) * kd ** -0.5,
                        jnp.float32),
            jnp.asarray(unit, jnp.float32),
            jnp.asarray(rng.randn(rows, heads, vd), jnp.float32),
            jnp.asarray(rng.rand(rows, heads), jnp.float32),
            jnp.asarray(rng.rand(rows, heads), jnp.float32))


@pytest.mark.parametrize("heads,rows_at,per_key", [
    (8, [2, 0, 3], 1),              # one head block a row
    (16, [1, 4, 4, 4], 2),          # two blocks a row; padding rows share
    (8, [0], 2),                    # the scratch slot; q and k a key head's
])
def test_the_state_kernel_is_its_stock_lowering(monkeypatch, heads, rows_at,
                                                per_key):
    """Interpret mode against the stock form: live rows' outputs and
    states, a padding row on the scratch slot (the last, shared) leaving
    every live and every unnamed slot untouched; with `heads_per_key` the
    value heads of a key head carry its q and k, transposed once."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    slots = jnp.asarray(rows_at, jnp.int32)
    state, q, k, v, decay, beta = kernel_operands(
        heads, len(rows_at), heads, 128, 128, 5)
    q, k = (jnp.repeat(x[:, ::per_key], per_key, axis=1) for x in (q, k))
    o0, s0 = gdu.stock_gated_delta_state_update(state, slots, q, k, v,
                                                decay, beta)
    o1, s1 = jax.jit(gdu.gated_delta_state_update,
                     static_argnames="heads_per_key")(
        state, slots, q, k, v, decay, beta, heads_per_key=per_key)
    assert telemetry.counter_get(
        "pallas.gated_delta_state_update_dispatches") == 1
    assert telemetry.counter_get(
        "pallas.gated_delta_state_update_fallbacks") == 0
    owned = [i for i, s in enumerate(rows_at) if rows_at.count(s) == 1]
    np.testing.assert_allclose(np.asarray(o1)[owned], np.asarray(o0)[owned],
                               rtol=1e-5, atol=1e-4)
    own = [rows_at[i] for i in owned]
    np.testing.assert_allclose(np.asarray(s1)[own], np.asarray(s0)[own],
                               rtol=1e-5, atol=1e-5)
    untouched = [s for s in range(5) if s not in rows_at]
    np.testing.assert_array_equal(np.asarray(s1)[untouched],
                                  np.asarray(state)[untouched])


def test_the_stock_form_reads_the_state_before_it_writes_it():
    """One head by hand: u is k^T of the DECAYED state, and o reads the
    state after the write."""
    state, q, k, v, decay, beta = kernel_operands(3, 1, 1, 4, 3, 2)
    slots = jnp.asarray([1], jnp.int32)
    o, new = gdu.stock_gated_delta_state_update(state, slots, q, k, v,
                                                decay, beta)
    s = np.asarray(state)[1, 0] * float(decay[0, 0])
    kk, qq = np.asarray(k)[0, 0], np.asarray(q)[0, 0]
    u = kk @ s
    s = s + np.outer(kk, float(beta[0, 0]) * (np.asarray(v)[0, 0] - u))
    np.testing.assert_allclose(np.asarray(new)[1, 0], s, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o)[0, 0], qq @ s, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(state)[0])


def test_the_state_update_counts_its_fallbacks(monkeypatch):
    telemetry.reset()
    state, q, k, v, decay, beta = kernel_operands(0, 2, 4, 16, 8, 3)
    slots = jnp.asarray([0, 1], jnp.int32)
    monkeypatch.setenv("PT_PALLAS", "off")
    gdu.gated_delta_state_update(state, slots, q, k, v, decay, beta)
    assert telemetry.counter_get(
        "pallas.gated_delta_state_update_fallbacks") == 1
    # a state held lower than float32 is no case of the kernel's
    monkeypatch.setenv("PT_PALLAS", "interpret")
    gdu.gated_delta_state_update(state.astype(jnp.bfloat16), slots, q, k, v,
                                 decay, beta)
    assert telemetry.counter_get(
        "pallas.gated_delta_state_update_fallbacks") == 2
    assert telemetry.counter_get(
        "pallas.gated_delta_state_update_dispatches") == 0


# -- the model behind the engine ---------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = small()
    params = qwen3_next.qwen3_next_params(cfg, 3)
    engine = engine_for(cfg, params).start(warmup=False)
    yield cfg, params, engine, reference_for(cfg, params)
    engine.close(drain=False, timeout=30)


@pytest.mark.parametrize("length", [1, 15, 16, 17, 33, 50])
def test_prefill_then_decode_through_pages_and_state_is_the_reference(
        served, length):
    """The prefill's logits, every greedy token teacher-forced, and the
    slot's matrix states after the decode: the reference's full forward
    over prompt + tokens (chunks of 16 here, so 15, 16, 17 and 33 lie
    around a chunk's edge and 50 in a padded bucket of 64)."""
    cfg, _, engine, ref = served
    prompt = np.random.RandomState(length).randint(3, cfg.vocab_size, length)
    new = 10
    req = engine.submit(prompt, max_new_tokens=new, stop_at_eos=False,
                        keep_first_logits=True, keep_final_state=True,
                        keep_final_pages=True)
    tokens = req.result(120)
    rows, states, pages = ref.rows(np.concatenate([prompt, tokens]), 64,
                            length - 1, new, state_at=length + new - 2)
    assert rq.logit_error(req.first_logits, rows[0]) < 2e-4
    assert rq.greedy_gaps(rows, tokens).max() < 1e-4
    assert states.shape == (3, cfg.linear_value_heads,
                            cfg.linear_key_head_dim,
                            cfg.linear_value_head_dim)
    assert max(rq.state_errors(engine_state(req, cfg), states)) < 2e-4
    # state and tail of the DeltaNet layers alone
    assert sorted(req.final_state) == sorted(
        f"{kind}_{i}" for i in (0, 1, 2) for kind in ("ssm_state",
                                                      "conv_tail"))
    assert np.asarray(req.final_state["conv_tail_0"]).shape \
        == (cfg.linear_conv_kernel_dim - 1, cfg.conv_dim)
    # the request's own pages of the one attention layer, in its table's
    # order: every position that was fed holds the reference's K and V
    assert sorted(req.final_pages) == ["kv_k_3", "kv_v_3"]
    fed = length + new - 1
    held = np.stack([np.asarray(req.final_pages[f"kv_{part}_3"]).reshape(
        -1, cfg.num_kv_heads * cfg.head_dim) for part in "kv"], axis=1)
    assert held.shape[0] == -(-(length + new) // 8) * 8
    assert pages.shape == (1, length + new, 2, cfg.head_dim)
    assert rq.kv_error(held[:fed], pages[0][:fed]) < 2e-5
    np.testing.assert_allclose(held[:fed], pages[0][:fed], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("dtype, near", [("float32", 1e-4),
                                         ("bfloat16", rq.LOGIT_ERR / 6)])
def test_a_prefill_through_the_attention_kernel_is_the_stock_routes(
        monkeypatch, dtype, near):
    """A 128-token bucket (one block of the kernel's smallest) with the
    shape rule out of the way, the program built in interpret mode: its
    attention layer goes through the flash forward kernel (counted) and
    the prefill's logits are the stock route's, far inside the limit the
    cell's check holds them to against the reference."""
    monkeypatch.setattr(llm_ops, "GQA_PREFILL_KERNEL_FROM", 1)
    cfg = small(dtype=dtype)
    params = qwen3_next.qwen3_next_params(cfg, 3)
    prompt = np.random.RandomState(50).randint(3, cfg.vocab_size, 100)
    logits = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("PT_PALLAS", mode)
        telemetry.reset()
        engine = engine_for(cfg, params, prefill_buckets=[128]).start(
            warmup=False)
        try:
            req = engine.submit(prompt, max_new_tokens=1, stop_at_eos=False,
                                keep_first_logits=True)
            req.result(120)
        finally:
            engine.close(drain=False, timeout=30)
        logits[mode] = np.asarray(req.first_logits)
        c = telemetry.snapshot()["counters"]
        # (an op is traced once when its program is built and once when
        # it is lowered)
        assert (c.get("pallas.gqa_prefill_dispatches"),
                c.get("pallas.gqa_prefill_fallbacks")) \
            == ((None, 2) if mode == "off" else (2, None))
    assert rq.logit_error(logits["interpret"], logits["off"]) < near
    rows, _, _ = reference_for(cfg, params).rows(
        np.concatenate([prompt, [3]]), 128, 99, 1)
    assert rq.logit_error(logits["interpret"], rows[0]) < (
        2e-4 if dtype == "float32" else rq.LOGIT_ERR)


def test_the_engine_counts_state_rows_keys_and_routed_pairs():
    cfg = small()
    telemetry.reset()
    engine = engine_for(cfg, qwen3_next.qwen3_next_params(cfg, 1)).start()
    try:
        prompt = np.arange(3, 12)
        engine.generate(prompt, timeout=120, max_new_tokens=6,
                        stop_at_eos=False)
        c = telemetry.counters()
        # the first token is the prefill's: 5 steps of one live row
        assert c["decode.steps"] == 5
        # three DeltaNet layers a live row; one attention layer's keys
        assert c["decode.state_rows_updated"] == 5 * 3
        assert c["decode.state_slots_seated"] == 1
        assert c["decode.kv_tokens_attended"] == sum(range(10, 15))
        # every layer routes: top-4 a live row and layer
        assert c["decode.moe_pairs_total"] == 5 * 4 * cfg.n_layers
        assert 0 < c["decode.moe_pairs_held"] <= c["decode.moe_pairs_total"]
        assert 0 < c["decode.moe_experts_hit"] <= c["decode.moe_pairs_held"]
        stats = engine.stats()
        assert stats["kv_cache"]["state"]["layers"] == 3
        gauges = telemetry.snapshot()["gauges"]
        assert gauges["mem.serving.state_pool_bytes"] \
            == 5 * engine.kv.state_slot_bytes
        # the attention layer's pages alone
        assert gauges["mem.serving.kv_pool_bytes"] \
            == 2 * 65 * 8 * cfg.num_kv_heads * cfg.head_dim * 4
    finally:
        engine.close(drain=False, timeout=30)


def test_continuous_batching_over_reused_slots_is_each_request_alone():
    """Seven requests of different lengths through two slots: rows of a
    step stand at different positions, and every slot is handed on to a
    successor whose states start from ITS prefill, not from the last
    owner's. Each request's tokens and final states are the reference's
    for that request alone."""
    cfg = small()
    params = qwen3_next.qwen3_next_params(cfg, 5)
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
        rows, states, pages = ref.rows(np.concatenate([prompt, tokens]), 64,
                                len(prompt) - 1, new,
                                state_at=len(prompt) + new - 2)
        assert rq.greedy_gaps(rows, tokens).max() < 1e-4
        assert max(rq.state_errors(engine_state(req, cfg), states)) < 2e-4


def test_a_program_compiled_mid_service_touches_the_scratch_slot_alone():
    cfg = small()
    params = qwen3_next.qwen3_next_params(cfg, 2)
    engine = engine_for(cfg, params)
    before = {n: np.asarray(v) for n, v in engine._pools.items()
              if n.startswith(("ssm_state", "conv_tail"))}
    assert len(before) == 6
    engine._entry("step", 4)
    engine._entry("prefill", 16)
    for name, was in before.items():
        now = np.asarray(engine._pools[name])
        np.testing.assert_array_equal(now[:4], was[:4])


# -- what the engine refuses -------------------------------------------------

@pytest.mark.parametrize("conf,why", [
    ({"prefix_cache": True}, "no per-token pages to share"),
    ({"role": "prefill"}, "runs unified"),
])
def test_the_engine_refuses_what_a_state_cannot_do(conf, why):
    cfg = small()
    with pytest.raises(ValueError, match=why):
        engine_for(cfg, qwen3_next.qwen3_next_params(cfg, 0), **conf)


def test_the_model_builds_no_chunk_program_and_feeds_state_only_layers():
    cfg = small()
    served_model = cfg.served()
    layout = served_model.cache_layout()
    assert [lc.state_only for lc in layout] == [True, True, True, False]
    kv = PagedKVCache(layout, 8, 17, slots=2)
    with pytest.raises(NotImplementedError, match="resume the matrix "
                                                  "state"):
        served_model.build_chunk_prefill_program(8, kv)
    main, feeds, fetches = served_model.build_step_program(2, kv)
    assert "state_slots" in feeds
    assert {"ssm_state_0_out", "conv_tail_2_out", "kv_k_3_out",
            "step_counts"} <= set(fetches)
    assert not {"kv_k_0_out", "ssm_state_3_out"} & set(fetches)
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_delta_state_update") == 3
    assert types.count("ssm_conv_update") == 3
    assert types.count("gdn_split") == 3
    assert types.count("gated_head_rms_norm") == 3
    assert types.count("cached_kv_attention") == 1
    assert types.count("split_head_pairs") == 1
    assert types.count("routed_experts") == cfg.n_layers
    main, feeds, fetches = served_model.build_prefill_program(32, kv)
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_delta_chunk_scan") == 3
    assert types.count("ssm_conv_prefill") == 3
    assert types.count("gqa_prefill_attention") == 1
    assert la.L2_EPS == rq.L2_EPS == 1e-6
