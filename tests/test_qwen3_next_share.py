"""The share test of the model-configs guide, section 4, at a small size on
the CPU: what the 4 chips of the TP4/EP4 deployment each compute of a layer
with the PROGRAM's ops (their heads' part of the DeltaNet and of the
attention output, their experts' part of the routed sum), with what every
chip computes alike (the router-weighted shared expert) counted once, adds
up to what the uncut plain reference gives for the whole layer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_qwen3_next as ref
from benchmark.families.qwen3_next import reference_config
from paddle_tpu.core import registry
from paddle_tpu.models.qwen3_next import Qwen3NextConfig, qwen3_next_params
from paddle_tpu.parallel.moe import routed_experts_share

SHARES = 4
T = 48
# the uncut toy: 8 query heads on 2 K/V heads (query head j on K/V head
# j // 4), 4 key heads with 8 value heads, 16 experts top-4
UNCUT = Qwen3NextConfig(
    vocab_size=64, hidden_size=64, n_layers=4, head_dim=16, num_heads=8,
    num_kv_heads=2, linear_key_heads=4, linear_value_heads=8,
    linear_key_head_dim=16, linear_value_head_dim=8, linear_chunk_size=16,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=16, num_experts_per_tok=4, experts_held=(0, 16),
    max_seq_len=64, dtype="float32")
HD, DK, DV = UNCUT.head_dim, UNCUT.linear_key_head_dim, \
    UNCUT.linear_value_head_dim


def run_op(name, ins, attrs):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs)


@pytest.fixture(scope="module")
def world():
    params = {k: jnp.asarray(v)
              for k, v in qwen3_next_params(UNCUT, 11).items()}
    # gains off their seeded constants, so that 1 + w and a plain gain differ
    rng = np.random.RandomState(3)
    for name in list(params):
        if name.endswith(("norm_in", "norm_post", "q_norm", "k_norm",
                          "gn_w")):
            params[name] = params[name] + jnp.asarray(
                0.3 * rng.randn(*params[name].shape), jnp.float32)
    x = jnp.asarray(np.random.RandomState(5).randn(T, UNCUT.hidden_size),
                    jnp.float32)
    return params, x


def lin(v, w):
    return run_op("linear_acc32", {"X": v, "W": w}, {})["Out"]


def attention_share(params, p, r):
    """Rank r's slices of attention layer p: two query heads with their
    gates, the K/V head they read (j // 4: ranks 0, 1 hold head 0)."""
    g = UNCUT.num_heads // SHARES
    q = slice(r * g * 2 * HD, (r + 1) * g * 2 * HD)
    kv_head = (r * g) // (UNCUT.num_heads // UNCUT.num_kv_heads)
    kv = slice(kv_head * HD, (kv_head + 1) * HD)
    return {"q_w": params[p + "q_w"][:, q], "k_w": params[p + "k_w"][:, kv],
            "v_w": params[p + "v_w"][:, kv],
            "o_w": params[p + "o_w"][r * g * HD:(r + 1) * g * HD, :],
            "q_norm": params[p + "q_norm"], "k_norm": params[p + "k_norm"]}


def program_attention(sp, x):
    """One share's attention output through the program's ops."""
    xb = x[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    halves = run_op("split_head_pairs", {"X": lin(xb, sp["q_w"])},
                    {"head_dim": HD})
    qk = run_op("qk_norm_rope",
                {"Q": halves["First"], "K": lin(xb, sp["k_w"]),
                 "QScale": sp["q_norm"], "KScale": sp["k_norm"],
                 "Positions": pos},
                {"head_dim": HD, "epsilon": UNCUT.rms_norm_eps,
                 "rope": True, "theta": UNCUT.rope_theta,
                 "rotary_dim": UNCUT.rotary_dim, "scale_offset": 1.0})
    o = run_op("gqa_prefill_attention",
               {"Q": qk["QOut"], "K": qk["KOut"], "V": lin(xb, sp["v_w"])},
               {"num_heads": UNCUT.num_heads // SHARES, "num_kv_heads": 1,
                "head_dim": HD, "block_q": 16})["Out"]
    gated = run_op("sigmoid_gate", {"X": o, "Gate": halves["Second"]},
                   {})["Out"]
    return lin(gated, sp["o_w"])[0]


def test_the_shares_attention_adds_up_to_the_uncut_layers(world):
    params, x = world
    p = "qn_l3_"
    with jax.default_matmul_precision("highest"):
        whole, _kv = ref.attention(params, p, x, reference_config(UNCUT),
                                   block=16)
        parts = sum(program_attention(attention_share(params, p, r), x)
                    for r in range(SHARES))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 2e-5 * scale


def delta_net_share(params, p, r):
    """Rank r's slices of DeltaNet layer p: key head r with its two value
    heads: its columns of both by-key-head projections, its channels of
    the convolution (of q, of k and of v), its value heads' scalars and
    rows of the output projection; the gated norm's gain whole."""
    nk, ratio = UNCUT.linear_key_heads, \
        UNCUT.linear_value_heads // UNCUT.linear_key_heads
    width = 2 * DK + 2 * ratio * DV
    q_ch = np.arange(r * DK, (r + 1) * DK)
    v_ch = 2 * nk * DK + np.arange(r * ratio * DV, (r + 1) * ratio * DV)
    channels = np.concatenate([q_ch, nk * DK + q_ch, v_ch])
    heads = slice(r * ratio, (r + 1) * ratio)
    return {"qkvz_w": params[p + "qkvz_w"][:, r * width:(r + 1) * width],
            "ba_w": params[p + "ba_w"][:, r * 2 * ratio:(r + 1) * 2 * ratio],
            "conv_w": params[p + "conv_w"][:, channels],
            "a_log": params[p + "a_log"][heads],
            "dt_bias": params[p + "dt_bias"][heads],
            "gn_w": params[p + "gn_w"],
            "out_w": params[p + "out_w"][r * ratio * DV:
                                         (r + 1) * ratio * DV, :]}


def program_delta_net(sp, x):
    """One share's DeltaNet output through the program's prefill ops."""
    ratio = UNCUT.linear_value_heads // UNCUT.linear_key_heads
    gdn = {"key_heads": 1, "key_dim": DK, "value_heads": ratio,
           "value_dim": DV}
    xb = x[None]
    slot, length = np.asarray([0], np.int32), np.asarray([T], np.int32)
    split = run_op("gdn_split", {"QKVZ": lin(xb, sp["qkvz_w"]),
                                 "BA": lin(xb, sp["ba_w"])}, gdn)
    taps = UNCUT.linear_conv_kernel_dim - 1
    conv = run_op("ssm_conv_prefill",
                  {"XBC": split["QKV"], "W": sp["conv_w"],
                   "ConvTail": jnp.zeros((1, taps, sp["conv_w"].shape[1])),
                   "Slots": slot, "Lengths": length},
                  {"n_heads": 1, "head_dim": DK, "n_groups": 1,
                   "d_state": DK})
    y = run_op("gated_delta_chunk_scan",
               {"Q": conv["X"], "K": conv["B"], "V": conv["C"],
                "A": split["A"], "B": split["B"], "ALog": sp["a_log"],
                "DtBias": sp["dt_bias"],
                "State": jnp.zeros((1, ratio, DK, DV)), "Slots": slot,
                "Lengths": length}, dict(gdn, chunk=16))["Y"]
    y = run_op("gated_head_rms_norm",
               {"X": y, "Gate": split["Z"], "Scale": sp["gn_w"]},
               {"head_dim": DV, "epsilon": UNCUT.rms_norm_eps})["Y"]
    return lin(y, sp["out_w"])[0]


@pytest.mark.parametrize("layer", [0, 2])
def test_the_shares_delta_net_adds_up_to_the_uncut_layers(world, layer):
    params, x = world
    p = f"qn_l{layer}_"
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.delta_net(params, p, x, reference_config(UNCUT))
        parts = sum(program_delta_net(delta_net_share(params, p, r), x)
                    for r in range(SHARES))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 5e-5 * scale


@pytest.mark.parametrize("layer", [1, 3])
def test_the_shares_experts_add_up_to_the_uncut_layers(world, layer):
    """The router-weighted shared expert ONCE + the 4 shares' routed sums =
    the uncut MoE layer; the shares' counters add up to every pair."""
    params, x = world
    p = f"qn_l{layer}_"
    cfg = reference_config(UNCUT)
    eh = UNCUT.num_experts // SHARES
    with jax.default_matmul_precision("highest"):
        shared = ref.shared(params, p, x)
        whole = shared + ref.routed(params, p, x,
                                    ref.route(params, p, x, cfg), cfg)
        # the program's shared expert: SwiGLU times a sigmoid of one column
        mid = run_op("swiglu", {"Gate": lin(x, params[p + "sh_w1"]),
                                "Up": lin(x, params[p + "sh_w3"])},
                     {})["Out"]
        total = run_op("sigmoid_gate",
                       {"X": lin(mid, params[p + "sh_w2"]),
                        "Gate": lin(x, params[p + "sh_gate_w"])}, {})["Out"]
        np.testing.assert_allclose(np.asarray(total), np.asarray(shared),
                                   rtol=1e-4, atol=1e-6)
        pairs = []
        for r in range(SHARES):
            ex = slice(r * eh, (r + 1) * eh)
            out, counts = routed_experts_share(
                x, params[p + "router_w"],
                jnp.zeros((UNCUT.num_experts,), jnp.float32),
                params[p + "ex_w1"][ex], params[p + "ex_w3"][ex],
                params[p + "ex_w2"][ex], top_k=UNCUT.num_experts_per_tok,
                held_lo=r * eh, score_func="softmax",
                route_norm=UNCUT.norm_topk_prob)
            total = total + out
            pairs.append(np.asarray(counts))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 2e-5 * scale
    pairs = np.stack(pairs)
    assert (pairs[:, 0] == T * 4).all()          # every share sees every pair
    assert pairs[:, 1].sum() == T * 4            # and each pair has one home
    assert (pairs[:, 2] <= eh).all() and pairs[:, 2].sum() > SHARES


def test_a_share_of_the_reference_is_a_share_of_the_program(world):
    """The reference handed one share's keys computes that share's partial
    result, the program's: what `check_correct` compares on the chip."""
    params, x = world
    p, r = "qn_l1_", 2
    sp = delta_net_share(params, p, r)
    cfg = dict(reference_config(UNCUT), linear_key_heads=1,
               linear_value_heads=2)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.delta_net({p + k: v for k, v in sp.items()}, p, x, cfg)
        got = program_delta_net(sp, x)
    scale = float(np.abs(want).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-5 * scale
