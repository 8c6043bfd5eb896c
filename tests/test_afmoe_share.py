"""The share test of the model-configs guide, section 4, at a small size on
the CPU: what the 8 chips of the deployment each compute of a layer with
the PROGRAM's ops (their heads' part of the attention output, their
experts' part of the routed sum), with what every chip computes alike (the
shared expert, the dense MLP) counted once, adds up to what the uncut
plain reference gives for the whole layer."""

import numpy as np
import pytest

from benchmark import reference_afmoe as ref
from benchmark.families.afmoe import reference_config
from paddle_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig, afmoe_params
from paddle_tpu.ops.llm_ops import (gqa_prefill_attention_op,
                                    linear_acc32_op, qk_norm_rope_op,
                                    sigmoid_gate_op)
from paddle_tpu.parallel.moe import routed_experts_share

SHARES = 8
T = 48
# the uncut toy: 16 query heads on 8 K/V heads, 32 experts top-4
UNCUT = AfmoeConfig(
    vocab_size=64, hidden_size=64, head_dim=16, num_heads=16,
    num_kv_heads=8, layer_types=(SLIDING, SLIDING, FULL),
    num_dense_layers=1, intermediate_size=96, moe_intermediate_size=32,
    num_experts=32, num_experts_per_tok=4, experts_held=(0, 32),
    sliding_window=20, max_seq_len=64, dtype="float32")


ref_cfg = reference_config      # what the reference reads of a config


@pytest.fixture(scope="module")
def world():
    import jax.numpy as jnp

    params = {k: jnp.asarray(v) for k, v in afmoe_params(UNCUT, 11).items()}
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(T, UNCUT.hidden_size), jnp.float32)
    return params, x


def share_of(params, p, r):
    """Rank r's slices of layer prefix p: its K/V head and that head's two
    query heads, its four experts; router and bias whole."""
    hd = UNCUT.head_dim
    g = UNCUT.num_heads // UNCUT.num_kv_heads
    eh = UNCUT.num_experts // SHARES
    q = slice(r * g * hd, (r + 1) * g * hd)
    kv = slice(r * hd, (r + 1) * hd)
    out = {p + "q_w": params[p + "q_w"][:, q],
           p + "g_w": params[p + "g_w"][:, q],
           p + "k_w": params[p + "k_w"][:, kv],
           p + "v_w": params[p + "v_w"][:, kv],
           p + "o_w": params[p + "o_w"][q, :],
           p + "q_norm": params[p + "q_norm"],
           p + "k_norm": params[p + "k_norm"]}
    for name in ("ex_w1", "ex_w3", "ex_w2"):
        if p + name in params:
            out[p + name] = params[p + name][r * eh:(r + 1) * eh]
    return out


def program_attention(sp, p, x, window):
    """One share's attention output through the program's ops."""
    import jax.numpy as jnp

    hd = UNCUT.head_dim
    nq = sp[p + "q_w"].shape[1] // hd
    nkv = sp[p + "k_w"].shape[1] // hd

    def lin(v, name):
        return linear_acc32_op({"X": [v], "W": [sp[p + name]]}, {})["Out"]

    xb = x[None]                                         # [1, T, hidden]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    qk = qk_norm_rope_op(
        {"Q": [lin(xb, "q_w")], "K": [lin(xb, "k_w")],
         "QScale": [sp[p + "q_norm"]], "KScale": [sp[p + "k_norm"]],
         "Positions": [pos]},
        {"head_dim": hd, "epsilon": UNCUT.rms_norm_eps, "rope": window > 0,
         "theta": UNCUT.rope_theta})
    o = gqa_prefill_attention_op(
        {"Q": [qk["QOut"]], "K": [qk["KOut"]], "V": [lin(xb, "v_w")]},
        {"num_heads": nq, "num_kv_heads": nkv, "head_dim": hd,
         "window": window, "block_q": 16})["Out"]
    gated = sigmoid_gate_op({"X": [o], "Gate": [lin(xb, "g_w")]}, {})["Out"]
    return lin(gated, "o_w")[0]


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_shares_attention_adds_up_to_the_uncut_layers(world, layer):
    import jax

    params, x = world
    p = f"af_l{layer}_"
    window = UNCUT.window_of(layer)
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(params, p, x, ref_cfg(UNCUT), window > 0,
                              block=16)
        parts = sum(program_attention(share_of(params, p, r), p, x, window)
                    for r in range(SHARES))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 2e-5 * scale


@pytest.mark.parametrize("layer", [1, 2])
def test_the_shares_experts_add_up_to_the_uncut_layers(world, layer):
    """Shared(x) once + the 8 shares' routed sums = the uncut MoE layer;
    the shares' counters add up to every pair, and each share holds about
    an eighth of them."""
    import jax

    params, x = world
    p = f"af_l{layer}_"
    cfg = ref_cfg(UNCUT)
    eh = UNCUT.num_experts // SHARES
    with jax.default_matmul_precision("highest"):
        weights, _gap = ref.route(params, p, x, cfg)
        shared = ref.swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                            params[p + "sh_w2"])
        whole = shared + ref.routed(params, p, x, weights, cfg)
        total, pairs = shared, []
        for r in range(SHARES):
            sp = share_of(params, p, r)
            out, counts = routed_experts_share(
                x, params[p + "router_w"], params[p + "select_bias"],
                sp[p + "ex_w1"], sp[p + "ex_w3"], sp[p + "ex_w2"],
                top_k=UNCUT.num_experts_per_tok, held_lo=r * eh,
                route_scale=UNCUT.route_scale)
            total = total + out
            pairs.append(np.asarray(counts))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 2e-5 * scale
    pairs = np.stack(pairs)
    assert (pairs[:, 0] == T * 4).all()          # every share sees every pair
    assert pairs[:, 1].sum() == T * 4            # and each pair has one home
    assert (pairs[:, 2] <= eh).all() and pairs[:, 2].sum() > SHARES


def test_the_whole_block_from_the_shares_sums_is_the_uncut_block(world):
    """A MoE layer end to end: the sums go through the norms (which are
    not linear, so only the sums do) and give the uncut layer's output;
    the dense layer's MLP, which every chip computes alike, counts once."""
    import jax

    params, x = world
    cfg = ref_cfg(UNCUT)
    eps = UNCUT.rms_norm_eps
    eh = UNCUT.num_experts // SHARES
    with jax.default_matmul_precision("highest"):
        for layer in range(UNCUT.n_layers):
            p = f"af_l{layer}_"
            window = UNCUT.window_of(layer)
            a_in = ref.rms_norm(x, params[p + "norm_in"], eps)
            a = sum(program_attention(share_of(params, p, r), p, a_in,
                                      window) for r in range(SHARES))
            h = x + ref.rms_norm(a, params[p + "norm_post_attn"], eps)
            m_in = ref.rms_norm(h, params[p + "norm_pre_mlp"], eps)
            if layer < UNCUT.num_dense_layers:
                m = ref.swiglu(m_in, params[p + "w1"], params[p + "w3"],
                               params[p + "w2"])            # once
            else:
                m = ref.swiglu(m_in, params[p + "sh_w1"],
                               params[p + "sh_w3"], params[p + "sh_w2"])
                for r in range(SHARES):
                    sp = share_of(params, p, r)
                    m = m + routed_experts_share(
                        m_in, params[p + "router_w"],
                        params[p + "select_bias"], sp[p + "ex_w1"],
                        sp[p + "ex_w3"], sp[p + "ex_w2"],
                        top_k=4, held_lo=r * eh,
                        route_scale=UNCUT.route_scale)[0]
            x_shares = h + ref.rms_norm(m, params[p + "norm_post_mlp"], eps)
            # the uncut reference's layer, from the same input
            a_u = ref.attention(params, p, a_in, cfg, window > 0, block=16)
            h_u = x + ref.rms_norm(a_u, params[p + "norm_post_attn"], eps)
            mi_u = ref.rms_norm(h_u, params[p + "norm_pre_mlp"], eps)
            if layer < UNCUT.num_dense_layers:
                m_u = ref.swiglu(mi_u, params[p + "w1"], params[p + "w3"],
                                 params[p + "w2"])
            else:
                w_u, _ = ref.route(params, p, mi_u, cfg)
                m_u = ref.swiglu(mi_u, params[p + "sh_w1"],
                                 params[p + "sh_w3"], params[p + "sh_w2"]) \
                    + ref.routed(params, p, mi_u, w_u, cfg)
            x_u = h_u + ref.rms_norm(m_u, params[p + "norm_post_mlp"], eps)
            assert np.abs(np.asarray(x_shares) - np.asarray(x_u)).max() \
                < 3e-5 * float(np.abs(x_u).max()), layer
            x = x_u


def test_no_pair_is_dropped_when_every_token_routes_here(world):
    """Dropless under imbalance: a selection bias sends every pair of every
    token to the experts one chip holds, far more than the leading rows
    the grouped products usually run over; the chip's sum is then the
    whole routed layer of the reference under the same bias."""
    import jax
    import jax.numpy as jnp

    params, x = world
    p = "af_l1_"
    eh = UNCUT.num_experts // SHARES
    bias = jnp.zeros(UNCUT.num_experts).at[eh:2 * eh].set(10.0)
    biased = dict(params, **{p + "select_bias": bias})
    cfg = dict(ref_cfg(UNCUT), experts_held=(eh, eh))
    sp = share_of(params, p, 1)
    with jax.default_matmul_precision("highest"):
        weights, _gap = ref.route(biased, p, x, cfg)
        whole = ref.routed(
            dict(biased, **{p + n: sp[p + n]
                            for n in ("ex_w1", "ex_w3", "ex_w2")}),
            p, x, weights, cfg)
        out, counts = routed_experts_share(
            x, params[p + "router_w"], bias, sp[p + "ex_w1"],
            sp[p + "ex_w3"], sp[p + "ex_w2"], top_k=4, held_lo=eh,
            route_scale=UNCUT.route_scale)
    assert list(np.asarray(counts)) == [T * 4, T * 4, eh]
    assert np.abs(np.asarray(out) - np.asarray(whole)).max() \
        < 2e-5 * float(np.abs(whole).max())


def test_rows_without_a_token_join_no_experts_group(world):
    """A padded prompt's tail and an engine's empty slots are rows of the
    same token: all of them would land on the same four experts. Given
    `live`, their pairs join no group and are not counted, their output is
    zero, and the live rows' output is what it is without them."""
    import jax
    import jax.numpy as jnp

    params, x = world
    p = "af_l1_"
    sp = share_of(params, p, 0)
    n_live = 29
    padded = x.at[n_live:].set(x[0])
    live = jnp.arange(T) < n_live

    def share(rows, mask):
        with jax.default_matmul_precision("highest"):
            return routed_experts_share(
                rows, params[p + "router_w"], params[p + "select_bias"],
                sp[p + "ex_w1"], sp[p + "ex_w3"], sp[p + "ex_w2"], top_k=4,
                held_lo=0, route_scale=UNCUT.route_scale, live=mask)

    out, counts = share(padded, live)
    alone, counts_alone = share(padded[:n_live], None)
    assert list(np.asarray(counts)) == list(np.asarray(counts_alone))
    assert int(counts[0]) == n_live * 4
    assert not np.asarray(out[n_live:]).any()
    assert np.abs(np.asarray(out[:n_live]) - np.asarray(alone)).max() \
        < 2e-5 * float(np.abs(alone).max())
