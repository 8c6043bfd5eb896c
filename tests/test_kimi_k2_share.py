"""The share test of the model-configs guide, section 4, for Kimi-K2 at a
small size on the CPU. The deployment spreads each layer's routed experts
over chips and keeps attention, the shared expert and the router whole on
every chip (data-parallel). So: what the 4 shares each compute of a routed
layer with the PROGRAM's op, plus what every chip computes alike (the
shared expert) counted once, adds up to what the uncut plain reference
gives for the whole layer; the logits of the vocabulary's slices
concatenate to the whole; and one share's model, through the engine, is
the reference given the same share."""

import numpy as np
import pytest

from benchmark import reference_kimi_k2 as ref
from benchmark.families.kimi_k2 import reference_config
from paddle_tpu.models.kimi_k2 import KimiK2Config
from paddle_tpu.parallel.moe import routed_experts_share

from test_kimi_k2_serving import seeded_params

SHARES = 4
T = 40
# the uncut toy: 64 experts top-8, 4 shares of 16
UNCUT = KimiK2Config(
    vocab_size=96, hidden_size=64, num_heads=4, n_layers=3, first_k_dense=1,
    intermediate_size=96, moe_intermediate_size=32, num_experts=64,
    num_experts_per_tok=8, experts_held=(0, 64), max_seq_len=64,
    dtype="float32")


@pytest.fixture(scope="module")
def world():
    import jax.numpy as jnp

    params = {k: jnp.asarray(v) for k, v in
              seeded_params(UNCUT, 11).items()}
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(T, UNCUT.hidden_size), jnp.float32)
    return params, x


def route_cfg(cfg):
    return reference_config(cfg)


@pytest.mark.parametrize("layer", [1, 2])
def test_the_shares_experts_add_up_to_the_uncut_layer(world, layer):
    """Shared(x) once + the 4 shares' routed sums = the uncut MoE layer;
    the shares' counters add up to every pair."""
    import jax

    params, x = world
    p = f"k2_l{layer}_"
    eh = UNCUT.num_experts // SHARES
    with jax.default_matmul_precision("highest"):
        weights, _gap = ref.route(params, p, x, route_cfg(UNCUT))
        shared = ref.swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                            params[p + "sh_w2"])
        whole = shared + ref.routed(params, p, x, weights, route_cfg(UNCUT))
        parts, counts = shared, []
        for r in range(SHARES):
            held = slice(r * eh, (r + 1) * eh)
            out, c = routed_experts_share(
                x, params[p + "router_w"], params[p + "select_bias"],
                params[p + "ex_w1"][held], params[p + "ex_w3"][held],
                params[p + "ex_w2"][held],
                top_k=UNCUT.num_experts_per_tok, held_lo=r * eh,
                route_scale=UNCUT.routed_scaling_factor,
                route_norm=UNCUT.norm_topk_prob)
            parts = parts + out
            counts.append(np.asarray(c))
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 2e-5 * scale
    counts = np.stack(counts)
    pairs = T * UNCUT.num_experts_per_tok
    assert (counts[:, 0] == pairs).all()
    assert counts[:, 1].sum() == pairs          # every pair on some share
    assert 0 < counts[:, 2].max() <= eh


def test_a_flood_of_held_pairs_is_computed_a_part_at_a_time(world):
    """A selection bias that sends every pair to the 16 held experts: 320
    held pairs where the leading rows hold 192, so the routed layer walks
    the sorted pairs in two parts, and drops none."""
    import jax
    import jax.numpy as jnp

    params, x = world
    p, eh = "k2_l1_", UNCUT.num_experts // SHARES
    bias = jnp.zeros((UNCUT.num_experts,)).at[eh:2 * eh].set(1.0)
    flooded = dict(params, **{p + "select_bias": bias})
    cfg = dict(route_cfg(UNCUT), experts_held=(eh, eh))
    held = slice(eh, 2 * eh)
    with jax.default_matmul_precision("highest"):
        weights, _ = ref.route(flooded, p, x, cfg)
        want = ref.routed(
            dict(flooded, **{p + n: params[p + n][held]
                             for n in ("ex_w1", "ex_w3", "ex_w2")}),
            p, x, weights, cfg)
        got, counts = routed_experts_share(
            x, params[p + "router_w"], bias, params[p + "ex_w1"][held],
            params[p + "ex_w3"][held], params[p + "ex_w2"][held],
            top_k=UNCUT.num_experts_per_tok, held_lo=eh,
            route_scale=UNCUT.routed_scaling_factor)
    pairs = T * UNCUT.num_experts_per_tok
    assert list(np.asarray(counts)[:2]) == [pairs, pairs]
    few = -(-(2 * pairs * eh // UNCUT.num_experts + 32) // 64) * 64
    assert few < pairs <= 2 * few
    scale = float(np.abs(want).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5 * scale


def share_params(params, rank=None, vocab_rows=None):
    """The parameters with (`rank`) that rank's experts of every MoE layer
    and (`vocab_rows`) those columns of the head; attention, the shared
    expert and the router whole."""
    eh = UNCUT.num_experts // SHARES
    out = dict(params)
    for name in params:
        if rank is not None and name.endswith(("ex_w1", "ex_w3", "ex_w2")):
            out[name] = params[name][rank * eh:(rank + 1) * eh]
    if vocab_rows is not None:
        out["k2_head_w"] = params["k2_head_w"][:, vocab_rows]
    return out


def test_the_vocabulary_slices_logits_concatenate_to_the_whole(world):
    """A slice of the head gives that slice of the logits: traffic that
    draws its ids from the held rows sees the whole model's logits over
    them (the embedding rows it looks up are the held ones)."""
    import jax.numpy as jnp

    params, _ = world
    rng = np.random.RandomState(2)
    per = UNCUT.vocab_size // SHARES
    tokens = jnp.asarray(rng.randint(3, per, T), jnp.int32)  # in slice 0
    whole, _ = ref.forward(params, tokens, reference_config(UNCUT))
    slices = [ref.forward(
        share_params(params, vocab_rows=slice(r * per, (r + 1) * per)),
        tokens, reference_config(UNCUT))[0] for r in range(SHARES)]
    np.testing.assert_allclose(np.concatenate(slices, axis=1),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_one_shares_engine_is_the_reference_given_the_same_share(world):
    """Experts 16-31 held, rows 0-23 of the vocabulary: the engine's
    prefill logits are the reference's when it is given the same share,
    and are NOT the uncut model's."""
    import copy

    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    params, _ = world
    eh, rows = UNCUT.num_experts // SHARES, UNCUT.vocab_size // SHARES
    cfg = copy.copy(UNCUT)
    cfg.experts_held, cfg.vocab_size = (eh, eh), rows
    mine = share_params(params, rank=1, vocab_rows=slice(0, rows))
    mine["k2_tok_emb"] = params["k2_tok_emb"][:rows]
    engine = DecodeEngine(cfg, mine, DecodeConfig(
        max_slots=2, page_size=16, kv_pages=9, prefill_buckets=[32],
        max_new_tokens=8)).start(warmup=False)
    try:
        prompt = np.random.RandomState(3).randint(3, rows, 21)
        req = engine.submit(prompt, max_new_tokens=4, stop_at_eos=False,
                            keep_first_logits=True)
        chosen = req.result(120)
    finally:
        engine.close()
    seq = np.concatenate([prompt, chosen])
    given, _ = ref.Reference(mine, reference_config(cfg)).rows(
        seq, 32, prompt.size - 1, len(chosen))
    assert ref.logit_error(np.asarray(req.first_logits), given[0]) < 1e-4
    assert ref.greedy_gaps(given, chosen).max() < 1e-4
    uncut, _ = ref.Reference(params, reference_config(UNCUT)).rows(
        seq, 32, prompt.size - 1, len(chosen))
    assert ref.logit_error(np.asarray(req.first_logits),
                           uncut[0][:rows]) > 0.05
