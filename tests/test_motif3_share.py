"""The share test of the model-configs guide, section 4, for Motif-3 at a
small size on the CPU. The deployment spreads each layer's routed experts
over 8 chips and keeps attention, the dense MLP, the shared expert, the
router and the residual maps whole on every chip. So: what the 8 shares
each compute of a routed layer with the PROGRAM's op (PolyNorm experts),
plus what every chip computes alike (the shared expert) counted once, adds
up to what the uncut plain reference gives for the whole layer; the logits
of the vocabulary's slices concatenate to the whole; and one share's model,
through the engine, is the reference given the same share."""

import copy

import numpy as np
import pytest

from benchmark import reference_motif3 as ref
from benchmark.families.motif3 import reference_config
from paddle_tpu.models.motif3 import Motif3Config, motif3_params
from paddle_tpu.parallel.moe import routed_experts_share

SHARES = 8
T = 40
# the uncut toy: 64 experts top-8, 8 shares of 8; layers 1 (dense), 2, 3
UNCUT = Motif3Config(
    vocab_size=96, hidden_size=64, layer_ids=(1, 2, 3),
    intermediate_size=96, moe_intermediate_size=32, num_experts=64,
    num_experts_per_tok=8, experts_held=(0, 64), max_seq_len=64,
    dtype="float32")


@pytest.fixture(scope="module")
def world():
    import jax.numpy as jnp

    params = {k: jnp.asarray(v) for k, v in motif3_params(UNCUT, 11).items()}
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(T, UNCUT.hidden_size), jnp.float32)
    return params, x


def poly(cfg, pn):
    return (pn, cfg.rms_norm_eps, cfg.polynorm_output_scale,
            cfg.polynorm_bias_clamp)


@pytest.mark.parametrize("layer", [1, 2])
def test_the_shares_experts_add_up_to_the_uncut_layer(world, layer):
    """Shared(x) once + the 8 shares' routed sums = the uncut MoE layer;
    the shares' counters add up to every pair."""
    import jax
    import jax.numpy as jnp

    params, x = world
    p = f"m3_l{layer}_"
    rc = reference_config(UNCUT)
    eh = UNCUT.num_experts // SHARES
    with jax.default_matmul_precision("highest"):
        weights, _gap = ref.route(params, p, x, rc)
        shared = ref.polyglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                             params[p + "sh_w2"], params[p + "sh_pn"], rc)
        whole = shared + ref.routed(params, p, x, weights, rc)
        parts, counts = shared, []
        for r in range(SHARES):
            held = slice(r * eh, (r + 1) * eh)
            out, c = routed_experts_share(
                x, params[p + "router_w"], jnp.zeros(UNCUT.num_experts),
                params[p + "ex_w1"][held], params[p + "ex_w3"][held],
                params[p + "ex_w2"][held],
                top_k=UNCUT.num_experts_per_tok, held_lo=r * eh,
                route_scale=UNCUT.route_scale, route_norm=UNCUT.route_norm,
                poly=poly(UNCUT, params[p + "ex_pn"][held]))
            parts = parts + out
            counts.append(np.asarray(c))
    # float32 sums in another order (grouped rows against every token)
    scale = float(np.abs(whole).max())
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 2e-5 * scale
    counts = np.stack(counts)
    pairs = T * UNCUT.num_experts_per_tok
    assert (counts[:, 0] == pairs).all()
    assert counts[:, 1].sum() == pairs          # every pair on some share
    assert 0 < counts[:, 2].max() <= eh


def test_the_experts_parameters_are_their_own(world):
    """Seeded PolyNorm parameters differ from expert to expert by O(1): a
    share given its neighbour's (w, b) computes another layer."""
    import jax
    import jax.numpy as jnp

    params, x = world
    p, eh = "m3_l1_", UNCUT.num_experts // SHARES
    pn = np.asarray(params[p + "ex_pn"])
    assert pn.shape == (64, 4) and pn[:, :3].std() > 0.15
    kw = dict(top_k=UNCUT.num_experts_per_tok, held_lo=0,
              route_scale=UNCUT.route_scale)
    held = slice(0, eh)
    args = (x, params[p + "router_w"], jnp.zeros(UNCUT.num_experts),
            params[p + "ex_w1"][held], params[p + "ex_w3"][held],
            params[p + "ex_w2"][held])
    with jax.default_matmul_precision("highest"):
        mine, _ = routed_experts_share(
            *args, poly=poly(UNCUT, params[p + "ex_pn"][held]), **kw)
        other, _ = routed_experts_share(
            *args, poly=poly(UNCUT, params[p + "ex_pn"][eh:2 * eh]), **kw)
    assert float(jnp.abs(mine - other).max()) \
        > 0.1 * float(jnp.abs(mine).max())


def share_params(params, rank=None, vocab_rows=None):
    """The parameters with (`rank`) that rank's experts of every MoE layer
    and (`vocab_rows`) those columns of the head; attention, the maps, the
    dense MLP, the shared expert and the router whole."""
    eh = UNCUT.num_experts // SHARES
    out = dict(params)
    for name in params:
        if rank is not None and name.endswith(
                ("ex_w1", "ex_w3", "ex_w2", "ex_pn")):
            out[name] = params[name][rank * eh:(rank + 1) * eh]
    if vocab_rows is not None:
        out["m3_head_w"] = params["m3_head_w"][:, vocab_rows]
    return out


def test_the_vocabulary_slices_logits_concatenate_to_the_whole(world):
    """A slice of the head gives that slice of the logits: a sliced
    vocabulary is a smaller vocabulary."""
    import jax.numpy as jnp

    params, _ = world
    rng = np.random.RandomState(2)
    per = UNCUT.vocab_size // SHARES
    tokens = jnp.asarray(rng.randint(3, per, T), jnp.int32)  # in slice 0
    rc = reference_config(UNCUT)
    whole, _, _ = ref.forward(params, tokens, rc)
    slices = [ref.forward(
        share_params(params, vocab_rows=slice(r * per, (r + 1) * per)),
        tokens, rc)[0] for r in range(SHARES)]
    np.testing.assert_allclose(np.concatenate(slices, axis=1),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_one_shares_engine_is_the_reference_given_the_same_share(world):
    """Experts 8-15 held, rows 0-11 of the vocabulary: the engine's
    prefill logits are the reference's when it is given the same share,
    and are NOT the uncut model's."""
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    params, _ = world
    eh, rows = UNCUT.num_experts // SHARES, UNCUT.vocab_size // SHARES
    cfg = copy.copy(UNCUT)
    cfg.experts_held, cfg.vocab_size = (eh, eh), rows
    mine = share_params(params, rank=1, vocab_rows=slice(0, rows))
    mine["m3_tok_emb"] = params["m3_tok_emb"][:rows]
    engine = DecodeEngine(cfg, mine, DecodeConfig(
        max_slots=2, page_size=8, kv_pages=17, prefill_buckets=[32],
        max_new_tokens=8)).start(warmup=False)
    try:
        prompt = np.random.RandomState(3).randint(3, rows, 21)
        req = engine.submit(prompt, max_new_tokens=4, stop_at_eos=False,
                            keep_first_logits=True)
        chosen = req.result(300)
    finally:
        engine.close()
    seq = np.concatenate([prompt, chosen])
    given, _, _ = ref.Reference(mine, reference_config(cfg)).rows(
        seq, 32, prompt.size - 1, len(chosen))
    # float32 against float32: the order of sums
    assert ref.logit_error(np.asarray(req.first_logits), given[0]) < 1e-4
    assert ref.greedy_gaps(given, chosen).max() < 1e-4
    uncut, _, _ = ref.Reference(params, reference_config(UNCUT)).rows(
        seq, 32, prompt.size - 1, len(chosen))
    assert ref.logit_error(np.asarray(req.first_logits),
                           uncut[0][:rows]) > 0.05
