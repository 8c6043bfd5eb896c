"""models/mellum.py through append_backward and Executor.run against the
plain reference (benchmark/reference_mellum.py) on seeded weights: the
loss and EVERY gradient in float32, two AdamW steps, the four shares of a
layer adding up to the uncut reference layer, the chunked head loss, YaRN
in `qk_norm_rope`, and the step's telemetry fetches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import reference_mellum as ref
from paddle_tpu import layers
from paddle_tpu.core import registry, telemetry
from paddle_tpu.models import mellum
from paddle_tpu.ops import llm_ops

YARN = dict(factor=16.0, original_max=32, beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.2772588722239782)
B, S = 2, 32


def _model(cfg):
    return dict(head_dim=cfg.head_dim, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, layer_types=cfg.layer_types,
                num_experts_per_tok=cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                sliding_window=cfg.sliding_window,
                rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                yarn=cfg.yarn)


@pytest.fixture(scope="module")
def built():
    cfg = mellum.MellumConfig(yarn=YARN, loss_chunk=16)
    main, startup, _feeds, fetches = mellum.build_pretraining_program(
        cfg, B, S, lr=1e-3, seed=3)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    params = {p.name: np.array(scope.find_var(p.name))
              for p in main.all_parameters()}
    return cfg, main, fetches["loss"], exe, scope, params


def test_loss_and_every_gradient_match_the_reference(built):
    cfg, main, loss_v, exe, scope, params = built
    assert set(params) == set(mellum.param_specs(cfg))
    batch = mellum.synthetic_batch(cfg, B, S, seed=5)
    names = sorted(params)
    out = exe.run(main, feed=batch, scope=scope, fetch_list=[loss_v] + [
        n + "@GRAD" for n in names] + [
            mellum.chosen_var(i) for i in range(cfg.n_layers)])
    loss = float(np.asarray(out[0]).reshape(-1)[0])
    grads = dict(zip(names, out[1:1 + len(names)]))
    chosen = np.stack(out[1 + len(names):], axis=1)
    assert chosen.shape == (B, cfg.n_layers, S, cfg.num_experts_per_tok)
    want_loss, want, want_chosen = ref.loss_and_grads(
        params, batch["tokens"], batch["labels"], _model(cfg))
    assert abs(loss / want_loss - 1) < 1e-5
    assert ref.routing_agreement(chosen, want_chosen) == 1.0
    for name in names:
        got, w = np.asarray(grads[name]), np.asarray(want[name])
        assert np.linalg.norm(got - w) <= 1e-4 * np.linalg.norm(w), name
    # the step above was an AdamW step; a second one on the same batch
    # goes on from its state, and the loss falls
    again = float(np.asarray(exe.run(main, feed=batch, scope=scope,
                                     fetch_list=[loss_v])[0]).reshape(-1)[0])
    third = float(np.asarray(exe.run(main, feed=batch, scope=scope,
                                     fetch_list=[loss_v])[0]).reshape(-1)[0])
    assert third < again < loss
    moved = np.array(scope.find_var("ml_l0_ex_w1"))
    assert np.abs(moved - params["ml_l0_ex_w1"]).max() > 1e-4


def test_two_adamw_steps_match_the_reference_by_hand(built):
    """From the seeded weights: p1 is the reference's first AdamW step of
    a zero state (`adamw_first_step`: p0 - lr (g / (|g| + eps') + wd p0)),
    then the reference's loss at p1 is the program's second loss."""
    cfg = mellum.MellumConfig(yarn=YARN, loss_chunk=16)
    main, startup, _f, fetches = mellum.build_pretraining_program(
        cfg, B, S, lr=1e-3, seed=4)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    p0 = {p.name: np.array(scope.find_var(p.name))
          for p in main.all_parameters()}
    batch = mellum.synthetic_batch(cfg, B, S, seed=6)
    losses = [float(np.asarray(exe.run(
        main, feed=batch, scope=scope,
        fetch_list=[fetches["loss"]])[0]).reshape(-1)[0]) for _ in range(2)]
    loss0, g0, _ = ref.loss_and_grads(p0, batch["tokens"], batch["labels"],
                                      _model(cfg))
    p1 = ref.adamw_first_step(p0, g0, lr=1e-3, weight_decay=0.01)
    loss1, _, _ = ref.loss_and_grads(p1, batch["tokens"], batch["labels"],
                                     _model(cfg))
    assert abs(losses[0] / loss0 - 1) < 1e-5
    assert abs(losses[1] / loss1 - 1) < 2e-4
    got = np.array(scope.find_var("ml_l1_q_w"))
    # after two steps the parameter left p1 by one more step of at most lr
    assert np.abs(got - p1["ml_l1_q_w"]).max() < 1.2e-3


def _uncut(seed=9):
    """An uncut layer: 8 query heads on 4 K/V heads, 16 experts."""
    cfg = mellum.MellumConfig(num_heads=8, num_kv_heads=4,
                              experts_held=(0, 16), layer_types=(
                                  mellum.SLIDING, mellum.FULL), yarn=YARN)
    rng = np.random.RandomState(seed)
    params = {}
    for name, (shape, kind, _dt) in mellum.param_specs(cfg).items():
        params[name] = (rng.uniform(0.5, 1.5, shape) if kind == "one"
                        else rng.normal(0, shape[-2] ** -0.5 if isinstance(
                            kind, str) else kind, shape)
                        ).astype(np.float32)
    return cfg, params


@pytest.mark.parametrize("layer", [0, 1], ids=["sliding", "full"])
def test_the_four_shares_add_up_to_the_uncut_layer(layer):
    """Each of four ranks computes, through the program's own ops, its two
    query heads on its K/V head and its four experts of the SAME normed
    input (router and norms whole on every rank, counted once): the
    partial attention outputs and the partial expert outputs add up to
    what the reference gives for the uncut layer."""
    cfg, params = _uncut()
    hd, d = cfg.head_dim, cfg.hidden_size
    x = np.random.RandomState(1).normal(0, 1, (B, S, d)).astype(np.float32)
    pre = f"ml_l{layer}_"
    attn_sum, moe_sum = 0.0, 0.0
    for rank in range(4):
        share = mellum.MellumConfig(
            num_heads=2, num_kv_heads=1, experts_held=(4 * rank, 4),
            layer_types=cfg.layer_types, yarn=YARN)
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            xv = layers.static_data("x", [B, S, d], "float32")
            blk = mellum._Block(share)
            a = blk.attention(blk.norm(xv, pre + "norm_attn"), layer)
            m = blk.experts(blk.norm(xv, pre + "norm_moe"), layer)
        exe, scope = pt.Executor(), pt.Scope()
        exe.run(startup, scope=scope, use_compiled=False)
        q, kv = slice(2 * rank * hd, 2 * (rank + 1) * hd), \
            slice(rank * hd, (rank + 1) * hd)
        ex = slice(4 * rank, 4 * rank + 4)
        for name, value in params.items():
            if not name.startswith(pre):
                continue
            cut = {"q_w": np.s_[:, q], "o_w": np.s_[q], "k_w": np.s_[:, kv],
                   "v_w": np.s_[:, kv], "ex_w1": np.s_[ex],
                   "ex_w3": np.s_[ex], "ex_w2": np.s_[ex]}.get(
                       name[len(pre):], np.s_[...])
            scope.set(name, jnp.asarray(value[cut]))
        got_a, got_m = exe.run(main, feed={"x": x}, scope=scope,
                               fetch_list=[a, m])
        attn_sum, moe_sum = attn_sum + got_a, moe_sum + got_m
    model = _model(cfg)
    p32 = {n: jnp.asarray(v) for n, v in params.items()}
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            want_a = ref.attention(
                p32, pre, ref._rms(x[b], p32[pre + "norm_attn"], eps),
                model, cfg.window_of(layer) > 0)
            want_m = ref.experts(
                p32, pre, ref._rms(x[b], p32[pre + "norm_moe"], eps), model)
            np.testing.assert_allclose(attn_sum[b], want_a, atol=2e-5)
            np.testing.assert_allclose(moe_sum[b], want_m, atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 64, 40])
def test_the_chunked_head_loss_and_its_saved_gradients(chunk):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (2, 32, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.2, (24, 50)), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 50, (2, 32)))
    out = registry.get("head_cross_entropy").forward(
        {"X": [x], "W": [w], "Label": [labels]}, {"chunk": chunk})

    def plain(x, w):
        logp = jax.nn.log_softmax(x.reshape(-1, 24) @ w, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels.reshape(-1, 1), 1))

    want, (dx, dw) = jax.value_and_grad(plain, (0, 1))(x, w)
    np.testing.assert_allclose(out["Loss"], [want], rtol=1e-6)
    np.testing.assert_allclose(out["XGrad"], dx, atol=1e-7)
    np.testing.assert_allclose(out["WGrad"], dw, atol=1e-7)
    scaled = registry.get("head_cross_entropy_grad").forward(
        {"XGrad": [out["XGrad"]], "WGrad": [out["WGrad"]],
         "LossGrad": [jnp.asarray([0.5])]}, {})
    np.testing.assert_allclose(scaled["XGrad"], 0.5 * dx, atol=1e-7)
    np.testing.assert_allclose(scaled["WGrad"], 0.5 * dw, atol=1e-7)


def test_yarn_in_qk_norm_rope_and_plain_rope_unchanged():
    """The program's YaRN frequencies are the reference's own (written
    apart); without the attrs the op computes what it computed."""
    for args in ((128, 5e5, 16.0, 8192, 32.0, 1.0), (16, 1e4, 4.0, 64, 8.0,
                                                     2.0)):
        np.testing.assert_array_equal(llm_ops.yarn_inv_freq(*args),
                                      ref.yarn_inv_freq(*args))
    inv = llm_ops.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    plain = 5e5 ** (-np.arange(64) * 2.0 / 128)
    assert inv[0] == np.float32(plain[0])                 # fast pairs stay
    np.testing.assert_allclose(inv[-1], plain[-1] / 16, rtol=1e-6)
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.normal(0, 1, (1, 6, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 6, 16)), jnp.float32)
    one = jnp.ones((16,))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    attrs = {"head_dim": 16, "rope": True, "theta": 1e4}
    fwd = registry.get("qk_norm_rope").forward
    fed = fwd({"Q": [q], "K": [k], "QScale": [one], "KScale": [one],
               "Positions": [pos]}, attrs)
    own = fwd({"Q": [q], "K": [k], "QScale": [one], "KScale": [one]}, attrs)
    np.testing.assert_array_equal(fed["QOut"], own["QOut"])
    scaled = fwd({"Q": [q], "K": [k], "QScale": [one], "KScale": [one]},
                 dict(attrs, yarn_factor=4.0, yarn_original_max=64,
                      yarn_beta_fast=8.0, yarn_beta_slow=2.0,
                      attention_factor=1.25))
    # position 0 turns nothing: the factor alone
    np.testing.assert_allclose(scaled["KOut"][0, 0], 1.25 * own["KOut"][0, 0],
                               rtol=1e-6)
    assert not np.allclose(scaled["KOut"][0, 5], 1.25 * own["KOut"][0, 5])


def test_a_step_tells_the_registry_its_routing_without_a_sync(built):
    cfg, main, loss_v, exe, scope, _params = built
    assert set(main.telemetry_fetches) == {
        mellum.COUNTS_VAR, mellum.MAX_ROWS_VAR, "moe_train_steps"}
    batch = mellum.synthetic_batch(cfg, B, S, seed=8)
    before = dict(telemetry.counters())

    def moved(name):
        return (telemetry.counter_get(name) or 0) - before.get(name, 0)

    out = exe.run(main, feed=batch, scope=scope, fetch_list=[loss_v])
    assert len(out) == 1                     # the caller's own fetch alone
    assert moved("moe.train.steps") == 1
    pairs = B * S * cfg.num_experts_per_tok * cfg.n_layers
    assert moved("moe.train.pairs") == pairs
    assert 0 < moved("moe.train.pairs_held") < pairs
    assert 0 < moved("moe.train.experts_hit") <= 4 * cfg.n_layers
    # unmaterialised fetches queue theirs; flush_telemetry publishes them
    for _ in range(3):
        out = exe.run(main, feed=batch, scope=scope, fetch_list=[loss_v],
                      sync_fetch=False)
    assert isinstance(out[0], jax.Array)
    exe.flush_telemetry()
    assert moved("moe.train.steps") == 4 and not exe._telemetry_pending
    rows = telemetry.snapshot()["hists"]["moe.train.max_group_rows"]
    assert rows["count"] >= 4 and rows["max"] <= B * S


def test_the_seeded_router_pairs_its_columns(built):
    """Unit columns in antithetic pairs, each layer's and each seed's own;
    a configuration that would cut a pair is refused."""
    cfg, _main, _loss_v, _exe, _scope, params = built
    routers = [params[f"ml_l{i}_router_w"] for i in range(cfg.n_layers)]
    for w in routers:
        assert w.shape == (cfg.hidden_size, cfg.num_experts)
        assert (w[:, 1::2] == -w[:, 0::2]).all()
        np.testing.assert_allclose(np.linalg.norm(w, axis=0), 1.0, rtol=1e-6)
        # the pairs' directions are independent draws
        gram = np.abs(w[:, 0::2].T @ w[:, 0::2] - np.eye(cfg.num_experts // 2))
        assert gram.max() < 0.6
    assert np.abs(routers[0] - routers[1]).max() > 0.1
    _m, startup, _f, _fe = mellum.build_pretraining_program(
        mellum.MellumConfig(yarn=YARN, loss_chunk=16), B, S, seed=4)
    scope = pt.Scope()
    pt.Executor().run(startup, scope=scope, use_compiled=False)
    other = np.array(scope.find_var("ml_l0_router_w"))
    assert np.abs(other - routers[0]).max() > 0.1
    with pytest.raises(ValueError, match="come in pairs"):
        mellum.MellumConfig(experts_held=(1, 4))
    with pytest.raises(ValueError, match="come in pairs"):
        mellum.MellumConfig(experts_held=(0, 3))
