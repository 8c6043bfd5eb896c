"""`routed_experts_share` as the trainer uses it: the softmax scoring, the
gradients of `trainable=True` against a dense loop over the held experts
on both branches of the `few` split (the leading rows; the chunks that go
on past them), nothing dropped at any imbalance, the sigmoid serving path bit-identical
to the parent commit's, and the plan over the pairs (PR 47: kept scores by
comparison, the sorted weights carried by the sort) equal to the parent's
gathers bit for bit, in its gradients, and free of any gather or scatter
of one float a pair."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import routed_experts_share

T, H, F, E, EH, K = 64, 32, 16, 16, 4, 4

# sha256 over (out, counts) of the two cases of `_parent_cases`, as the
# parent commit a59ba39 computed them (PT_PALLAS off and interpret alike)
PARENT_SIGMOID_SHA256 = \
    "a733c5815b593c3b02f784d109116e6e24f5f2654e31c47ef20b33b88a03540b"


def _weights(seed=1, h=H, f=F):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (T, h)),
            jax.random.normal(ks[1], (h, E)) * 0.5 * (H / h) ** 0.5,
            jax.random.normal(ks[2], (EH, h, f)) * 0.2 * (H / h) ** 0.5,
            jax.random.normal(ks[3], (EH, h, f)) * 0.2 * (H / h) ** 0.5,
            jax.random.normal(ks[4], (EH, f, h)) * 0.2 * (F / f) ** 0.5,
            jax.random.normal(ks[5], (T, h)))


def dense(x, rw, w1, w3, w2, held_lo, score_func):
    logits = x @ rw
    p = jax.nn.softmax(logits, -1) if score_func == "softmax" \
        else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(p, K)
    kept = jnp.take_along_axis(p, idx, 1)
    w = kept / kept.sum(1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(EH):
        mine = jnp.sum(jnp.where(idx == held_lo + e, w, 0.0), 1)
        out += mine[:, None] * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e]))
                                @ w2[e])
    return out


@pytest.mark.parametrize("score_func", ["softmax", "sigmoid"])
@pytest.mark.parametrize("branch, held_lo", [("leading", 0), ("leading", 4),
                                             ("every", 0)])
def test_gradients_against_a_dense_loop(score_func, branch, held_lo):
    x, rw, w1, w3, w2, co = _weights()
    if branch == "every":
        # every token's top four are the held four: 256 held pairs, over
        # the 128 leading rows, so the chunks go on
        x = jnp.abs(x) + 0.5
        rw = jnp.where(jnp.arange(E)[None, :] < EH, jnp.abs(rw),
                       -jnp.abs(rw))

    def layer(x, rw, w1, w3, w2):
        return routed_experts_share(
            x, rw, jnp.zeros((E,)), w1, w3, w2, top_k=K, held_lo=held_lo,
            score_func=score_func, trainable=True)

    (out, counts), vjp = jax.vjp(layer, x, rw, w1, w3, w2)
    want, want_vjp = jax.vjp(
        lambda *a: dense(*a, held_lo, score_func), x, rw, w1, w3, w2)
    counts = np.asarray(counts)
    assert counts.shape == (4,) and counts[0] == T * K
    assert (counts[1] > 128) == (branch == "every")     # no pair dropped
    assert counts[3] * EH >= counts[1] >= counts[3]
    np.testing.assert_allclose(out, want, atol=5e-6)
    grads = vjp((co, np.zeros(counts.shape, jax.dtypes.float0)))
    for got, ref in zip(grads, want_vjp(co)):
        assert float(jnp.max(jnp.abs(got - ref))) \
            <= 2e-6 * float(jnp.max(jnp.abs(ref)))


@pytest.mark.parametrize("combine", ["scatter_add", "kernel"])
@pytest.mark.parametrize("branch", ["leading", "every"])
def test_gradients_through_the_backward_kernels(monkeypatch, branch,
                                                combine):
    """The same dense-loop gradients at widths the kernels tile (128 x
    128), in interpret mode: forward and backward kernels in the branch
    the `cond` takes, none of the backward's ragged products; the
    combine as the scatter-add (64 tokens are one tile of 256) and, at
    token tiles of 32, as the `routed_combine` kernel forward and
    backward."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.ops.pallas import routed_combine as rc

    monkeypatch.setenv("PT_PALLAS", "interpret")
    if combine == "kernel":
        monkeypatch.setattr(rc, "TOKEN_TILE", 32)
    telemetry.reset()
    x, rw, w1, w3, w2, co = _weights(seed=3, h=128, f=128)
    if branch == "every":
        x = jnp.abs(x) + 0.5
        rw = jnp.where(jnp.arange(E)[None, :] < EH, jnp.abs(rw),
                       -jnp.abs(rw))

    def layer(x, rw, w1, w3, w2):
        return routed_experts_share(
            x, rw, jnp.zeros((E,)), w1, w3, w2, top_k=K, held_lo=0,
            score_func="softmax", trainable=True)

    (out, counts), vjp = jax.vjp(layer, x, rw, w1, w3, w2)
    want, want_vjp = jax.vjp(
        lambda *a: dense(*a, 0, "softmax"), x, rw, w1, w3, w2)
    assert (int(counts[1]) > 128) == (branch == "every")
    np.testing.assert_allclose(out, want, atol=2e-5)
    grads = vjp((co, np.zeros(counts.shape, jax.dtypes.float0)))
    # one site a `cond` branch, at trace time
    assert telemetry.counter_get("pallas.grouped_swiglu_bwd_dispatches") == 2
    assert telemetry.counter_get("pallas.grouped_swiglu_bwd_fallbacks") == 0
    # forward and backward, a `cond` branch each
    kernel = 4 * (combine == "kernel")
    assert telemetry.counter_get("pallas.routed_combine_dispatches") == kernel
    assert telemetry.counter_get("pallas.routed_combine_fallbacks") \
        == 4 - kernel
    for got, ref in zip(grads, want_vjp(co)):
        assert float(jnp.max(jnp.abs(got - ref))) \
            <= 1e-5 * float(jnp.max(jnp.abs(ref)))


def test_the_trained_forward_is_the_served_forward():
    x, rw, w1, w3, w2, _co = _weights(seed=2)
    args = (x, rw, jnp.zeros((E,)), w1, w3, w2)
    kw = dict(top_k=K, held_lo=0, score_func="softmax")
    served, counts = routed_experts_share(*args, **kw)
    trained, counts4, chosen = routed_experts_share(
        *args, trainable=True, with_chosen=True, **kw)
    assert (np.asarray(served) == np.asarray(trained)).all()
    assert (np.asarray(counts) == np.asarray(counts4)[:3]).all()
    assert chosen.shape == (T, K) and chosen.dtype == jnp.int32
    with pytest.raises(ValueError, match="score_func"):
        routed_experts_share(*args, top_k=K, held_lo=0, score_func="tanh")


def _parent_cases():
    for case, (t, e, eh, k, lo, skew) in enumerate(
            [(64, 16, 4, 4, 4, 0.0), (96, 8, 4, 2, 0, 1.0)]):
        ks = jax.random.split(jax.random.PRNGKey(100 + case), 6)
        x = jax.random.normal(ks[0], (t, 32))
        rw = jax.random.normal(ks[1], (32, e)) * 0.4
        if skew:        # every token chooses held experts: `every`
            x = jnp.abs(x) + 0.5
            rw = jnp.where(jnp.arange(e)[None, :] < eh, jnp.abs(rw),
                           -jnp.abs(rw))
        w1, w3, w2 = ((jax.random.normal(key, shape) * 0.2).astype(
            jnp.bfloat16) for key, shape in (
                (ks[2], (eh, 32, 16)), (ks[3], (eh, 32, 16)),
                (ks[4], (eh, 16, 32))))
        yield dict(
            x=x, router_w=rw, select_bias=jax.random.normal(ks[5], (e,))
            * 0.01, w1=w1, w3=w3, w2=w2, top_k=k, held_lo=lo,
            route_scale=2.448, route_norm=case % 2 == 0,
            live=jnp.arange(t) % 7 != 3)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_sigmoid_path_is_bit_identical_to_the_parents(monkeypatch, mode):
    monkeypatch.setenv("PT_PALLAS", mode)
    digest = hashlib.sha256()
    for case in _parent_cases():
        out, counts = routed_experts_share(**case)
        assert counts.shape == (3,)
        digest.update(np.asarray(out).tobytes())
        digest.update(np.asarray(counts).tobytes())
    assert digest.hexdigest() == PARENT_SIGMOID_SHA256


def parent_plan(scores, select_bias, alive, *, top_k, held_lo, e_held,
                route_scale, route_norm, norm_eps=1e-20):
    """`moe._pair_plan` as the parent commit 802ff6f wrote it inside
    `routed_experts_share`: the kept scores and the sorted weights by a
    gather each. That commit had one denominator; `norm_eps` (PR 58) is
    handed on at its default, which is that one."""
    assert norm_eps == 1e-20
    _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
    kept = jnp.take_along_axis(scores, idx, axis=1)
    weight = kept
    if route_norm:
        weight = kept / (jnp.sum(kept, axis=1, keepdims=True) + 1e-20)
    weight = weight * route_scale
    local = idx - held_lo
    held = (local >= 0) & (local < e_held) & alive[:, None]
    key = jnp.where(held, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, e_held + 1, dtype=jnp.int32),
                    axis=0)[:e_held]
    rows = (order // top_k).astype(jnp.int32)
    w_sorted = jnp.where(held, weight, 0.0).reshape(-1)[order]
    return idx, kept, order, rows, sizes, w_sorted


# (E, top_k, held experts, H, F): Mellum's routing, and Qwen3-Next's many
# small experts at a small H
PLAN_SHAPES = {"e64_top8_held16": (64, 8, 16, 32, 16),
               "e512_top10_held128": (512, 10, 128, 16, 8)}
PLAN_T = 48


def _plan_case(shape, variant, seed=7):
    e, k, eh, h, f = PLAN_SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (PLAN_T, h))
    if variant == "tied_scores":
        # a row of zeros scores every expert alike, and half of another
        # row's experts score alike in pairs
        x = x.at[5].set(0.0)
    rw = jax.random.normal(ks[1], (h, e)) * 0.5
    if variant == "tied_scores":
        rw = rw.at[:, 1::2].set(rw[:, 0::2])
    live = jnp.arange(PLAN_T) % 5 != 2 if variant == "live_mask" else None
    bias = jax.random.normal(ks[5], (e,)) * 0.05 \
        if variant == "select_bias" else jnp.zeros((e,))
    return dict(
        x=x, router_w=rw, select_bias=bias,
        w1=jax.random.normal(ks[2], (eh, h, f)) * 0.2,
        w3=jax.random.normal(ks[3], (eh, h, f)) * 0.2,
        w2=jax.random.normal(ks[4], (eh, f, h)) * 0.2, top_k=k,
        held_lo=eh if variant == "held_lo" else 0, route_scale=1.5,
        live=live)


@pytest.mark.parametrize("variant", ["plain", "live_mask", "select_bias",
                                     "held_lo", "tied_scores"])
@pytest.mark.parametrize("shape", list(PLAN_SHAPES))
@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
def test_the_plan_is_the_parents_bit_for_bit(monkeypatch, score_func, shape,
                                             variant):
    """Op by op (no `jit` around the layer: under one XLA may fuse the
    softmax's division another way): the kept scores, the order, the
    sorted weights, the groups, the layer's output and its counts."""
    case = _plan_case(shape, variant)
    _e, k, eh, _h, _f = PLAN_SHAPES[shape]
    logits = jnp.matmul(case["x"], case["router_w"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    alive = jnp.ones((PLAN_T,), bool) if case["live"] is None \
        else case["live"]
    kw = dict(top_k=k, held_lo=case["held_lo"], e_held=eh, route_scale=1.5,
              route_norm=True)
    got = moe._pair_plan(scores, case["select_bias"], alive, **kw)
    want = parent_plan(scores, case["select_bias"], alive, **kw)
    for name, a, b in zip(("idx", "kept", "order", "rows", "sizes",
                           "w_sorted"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), name
    assert 0 < int(jnp.sum(got[4])) < PLAN_T * k    # some held, some not
    if variant == "tied_scores":
        assert len(np.unique(np.asarray(scores[5]))) == 1

    out, counts = routed_experts_share(**case, score_func=score_func)
    monkeypatch.setattr(moe, "_pair_plan", parent_plan)
    want_out, want_counts = routed_experts_share(**case,
                                                 score_func=score_func)
    assert np.asarray(out).tobytes() == np.asarray(want_out).tobytes()
    assert np.asarray(counts).tobytes() == np.asarray(want_counts).tobytes()
    assert int(counts[1]) == int(jnp.sum(got[4]))


@pytest.mark.parametrize("shape", list(PLAN_SHAPES))
@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
def test_the_plans_gradients_are_the_parents(monkeypatch, score_func, shape):
    """A trained layer's gradients in x, the router and the three
    matrices, the plan's transpose by comparison and by a sort, against
    JAX's own differentiation of the parent's gathers."""
    case = _plan_case(shape, "select_bias")
    co = jax.random.normal(jax.random.PRNGKey(11), case["x"].shape)

    def grads():
        # a function of its own a call: a trace is cached by it
        def loss(x, rw, w1, w3, w2):
            out, _counts = routed_experts_share(
                x, rw, case["select_bias"], w1, w3, w2,
                top_k=case["top_k"], held_lo=0, route_scale=1.5,
                score_func=score_func, trainable=True)
            return jnp.sum(out * co)

        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
            *(case[n] for n in ("x", "router_w", "w1", "w3", "w2")))

    got = grads()
    monkeypatch.setattr(moe, "_pair_plan", parent_plan)
    for name, a, b in zip(("x", "router_w", "w1", "w3", "w2"), got, grads()):
        largest = float(jnp.max(jnp.abs(b)))
        assert largest > 0, name
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * largest, name


def _single_float_moves(jaxpr, shapes):
    """The gathers and scatters of `jaxpr`, through every sub-jaxpr, whose
    operand or update has one of `shapes`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("gather", "scatter", "scatter-add",
                                  "scatter_add"):
            moved = [v.aval.shape for v in eqn.invars[:1] + eqn.invars[2:]]
            if any(s in shapes for s in moved):
                found.append((eqn.primitive.name, moved))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _single_float_moves(sub, shapes)
    return found


@pytest.mark.parametrize("program", ["forward", "gradient"])
def test_the_plan_moves_no_single_floats(monkeypatch, program):
    """Neither the forward's jaxpr nor the gradient's holds a gather or a
    scatter over one float a pair ([T*k], [T, k], [T, E]); the parent's
    plan under the same walk holds both (the row gathers of [n, H]
    around the kernels stay, and are not this test's)."""
    case = _plan_case("e64_top8_held16", "plain")
    e, k = 64, 8
    shapes = {(PLAN_T * k,), (PLAN_T, k), (PLAN_T, e)}

    def moves():
        # a function of its own a call: a trace is cached by it
        def layer(x, rw, w1, w3, w2):
            out, _counts = routed_experts_share(
                x, rw, case["select_bias"], w1, w3, w2, top_k=k, held_lo=0,
                score_func="softmax", trainable=True)
            return jnp.sum(out * out)

        fn = layer if program == "forward" \
            else jax.grad(layer, argnums=(0, 1, 2, 3, 4))
        jaxpr = jax.make_jaxpr(fn)(
            *(case[n] for n in ("x", "router_w", "w1", "w3", "w2")))
        return _single_float_moves(jaxpr.jaxpr, shapes)

    assert moves() == []
    monkeypatch.setattr(moe, "_pair_plan", parent_plan)
    parents = {name for name, _ in moves()}
    assert "gather" in parents
    if program == "gradient":
        assert parents & {"scatter-add", "scatter_add"}
