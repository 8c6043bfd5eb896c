"""`routed_experts_share` as the trainer uses it: the softmax scoring, the
gradients of `trainable=True` against a dense loop over the held experts
on both branches of the `few` split (the leading rows; the chunks that go
on past them), nothing dropped at any imbalance, and the sigmoid serving
path bit-identical to the parent commit's."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
