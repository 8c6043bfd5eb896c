"""The window / grouped-head flash kernels (ops/pallas/flash_window.py) in
interpret mode against the plain masked form: the forward and the three
gradients, over windows under, equal to and over a block and over the
sequence, for groups of 1 and 8; the routes; the `flash_attention` op and
its grad op with `window` / `num_kv_heads` through the Executor."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import telemetry
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.models.program_block import op
from paddle_tpu.ops.pallas import flash_window as fw

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

S, HD, BLOCK = 256, 128, 64


def _inputs(group, kv_heads, seed=0, dtype=jnp.float32, s=S):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = group * kv_heads
    return (jax.random.normal(keys[0], (1, s, n * HD), dtype),
            jax.random.normal(keys[1], (1, s, kv_heads * HD), dtype),
            jax.random.normal(keys[2], (1, s, kv_heads * HD), dtype),
            jax.random.normal(keys[3], (1, s, n * HD), dtype), n)


@pytest.mark.parametrize("group, kv_heads", [(1, 2), (8, 1)])
@pytest.mark.parametrize("window", [0, 32, 64, 100, 300],
                         ids=["full", "under_a_block", "a_block",
                              "over_a_block", "over_the_sequence"])
def test_the_kernels_match_the_masked_form(monkeypatch, window, group,
                                           kv_heads):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    q, k, v, do, n = _inputs(group, kv_heads)
    assert fw.window_route(q, k, n, kv_heads, BLOCK) \
        == ("pallas_interpret", BLOCK)

    def kernels(q, k, v):
        return fw.flash_window_attention(q, k, v, n, kv_heads, window, None,
                                         BLOCK)

    def plain(q, k, v):
        return fw.masked_attention(q, k, v, num_heads=n,
                                   num_kv_heads=kv_heads, window=window)

    out, vjp = jax.vjp(kernels, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    for got, ref in zip(vjp(do), want_vjp(do)):
        assert float(jnp.max(jnp.abs(got - ref))) \
            <= 3e-6 * float(jnp.max(jnp.abs(ref)))


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("window", [0, 100])
def test_the_forward_writes_its_accumulator_in_the_dtype_asked_for(
        monkeypatch, mode, window):
    """bfloat16 operands, `out_dtype` float32: the float32 accumulator
    unrounded (the whole-prompt attention op's contract), whose rounding
    to bfloat16 is, bit for bit, what the forward gives without the
    argument; lse is the same array either way."""
    monkeypatch.setenv("PT_PALLAS", mode)
    q, k, v, _do, n = _inputs(2, 1, dtype=jnp.bfloat16)
    kw = dict(num_heads=n, num_kv_heads=1, window=window, block=BLOCK)
    out, lse = fw.flash_window_fwd_lse(q, k, v, **kw)
    wide, lse32 = fw.flash_window_fwd_lse(q, k, v, out_dtype=jnp.float32,
                                          **kw)
    assert out.dtype == jnp.bfloat16 and wide.dtype == jnp.float32
    assert bool(jnp.any(wide != out.astype(jnp.float32)))
    np.testing.assert_array_equal(wide.astype(jnp.bfloat16), out)
    np.testing.assert_array_equal(lse32, lse)


# sha256 of the jaxpr `flash_window_fwd_lse` traced to at the parent of the
# PR that gave it `out_dtype` (474886e, interpret mode, the shapes below)
_FWD_JAXPR_BEFORE_OUT_DTYPE = {0: "76683cc4524958f4", 100: "b204f25f90762206"}


@pytest.mark.parametrize("window", sorted(_FWD_JAXPR_BEFORE_OUT_DTYPE))
def test_the_forward_without_out_dtype_traces_to_what_it_did(monkeypatch,
                                                             window):
    """The trainer calls the forward without the argument: its program
    does not move (the trainers' fingerprints are traced with kernels
    off and cannot say so)."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    q = jax.ShapeDtypeStruct((1, 256, 2 * HD), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, HD), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v: fw.flash_window_fwd_lse(
        q, k, v, num_heads=2, num_kv_heads=1, window=window, block=BLOCK))(
            q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _FWD_JAXPR_BEFORE_OUT_DTYPE[window]


def test_a_block_is_visited_only_if_the_window_reaches_it():
    assert fw.reach_of(8192, 512, 1024) == 3        # of 16
    assert fw.reach_of(8192, 512, 0) == 16
    assert fw.reach_of(8192, 512, 512) == 2
    assert fw.reach_of(8192, 512, 513) == 2
    assert fw.reach_of(8192, 512, 514) == 3
    assert fw.reach_of(1024, 512, 4096) == 2        # the whole sequence


def test_the_saved_residuals_feed_the_backward(monkeypatch):
    """`flash_attention_fwd_lse` / `flash_attention_bwd` with a window: the
    grad op's path, no forward again; bnsd arrays come back bnsd."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    q, k, v, do, n = _inputs(4, 2, seed=3, s=128)
    out, lse = fa.flash_attention_fwd_lse(q, k, v, causal=True, num_heads=n,
                                          window=48, num_kv_heads=2)
    assert lse.shape == (1, n, 128) and float(jnp.max(jnp.abs(lse))) > 0
    dq, dk, dv, dbias = fa.flash_attention_bwd(
        q, k, v, None, out, lse, do, causal=True, num_heads=n, window=48,
        num_kv_heads=2)
    _, want = jax.vjp(lambda *a: fw.masked_attention(
        *a, num_heads=n, num_kv_heads=2, window=48), q, k, v)
    for got, ref in zip((dq, dk, dv), want(do)):
        np.testing.assert_allclose(got, ref, atol=3e-5)
    assert dbias is None
    to4 = lambda x, h: jnp.swapaxes(x.reshape(1, 128, h, HD), 1, 2)  # noqa
    out4 = fa.flash_attention(to4(q, n), to4(k, 2), to4(v, 2), causal=True,
                              window=48, num_kv_heads=2)
    np.testing.assert_allclose(out4, to4(out, n), atol=1e-6)


def test_what_the_kernels_do_not_compute_is_refused_or_falls_back(
        monkeypatch):
    q, k, v, _do, n = _inputs(2, 1, s=64)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, num_heads=n, window=8)
    with pytest.raises(ValueError, match="no bias"):
        fa.flash_attention(q, k, v, causal=True, num_heads=n, window=8,
                           bias=jnp.zeros((1, 64)))
    monkeypatch.setenv("PT_PALLAS", "off")
    before = telemetry.counter_get("pallas.flash_window_fallbacks") or 0
    out = fa.flash_attention(q, k, v, causal=True, num_heads=n, window=8,
                             num_kv_heads=1)
    assert (telemetry.counter_get("pallas.flash_window_fallbacks") or 0) \
        == before + 1
    np.testing.assert_allclose(out, fw.masked_attention(
        q, k, v, num_heads=n, num_kv_heads=1, window=8), atol=1e-6)
    # a sequence no block divides takes the plain form in interpret mode too
    monkeypatch.setenv("PT_PALLAS", "interpret")
    assert fw.window_route(q[:, :50], k[:, :50], n, 1) == ("reference", None)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_op_and_its_grad_op_through_the_executor(monkeypatch, mode):
    monkeypatch.setenv("PT_PALLAS", mode)
    q, k, v, do, n = _inputs(2, 2, seed=5, s=128)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        qv, kv, vv = (layers.create_parameter(
            list(a.shape), "float32", attr=pt.ParamAttr(
                name=name, initializer=NumpyArrayInitializer(np.asarray(a))))
            for name, a in (("q", q), ("k", k), ("v", v)))
        w = layers.static_data("w", list(do.shape), "float32")
        out, _lse = op("flash_attention", {"Q": qv, "K": kv, "V": vv},
                       {"Out": None, "Lse": None},
                       {"causal": True, "head_dim": HD, "num_heads": n,
                        "num_kv_heads": 2, "window": 40})
        loss = layers.reduce_sum(out * w)
        pt.append_backward(loss)
    assert "flash_attention_grad" in [o.type for o in
                                      main.global_block().ops]
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    got = exe.run(main, feed={"w": np.asarray(do)}, scope=scope,
                  fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    want, vjp = jax.vjp(lambda *a: fw.masked_attention(
        *a, num_heads=n, num_kv_heads=2, window=40), q, k, v)
    for a, b in zip(got, (want,) + vjp(do)):
        np.testing.assert_allclose(a, b, atol=3e-5)
