"""Pallas kernel tests: interpreter-mode kernels vs jnp references.

Mirrors the reference's fused-kernel tests (test_fused_multihead_matmul_op,
test_layer_norm_op) — the kernel is validated against the unfused
composition, fwd and grad.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "interpret")


def _rand(*shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(np.float32))


class TestFlashAttention:
    def test_fwd_matches_reference(self, interpret_mode):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = _rand(2, 2, 128, 64, seed=0), _rand(2, 2, 128, 64, seed=1), \
            _rand(2, 2, 128, 64, seed=2)
        out = flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_padding_bias(self, interpret_mode):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(2, 2, 128, 64, seed=s) for s in range(3))
        mask = (np.random.RandomState(3).rand(2, 128) < 0.25)
        bias = jnp.asarray(mask * -10000.0).astype(jnp.float32)
        out = flash_attention(q, k, v, bias=bias.reshape(2, 1, 1, 128))
        ref = reference_attention(q, k, v, bias_kv=bias)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_causal_multiblock(self, interpret_mode):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(1, 2, 256, 64, seed=s) for s in range(3))
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_grads_match_reference(self, interpret_mode):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(1, 2, 128, 64, seed=s) for s in range(3))

        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(reference_attention(*a, causal=True) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_causal_cross_shape(self, interpret_mode):
        """sq != sk causal must be bottom-right aligned like the reference."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q = _rand(1, 1, 128, 32, seed=0)
        k, v = _rand(1, 1, 256, 32, seed=1), _rand(1, 1, 256, 32, seed=2)
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_bias_grad(self, interpret_mode):
        """A trainable additive key bias must receive a real gradient
        (ADVICE r1: dbias was silently None)."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(2, 2, 128, 64, seed=s) for s in range(3))
        bias = _rand(2, 128, seed=7) * 0.1

        db1 = jax.grad(lambda b: jnp.sum(
            flash_attention(q, k, v, bias=b) ** 2))(bias)
        db2 = jax.grad(lambda b: jnp.sum(
            reference_attention(q, k, v, bias_kv=b) ** 2))(bias)
        assert float(jnp.max(jnp.abs(db2))) > 1e-3  # non-trivial signal
        np.testing.assert_allclose(db1, db2, atol=5e-5)

    def test_xla_recompute_path_matches_reference(self):
        """The XLA custom_vjp (recompute backward) implementation must match
        the reference for outputs and all four gradients."""
        from paddle_tpu.ops.pallas.flash_attention import (
            _xla_attention, reference_attention)

        q, k, v = (_rand(2, 2, 64, 32, seed=s) for s in range(3))
        bias = _rand(2, 64, seed=9) * 0.1
        scale = 1.0 / np.sqrt(32)

        seed = jnp.uint32(0)
        out = _xla_attention(q, k, v, bias, seed, False, scale)
        ref = reference_attention(q, k, v, bias_kv=bias, scale=scale)
        np.testing.assert_allclose(out, ref, atol=2e-5)

        g1 = jax.grad(lambda *a: jnp.sum(
            _xla_attention(*a, seed, False, scale) ** 2),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(lambda *a: jnp.sum(reference_attention(
            *a[:3], bias_kv=a[3], scale=scale) ** 2),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5)

        # causal variant
        out = _xla_attention(q, k, v, None, seed, True, scale)
        ref = reference_attention(q, k, v, causal=True, scale=scale)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_xla_chunked_path_matches_reference(self, monkeypatch):
        """q-chunked XLA attention (scan over query chunks, bounded f32
        scores transients) must match the reference exactly."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        monkeypatch.setattr(fa, "XLA_ATTN_CHUNK_TARGET_BYTES", 1 << 10)
        q, k, v = (_rand(2, 2, 256, 32, seed=s) for s in range(3))
        bias = _rand(2, 256, seed=9) * 0.1
        assert fa._q_chunk(q, k) < 256  # chunking actually engaged
        seed = jnp.uint32(0)
        for causal in (False, True):
            out = fa._xla_attention(q, k, v, bias, seed, causal, 0.17)
            ref = fa.reference_attention(q, k, v, bias_kv=bias,
                                         causal=causal, scale=0.17)
            np.testing.assert_allclose(out, ref, atol=3e-5)
            g1 = jax.grad(lambda *a: jnp.sum(
                fa._xla_attention(*a, seed, causal, 0.17) ** 2),
                argnums=(0, 1, 2, 3))(q, k, v, bias)
            g2 = jax.grad(lambda *a: jnp.sum(fa.reference_attention(
                *a[:3], bias_kv=a[3], causal=causal, scale=0.17) ** 2),
                argnums=(0, 1, 2, 3))(q, k, v, bias)
            for a, b in zip(g1, g2):
                np.testing.assert_allclose(a, b, atol=1e-4)

    def test_unsupported_shapes_fall_back(self):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(1, 1, 40, 16, seed=s) for s in range(3))
        out = flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)


class TestAttentionProbsDropout:
    """Attention-probs dropout on the fused paths (VERDICT r2 #3): the
    position-keyed stateless mask must (a) actually drop ~rate of probs,
    (b) be identical across the XLA-recompute / chunked / Pallas paths,
    (c) recompute bit-identically in the backward (grads match autodiff
    through the reference with the same mask)."""

    RATE = 0.25

    def test_mask_statistics_and_effect(self):
        from paddle_tpu.ops.pallas.flash_attention import (
            _attn_keep_scale, reference_attention)

        m = _attn_keep_scale(jnp.uint32(123), self.RATE, (2, 4, 64, 64),
                             0, 0, 4, 64, 64)
        keep_frac = float(jnp.mean(m > 0))
        assert abs(keep_frac - (1 - self.RATE)) < 0.02
        # kept entries carry the 1/(1-rate) upscale
        assert np.allclose(float(jnp.max(m)), 1.0 / (1 - self.RATE))
        # different seeds -> different masks
        m2 = _attn_keep_scale(jnp.uint32(124), self.RATE, (2, 4, 64, 64),
                              0, 0, 4, 64, 64)
        assert float(jnp.mean((m > 0) != (m2 > 0))) > 0.1

        q, k, v = (_rand(1, 2, 64, 32, seed=s) for s in range(3))
        on = reference_attention(q, k, v, dropout_rate=self.RATE,
                                 dropout_seed=jnp.uint32(5))
        off = reference_attention(q, k, v)
        assert float(jnp.max(jnp.abs(on - off))) > 1e-3

    def test_xla_recompute_dropout_matches_reference(self):
        from paddle_tpu.ops.pallas.flash_attention import (
            _xla_attention, reference_attention)

        q, k, v = (_rand(2, 2, 64, 32, seed=s) for s in range(3))
        bias = _rand(2, 64, seed=9) * 0.1
        seed = jnp.uint32(77)
        scale = 1.0 / np.sqrt(32)

        out = _xla_attention(q, k, v, bias, seed, False, scale, self.RATE)
        ref = reference_attention(q, k, v, bias_kv=bias, scale=scale,
                                  dropout_rate=self.RATE, dropout_seed=seed)
        np.testing.assert_allclose(out, ref, atol=2e-5)

        g1 = jax.grad(lambda *a: jnp.sum(
            _xla_attention(*a, seed, False, scale, self.RATE) ** 2),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(lambda *a: jnp.sum(reference_attention(
            *a[:3], bias_kv=a[3], scale=scale, dropout_rate=self.RATE,
            dropout_seed=seed) ** 2), argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_chunked_dropout_matches_unchunked(self, monkeypatch):
        """q-chunking must not change the mask (global-position keying)."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        q, k, v = (_rand(2, 2, 256, 32, seed=s) for s in range(3))
        seed = jnp.uint32(3)
        ref = fa.reference_attention(q, k, v, scale=0.17,
                                     dropout_rate=self.RATE,
                                     dropout_seed=seed)
        monkeypatch.setattr(fa, "XLA_ATTN_CHUNK_TARGET_BYTES", 1 << 10)
        assert fa._q_chunk(q, k) < 256
        out = fa._xla_attention(q, k, v, None, seed, False, 0.17, self.RATE)
        np.testing.assert_allclose(out, ref, atol=3e-5)
        g1 = jax.grad(lambda *a: jnp.sum(fa._xla_attention(
            *a, None, seed, False, 0.17, self.RATE) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(fa.reference_attention(
            *a, scale=0.17, dropout_rate=self.RATE,
            dropout_seed=seed) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_pallas_dropout_matches_reference(self, interpret_mode):
        """In-kernel dropout (interpret mode) == reference, fwd + grads,
        with a padding bias in play."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention, reference_attention)

        q, k, v = (_rand(2, 2, 128, 64, seed=s) for s in range(3))
        mask = (np.random.RandomState(3).rand(2, 128) < 0.25)
        bias = jnp.asarray(mask * -10000.0).astype(jnp.float32)
        seed = jnp.uint32(42)
        out = flash_attention(q, k, v, bias=bias.reshape(2, 1, 1, 128),
                              dropout_rate=self.RATE, dropout_seed=seed)
        ref = reference_attention(q, k, v, bias_kv=bias,
                                  dropout_rate=self.RATE, dropout_seed=seed)
        np.testing.assert_allclose(out, ref, atol=2e-5)

        g1 = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a[:3], bias=a[3].reshape(2, 1, 1, 128),
            dropout_rate=self.RATE, dropout_seed=seed) ** 2),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(lambda *a: jnp.sum(reference_attention(
            *a[:3], bias_kv=a[3], dropout_rate=self.RATE,
            dropout_seed=seed) ** 2), argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-5)

    def test_flash_attention_op_dropout_steps_vary(self):
        """Through the registered op: dropout_prob>0 changes the output,
        and different __step__ values give different masks (fresh noise
        per training step) while the same step reproduces."""
        from paddle_tpu.core.registry import get as get_op

        q, k, v = (_rand(1, 2, 64, 32, seed=s) for s in range(3))
        op = get_op("flash_attention")
        ins = {"Q": [q], "K": [k], "V": [v]}
        base = dict(dropout_prob=self.RATE, seed=11)
        o1 = op.forward(ins, {**base, "__step__": jnp.int32(0)})["Out"]
        o1b = op.forward(ins, {**base, "__step__": jnp.int32(0)})["Out"]
        o2 = op.forward(ins, {**base, "__step__": jnp.int32(1)})["Out"]
        otest = op.forward(ins, {**base, "is_test": True})["Out"]
        onone = op.forward(ins, {})["Out"]
        np.testing.assert_allclose(o1, o1b, atol=0)
        assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-4
        np.testing.assert_allclose(otest, onone, atol=0)


class TestFusedLayerNorm:
    def _ref(self, x, s, b, eps=1e-5):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * s + b

    def test_fwd_and_grad(self, interpret_mode):
        from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm

        x = _rand(6, 384, seed=0)
        s, b = _rand(384, seed=1), _rand(384, seed=2)
        y, mean, rstd = fused_layer_norm(x, s, b)
        np.testing.assert_allclose(y, self._ref(x, s, b), atol=2e-5)
        np.testing.assert_allclose(mean, jnp.mean(x, -1), atol=1e-5)

        g1 = jax.grad(lambda *a: jnp.sum(fused_layer_norm(*a)[0] ** 2),
                      argnums=(0, 1, 2))(x, s, b)
        g2 = jax.grad(lambda *a: jnp.sum(self._ref(*a) ** 2),
                      argnums=(0, 1, 2))(x, s, b)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(a, b_, atol=5e-5)


def _route_case(layout, b, n, sq, sk, hd):
    """(q, k) shapes only: the route function reads nothing else."""
    if layout == "packed":
        return (jax.ShapeDtypeStruct((b, sq, n * hd), jnp.bfloat16),
                jax.ShapeDtypeStruct((b, sk, n * hd), jnp.bfloat16), n)
    return (jax.ShapeDtypeStruct((b, n, sq, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, n, sk, hd), jnp.bfloat16), None)


def _bias(form, b, sk):
    return {None: None,
            "key4": jnp.zeros((b, 1, 1, sk), jnp.float32),
            "key2": jnp.zeros((b, sk), jnp.float32),
            "general": jnp.zeros((b, 1, 8, sk), jnp.float32)}[form]


# mode, layout, (b, n, sq, sk, hd), bias form -> route. The routes are
# the ones the three functions this table replaced gave for these shapes.
ROUTE_TABLE = [
    # cell 1 of the benchmark: ERNIE-large b40 s512 16x64, packed
    ("tpu", "packed", (40, 16, 512, 512, 64), "key4", "packed"),
    # BERT-base s128 b384: packed kernels come before FUSED_MIN_SEQ
    ("tpu", "packed", (384, 12, 128, 128, 64), "key4", "packed"),
    ("interpret", "packed", (2, 4, 256, 256, 64), "key4", "packed"),
    # bnsd below FUSED_MIN_SEQ: XLA wins in-program on a TPU
    ("tpu", "bnsd", (384, 12, 128, 128, 64), None, "xla"),
    ("off", "bnsd", (384, 12, 128, 128, 64), None, "reference"),
    ("interpret", "bnsd", (2, 2, 128, 128, 64), None, "pallas_interpret"),
    # past one tile a row: the two-pass kernels, either layout
    ("tpu", "bnsd", (4, 16, 2048, 2048, 64), "key2", "pallas"),
    ("tpu", "packed", (4, 16, 2048, 2048, 64), None, "pallas"),
    # s4096: scores XLA cannot hold
    ("tpu", "bnsd", (4, 16, 4096, 4096, 64), None, "pallas"),
    # sq < FUSED_MIN_SEQ but 4*b*h*sq*sk >= PALLAS_MIN_SCORES_BYTES
    ("tpu", "bnsd", (64, 16, 128, 4096, 64), None, "pallas"),
    # cross-attention sq != sk: never the packed kernels
    ("tpu", "packed", (2, 4, 256, 128, 64), "key4", "pallas"),
    ("tpu", "packed", (2, 4, 128, 256, 64), "key4", "xla"),
    ("interpret", "packed", (2, 4, 256, 128, 64), "key4",
     "pallas_interpret"),
    # shapes the kernels cannot tile
    ("tpu", "packed", (2, 4, 1000, 1000, 64), None, "xla"),
    ("interpret", "packed", (2, 4, 1000, 1000, 64), None, "reference"),
    # tiles, but the head blocks are not lane-aligned: bnsd kernels
    ("interpret", "packed", (2, 4, 40, 40, 16), None, "pallas_interpret"),
    # a bias that is not a key bias
    ("tpu", "bnsd", (2, 4, 512, 512, 64), "general", "reference_general"),
    # a step XLA partitions itself holds no Mosaic kernel
    ("auto_partitioned", "packed", (40, 16, 512, 512, 64), "key4", "xla"),
]


def _kernel_eqns(jaxpr, name):
    """The equations of the pallas_call named `name` inside `jaxpr`,
    through jit calls."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" \
                and eqn.params["name"] == name:
            return eqn.params["jaxpr"].eqns
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _kernel_eqns(sub, name)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("kind,products", [("fwd", 2), ("bwd", 5)])
def test_packed_cell_shape_takes_two_heads_a_lane_tile(kind, products):
    """ERNIE-large's attention (b40 s512 16 x 64, key bias, dropout) as
    the packed kernels trace it: every block access is a whole 128-lane
    tile of the [512, g*64] block (no head is sliced out at lane 64),
    every product contracts or puts out 128 lanes (two heads' worth, one
    of them masked), and there are `products` of them a head."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    b, n, s, hd = 40, 16, 512, 64
    assert fa._packed_unit(hd) == (2, 128)
    x = jax.ShapeDtypeStruct((b, s, n * hd), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((b, s), jnp.float32)
    lse = jax.ShapeDtypeStruct((b, n, s), jnp.float32)
    seed = jax.ShapeDtypeStruct((), jnp.uint32)
    if kind == "fwd":
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, bi, se: fa._fwd_pallas_packed(
                q, k, v, bi, False, 0.125, False, se, 0.1, n))(
            x, x, x, bias, seed)
    else:
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, bi, o, l, do, se: fa._bwd_pallas_packed(
                q, k, v, bi, False, 0.125, False, o, l, do, se, 0.1, n))(
            x, x, x, bias, x, lse, x, seed)
    eqns = _kernel_eqns(jaxpr.jaxpr, f"flash_{kind}_packed")
    assert eqns is not None
    g = fa._packed_g(n, hd, s)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == products * g
    for e in dots:
        lhs, rhs = (v.aval.shape for v in e.invars)
        assert lhs in ((s, 128), (s, s)) and rhs == (s, 128), (lhs, rhs)
    # loads and stores of the [1, 512, g*64] blocks: 128 lanes at a
    # multiple of 128
    seen = 0
    for e in eqns:
        if e.primitive.name not in ("get", "swap"):
            continue
        ref = e.invars[0].aval
        if ref.shape != (1, s, g * hd):
            continue
        lanes = e.params["tree"].unflatten(
            e.invars[(1 if e.primitive.name == "get" else 2):])[0].indices[-1]
        assert lanes.size == 128 and lanes.start % 128 == 0, lanes
        seen += 1
    # q, k, v -> o forward; q, k, v, do, o -> dq, dk, dv backward
    assert seen == (4 if kind == "fwd" else 8) * (g // 2)


@pytest.mark.parametrize("mode,layout,dims,bias_form,want", ROUTE_TABLE)
def test_flash_route_table(monkeypatch, mode, layout, dims, bias_form, want):
    """attention_route() is the one place that picks the implementation;
    the forward and the grad op both take its answer."""
    import contextlib
    import importlib

    import paddle_tpu.ops.pallas as pallas

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.delenv("PT_PALLAS", raising=False)
    scope = contextlib.nullcontext()
    if mode == "auto_partitioned":
        mode, scope = "tpu", pallas.auto_partitioned()
    monkeypatch.setattr(pallas, "_requested_mode", lambda: mode)
    b, n, sq, sk, hd = dims
    q, k, heads = _route_case(layout, *dims)
    bias = _bias(bias_form, b, sk)
    with scope:
        route, bias_kv = fa.attention_route(q, k, bias, heads)
    assert route == want
    if bias_form in ("key4", "key2"):
        assert bias_kv.shape == (b, sk)
    else:
        assert bias_kv is None

    if mode != "interpret":
        return
    # small enough to run: the forward op and the grad op each ask the
    # route function first, and get this row's answer
    from paddle_tpu.ops import attention_ops

    asked = []
    route_fn = fa.attention_route
    monkeypatch.setattr(
        fa, "attention_route",
        lambda *a, **kw: asked.append(route_fn(*a, **kw)) or asked[-1])
    rng = np.random.RandomState(0)
    qa, ka = (jnp.asarray(rng.randn(*s.shape).astype(np.float32) * 0.3)
              for s in (q, k))
    ins = {"Q": [qa], "K": [ka], "V": [ka]}
    if bias is not None:
        ins["Bias"] = [bias]
    attrs = {"num_heads": n, "head_dim": hd, "is_test": True}
    fwd = attention_ops.flash_attention_op(ins, attrs)
    assert asked[0][0] == want
    del asked[:]
    grads = attention_ops.flash_attention_grad_op(
        dict(ins, Out=[fwd["Out"]], Lse=[fwd["Lse"]],
             OutGrad=[jnp.ones_like(fwd["Out"])]), attrs)
    assert asked[0][0] == want
    assert grads["QGrad"].shape == q.shape


class TestFlashAttentionInProgram:
    def test_bert_flash_vs_unfused(self, interpret_mode):
        """Whole-program parity: tiny BERT with the flash_attention op vs the
        unfused matmul/softmax chain (dropout off)."""
        import paddle_tpu as pt
        from paddle_tpu.models import bert

        losses = {}
        for fused in (False, True):
            cfg = bert.BertConfig(
                vocab_size=128, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=128,
                max_position_embeddings=128, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0, use_flash_attention=fused)
            from paddle_tpu.core import ir, unique_name

            ir._main_program, ir._startup_program = ir.Program(), ir.Program()
            unique_name.switch()
            main, startup, feeds, fetches = bert.build_pretraining_program(
                cfg, seq_len=128, optimizer_name="adamw")
            exe = pt.Executor()
            scope = pt.Scope()
            exe.run(startup, scope=scope, use_compiled=False)
            batch = bert.synthetic_pretraining_batch(cfg, 2, 128)
            out = exe.run(main, feed=batch, fetch_list=[fetches["loss"]],
                          scope=scope)
            losses[fused] = float(np.asarray(out[0]))
        assert np.isfinite(losses[True])
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4)


class TestHeadBlockedFusedKernels:
    """The g-sliced single-block kernels (_fused_g) — g consecutive
    (b,h) slices per grid cell for sequences below FUSED_MIN_SEQ."""

    def test_g_path_selected_and_matches(self, interpret_mode):
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        B, H, S, D = 2, 4, 128, 64
        assert fa._fused_g(S, S, H) == 4
        q, k, v = (_rand(B, H, S, D, seed=i) for i in range(3))
        bias = (np.random.RandomState(9).rand(B, S) > 0.2).astype(
            np.float32)
        bias_kv = jnp.asarray((bias - 1.0) * 10000.0)

        def f(q, k, v, b):
            out, _lse = fa._flash(q, k, v, b, jnp.uint32(3), False,
                                  1.0 / np.sqrt(D), True, 0.1)
            return out

        def ref(q, k, v, b):
            return fa.reference_attention(
                q, k, v, b, causal=False, scale=1.0 / np.sqrt(D),
                dropout_rate=0.1, dropout_seed=jnp.uint32(3))

        out, ref_out = f(q, k, v, bias_kv), ref(q, k, v, bias_kv)
        np.testing.assert_allclose(out, ref_out, atol=5e-3)
        do = _rand(B, H, S, D, seed=7)
        _, vjp = jax.vjp(f, q, k, v, bias_kv)
        _, vjp_r = jax.vjp(ref, q, k, v, bias_kv)
        for g_, r_ in zip(vjp(do)[:4], vjp_r(do)[:4]):
            np.testing.assert_allclose(g_, r_, atol=2e-2)

    def test_g_requires_h_divisor(self):
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        assert fa._fused_g(128, 128, 12) == 4   # 512//128 -> 4 | 12
        assert fa._fused_g(128, 128, 7) == 0    # no divisor <= 4 > 1
        assert fa._fused_g(64, 64, 16) == 8     # 512//64=8 | 16
        assert fa._fused_g(256, 256, 16) == 0   # plain fused regime


class TestSavedResidualGrad:
    """Round 5: the flash_attention_grad op consumes the SAVED forward
    (Out, Lse) — the program backward must contain it (not the generic
    __vjp_grad__ that re-runs the fwd kernel) and its grads must match
    the reference attention's."""

    def _build(self, rate):
        import paddle_tpu as pt
        from paddle_tpu import layers
        from paddle_tpu.core.ir import Program, program_guard

        main, startup = Program(), Program()
        with program_guard(main, startup):
            q = layers.static_data("q", [2, 4, 256, 64], "float32")
            k = layers.static_data("k", [2, 4, 256, 64], "float32")
            v = layers.static_data("v", [2, 4, 256, 64], "float32")
            bias = layers.static_data("bias", [2, 1, 1, 256], "float32")
            for t in (q, k, v):
                t.stop_gradient = False
            out = layers.flash_attention(q, k, v, bias=bias,
                                         dropout_rate=rate, seed=11)
            loss = layers.reduce_sum(out * out)
            from paddle_tpu.core.backward import gradients

            gq, gk, gv = gradients([loss], [q, k, v])
        return main, startup, loss, (gq, gk, gv)

    def test_grad_op_emitted_and_matches_reference(self, interpret_mode,
                                                   scope):
        import paddle_tpu as pt
        from paddle_tpu.ops.pallas.flash_attention import (
            reference_attention)

        main, startup, loss, grads = self._build(rate=0.1)
        ops = main.global_block().ops
        assert any(op.type == "flash_attention_grad" for op in ops)
        assert not any(op.type == "__vjp_grad__" and
                       op.attrs.get("fwd_type") == "flash_attention"
                       for op in ops)

        rng = np.random.RandomState(0)
        feed = {n: rng.randn(2, 4, 256, 64).astype(np.float32) * 0.3
                for n in ("q", "k", "v")}
        feed["bias"] = np.where(rng.rand(2, 1, 1, 256) < 0.2, -10000.0,
                                0.0).astype(np.float32)
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        got = exe.run(main, feed=feed, fetch_list=[loss, *grads],
                      scope=scope)

        # reference oracle with the same position-keyed dropout mask: seed
        # attr 11 + the ACTUAL __step__ the main run used (the scope's
        # counter post-run minus one — startup bumped it too)
        from paddle_tpu.ops.attention_ops import _attn_dropout

        step_used = int(scope.find_var("@STEP_COUNTER@")) - 1
        rate, seed = _attn_dropout({"dropout_prob": 0.1, "seed": 11,
                                    "__step__": np.int32(step_used)})
        qj, kj, vj = (jnp.asarray(feed[n]) for n in ("q", "k", "v"))
        bias_kv = jnp.asarray(feed["bias"]).reshape(2, 256)

        def f(q_, k_, v_):
            o = reference_attention(q_, k_, v_, bias_kv,
                                    causal=False, scale=1.0 / np.sqrt(64),
                                    dropout_rate=rate, dropout_seed=seed)
            return jnp.sum(o * o)

        ref_loss, ref_grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
            qj, kj, vj)
        np.testing.assert_allclose(got[0], ref_loss, rtol=2e-4)
        for g_, r_ in zip(got[1:], ref_grads):
            np.testing.assert_allclose(g_, r_, atol=5e-3, rtol=1e-3)

    def test_fallback_without_lse_output(self, interpret_mode, scope):
        """Descs built without the Lse output (pre-round-5 programs, the
        inference fuse pass) must fall back to the generic vjp grad."""
        import paddle_tpu as pt
        from paddle_tpu.core.backward import gradients
        from paddle_tpu import layers
        from paddle_tpu.core.ir import Program, program_guard

        main, startup = Program(), Program()
        with program_guard(main, startup):
            q = layers.static_data("q", [1, 2, 128, 64], "float32")
            q.stop_gradient = False
            k = layers.static_data("k", [1, 2, 128, 64], "float32")
            v = layers.static_data("v", [1, 2, 128, 64], "float32")
            out = layers.flash_attention(q, k, v)
            # strip the Lse output as an old serialised desc would be
            op = [o for o in main.global_block().ops
                  if o.type == "flash_attention"][0]
            op.outputs.pop("Lse")
            loss = layers.reduce_sum(out * out)
            (gq,) = gradients([loss], [q])
        types = [op.type for op in main.global_block().ops]
        assert "flash_attention_grad" not in types
        assert "__vjp_grad__" in types
        rng = np.random.RandomState(1)
        feed = {n: rng.randn(1, 2, 128, 64).astype(np.float32) * 0.3
                for n in ("q", "k", "v")}
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        got = exe.run(main, feed=feed, fetch_list=[loss, gq], scope=scope)
        assert np.isfinite(np.asarray(got[0]))
        assert np.isfinite(np.asarray(got[1])).all()

    def test_grad_op_tagged_backward_and_stripped_by_clone(self,
                                                           interpret_mode):
        """The maker must not inherit the forward's op_role: the grad op
        has to be OpRole.Backward so clone(for_test=True) strips it."""
        main, _startup, _loss, _grads = self._build(rate=0.0)
        test_prog = main.clone(for_test=True)
        types = [o.type for o in test_prog.global_block().ops]
        assert "flash_attention_grad" not in types


class TestPackedLayout:
    """Round 5: packed [B,S,n*hd] kernels must match the bnsd path
    bit-for-bit (same per-head math, same position-keyed dropout), and
    the program-level packed op must route to flash_attention_grad."""

    def test_packed_matches_bnsd(self, interpret_mode):
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        B, N, S, D = 2, 4, 256, 64
        rng = np.random.RandomState(0)
        q3, k3, v3 = (jnp.asarray(
            rng.randn(B, S, N * D).astype(np.float32) * 0.3)
            for _ in range(3))
        bias = jnp.asarray(np.where(rng.rand(B, 1, 1, S) < 0.2,
                                    -10000.0, 0.0).astype(np.float32))
        assert fa.attention_route(q3, k3, bias, N)[0] == "packed"
        out_p, lse_p = fa.flash_attention_fwd_lse(
            q3, k3, v3, bias=bias, dropout_rate=0.1,
            dropout_seed=jnp.uint32(5), num_heads=N)
        q4 = fa._packed_to_bnsd(q3, N)
        out_4, lse_4 = fa.flash_attention_fwd_lse(
            fa._packed_to_bnsd(q3, N), fa._packed_to_bnsd(k3, N),
            fa._packed_to_bnsd(v3, N), bias=bias, dropout_rate=0.1,
            dropout_seed=jnp.uint32(5))
        # not bit for bit since PR 36: the packed kernels put dropout's
        # 1 / (1 - rate) on the [S, hd] product and not on the [S, S]
        # probabilities, and multiply by 1 / l where the bnsd kernels
        # divide. One ulp of the operands' dtype is allowed for that:
        # float32 here (the same reordering is one bfloat16 ulp at
        # bfloat16, where the probabilities are rounded before the scale)
        np.testing.assert_allclose(np.asarray(out_p),
                                   np.asarray(fa._bnsd_to_packed(out_4)),
                                   rtol=2e-7, atol=2e-7)
        np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_4),
                                   atol=1e-6)

        # saved-residual packed backward vs the bnsd backward
        do3 = jnp.asarray(rng.randn(B, S, N * D).astype(np.float32))
        dq_p, dk_p, dv_p, db_p = fa.flash_attention_bwd(
            q3, k3, v3, bias, out_p, lse_p, do3, dropout_rate=0.1,
            dropout_seed=jnp.uint32(5), num_heads=N)
        dq_4, dk_4, dv_4, db_4 = fa.flash_attention_bwd(
            q4, fa._packed_to_bnsd(k3, N), fa._packed_to_bnsd(v3, N),
            bias, out_4, lse_4, fa._packed_to_bnsd(do3, N),
            dropout_rate=0.1, dropout_seed=jnp.uint32(5))
        for a, b4 in ((dq_p, dq_4), (dk_p, dk_4), (dv_p, dv_4)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(fa._bnsd_to_packed(b4)),
                atol=2e-5)
        np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_4),
                                   atol=2e-5)

    # heads, seq, head dim, key bias, causal, dropout, scale: the forms
    # the packed kernels take from what they can see. hd 128 / 64 / 32 are
    # 1 / 2 / 4 heads a lane tile (masked apart), 6 x 64 an odd count of
    # tiles, 48 no divisor of 128 (the sliced form); a scale that is a
    # power of two goes into q, another stays on the scores
    FORMS = [
        pytest.param(2, 128, 128, True, False, 0.1, None, id="hd128"),
        pytest.param(2, 128, 128, False, True, 0.0, 0.125, id="hd128_causal"),
        pytest.param(4, 256, 64, True, False, 0.1, None, id="hd64"),
        pytest.param(4, 128, 64, False, False, 0.0, None, id="hd64_plain"),
        pytest.param(4, 128, 64, True, True, 0.1, 0.3, id="hd64_causal_s0.3"),
        pytest.param(6, 128, 64, True, False, 0.1, None, id="hd64_odd_tiles"),
        pytest.param(6, 128, 64, False, True, 0.0, 0.3, id="hd64_odd_causal"),
        pytest.param(8, 128, 32, True, False, 0.1, None, id="hd32_s_not_pow2"),
        pytest.param(8, 128, 32, False, True, 0.1, 0.25, id="hd32_causal"),
        pytest.param(16, 128, 32, True, False, 0.0, 0.25, id="hd32_16heads"),
        pytest.param(8, 128, 48, True, False, 0.1, None, id="hd48_sliced"),
        pytest.param(8, 128, 48, False, True, 0.0, 0.25, id="hd48_causal"),
    ]

    @staticmethod
    def _form_case(n, s, d, with_bias, seed=7):
        rng = np.random.RandomState(seed)
        b = 2
        q3, k3, v3, do3 = (jnp.asarray(
            rng.randn(b, s, n * d).astype(np.float32) * 0.3)
            for _ in range(4))
        bias = jnp.asarray(np.where(rng.rand(b, s) < 0.2, -10000.0,
                                    0.0).astype(np.float32)) \
            if with_bias else None
        return q3, k3, v3, do3, bias

    @staticmethod
    def _packed_all(fa, q3, k3, v3, do3, bias, n, **kw):
        """(out, lse, dq, dk, dv, dbias) of the packed route."""
        assert fa.attention_route(q3, k3, bias, n)[0] == "packed"
        out, lse = fa.flash_attention_fwd_lse(q3, k3, v3, bias=bias,
                                              num_heads=n, **kw)
        return (out, lse) + tuple(fa.flash_attention_bwd(
            q3, k3, v3, bias, out, lse, do3, num_heads=n, **kw))

    @pytest.mark.parametrize("n,s,d,with_bias,causal,rate,scale", FORMS)
    def test_packed_forms_match_reference(self, interpret_mode, n, s, d,
                                          with_bias, causal, rate, scale):
        """Forward output, lse and all four gradients against the plain
        reference (its vjp), the dropout mask included: a kept position
        that moved would show as an O(1) error."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        q3, k3, v3, do3, bias = self._form_case(n, s, d, with_bias)
        kw = dict(causal=causal, scale=scale, dropout_rate=rate,
                  dropout_seed=jnp.uint32(11))
        got = self._packed_all(fa, q3, k3, v3, do3, bias, n, **kw)

        sc = scale if scale is not None else d ** -0.5

        def ref(q, k, v, bias_kv):
            return fa._bnsd_to_packed(fa.reference_attention(
                fa._packed_to_bnsd(q, n), fa._packed_to_bnsd(k, n),
                fa._packed_to_bnsd(v, n), bias_kv=bias_kv, causal=causal,
                scale=sc, dropout_rate=rate, dropout_seed=kw["dropout_seed"]))

        want, vjp = jax.vjp(ref, q3, k3, v3, bias)
        np.testing.assert_allclose(got[0], want, atol=2e-5)
        # lse of the scores as the reference forms them
        q4, k4 = fa._packed_to_bnsd(q3, n), fa._packed_to_bnsd(k3, n)
        sc_ = jnp.einsum("bnqd,bnkd->bnqk", q4, k4) * sc
        if bias is not None:
            sc_ = sc_ + bias[:, None, None, :]
        if causal:
            sc_ = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc_, -1e30)
        np.testing.assert_allclose(
            got[1], jax.scipy.special.logsumexp(sc_, axis=-1), atol=2e-5)
        for name, a, b_ in zip(("dq", "dk", "dv", "dbias"), got[2:],
                               vjp(do3)):
            if b_ is None or a is None:
                assert name == "dbias" and bias is None and a is None
                continue
            np.testing.assert_allclose(a, b_, atol=5e-5, err_msg=name)

    @pytest.mark.parametrize("n,s,d,with_bias,causal,rate,scale", FORMS)
    def test_packed_forms_match_bnsd(self, interpret_mode, n, s, d,
                                     with_bias, causal, rate, scale):
        """The same against the bnsd kernels (another kernel, the same
        position-keyed mask)."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        q3, k3, v3, do3, bias = self._form_case(n, s, d, with_bias, seed=8)
        kw = dict(causal=causal, scale=scale, dropout_rate=rate,
                  dropout_seed=jnp.uint32(12))
        got = self._packed_all(fa, q3, k3, v3, do3, bias, n, **kw)
        q4, k4, v4, do4 = (fa._packed_to_bnsd(x, n)
                           for x in (q3, k3, v3, do3))
        out4, lse4 = fa.flash_attention_fwd_lse(q4, k4, v4, bias=bias, **kw)
        grads4 = fa.flash_attention_bwd(q4, k4, v4, bias, out4, lse4, do4,
                                        **kw)
        np.testing.assert_allclose(got[0], fa._bnsd_to_packed(out4),
                                   atol=2e-6)
        np.testing.assert_allclose(got[1], lse4, atol=1e-6)
        for name, a, b4 in zip(("dq", "dk", "dv"), got[2:5], grads4):
            np.testing.assert_allclose(a, fa._bnsd_to_packed(b4),
                                       atol=2e-5, err_msg=name)
        if bias is None:
            assert got[5] is None and grads4[3] is None
        else:
            np.testing.assert_allclose(got[5], grads4[3], atol=2e-5)

    def test_packed_bfloat16_within_an_ulp_of_bnsd(self, interpret_mode):
        """bfloat16 operands, as the training cell runs them: the packed
        output is the bnsd kernels' to one bfloat16 ulp of the outputs'
        scale (the probabilities are rounded before dropout's scale, not
        after)."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        n = 4
        q3, k3, v3, _, bias = self._form_case(n, 256, 64, True, seed=9)
        q3, k3, v3 = (x.astype(jnp.bfloat16) for x in (q3, k3, v3))
        kw = dict(bias=bias, dropout_rate=0.1, dropout_seed=jnp.uint32(3))
        out_p, lse_p = fa.flash_attention_fwd_lse(q3, k3, v3, num_heads=n,
                                                  **kw)
        out_4, lse_4 = fa.flash_attention_fwd_lse(
            *(fa._packed_to_bnsd(x, n) for x in (q3, k3, v3)), **kw)
        assert out_p.dtype == jnp.bfloat16
        a = np.asarray(out_p, np.float32)
        b4 = np.asarray(fa._bnsd_to_packed(out_4), np.float32)
        # an output is a sum over 256 keys of probabilities rounded one
        # way or the other: one ulp at the scale of the largest output
        assert np.abs(a - b4).max() <= 2.0 ** -7 * np.abs(b4).max()
        np.testing.assert_allclose(lse_p, lse_4, atol=1e-5)

    def test_packed_program_grad_op(self, interpret_mode, scope):
        import paddle_tpu as pt
        from paddle_tpu import layers
        from paddle_tpu.core.backward import gradients
        from paddle_tpu.core.ir import Program, program_guard

        main, startup = Program(), Program()
        with program_guard(main, startup):
            q = layers.static_data("q", [2, 256, 256], "float32")
            q.stop_gradient = False
            k = layers.static_data("k", [2, 256, 256], "float32")
            v = layers.static_data("v", [2, 256, 256], "float32")
            out = layers.flash_attention(q, k, v, num_heads=4)
            loss = layers.reduce_sum(out * out)
            (gq,) = gradients([loss], [q])
        assert any(op.type == "flash_attention_grad"
                   for op in main.global_block().ops)
        rng = np.random.RandomState(1)
        feed = {n: rng.randn(2, 256, 256).astype(np.float32) * 0.3
                for n in ("q", "k", "v")}
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        lv, gv = exe.run(main, feed=feed, fetch_list=[loss, gq],
                         scope=scope)
        assert np.isfinite(np.asarray(lv))
        assert np.abs(np.asarray(gv)).max() > 0

    def test_packed_fallback_shapes(self, interpret_mode):
        """Below the fused regime (S=128 -> xla route on tpu, reference
        on cpu) the packed entry transposes internally and still
        matches."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        B, N, S, D = 2, 4, 40, 16   # odd shapes: no kernel support
        rng = np.random.RandomState(2)
        q3, k3, v3 = (jnp.asarray(
            rng.randn(B, S, N * D).astype(np.float32) * 0.3)
            for _ in range(3))
        assert fa.attention_route(q3, k3, None, N)[0] == "pallas_interpret"
        out_p, _ = fa.flash_attention_fwd_lse(q3, k3, v3, num_heads=N)
        ref = fa.reference_attention(
            fa._packed_to_bnsd(q3, N), fa._packed_to_bnsd(k3, N),
            fa._packed_to_bnsd(v3, N))
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(fa._bnsd_to_packed(ref)),
            atol=2e-5)

    def test_packed_cross_attention(self, interpret_mode):
        """sq != sk with a key bias (the transformer decoder's
        cross-attention) must dispatch on K's OWN sequence length —
        a q-shaped proxy crashed the bias broadcast here."""
        import importlib

        fa = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        B, N, SQ, SK, D = 2, 4, 256, 128, 64
        rng = np.random.RandomState(3)
        q3 = jnp.asarray(rng.randn(B, SQ, N * D).astype(np.float32) * 0.3)
        k3 = jnp.asarray(rng.randn(B, SK, N * D).astype(np.float32) * 0.3)
        v3 = jnp.asarray(rng.randn(B, SK, N * D).astype(np.float32) * 0.3)
        bias = jnp.asarray(np.where(rng.rand(B, 1, 1, SK) < 0.2,
                                    -10000.0, 0.0).astype(np.float32))
        assert fa.attention_route(q3, k3, bias, N)[0] == "pallas_interpret"
        out_p, _ = fa.flash_attention_fwd_lse(q3, k3, v3, bias=bias,
                                              num_heads=N)
        ref = fa.reference_attention(
            fa._packed_to_bnsd(q3, N), fa._packed_to_bnsd(k3, N),
            fa._packed_to_bnsd(v3, N),
            bias_kv=bias.reshape(B, SK))
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(fa._bnsd_to_packed(ref)),
            atol=2e-5)
