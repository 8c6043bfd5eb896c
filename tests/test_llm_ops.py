"""The ops of ops/llm_ops.py and the `routed_experts` op, one by one
through the registry against plain numpy: models/afmoe.py builds its
programs from them (tests/test_afmoe_serving.py holds the whole block
against the reference)."""

import ml_dtypes
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (registers the ops)
from paddle_tpu.core import registry, telemetry
from paddle_tpu.ops import llm_ops


def fwd(op, ins, attrs=None):
    out = registry.lookup(op).forward(
        {k: [v] for k, v in ins.items()}, attrs or {})
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.fixture
def rng():
    return np.random.RandomState(4)


def test_embed_scaled_looks_rows_up_and_scales_them(rng):
    w = rng.randn(10, 6).astype(ml_dtypes.bfloat16)
    ids = np.asarray([[3, 0, 9]], np.int32)
    out = fwd("embed_scaled", {"W": w, "Ids": ids}, {"scale": 8.0})["Out"]
    assert out.dtype == np.float32 and out.shape == (1, 3, 6)
    np.testing.assert_array_equal(out, w[ids].astype(np.float32) * 8.0)


@pytest.mark.parametrize("transpose", [False, True])
def test_linear_acc32_rounds_its_input_and_accumulates_in_float32(
        rng, transpose):
    x = rng.randn(5, 64).astype(np.float32)
    w = rng.randn(64, 7).astype(ml_dtypes.bfloat16)
    out = fwd("linear_acc32", {"X": x, "W": w.T.copy() if transpose else w},
              {"transpose_Y": transpose})["Out"]
    assert out.dtype == np.float32
    want = x.astype(ml_dtypes.bfloat16).astype(np.float64) \
        @ w.astype(np.float64)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    # not the product of the unrounded input
    assert np.abs(out - x.astype(np.float64) @ w.astype(np.float64)).max() \
        > 1e-4


def test_sigmoid_gate_and_swiglu(rng):
    x, g = rng.randn(2, 3, 8).astype(np.float32), \
        rng.randn(2, 3, 8).astype(np.float32)
    sig = 1.0 / (1.0 + np.exp(-g))
    np.testing.assert_allclose(
        fwd("sigmoid_gate", {"X": x, "Gate": g})["Out"], x * sig, rtol=1e-5)
    np.testing.assert_allclose(
        fwd("swiglu", {"Gate": g, "Up": x})["Out"], g * sig * x, rtol=1e-5,
        atol=1e-6)


def test_last_token_rows_takes_each_rows_last_real_position(rng):
    x = rng.randn(3, 6, 4).astype(np.float32)
    lengths = np.asarray([1, 6, 4], np.int32)
    out = fwd("last_token_rows", {"X": x, "Lengths": lengths})["Out"]
    np.testing.assert_array_equal(out, x[np.arange(3), lengths - 1])


def test_rows_live_and_prompt_rows_live():
    table = np.asarray([[4, 5, 0], [0, 0, 0], [9, 0, 0]], np.int32)
    assert list(fwd("rows_live", {"PageTable": table})["Live"]) \
        == [True, False, True]
    live = fwd("prompt_rows_live",
               {"Tokens": np.zeros((2, 5), np.int32),
                "Lengths": np.asarray([3, 5], np.int32)})["Live"]
    assert live.tolist() == [[True] * 3 + [False] * 2, [True] * 5]


@pytest.mark.parametrize("rope", [False, True])
def test_qk_norm_rope_norms_each_head_and_rotates_by_position(rng, rope):
    hd, t = 8, 5
    q = rng.randn(1, t, 3 * hd).astype(np.float32)
    k = rng.randn(1, t, hd).astype(np.float32)
    qs, ks = rng.rand(hd).astype(np.float32) + 0.5, \
        rng.rand(hd).astype(np.float32) + 0.5
    pos = np.asarray([[0, 1, 2, 7, 40]], np.int32)
    out = fwd("qk_norm_rope",
              {"Q": q, "K": k, "QScale": qs, "KScale": ks, "Positions": pos},
              {"head_dim": hd, "epsilon": 1e-5, "rope": rope,
               "theta": 100.0})

    def want(x, scale):
        xh = x.reshape(1, t, -1, hd).astype(np.float64)
        xh = xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-5) * scale
        if rope:
            half = hd // 2
            ang = pos[..., None, None] * 100.0 ** (
                -np.arange(half) * 2.0 / hd)
            x1, x2 = xh[..., :half], xh[..., half:]
            xh = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                 x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
        return xh.reshape(x.shape)

    np.testing.assert_allclose(out["QOut"], want(q, qs), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(out["KOut"], want(k, ks), rtol=2e-4,
                               atol=2e-5)
    if rope:        # position 0 is not rotated
        np.testing.assert_allclose(out["KOut"][0, 0], want(k, ks)[0, 0],
                                   rtol=1e-5)


@pytest.mark.parametrize("window", [0, 5])
def test_gqa_prefill_attention_is_masked_softmax_over_grouped_heads(
        rng, window):
    """4 query heads on 2 K/V heads, 16 positions in query blocks of 4:
    causal, and with a window keys at t - window < s <= t only."""
    n, nkv, hd, s = 4, 2, 8, 16
    q = rng.randn(1, s, n * hd).astype(np.float32)
    k = rng.randn(1, s, nkv * hd).astype(np.float32)
    v = rng.randn(1, s, nkv * hd).astype(np.float32)
    out = fwd("gqa_prefill_attention", {"Q": q, "K": k, "V": v},
              {"num_heads": n, "num_kv_heads": nkv, "head_dim": hd,
               "scale": hd ** -0.5, "window": window, "block_q": 4})["Out"]
    qh = q.reshape(s, n, hd).astype(np.float64)
    kh = k.reshape(s, nkv, hd).astype(np.float64)
    vh = v.reshape(s, nkv, hd).astype(np.float64)
    t = np.arange(s)
    ok = t[None, :] <= t[:, None]
    if window:
        ok &= t[None, :] > t[:, None] - window
    want = np.zeros((s, n, hd))
    for j in range(n):
        sc = qh[:, j] @ kh[:, j // 2].T * hd ** -0.5
        sc = np.where(ok, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[:, j] = p / p.sum(-1, keepdims=True) @ vh[:, j // 2]
    np.testing.assert_allclose(out[0], want.reshape(s, n * hd), rtol=2e-4,
                               atol=2e-5)


def _whole_prompt(rng, n, nkv, hd, s, live):
    """Q, K, V of a padded prompt whose tail past `live` holds numbers far
    larger than any real row's."""
    q, k, v = (rng.randn(1, s, heads * hd).astype(np.float32)
               for heads in (n, nkv, nkv))
    for a in (q, k, v):
        a[:, live:] *= 40.0
    return {"Q": q, "K": k, "V": v}


def _pallas_counters():
    return {k: v for k, v in telemetry.snapshot()["counters"].items()
            if k.startswith(("pallas.gqa_prefill", "pallas.flash_window"))}


# query heads, K/V heads, head, window, padded length (three blocks of 128
# in interpret mode), real tokens
_KERNEL_ROUTE = {
    "qwen3_next_4+1_heads_of_256": (4, 1, 256, 0, 384, 384),
    "trinity_6+1_heads_of_128_window": (6, 1, 128, 100, 384, 384),
    "trinity_6+1_heads_of_128_full": (6, 1, 128, 0, 384, 384),
    "padded_tail": (4, 1, 256, 0, 384, 200),
}


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-6),
                                        ("bfloat16", 1e-2)])
@pytest.mark.parametrize("case", sorted(_KERNEL_ROUTE))
def test_gqa_prefill_attention_through_the_kernel_is_the_xla_form(
        rng, monkeypatch, case, dtype, tol):
    """Over its shape rule the op hands the prompt to the flash forward
    kernel: the XLA form's rows within the kernel's own tolerance
    (float32 products to rounding; bfloat16 ones to the rounding of a
    block's weights before or after they are normalised), `Out` float32
    on both routes, and a real row untouched by what the padded tail
    holds."""
    n, nkv, hd, window, s, live = _KERNEL_ROUTE[case]
    ins = _whole_prompt(rng, n, nkv, hd, s, live)
    attrs = {"num_heads": n, "num_kv_heads": nkv, "head_dim": hd,
             "window": window, "compute_dtype": dtype, "block_q": s}
    monkeypatch.setattr(llm_ops, "GQA_PREFILL_KERNEL_FROM", 1)
    monkeypatch.setenv("PT_PALLAS", "off")
    want = fwd("gqa_prefill_attention", ins, attrs)["Out"]
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    got = fwd("gqa_prefill_attention", ins, attrs)["Out"]
    assert _pallas_counters() == {"pallas.gqa_prefill_dispatches": 1,
                                  "pallas.flash_window_dispatches": 1}
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape == (1, s, n * hd)
    scale = np.abs(want[:, :live]).max()
    assert np.abs(got - want)[:, :live].max() <= tol * scale
    if live < s:
        other = {name: np.where(np.arange(s)[None, :, None] < live, a, 0.0)
                 .astype(np.float32) for name, a in ins.items()}
        np.testing.assert_array_equal(
            fwd("gqa_prefill_attention", other, attrs)["Out"][:, :live],
            got[:, :live])


@pytest.mark.parametrize("reason, mode, kernel_from, s", [
    ("mode", "off", 1, 384),
    ("short", "interpret", None, 384),      # the constant as it stands
    ("shape", "interpret", 1, 200),         # no block divides 200
])
def test_gqa_prefill_attention_says_why_it_kept_the_xla_form(
        rng, monkeypatch, reason, mode, kernel_from, s):
    """The shape rule is the op's own: a prompt whose query block would
    hold fewer scores than the rule asks is a
    `pallas.gqa_prefill_fallbacks` with `reason=short`, and neither it
    nor a shape the kernel cannot tile reaches the kernel's dispatcher
    (no `pallas.flash_window_fallbacks`)."""
    monkeypatch.setenv("PT_PALLAS", mode)
    if kernel_from is not None:
        monkeypatch.setattr(llm_ops, "GQA_PREFILL_KERNEL_FROM", kernel_from)
    seen = []
    add = telemetry.counter_add
    monkeypatch.setattr(
        telemetry, "counter_add",
        lambda name, delta=1, **attrs: (seen.append((name, attrs)),
                                        add(name, delta, **attrs))[1])
    telemetry.reset()
    ins = _whole_prompt(rng, 2, 1, 128, s, s)
    attrs = {"num_heads": 2, "num_kv_heads": 1, "head_dim": 128,
             "block_q": s}
    out = fwd("gqa_prefill_attention", ins, attrs)["Out"]
    assert seen == [("pallas.gqa_prefill_fallbacks", {"reason": reason})]
    assert _pallas_counters() == {"pallas.gqa_prefill_fallbacks": 1}
    monkeypatch.setenv("PT_PALLAS", "off")
    np.testing.assert_array_equal(
        out, fwd("gqa_prefill_attention", ins, attrs)["Out"])


@pytest.mark.parametrize("n, nkv, hd, window, s, kernel", [
    (4, 1, 256, 0, 4096, False), (4, 1, 256, 0, 8192, True),
    (4, 1, 256, 0, 16384, True),                # Qwen3-Next's share
    (6, 1, 128, 4096, 4096, False), (6, 1, 128, 4096, 8192, False),
    (6, 1, 128, 0, 4096, False), (6, 1, 128, 0, 8192, True),  # Trinity's
    (20, 4, 128, 0, 1024, False),               # Falcon-H1's longest bucket
])
def test_the_shape_rule_at_the_served_families_buckets(monkeypatch, n, nkv,
                                                       hd, window, s, kernel):
    """Which form each served family's long buckets take as the constant
    stands (its comment has the chip's readings): traced, nothing run."""
    import jax

    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    q = jax.ShapeDtypeStruct((1, s, n * hd), np.float32)
    k = jax.ShapeDtypeStruct((1, s, nkv * hd), np.float32)
    jax.eval_shape(lambda q, k, v: llm_ops.gqa_prefill_attention_op(
        {"Q": [q], "K": [k], "V": [v]},
        {"num_heads": n, "num_kv_heads": nkv, "head_dim": hd,
         "window": window, "compute_dtype": "bfloat16", "block_q": 512}),
        q, k, k)
    assert ("pallas.gqa_prefill_dispatches" in _pallas_counters()) == kernel


def chunk_inputs(rng, n, nkv, hd, page, start, c):
    """A prompt of start + c tokens: its first `start` already in pages
    3, 1 (of a pool of 6 pages), the last `c` the chunk."""
    total = start + c
    q = rng.randn(total, n * hd).astype(np.float32)
    k = rng.randn(total, nkv * hd).astype(np.float32)
    v = rng.randn(total, nkv * hd).astype(np.float32)
    table = np.array([[3, 1, 5, 2]], np.int32)
    pool_k = rng.randn(6, page, nkv * hd).astype(np.float32)
    pool_v = rng.randn(6, page, nkv * hd).astype(np.float32)
    for s in range(start):
        pool_k[table[0, s // page], s % page] = k[s]
        pool_v[table[0, s // page], s % page] = v[s]
    ins = {"Q": q[None, start:], "K": k[None, start:], "V": v[None, start:],
           "PoolK": pool_k, "PoolV": pool_v, "PageTable": table,
           "ChunkStart": np.array([start], np.int32),
           "Lengths": np.array([c], np.int32)}
    return ins, q, k, v


def test_chunk_cached_attention_over_grouped_heads_is_the_causal_softmax(
        rng):
    """4 query heads on 2 K/V heads: a chunk of 4 tokens after 8 in the
    pool attends the pool's 8 and itself causally, and lands in its page."""
    n, nkv, hd, page, start, c = 4, 2, 8, 4, 8, 4
    ins, q, k, v = chunk_inputs(rng, n, nkv, hd, page, start, c)
    out = fwd("chunk_cached_attention", ins,
              {"num_heads": n, "num_kv_heads": nkv, "head_dim": hd})
    total = start + c
    qh = q.reshape(total, n, hd).astype(np.float64)
    kh = k.reshape(total, nkv, hd).astype(np.float64)
    vh = v.reshape(total, nkv, hd).astype(np.float64)
    t = np.arange(total)
    want = np.zeros((total, n, hd))
    for j in range(n):
        sc = qh[:, j] @ kh[:, j // 2].T * hd ** -0.5
        sc = np.where(t[None, :] <= t[:, None], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[:, j] = p / p.sum(-1, keepdims=True) @ vh[:, j // 2]
    np.testing.assert_allclose(out["Out"][0],
                               want[start:].reshape(c, n * hd),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(out["PoolKOut"][5], k[start:])
    np.testing.assert_array_equal(out["PoolVOut"][5], v[start:])


@pytest.mark.parametrize("attrs", [{"ring": True, "window": 8},
                                   {"window": 8}])
def test_chunk_cached_attention_refuses_a_window_layers_ring(rng, attrs):
    ins, *_ = chunk_inputs(rng, 4, 2, 8, 4, 8, 4)
    with pytest.raises(NotImplementedError, match="context pages only"):
        fwd("chunk_cached_attention", ins,
            dict(attrs, num_heads=4, num_kv_heads=2, head_dim=8))


def test_routed_experts_op_is_the_held_experts_weighted_sum(rng):
    """Top-2 of 8 sigmoid-scored experts, experts 2-5 held: each live
    token's output is the weighted sum over its kept experts that are
    held; a dead row's is zero and it is not counted."""
    t, h, f, e, lo, eh, k = 6, 16, 8, 8, 2, 4, 2
    x = rng.randn(1, t, h).astype(np.float32)
    wr = rng.randn(h, e).astype(np.float32)
    w1, w3 = rng.randn(eh, h, f).astype(np.float32) * 0.3, \
        rng.randn(eh, h, f).astype(np.float32) * 0.3
    w2 = rng.randn(eh, f, h).astype(np.float32) * 0.3
    live = np.asarray([[True] * 5 + [False]])
    out = fwd("routed_experts",
              {"X": x, "RouterW": wr, "SelectBias": np.zeros(e, np.float32),
               "W1": w1, "W3": w3, "W2": w2, "Live": live},
              {"top_k": k, "held_lo": lo, "route_scale": 1.5})
    score = 1.0 / (1.0 + np.exp(-(x[0].astype(np.float64) @ wr)))
    want = np.zeros((t, h))
    pairs_held, hit = 0, set()
    for i in range(t - 1):
        kept = np.argsort(-score[i])[:k]
        for j in kept:
            if lo <= j < lo + eh:
                a = x[0, i] @ w1[j - lo]
                mid = a / (1.0 + np.exp(-a)) * (x[0, i] @ w3[j - lo])
                want[i] += score[i, j] / score[i, kept].sum() * 1.5 \
                    * (mid @ w2[j - lo])
                pairs_held += 1
                hit.add(j)
    assert out["Out"].shape == x.shape
    np.testing.assert_allclose(out["Out"][0], want, rtol=2e-3, atol=2e-4)
    assert out["Counts"].tolist() == [5 * k, pairs_held, len(hit)]


def test_perf_report_renders_the_two_classes_of_pages(tmp_path):
    """`mem.serving.kv_pool_bytes.<class>` reaches the Decode section."""
    import io

    from tools.perf_report import render, summarize_log

    recs = [{"ts": 1.0, "kind": "counter", "name": "decode.tokens",
             "value": 3, "attrs": {"delta": 3}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_pool_bytes", "value": 300,
             "attrs": {}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_pool_bytes.context", "value": 200,
             "attrs": {}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_pool_bytes.ring", "value": 100,
             "attrs": {}}]
    summary = summarize_log(recs)
    assert summary["decode"]["kv_pool_bytes_by_class"] \
        == {"context": 200, "ring": 100}
    buf = io.StringIO()
    render(summary, out=buf)
    text = buf.getvalue()
    assert "context pages: 200 B" in text and "ring pages: 100 B" in text
