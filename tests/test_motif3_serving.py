"""models/motif3.py behind `DecodeEngine` at a small size on the CPU: the
latent ring beside latent pages, grouped differential heads absorbed and
expanded, the band in the prefill kernel, the streams' residual kernels and
the PolyNorm grouped kernel against their stock lowerings (interpret mode),
and prefill + decode through ring and pages against the plain reference by
logits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_motif3 as rm
from benchmark.families import motif3 as family
from paddle_tpu.core import registry, telemetry
from paddle_tpu.models import motif3
from paddle_tpu.ops.pallas import grouped_swiglu as gs
from paddle_tpu.ops.pallas import mhc_mix
from paddle_tpu.ops.pallas import mla_prefill_attention as mpa
from paddle_tpu.ops.pallas import paged_mla_attention as pma
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine


def small(**kw):
    kw.setdefault("layer_ids", (1, 2, 3, 4))    # dense, window, FULL, window
    return motif3.Motif3Config(max_seq_len=128, **kw)


def engine_for(cfg, params, **kw):
    # window 16 on pages of 8: a ring of 3 pages = 24 rows a slot
    conf = dict(max_slots=4, page_size=8, kv_pages=4 * 16 + 1,
                prefill_buckets=[32, 64, 128], max_new_tokens=64)
    conf.update(kw)
    return DecodeEngine(cfg, params, DecodeConfig(**conf))


def run_op(name, ins, attrs):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs)


def reference(cfg, params, **kw):
    return rm.Reference({k: jnp.asarray(v) for k, v in params.items()},
                        family.reference_config(cfg), **kw)


# -- through the engine ------------------------------------------------------

# float32: what is left is the order of float32 sums (absorbed against
# expanded, grouped against dense): 1e-4 of a logit's scale. bfloat16: the
# weights', pages' and activations' rounding through 4 layers of a toy whose
# sublayers write at unit scale; 0.08 is what bfloat16's 2^-9 a product
# gives after 8 sublayers (measured 0.02-0.04 on these seeds), and a gap
# of 0.15 of unit-scale logits is that rounding turning a near-tie
@pytest.mark.parametrize("dtype, logit_tol, gap_tol",
                         [("float32", 1e-4, 1e-4), ("bfloat16", 0.08, 0.15)])
def test_prefill_and_decode_through_ring_and_pages_against_the_reference(
        dtype, logit_tol, gap_tol):
    """Prompts shorter and longer than the window, decoded until each
    ring has wrapped (24 rows a ring, 30 new tokens), against the
    reference's full forward; the counters tell ring rows from page
    rows."""
    cfg = small(dtype=dtype)
    params = motif3.motif3_params(cfg, 0)
    ref = reference(cfg, params)
    telemetry.reset()
    engine = engine_for(cfg, params).start(warmup=False)
    new = 30
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(3, cfg.vocab_size, n) for n in (40, 9, 64)]
        reqs = [engine.submit(p, max_new_tokens=new, stop_at_eos=False,
                              keep_first_logits=True) for p in prompts]
        for prompt, req in zip(prompts, reqs):
            chosen = req.result(300)
            rows, _, _ = ref.rows(np.concatenate([prompt, chosen]), 128,
                                  prompt.size - 1, len(chosen))
            assert rm.logit_error(np.asarray(req.first_logits),
                                  rows[0]) < logit_tol
            assert rm.greedy_gaps(rows, chosen).max() < gap_tol
    finally:
        engine.close()
    c = telemetry.snapshot()["counters"]
    assert 0 < c["decode.moe_pairs_held"] < c["decode.moe_pairs_total"]
    ctx = [p.size + 1 + i for p in prompts for i in range(new - 1)]
    w = cfg.sliding_window
    rings = 3 * sum(min(t, w) for t in ctx)         # three window layers
    assert c["decode.ring_latent_rows_attended"] == rings
    assert c["decode.kv_tokens_attended"] == rings + sum(ctx)
    assert c["decode.rows_past_window"] == sum(t > w for t in ctx)


def test_the_ring_and_the_pages_hold_the_references_latent_rows():
    """What a request's pages and rings hold when it retires, past a
    wrap of the ring, is the reference's [c, k_r] of each position: a
    full layer's pages in position order, a window layer's ring at index
    position mod 24."""
    cfg = small(dtype="float32")
    params = motif3.motif3_params(cfg, 2)
    ref = reference(cfg, params)
    engine = engine_for(cfg, params).start(warmup=False)
    try:
        prompt = np.random.RandomState(1).randint(3, cfg.vocab_size, 45)
        req = engine.submit(prompt, max_new_tokens=20, stop_at_eos=False,
                            keep_final_pages=True)
        chosen = req.result(300)
    finally:
        engine.close()
    fed = prompt.size + len(chosen) - 1
    _, _, latents = ref.rows(np.concatenate([prompt, chosen]), 128, 0, 1)
    kept = {i: np.asarray(req.final_pages[f"kv_c_{i}"]).reshape(
        -1, cfg.latent_row_width) for i in range(cfg.n_layers)}
    assert kept[0].shape[0] == 24 and kept[2].shape[0] == 72   # 9 pages
    ring, pages = family.latent_errors(family.reference_config(cfg), kept,
                                       latents, fed)
    assert ring < 1e-5 and pages < 1e-5
    # the ring's other rows are OLDER positions, not these
    stale = np.arange(fed - 24, fed - 16)
    assert rm.latent_error(kept[0][stale % 24], latents[0][stale]) < 1e-5
    assert rm.latent_error(kept[0][(stale + 8) % 24],
                           latents[0][stale]) > 0.1


def test_ring_and_pages_are_allocated_and_freed_side_by_side():
    cfg = small(dtype="float32")
    params = motif3.motif3_params(cfg, 3)
    engine = engine_for(cfg, params)
    assert sorted(engine._pools) == [f"kv_c_{i}" for i in range(4)]
    assert engine._pools["kv_c_2"].shape == (65, 8, 128)     # the context
    assert engine._pools["kv_c_0"].shape == (4 * 3 + 1, 8, 128)   # rings
    served = cfg.served()
    layout = served.cache_layout()
    assert [lc.latent for lc in layout] == [True] * 4
    assert [lc.window for lc in layout] == [16, 16, 0, 16]
    _, feeds, fetches = served.build_step_program(4, engine.kv)
    assert feeds == ["tokens", "positions", "page_table", "ring_table"]
    assert fetches == ["logits"] + [f"kv_c_{i}_out" for i in range(4)] \
        + ["step_counts"]
    _, feeds, _ = served.build_prefill_program(32, engine.kv)
    assert feeds == ["tokens", "positions", "lengths", "page_table",
                     "ring_table"]
    engine.start(warmup=False)
    try:
        rng = np.random.RandomState(3)
        reqs = [engine.submit(rng.randint(3, cfg.vocab_size, n),
                              max_new_tokens=6, stop_at_eos=False)
                for n in (30, 5, 50, 12, 20, 41)]       # six over four slots
        for r in reqs:
            r.result(300)
    finally:
        engine.close()
    stats = engine.kv.stats()
    assert stats["pages_used"] == 0 and stats["ring"]["pages_used"] == 0
    assert stats["ring"]["high_water_pages"] <= 4 * 3
    assert not engine.kv.audit([], [])


def test_no_chunked_prefill_so_no_prefix_store():
    cfg = small(dtype="float32")
    params = motif3.motif3_params(cfg, 3)
    with pytest.raises(ValueError, match="latent"):
        engine_for(cfg, params, prefix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill"):
        cfg.served().build_chunk_prefill_program(
            16, engine_for(cfg, params).kv)
    with pytest.raises(ValueError, match="full layer"):
        small(layer_ids=(1, 2))


def test_decode_is_the_same_alone_and_in_a_full_batch():
    """Each slot reads its own page table AND its own ring table."""
    cfg = small(dtype="float32")
    params = motif3.motif3_params(cfg, 5)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(3, cfg.vocab_size, n) for n in (20, 33, 7, 50)]
    engine = engine_for(cfg, params).start(warmup=False)
    try:
        alone = [engine.generate(p, max_new_tokens=12, stop_at_eos=False,
                                 timeout=300) for p in prompts]
        reqs = [engine.submit(p, max_new_tokens=12, stop_at_eos=False)
                for p in prompts]
        together = [r.result(300) for r in reqs]
    finally:
        engine.close()
    for a, t in zip(alone, together):
        assert list(a) == list(t)


# -- absorbed and expanded ---------------------------------------------------

def test_absorbed_latent_subtraction_equals_expanded_value_subtraction():
    """o_s W_uv - lambda o_n W_uv = (obar_s - lambda obar_n) W_uv: the
    step's order (subtract latents, expand 8 signal heads by their group's
    W_uv) against the prefill's (expand all 10, subtract values)."""
    rng = np.random.RandomState(0)
    b, nkv, g, rank, nope, dv = 3, 2, 5, 32, 16, 16
    obar = jnp.asarray(rng.randn(b, nkv * g * rank), jnp.float32)
    lam = jnp.asarray(rng.randn(b, nkv * (g - 1)), jnp.float32)
    w = jnp.asarray(rng.randn(rank, nkv * (nope + dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        d = run_op("diff_head_combine", {"X": obar, "LambdaLogits": lam},
                   {"num_groups": nkv, "width": rank})["Out"]
        step = run_op("mla_expand_output", {"X": d, "W": w},
                      {"num_heads": nkv * (g - 1), "num_kv_heads": nkv,
                       "nope_dim": nope})["Out"]
        values = run_op("mla_expand_output", {"X": obar, "W": w},
                        {"num_heads": nkv * g, "num_kv_heads": nkv,
                         "nope_dim": nope})["Out"]
        prefill = run_op("diff_head_combine",
                         {"X": values, "LambdaLogits": lam},
                         {"num_groups": nkv, "width": dv})["Out"]
    # float32 sums in two orders
    np.testing.assert_allclose(step, prefill, rtol=1e-4, atol=1e-4)
    # and by hand: head 1 of group 1 minus lambda times the group's fifth
    o = np.asarray(obar).reshape(b, nkv, g, rank)
    sig = 1 / (1 + np.exp(-np.asarray(lam).reshape(b, nkv, g - 1)))
    want = (o[:, 1, 1] - sig[:, 1, 1, None] * o[:, 1, 4]) \
        @ np.asarray(w).reshape(rank, nkv, nope + dv)[:, 1, nope:]
    np.testing.assert_allclose(
        np.asarray(step).reshape(b, nkv, g - 1, dv)[:, 1, 1], want,
        rtol=1e-4, atol=1e-4)


def test_the_absorbed_query_of_grouped_heads_reads_its_groups_key_head():
    rng = np.random.RandomState(1)
    b, n, nkv, rank, nope, rope, dv = 2, 10, 2, 32, 16, 8, 16
    qn = jnp.asarray(rng.randn(b, n * nope), jnp.float32)
    qr = jnp.asarray(rng.randn(b, n * rope), jnp.float32)
    w = jnp.asarray(rng.randn(rank, nkv * (nope + dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q = np.asarray(run_op(
            "mla_absorb_query", {"QNope": qn, "QRope": qr, "W": w},
            {"num_heads": n, "num_kv_heads": nkv, "nope_dim": nope})["Q"]
        ).reshape(b, n, rank + rope)
    wk = np.asarray(w).reshape(rank, nkv, nope + dv)[:, :, :nope]
    for h in (0, 4, 5, 9):
        want = np.asarray(qn).reshape(b, n, nope)[:, h] @ wk[:, h // 5].T
        np.testing.assert_allclose(q[:, h, :rank], want, rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_array_equal(q[:, :, rank:],
                                  np.asarray(qr).reshape(b, n, rope))


# -- the paged kernel over a ring ---------------------------------------------

@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_a_ring_is_attended_by_true_position(monkeypatch, mode):
    """Rows at positions before, at and past a wrap of a 3-page ring: the
    kernel (interpret) and the stock lowering both attend exactly the last
    `window` positions, wherever the ring holds them."""
    monkeypatch.setenv("PT_PALLAS", mode)
    rng = np.random.RandomState(0)
    n, width, vdim, page, rp, window = 8, 256, 128, 8, 3, 16
    cap = rp * page
    pos = np.array([3, 15, 16, 23, 24, 40, 100], np.int32)
    b = pos.size
    table = np.arange(1, 1 + b * rp, dtype=np.int32).reshape(b, rp)
    pool = np.zeros((1 + b * rp, page, width), np.float32)
    rows = rng.randn(b, 128, width).astype(np.float32)   # by TRUE position
    for i in range(b):
        for t in range(max(0, pos[i] - cap + 1), pos[i] + 1):
            j = t % cap
            pool[table[i, j // page], j % page] = rows[i, t]
    q = rng.randn(b, n * width).astype(np.float32)
    out = np.asarray(pma.paged_mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(pos), num_heads=n, value_dim=vdim, scale=0.05,
        window=window)).reshape(b, n, vdim)
    for i in range(b):
        keys = rows[i, max(0, pos[i] - window + 1):pos[i] + 1]
        s = q[i].reshape(n, width) @ keys.T * 0.05
        p = np.exp(s - s.max(axis=1, keepdims=True))
        want = (p / p.sum(axis=1, keepdims=True)) @ keys[:, :vdim]
        # float32 against float64-free numpy: sums in another order
        np.testing.assert_allclose(out[i], want, rtol=2e-4, atol=2e-4)


# -- the band in the prefill kernel -------------------------------------------

def _dense(qn, qr, kv, kr, n, nkv, nope, dv, scale, window):
    """Every pair, float32, by hand."""
    s = qn.shape[0]
    g = n // nkv
    q = np.concatenate([qn.reshape(s, n, nope), qr.reshape(s, n, -1)], -1)
    kvh = kv.reshape(s, nkv, nope + dv)
    gap = np.arange(s)[:, None] - np.arange(s)[None, :]
    ok = (gap >= 0) & ((gap < window) if window else True)
    out = np.zeros((s, n, dv), np.float32)
    for h in range(n):
        k = np.concatenate([kvh[:, h // g, :nope], kr], -1)
        sc = np.where(ok, q[:, h] @ k.T * scale, -np.inf)
        p = np.exp(sc - sc.max(axis=1, keepdims=True))
        out[:, h] = (p / p.sum(axis=1, keepdims=True)) @ kvh[:, h // g, nope:]
    return out.reshape(s, n * dv)


@pytest.mark.parametrize("s, n, nkv, window, dtype, tol", [
    # ten heads a step (a group of 5 twice) fit VMEM_BLOCKS in bfloat16
    # only; its tolerance is the output's one rounding to bfloat16
    (1024, 10, 2, 128, "bfloat16", 2e-2),   # grouped heads, a band
    (1024, 10, 2, 0, "bfloat16", 2e-2),     # grouped heads, the triangle
    (256, 10, 2, 128, "bfloat16", 2e-2),    # a bucket shorter than a block
    # float32 throughout; exp2 against exp and blockwise sums
    (512, 2, 2, 128, "float32", 2e-4),      # group 1, a band in one block
    (1024, 2, 2, 128, "float32", 2e-4),     # group 1, a band over two
    (1024, 2, 2, 0, "float32", 2e-4)])      # window 0, group 1: the parent's
def test_the_band_kernel_against_every_pair(monkeypatch, s, n, nkv, window,
                                            dtype, tol):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    rng = np.random.RandomState(s + n + window)
    nope, rope, dv = 128, 64, 128
    qn, qr, kv, kr = (np.asarray(jnp.asarray(rng.randn(s, w), dtype),
                                 np.float32) for w in (
        n * nope, n * rope, nkv * (nope + dv), rope))
    telemetry.reset()
    out = mpa.mla_prefill_attention(
        *(jnp.asarray(a, dtype) for a in (qn, qr, kv, kr)), 0.07,
        num_heads=n, nope_dim=nope, num_kv_heads=nkv, window=window)
    c = telemetry.snapshot()["counters"]
    assert c.get("pallas.mla_prefill_dispatches") == 1
    assert "pallas.mla_prefill_fallbacks" not in c
    want = _dense(qn, qr, kv, kr, n, nkv, nope, dv, 0.07, window)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, rtol=tol,
                               atol=tol)


def test_a_window_layer_is_given_the_bands_block_pairs_only(monkeypatch):
    """At 16 blocks the triangle is 136 pairs, the band 31; the stock
    lowering with a window agrees with every pair too."""
    monkeypatch.setenv("PT_PALLAS", "off")
    rng = np.random.RandomState(5)
    s, n, nkv, nope, rope, dv = 256, 10, 2, 16, 8, 16
    qn, qr, kv, kr = (rng.randn(s, w).astype(np.float32) for w in (
        n * nope, n * rope, nkv * (nope + dv), rope))
    telemetry.reset()
    out = mpa.mla_prefill_attention(
        *map(jnp.asarray, (qn, qr, kv, kr)), 0.2, num_heads=n,
        nope_dim=nope, num_kv_heads=nkv, window=16)
    assert telemetry.snapshot()["counters"][
        "pallas.mla_prefill_fallbacks"] == 1
    np.testing.assert_allclose(
        np.asarray(out), _dense(qn, qr, kv, kr, n, nkv, nope, dv, 0.2, 16),
        rtol=2e-4, atol=2e-4)
    spans = mpa._spans(512, 512, 256, 128, False)
    assert [(r0, r1, k0, k1) for r0, r1, k0, k1, _ in spans] == [
        (0, 128, 0, 128), (128, 256, 0, 256), (256, 384, 128, 384),
        (384, 512, 256, 512)]
    assert mpa._spans(512, 512, 256, 128, True) == [
        (0, 128, 384, 512, "before")]
    assert mpa._heads_a_step(80, 128, 64, 128, 512, 2, group=5) == 10
    assert mpa._heads_a_step(64, 128, 64, 128, 512, 2) == 8


def test_a_window_the_kernel_cannot_tile_is_counted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    telemetry.reset()
    z = jnp.zeros
    mpa.mla_prefill_attention(z((256, 256)), z((256, 128)), z((256, 512)),
                              z((256, 64)), 0.1, num_heads=2, nope_dim=128,
                              window=96)
    assert telemetry.snapshot()["counters"][
        "pallas.mla_prefill_fallbacks"] == 1


# -- the streams' residual path ----------------------------------------------

def _streams(rng, t, n, c):
    k = n * c
    x = rng.randn(t, k).astype(np.float32)
    gamma = (1 + 0.1 * rng.randn(k)).astype(np.float32)
    phi = jnp.asarray(rng.randn(k, 2 * n + n * n) * k ** -0.5, jnp.bfloat16)
    scale = np.array([1.0, 0.9, 1.1], np.float32)
    bias = rng.randn(2 * n + n * n).astype(np.float32)
    return tuple(map(jnp.asarray, (x, gamma))) + (phi,) \
        + tuple(map(jnp.asarray, (scale, bias)))


@pytest.mark.parametrize("t, n, c", [(16, 4, 128), (72, 4, 256),
                                     (8, 2, 128)])
def test_the_mhc_kernels_against_their_stock_lowerings(monkeypatch, t, n, c):
    monkeypatch.setenv("PT_PALLAS", "interpret")
    rng = np.random.RandomState(t)
    args = _streams(rng, t, n, c)
    telemetry.reset()
    u, maps = mhc_mix.mhc_pre(*args, n=n, iters=20, eps=1e-5)
    u0, maps0 = mhc_mix.stock_mhc_pre(*args, n=n, iters=20, eps=1e-5)
    # the same float32 arithmetic; the sums of Sinkhorn in another order
    np.testing.assert_allclose(u, u0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(maps, maps0, rtol=1e-5, atol=1e-6)
    res = np.asarray(maps0)[:, 2 * n:2 * n + n * n].reshape(t, n, n)
    # doubly stochastic after 20 iterations (the columns, normalised
    # last, exactly; the rows as far as 20 iterations bring them), and not
    # uniform
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(2), 1.0, atol=5e-3)
    assert res.std() > 0.05 and np.asarray(maps0)[:, 2 * n + n * n:].max() == 0
    y = jnp.asarray(rng.randn(t, c), jnp.float32)
    out = mhc_mix.mhc_post(args[0], y, maps0, n=n, clamp=2.5)
    out0 = mhc_mix.stock_mhc_post(args[0], y, maps0, n=n, clamp=2.5)
    np.testing.assert_allclose(out, out0, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(out).max()) == 2.5         # the clamp clamps
    c_ = telemetry.snapshot()["counters"]
    assert c_["pallas.mhc_dispatches"] == 2
    assert "pallas.mhc_fallbacks" not in c_


def test_the_mhc_stock_lowering_is_counted_by_reason(monkeypatch):
    rng = np.random.RandomState(0)
    monkeypatch.setenv("PT_PALLAS", "off")
    telemetry.reset()
    mhc_mix.mhc_pre(*_streams(rng, 8, 4, 128), n=4, iters=2, eps=1e-5)
    monkeypatch.setenv("PT_PALLAS", "interpret")
    mhc_mix.mhc_pre(*_streams(rng, 8, 4, 64), n=4, iters=2, eps=1e-5)
    mhc_mix.mhc_pre(*_streams(rng, 6, 4, 128), n=4, iters=2, eps=1e-5)
    assert telemetry.snapshot()["counters"]["pallas.mhc_fallbacks"] == 3


def test_sinkhorn_of_one_iteration_is_another_map():
    rng = np.random.RandomState(3)
    args = _streams(rng, 8, 4, 128)
    _, one = mhc_mix.stock_mhc_pre(*args, n=4, iters=1, eps=1e-5)
    _, twenty = mhc_mix.stock_mhc_pre(*args, n=4, iters=20, eps=1e-5)
    assert float(jnp.abs(one - twenty)[:, 8:24].max()) > 0.02


# -- PolyNorm experts --------------------------------------------------------

def _experts(rng, e, h, f, n, dtype):
    xs = jnp.asarray(rng.randn(n, h), dtype)
    w1, w3 = (jnp.asarray(rng.randn(e, h, f) * h ** -0.5, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(e, f, h) * f ** -0.5, dtype)
    pn = jnp.asarray(np.concatenate(
        [1 / 3 + 0.25 * rng.randn(e, 3), rng.randn(e, 1)], 1), jnp.float32)
    return xs, w1, w3, w2, pn


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("sizes", [[5, 0, 17, 9, 0, 12], [0, 0, 48, 0, 0, 0],
                                   [8, 8, 8, 8, 8, 8]])
def test_grouped_polyglu_against_its_stock_lowering(monkeypatch, dtype, tol,
                                                    sizes):
    """Two F blocks an expert (the row statistic spans both), empty
    experts, rows past the groups. bfloat16: `mid` is rounded once in both
    forms, at values of ~1, so 2^-8 of the output's scale."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(gs, "BLOCK_BYTES", 256 * 256 * jnp.dtype(
        dtype).itemsize)
    rng = np.random.RandomState(sum(sizes[:3]))
    xs, w1, w3, w2, pn = _experts(rng, 6, 256, 512, 48, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    kw = dict(eps=1e-5, out_scale=0.5, bias_clamp=0.5)
    assert gs._tiles(48, 256, 512, dtype)[2] == 256
    telemetry.reset()
    got = gs.grouped_polyglu(xs, w1, w3, w2, pn, sizes, **kw)
    want = gs.stock_grouped_polyglu(xs, w1, w3, w2, pn, sizes, **kw)
    c = telemetry.snapshot()["counters"]
    assert c["pallas.grouped_polyglu_dispatches"] == 1
    assert "pallas.grouped_polyglu_fallbacks" not in c
    m = int(sizes.sum())
    scale = float(jnp.abs(want[:m]).max())
    assert float(jnp.abs(got[:m] - want[:m]).max()) < tol * scale
    # an expert's own parameters: with another's the rows differ
    other = gs.stock_grouped_polyglu(xs, w1, w3, w2, pn[::-1], sizes, **kw)
    assert float(jnp.abs(other[:m] - want[:m]).max()) > 0.05 * scale


def test_silu_experts_are_the_parents_routed_layer(monkeypatch):
    """`poly` left out is SwiGLU, the only thing the other served families
    trace: the same function, the same counters."""
    from paddle_tpu.parallel.moe import routed_experts_share

    monkeypatch.setenv("PT_PALLAS", "interpret")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256), jnp.float32)
    xs, w1, w3, w2, pn = _experts(rng, 4, 256, 128, 8, "float32")
    router = jnp.asarray(rng.randn(256, 16), jnp.float32)
    kw = dict(top_k=4, held_lo=4, route_scale=2.0)
    telemetry.reset()
    a, ca = routed_experts_share(x, router, jnp.zeros(16), w1, w3, w2, **kw)
    b, cb = routed_experts_share(x, router, jnp.zeros(16), w1, w3, w2,
                                 poly=None, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = telemetry.snapshot()["counters"]
    assert c["pallas.grouped_swiglu_dispatches"] > 0
    assert "pallas.grouped_polyglu_dispatches" not in c
    p, cp = routed_experts_share(
        x, router, jnp.zeros(16), w1, w3, w2, poly=(pn, 1e-5, 0.5, 0.5),
        **kw)
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cp))
    assert float(jnp.abs(p - a).max()) > 0.01
    after = telemetry.snapshot()["counters"]
    assert after["pallas.grouped_polyglu_dispatches"] > 0
    assert after["pallas.grouped_swiglu_dispatches"] \
        == c["pallas.grouped_swiglu_dispatches"]
    with pytest.raises(ValueError, match="activation"):
        registry.lookup("routed_experts").forward(
            {"X": [x], "RouterW": [router], "W1": [w1], "W3": [w3],
             "W2": [w2]}, {"top_k": 4, "held_lo": 4, "activation": "gelu"})
    with pytest.raises(ValueError, match="backward"):
        routed_experts_share(x, router, jnp.zeros(16), w1, w3, w2,
                             trainable=True, poly=(pn, 1e-5, 0.5, 0.5), **kw)


def test_the_motif3_ops_state_the_attrs_they_need():
    for name, attrs in (("diff_head_combine", ("num_groups", "width")),
                        ("mhc_pre", ("n_streams", "sinkhorn_iters")),
                        ("mhc_post", ("n_streams",)),
                        ("embed_streams", ("n_streams",)),
                        ("sum_streams", ("n_streams",))):
        assert set(attrs) <= set(registry.lookup(name).required_attrs)
