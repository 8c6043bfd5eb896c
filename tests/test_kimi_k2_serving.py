"""models/kimi_k2.py behind `DecodeEngine` at a small size on the CPU: the
latent kind of `LayerCache` and its pool, the latent ops (cache write,
absorbed decode, expanded prefill, YaRN rotary), the paged latent kernel
against its stock lowering, and prefill + decode through latent pages
against the plain reference by logits."""

import math
import time

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import reference_kimi_k2 as rk
from benchmark.families import kimi_k2 as family
from paddle_tpu.core import registry, telemetry
from paddle_tpu.models import kimi_k2
from paddle_tpu.ops import llm_ops
from paddle_tpu.ops.pallas import mla_prefill_attention as mpa
from paddle_tpu.ops.pallas import paged_mla_attention as pma
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_cache import (KVPagePool, LayerCache,
                                         PagedKVCache, pool_array_names)


def small(**kw):
    return kimi_k2.KimiK2Config(max_seq_len=128, **kw)


def seeded_params(cfg, seed):
    """`kimi_k2_params` with the embedding at unit elements and the
    matrices that write to the residual stream at 0.3 of their fan-in
    scale: a toy whose logits depend on the token and on attention both."""
    params = kimi_k2.kimi_k2_params(cfg, seed)

    def scaled(v, by):
        return (v.astype(np.float32) * by).astype(v.dtype)

    for name, v in params.items():
        if name == "k2_tok_emb":
            params[name] = scaled(v, cfg.hidden_size ** 0.5)
        elif name.endswith(("_o_w", "_w2", "_sh_w2", "_ex_w2")):
            params[name] = scaled(v, 0.3)
    return params


def engine_for(cfg, params, **kw):
    conf = dict(max_slots=4, page_size=16, kv_pages=4 * 8 + 1,
                prefill_buckets=[32, 64], max_new_tokens=32)
    conf.update(kw)
    return DecodeEngine(cfg, params, DecodeConfig(**conf))


def run_op(name, ins, attrs):
    return registry.lookup(name).forward({k: [v] for k, v in ins.items()},
                                         attrs)


# -- the cache ---------------------------------------------------------------

def test_a_latent_layer_keeps_one_array_and_books_its_own_bytes():
    telemetry.reset()
    layout = [LayerCache(640, latent=True)] * 3
    assert pool_array_names(2, latent=True) == ("kv_c_2",)
    assert pool_array_names(2, latent=False) == ("kv_k_2", "kv_v_2")
    kv = PagedKVCache(layout, page_size=16, context_pages=9, dtype="bfloat16")
    one_array = 9 * 16 * 640 * 2
    assert kv.pool_bytes == 3 * one_array
    assert sorted(kv.make_arrays()) == ["kv_c_0", "kv_c_1", "kv_c_2"]
    assert kv.make_arrays()["kv_c_1"].shape == (9, 16, 640)
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["mem.serving.kv_pool_bytes"] == 3 * one_array
    assert gauges["mem.serving.kv_pool_bytes.latent"] == 3 * one_array
    pages = kv.context.try_alloc(2)
    assert telemetry.snapshot()["gauges"]["mem.serving.kv_used_bytes"] \
        == 2 * 3 * one_array // 9
    kv.context.free(pages)


def test_a_pool_of_k_and_v_layers_reads_as_before_beside_a_latent_one():
    telemetry.reset()
    pool = KVPagePool(2, 5, 4, 8, "float32", kv_dims=[8, 24],
                      latent=[False, True])
    assert pool.pool_bytes == 5 * 4 * 4 * (2 * 8 + 24)
    assert sorted(pool.make_arrays()) == ["kv_c_1", "kv_k_0", "kv_v_0"]
    assert telemetry.snapshot()["gauges"][
        "mem.serving.kv_pool_bytes.latent"] == 5 * 4 * 4 * 24
    telemetry.reset()
    plain = KVPagePool(2, 5, 4, 8, "float32")
    assert plain.pool_bytes == 2 * 5 * 4 * 2 * 8 * 4
    assert "mem.serving.kv_pool_bytes.latent" \
        not in telemetry.snapshot()["gauges"]


def test_a_latent_layer_has_no_ring():
    """It has one since models/motif3.py: `LayerCache(latent=True,
    window=w)` is a LATENT RING, one array a layer in the ring class,
    `ring_pages_per_slot` pages a slot, its bytes booked as latent AND as
    ring."""
    telemetry.reset()
    layout = [LayerCache(640, window=128, latent=True),
              LayerCache(640, latent=True),
              LayerCache(640, window=128, latent=True)]
    assert all(lc.latent for lc in layout) and layout[0].ring
    kv = PagedKVCache(layout, page_size=64, context_pages=9, ring_pages=7,
                      dtype="bfloat16")
    assert kv.ring_slot_pages == 3 and kv.window == 128
    assert kv.ring.layers == [0, 2] and kv.context.layers == [1]
    assert kv.ring.array_names() == ["kv_c_0", "kv_c_2"]
    arrays = kv.make_arrays()
    assert sorted(arrays) == ["kv_c_0", "kv_c_1", "kv_c_2"]
    assert arrays["kv_c_0"].shape == (7, 64, 640)
    assert arrays["kv_c_1"].shape == (9, 64, 640)
    one_page = 64 * 640 * 2
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["mem.serving.kv_pool_bytes.ring"] == 2 * 7 * one_page
    assert gauges["mem.serving.kv_pool_bytes.context"] == 9 * one_page
    assert gauges["mem.serving.kv_pool_bytes.latent"] \
        == (2 * 7 + 9) * one_page == kv.pool_bytes
    # a request past the window takes a whole ring and no more
    assert kv.pages_for_tokens(1000) == (16, 3)
    got = kv.try_alloc(4, 3)
    assert got is not None and len(got[1]) == 3
    kv.free(*got)
    assert not kv.audit([], [])


# -- rotary ------------------------------------------------------------------

def test_yarn_frequencies_and_temperature_against_their_closed_forms():
    inv = llm_ops.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    plain = 50000.0 ** (-np.arange(32) * 2.0 / 64)

    def index_of(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) \
            / (2 * math.log(50000.0))

    low, high = math.floor(index_of(32)), math.ceil(index_of(1))
    assert (low, high) == (8, 20)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], plain[high:] / 64, rtol=1e-6)
    mid = 14
    blend = (mid - low) / (high - low)
    np.testing.assert_allclose(
        inv[mid], plain[mid] * (1 - blend) + plain[mid] / 64 * blend,
        rtol=1e-6)
    assert llm_ops.yarn_mscale(64.0) == pytest.approx(
        0.1 * math.log(64) + 1)
    assert llm_ops.yarn_mscale(64.0) == pytest.approx(1.4159, abs=1e-4)
    assert llm_ops.yarn_mscale(1.0) == 1.0
    np.testing.assert_allclose(
        llm_ops.yarn_inv_freq(64, 50000.0, 1.0, 4096, 32.0, 1.0), plain,
        rtol=1e-6)
    # the reference states the same frequencies and scale on its own
    cfg = kimi_k2.KimiK2Config(qk_rope_head_dim=64, qk_nope_head_dim=128)
    rc = family.reference_config(cfg)
    np.testing.assert_allclose(np.asarray(rk.yarn_inv_freq(rc)), inv,
                               rtol=1e-6)
    assert rk.softmax_scale(rc) == pytest.approx(cfg.softmax_scale)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)


def test_rotary_pairs_are_interleaved_and_keep_relative_position():
    rng = np.random.RandomState(0)
    inv = llm_ops.yarn_inv_freq(8, 50000.0, 64.0, 4096, 32.0, 1.0)
    q, k = rng.randn(8).astype(np.float32), rng.randn(8).astype(np.float32)

    def dot(tq, tk):
        return float(jnp.dot(
            llm_ops._rope_pairs(jnp.asarray(q), jnp.asarray(tq), inv),
            llm_ops._rope_pairs(jnp.asarray(k), jnp.asarray(tk), inv)))

    assert dot(5, 3) == pytest.approx(dot(105, 103), rel=1e-4)
    assert dot(5, 3) != pytest.approx(dot(5, 4), rel=1e-3)
    turned = np.asarray(llm_ops._rope_pairs(jnp.asarray(q), jnp.asarray(7),
                                            inv))
    ang = 7 * inv[0]
    assert turned[0] == pytest.approx(q[0] * math.cos(ang)
                                      - q[1] * math.sin(ang), rel=1e-5)
    assert turned[1] == pytest.approx(q[1] * math.cos(ang)
                                      + q[0] * math.sin(ang), rel=1e-5)


# -- absorbed = expanded -------------------------------------------------------

def test_absorbed_decode_attention_equals_expanded_prefill_attention():
    """The last position of a prompt attended two ways: expanded (per-head
    keys and values from W_kvb, `mla_prefill_attention`) and absorbed over
    latent pages (`mla_absorb_query`, `cached_latent_attention`,
    `mla_expand_output`), float32 so that only the order of sums differs."""
    rng = np.random.RandomState(1)
    n, nope, rope, rank, v, s, page = 4, 16, 8, 32, 16, 40, 8
    heads = {"num_heads": n, "nope_dim": nope, "rope_dim": rope}
    q_nope = jnp.asarray(rng.randn(1, s, n * nope), jnp.float32)
    q_rope = jnp.asarray(rng.randn(1, s, n * rope), jnp.float32)
    latent = jnp.asarray(rng.randn(1, s, rank + rope), jnp.float32)
    w_kvb = jnp.asarray(rng.randn(rank, n * (nope + v)) * rank ** -0.5,
                        jnp.float32)
    scale = 0.17
    kv = latent[..., :rank] @ w_kvb
    expanded = run_op("mla_prefill_attention",
                      {"QNope": q_nope, "QRope": q_rope, "KV": kv,
                       "Latent": latent},
                      dict(heads, scale=scale))["Out"]
    width = 128                      # rank + rope = 40, in whole lane tiles
    pool = jnp.zeros((7, page, width), jnp.float32)
    table = jnp.asarray([[3, 1, 6, 2, 5]], jnp.int32)
    pool = run_op("latent_cache_write",
                  {"Latent": latent[:, :-1], "Pool": pool,
                   "PageTable": table,
                   "Lengths": jnp.asarray([s - 1], jnp.int32)},
                  {})["PoolOut"]
    assert float(jnp.abs(pool[0]).max()) == 0.0      # nothing was padding
    q = run_op("mla_absorb_query",
               {"QNope": q_nope[:, -1], "QRope": q_rope[:, -1], "W": w_kvb},
               heads)["Q"]
    assert q.shape == (1, n * (rank + rope))
    out = run_op("cached_latent_attention",
                 {"Q": q, "Latent": latent[:, -1], "Pool": pool,
                  "PageTable": table,
                  "Positions": jnp.asarray([s - 1], jnp.int32)},
                 {"num_heads": n, "value_dim": rank, "scale": scale})
    # the step's own row was written at its position
    np.testing.assert_allclose(
        np.asarray(out["PoolOut"][5, (s - 1) % page, :rank + rope]),
        np.asarray(latent[0, -1]), rtol=1e-6)
    absorbed = run_op("mla_expand_output", {"X": out["Out"], "W": w_kvb},
                      heads)["Out"]
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded[:, -1]), rtol=2e-4,
                               atol=2e-5)


def test_a_row_wider_than_its_pages_is_refused():
    with pytest.raises(ValueError, match="pages"):
        run_op("latent_cache_write",
               {"Latent": jnp.zeros((1, 4, 40)),
                "Pool": jnp.zeros((3, 4, 32)),
                "PageTable": jnp.zeros((1, 2), jnp.int32),
                "Lengths": jnp.asarray([4], jnp.int32)}, {})


# -- the kernel --------------------------------------------------------------

@pytest.mark.parametrize("chunk", [32, 1024])
def test_the_paged_latent_kernel_against_its_stock_lowering(monkeypatch,
                                                            chunk):
    """Interpreted on the CPU: rows of 0, 1, several and a table's worth
    of chunks, pages in no order, a pool full of another request's rows."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(pma, "CHUNK_TOKENS", chunk)
    rng = np.random.RandomState(2)
    b, n, width, v, page, mp, pages = 5, 8, 256, 128, 16, 12, 80
    pool = jnp.asarray(rng.randn(pages, page, width), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pages - 1)[:b * mp].reshape(b, mp)
                        + 1, jnp.int32)
    table = table.at[0].set(0)                       # an empty slot
    pos = jnp.asarray([0, 0, 17, 100, mp * page - 1], jnp.int32)
    q = jnp.asarray(rng.randn(b, n * width), jnp.float32)
    telemetry.reset()
    got = pma.paged_mla_decode_attention(q, pool, table, pos, n, v, 0.05)
    want = pma.stock_paged_mla_attention(q, pool, table, pos, n, v, 0.05)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    c = telemetry.snapshot()["counters"]
    assert c.get("pallas.paged_attn_dispatches") == 1
    assert not c.get("pallas.paged_attn_fallbacks")


def test_the_stock_gather_is_counted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "off")
    telemetry.reset()
    pma.paged_mla_decode_attention(
        jnp.zeros((2, 4 * 128)), jnp.zeros((5, 8, 128)),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32), 4, 64,
        1.0)
    assert telemetry.snapshot()["counters"][
        "pallas.paged_attn_fallbacks"] == 1


def _prompt(rng, s, n, nope, rope, dv, dtype=jnp.float32):
    """(q_nope, q_rope, k_nope, k_rope, v), heads apart as the stock
    lowering takes them."""
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for shape in (
        (s, n, nope), (s, n, rope), (s, n, nope), (s, rope), (s, n, dv)))


def _rows(q_nope, q_rope, k_nope, k_rope, v):
    """The same prompt as the layer's own arrays: (q_nope [S, n*nope],
    q_rope [S, n*rope], kv [S, n*(nope+dv)], k_rope)."""
    s = q_nope.shape[0]
    kv = jnp.concatenate([k_nope, v], axis=-1)
    return (q_nope.reshape(s, -1), q_rope.reshape(s, -1), kv.reshape(s, -1),
            k_rope)


def _kernel_of(args, scale=0.1):
    q_nope, _, _, _, v = args
    s, n, nope = q_nope.shape
    out = mpa.mla_prefill_attention(*_rows(*args), scale, num_heads=n,
                                    nope_dim=nope)
    assert out.shape == (s, n * v.shape[2]) and out.dtype == v.dtype
    return np.asarray(out.astype(jnp.float32)).reshape(s, n, -1)


@pytest.mark.parametrize("s, block", [(384, 128), (128, 512)])
def test_the_prefill_kernel_against_its_stock_lowering(monkeypatch, s,
                                                       block):
    """Interpreted on the CPU: three key blocks to a query block's one, and
    a prompt of a single block; key and value widths differ and the shared
    rotary key is one for all heads."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(mpa, "BLOCK", block)
    args = _prompt(np.random.RandomState(6), s, 4, 128, 64, 128)
    telemetry.reset()
    got = _kernel_of(args)
    want = mpa.stock_mla_prefill_attention(*args, 0.1, block_q=128)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    # causal: a query's output does not move with a later key
    later = list(args)
    later[2] = args[2].at[s - 1].add(5.0)
    np.testing.assert_array_equal(_kernel_of(later)[:s - 1], got[:s - 1])
    c = telemetry.snapshot()["counters"]
    assert c["pallas.mla_prefill_dispatches"] == 2
    assert not c.get("pallas.mla_prefill_fallbacks")


def _dense_attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Every score at once, float32: the plainest statement."""
    f = [np.asarray(a, np.float32) for a in (q_nope, q_rope, k_nope,
                                             k_rope, v)]
    sc = (np.einsum("qhd,khd->hqk", f[0], f[2])
          + np.einsum("qhr,kr->hqk", f[1], f[3])) * scale
    s = sc.shape[-1]
    sc = np.where(np.tri(s, dtype=bool), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    return np.einsum("hqk,khv->qhv", p / p.sum(-1, keepdims=True), f[4])


# (prompt, BLOCK, heads, rope, VMEM_BLOCKS or None, heads a step, dtype,
# tolerance): what the dispatch tells apart. A block of 512 is attended on
# its diagonal in two sub-blocks of SUB rows, one of 128 whole; the heads
# a step follow from the widths, the dtype and VMEM_BLOCKS.
_FORMS = [
    (512, 512, 2, 64, None, 2, "float32", 1e-5),      # one block, sub-blocks
    (1024, 512, 4, 64, None, 4, "float32", 1e-5),     # + a block below it
    (1024, 512, 4, 64, 8 << 20, 2, "float32", 1e-5),  # two head groups
    (512, 128, 8, 64, None, 8, "float32", 1e-5),      # 4 key blocks to one
    (512, 128, 8, 64, 4 << 20, 4, "float32", 1e-5),
    (384, 128, 2, 128, None, 2, "float32", 1e-5),     # rope of a lane tile
    (384, 128, 3, 128, None, 3, "float32", 1e-5),     # so heads may be odd
    (256, 128, 4, 32, None, 4, "float32", 1e-5),      # 4 heads a rope tile
    (1024, 512, 8, 64, None, 8, "bfloat16", 2e-2),    # the Kimi form
    (512, 128, 4, 64, None, 4, "bfloat16", 2e-2),
]


@pytest.mark.parametrize(
    "s, block, n, rope, vmem, heads, dtype, tol", _FORMS,
    ids=[f"s{f[0]}-b{f[1]}-n{f[2]}-r{f[3]}-g{f[5]}-{f[6]}" for f in _FORMS])
def test_every_form_of_the_prefill_kernel(monkeypatch, s, block, n, rope,
                                          vmem, heads, dtype, tol):
    """Interpreted on the CPU against the stock lowering AND the plainest
    dense statement, in every form the dispatch can pick; then causality
    and a padded tail: whatever stands after a row moves no bit of it."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    monkeypatch.setattr(mpa, "BLOCK", block)
    if vmem is not None:
        monkeypatch.setattr(mpa, "VMEM_BLOCKS", vmem)
    dt = jnp.dtype(dtype)
    assert mpa._heads_a_step(n, 128, rope, 128, block, dt.itemsize) == heads
    rng = np.random.RandomState(s + n + rope)
    args = _prompt(rng, s, n, 128, rope, 128, dt)
    telemetry.reset()
    got = _kernel_of(args)
    c = telemetry.snapshot()["counters"]
    assert c["pallas.mla_prefill_dispatches"] == 1
    assert not c.get("pallas.mla_prefill_fallbacks")
    want = np.asarray(mpa.stock_mla_prefill_attention(*args, 0.1,
                                                      block_q=128))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(got, _dense_attention(*args, 0.1),
                               rtol=max(tol, 2e-5), atol=max(tol, 2e-5))
    # a padded tail: rows from `real` on are another prompt's, and large
    real = s - block // 2 - 3
    tail = _prompt(rng, s, n, 128, rope, 128, dt)
    padded = [jnp.concatenate([a[:real], 7.0 * t[real:]])
              for a, t in zip(args, tail)]
    np.testing.assert_array_equal(_kernel_of(padded)[:real], got[:real])
    # causality, row by row: the last key alone
    later = list(args)
    later[2] = args[2].at[s - 1].add(5.0)
    later[3] = args[3].at[s - 1].add(5.0)
    later[4] = args[4].at[s - 1].add(5.0)
    np.testing.assert_array_equal(_kernel_of(later)[:s - 1], got[:s - 1])


@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
def test_the_prefill_op_on_the_layers_arrays_equals_the_parents(monkeypatch,
                                                                dtype, tol):
    """`run_op("mla_prefill_attention")` on [B, S, n*d] arrays, the kernel
    interpreted, against what the op gave before PR 34: the stock products
    on head-major slices of the rounded inputs, float32 [B, S, n*v] (which
    W_o's product then rounded to its weight's dtype: Out's dtype now)."""
    rng = np.random.RandomState(11)
    b, s, n, nope, rope, dv, rank = 2, 256, 4, 128, 64, 128, 32
    ins = {"QNope": jnp.asarray(rng.randn(b, s, n * nope), jnp.float32),
           "QRope": jnp.asarray(rng.randn(b, s, n * rope), jnp.float32),
           "KV": jnp.asarray(rng.randn(b, s, n * (nope + dv)), jnp.float32),
           "Latent": jnp.asarray(rng.randn(b, s, rank + rope), jnp.float32)}
    attrs = {"num_heads": n, "nope_dim": nope, "rope_dim": rope,
             "scale": 0.09, "compute_dtype": dtype}
    dt = jnp.dtype(dtype)
    kvh = ins["KV"].reshape(b, s, n, -1).astype(dt)
    parent = jnp.stack([mpa.stock_mla_prefill_attention(
        ins["QNope"][i].reshape(s, n, nope).astype(dt),
        ins["QRope"][i].reshape(s, n, rope).astype(dt), kvh[i, :, :, :nope],
        ins["Latent"][i, :, -rope:].astype(dt), kvh[i, :, :, nope:], 0.09)
        for i in range(b)]).reshape(b, s, -1)
    for mode, exact in (("interpret", False), ("off", True)):
        monkeypatch.setenv("PT_PALLAS", mode)
        telemetry.reset()
        out = run_op("mla_prefill_attention", ins, attrs)["Out"]
        assert out.shape == (b, s, n * dv) and out.dtype == dt
        c = telemetry.snapshot()["counters"]
        assert c.get("pallas.mla_prefill_fallbacks", 0) == (b if exact
                                                            else 0)
        if exact:       # the stock lowering itself, rounded as W_o did
            np.testing.assert_array_equal(
                np.asarray(out.astype(jnp.float32)),
                np.asarray(parent.astype(dt).astype(jnp.float32)))
        else:
            np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                                       np.asarray(parent), rtol=tol,
                                       atol=tol)


def test_the_prefill_kernels_stock_lowering_is_counted(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "off")
    telemetry.reset()
    mpa.mla_prefill_attention(
        jnp.zeros((16, 2 * 8)), jnp.zeros((16, 2 * 4)),
        jnp.zeros((16, 2 * 16)), jnp.zeros((16, 4)), 1.0, num_heads=2,
        nope_dim=8)
    assert telemetry.snapshot()["counters"][
        "pallas.mla_prefill_fallbacks"] == 1


# (s, heads, nope, rope, BLOCK, VMEM_BLOCKS, mode) -> reason
_REFUSED = {
    "mode_off": (256, 4, 128, 64, 128, None, "off"),
    "length": (576, 4, 128, 64, 512, None, "interpret"),
    "tpu_tiling-head_width": (256, 4, 64, 64, 128, None, "interpret"),
    "tpu_tiling-block": (64, 4, 128, 64, 512, None, "interpret"),
    "tpu_tiling-rope": (256, 4, 128, 48, 128, None, "interpret"),
    "tpu_tiling-odd_heads_share_a_rope_tile": (256, 3, 128, 64, 128, None,
                                               "interpret"),
    "vmem": (256, 4, 128, 64, 128, 1 << 16, "interpret"),
}


@pytest.mark.parametrize("case", sorted(_REFUSED))
def test_a_refused_shape_is_counted_with_its_reason(monkeypatch, case):
    """A shape the kernel's form cannot take attends through the stock
    lowering, correctly, and the counter's record says why."""
    s, n, nope, rope, block, vmem, mode = _REFUSED[case]
    monkeypatch.setenv("PT_PALLAS", mode)
    monkeypatch.setattr(mpa, "BLOCK", block)
    if vmem is not None:
        monkeypatch.setattr(mpa, "VMEM_BLOCKS", vmem)
    seen = []
    add = telemetry.counter_add
    monkeypatch.setattr(
        mpa.telemetry, "counter_add",
        lambda name, delta=1, **attrs: (seen.append((name, attrs)),
                                        add(name, delta, **attrs))[1])
    args = _prompt(np.random.RandomState(3), s, n, nope, rope, 128)
    telemetry.reset()
    got = _kernel_of(args)
    assert seen == [("pallas.mla_prefill_fallbacks",
                     {"reason": case.split("-")[0]})]
    c = telemetry.snapshot()["counters"]
    assert c["pallas.mla_prefill_fallbacks"] == 1
    assert not c.get("pallas.mla_prefill_dispatches")
    np.testing.assert_allclose(got, _dense_attention(*args, 0.1), rtol=2e-5,
                               atol=2e-5)


def test_a_length_halfway_between_two_blocks_takes_a_smaller_block(
        monkeypatch):
    """768 is no multiple of BLOCK 512: the kernel attends it in blocks of
    384, the largest whole-lane-tile divisor, and not by the stock
    lowering (a prefill bucket halfway between two powers of two)."""
    monkeypatch.setenv("PT_PALLAS", "interpret")
    args = _prompt(np.random.RandomState(4), 768, 4, 128, 64, 128)
    telemetry.reset()
    got = _kernel_of(args)
    c = telemetry.snapshot()["counters"]
    assert c["pallas.mla_prefill_dispatches"] == 1
    assert not c.get("pallas.mla_prefill_fallbacks")
    np.testing.assert_allclose(got, _dense_attention(*args, 0.1), rtol=2e-5,
                               atol=2e-5)


def test_a_scale_that_is_not_positive_is_refused():
    with pytest.raises(ValueError, match="positive"):
        mpa.mla_prefill_attention(
            jnp.zeros((16, 16)), jnp.zeros((16, 8)), jnp.zeros((16, 32)),
            jnp.zeros((16, 4)), 0.0, num_heads=2, nope_dim=8)


# -- through the engine ------------------------------------------------------

@pytest.mark.parametrize("dtype, logit_tol, gap_tol",
                         [("float32", 1e-4, 1e-4), ("bfloat16", 0.05, 0.1)])
def test_prefill_and_decode_through_latent_pages_against_the_reference(
        dtype, logit_tol, gap_tol):
    cfg = small(dtype=dtype)
    params = seeded_params(cfg, 0)
    ref = rk.Reference(params, family.reference_config(cfg))
    telemetry.reset()
    engine = engine_for(cfg, params).start(warmup=False)
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(3, cfg.vocab_size, n) for n in (37, 9, 64)]
        reqs = [engine.submit(p, max_new_tokens=12, stop_at_eos=False,
                              keep_first_logits=True) for p in prompts]
        for prompt, req in zip(prompts, reqs):
            chosen = req.result(120)
            rows, _ = ref.rows(np.concatenate([prompt, chosen]), 128,
                               prompt.size - 1, len(chosen))
            assert rk.logit_error(np.asarray(req.first_logits),
                                  rows[0]) < logit_tol
            assert rk.greedy_gaps(rows, chosen).max() < gap_tol
    finally:
        engine.close()
    c = telemetry.snapshot()["counters"]
    # the two MoE layers' counts ride behind the tokens
    assert c["decode.moe_pairs_total"] > 0
    assert 0 < c["decode.moe_pairs_held"] < c["decode.moe_pairs_total"]
    # latent rows read: rows x layers x context, 11 steps a request
    want = sum(cfg.n_layers * (p.size + 1 + i)
               for p in prompts for i in range(11))
    assert c["decode.kv_tokens_attended"] == want
    assert "decode.rows_past_window" not in c


def test_the_engine_feeds_and_fetches_what_the_layout_names():
    cfg = small(dtype="float32")
    params = kimi_k2.kimi_k2_params(cfg, 3)
    engine = engine_for(cfg, params)
    assert sorted(engine._pools) == [f"kv_c_{i}"
                                     for i in range(cfg.n_layers)]
    assert engine._pools["kv_c_0"].shape == (33, 16, 128)   # 40 in 128
    served = cfg.served()
    assert [lc.latent for lc in served.cache_layout()] == [True] * 3
    _, feeds, fetches = served.build_step_program(4, engine.kv)
    assert feeds == ["tokens", "positions", "page_table"]
    assert fetches == ["logits", "kv_c_0_out", "kv_c_1_out", "kv_c_2_out",
                       "step_counts"]
    _, feeds, fetches = served.build_prefill_program(32, engine.kv)
    assert feeds == ["tokens", "positions", "lengths", "page_table"]
    assert fetches[1:] == ["kv_c_0_out", "kv_c_1_out", "kv_c_2_out"]


def test_no_chunked_prefill_so_no_prefix_store():
    cfg = small(dtype="float32")
    params = kimi_k2.kimi_k2_params(cfg, 3)
    with pytest.raises(ValueError, match="latent"):
        engine_for(cfg, params, prefix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill"):
        cfg.served().build_chunk_prefill_program(16, engine_for(cfg,
                                                                params).kv)


def test_decode_is_the_same_alone_and_in_a_full_batch():
    """Each slot reads its own page table: a request's greedy tokens do not
    depend on who else is decoding."""
    cfg = small(dtype="float32")
    params = seeded_params(cfg, 5)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(3, cfg.vocab_size, n) for n in (20, 33, 7, 50)]
    engine = engine_for(cfg, params).start(warmup=False)
    try:
        alone = [engine.generate(p, max_new_tokens=10, stop_at_eos=False,
                                 timeout=120) for p in prompts]
        reqs = [engine.submit(p, max_new_tokens=10, stop_at_eos=False)
                for p in prompts]
        together = [r.result(120) for r in reqs]
    finally:
        engine.close()
    for a, t in zip(alone, together):
        assert list(a) == list(t)


def test_the_latent_ops_state_the_attrs_they_need():
    for name, attrs in (
            ("mla_rope_split", ("num_heads", "nope_dim", "rope_dim")),
            ("mla_absorb_query", ("num_heads", "nope_dim")),
            ("mla_expand_output", ("num_heads", "nope_dim")),
            ("mla_prefill_attention", ("num_heads", "nope_dim", "rope_dim")),
            ("cached_latent_attention", ("num_heads", "value_dim"))):
        assert registry.lookup(name).required_attrs == attrs


# -- a program compiled late, on the engine's own pools -----------------------

def _engines():
    """A latent model, and one with context pages and rings, as made anew:
    name -> () -> (cfg, engine not yet started)."""
    from paddle_tpu.models.afmoe import AfmoeConfig, afmoe_params

    def latent():
        cfg = small(dtype="float32")
        return cfg, engine_for(cfg, seeded_params(cfg, 7), max_new_tokens=96)

    def rings():
        cfg = AfmoeConfig(dtype="float32")
        return cfg, DecodeEngine(cfg, afmoe_params(cfg, 3), DecodeConfig(
            max_slots=4, page_size=8, kv_pages=4 * 32 + 1,
            kv_ring_pages=4 * 5 + 1, prefill_buckets=[32, 64],
            prefix_cache=False, max_new_tokens=96, buckets=[4]))

    return {"latent": latent, "rings": rings}


@pytest.mark.parametrize("model", ["latent", "rings"])
def test_a_first_compile_writes_the_scratch_page_alone(model):
    """`_entry` runs a new program once on the engine's OWN pools, fed
    zeros: its page tables name page 0 and nothing else, in the ring pool
    too, so every other page comes back as it was."""
    _, engine = _engines()[model]()
    try:
        rng = np.random.RandomState(1)
        engine._pools = {
            name: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for name, a in engine._pools.items()}
        before = {name: np.asarray(a) for name, a in engine._pools.items()}
        engine._entry("prefill", 64)
        engine._entry("step", 4)
        assert sorted(engine._pools) == sorted(before)
        for name, was in before.items():
            np.testing.assert_array_equal(
                np.asarray(engine._pools[name])[1:], was[1:], err_msg=name)
    finally:
        engine.close()


@pytest.mark.parametrize("model", ["latent", "rings"])
def test_a_late_compile_leaves_a_seated_request_as_it_was(model):
    """A bucket's first use while a request is decoding: the new prefill
    program is compiled through a run on the pools that hold the seated
    request's pages, and the request gets the tokens it gets alone."""
    cfg, engine = _engines()[model]()
    rng = np.random.RandomState(2)
    first = rng.randint(3, cfg.vocab_size, 20)
    late = rng.randint(3, cfg.vocab_size, 50)
    engine.start(warmup=False)
    try:
        alone = engine.generate(first, max_new_tokens=96, stop_at_eos=False,
                                timeout=300)
        compiled = telemetry.counter_get("decode.compiles")
        seated = engine.submit(first, max_new_tokens=96, stop_at_eos=False)
        deadline = time.monotonic() + 300
        while len(seated.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert 3 <= len(seated.tokens) < 96
        other = engine.submit(late, max_new_tokens=4, stop_at_eos=False)
        assert len(other.result(300)) == 4
        assert telemetry.counter_get("decode.compiles") == compiled + 1
        assert list(seated.result(300)) == list(alone)
    finally:
        engine.close()
