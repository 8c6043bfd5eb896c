"""AOT-compile the main-path Pallas kernels for a described v5e chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached: what Mosaic would refuse on the machine
with the chip it refuses here, at no chip time. Each case compiles one
kernel at a real width and asserts the compiled module carries the
kernel (``tpu_custom_call``) — a compile that passes is not a chip run.

This is the ONLY file that describes the chip, and it does so inside the
``topo`` fixture: one process may load libtpu at a time, so nothing here
runs at import, in ``skipif`` or in ``parametrize``.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep the
    # cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_mode(monkeypatch):
    """The dispatchers ask jax.default_backend(), which is the CPU here:
    steer them onto their compiled-kernel route for the trace."""
    import paddle_tpu.ops.pallas as pallas

    monkeypatch.delenv("PT_PALLAS", raising=False)
    monkeypatch.setattr(pallas, "_requested_mode", lambda: "tpu")


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


@pytest.mark.parametrize("m,k,n", [(8, 2048, 8192), (8, 8192, 2048)])
def test_int8_gemm_bias_relu(one_chip, tpu_mode, m, k, n):
    from paddle_tpu.ops.pallas.int8_gemm import int8_weight_only_gemm

    _compile(lambda x, w, s, b: int8_weight_only_gemm(x, w, s, b, "relu"),
             one_chip, ((m, k), F32), ((k, n), I8), ((n,), F32),
             ((n,), F32))


@pytest.mark.parametrize("mp,pool", [(128, 1024), (64, 513)])
def test_paged_attention_multi_chunk(one_chip, tpu_mode, mp, pool):
    """b8, 16 heads x 128, page 16, at the default chunk: 128 pages per
    row (2048-token context), and the serving cell's own shape (64 pages
    a row, a pool of 513). The streamed branch: a dynamic walk, two K and
    two V buffers, a DMA semaphore each."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, n, hd, page = 8, 16, 128, 16
    assert pa._chunk_pages(page, mp, n * hd) < mp      # multi-chunk
    _compile(lambda q, pk, pv, t, p: pa.paged_decode_attention(
        q, pk, pv, t, p, n, hd, hd ** -0.5), one_chip,
        ((b, n * hd), F32), ((pool, page, n * hd), F32),
        ((pool, page, n * hd), F32), ((b, mp), I32), ((b,), I32))


@pytest.mark.parametrize("ring,pages,table", [(False, 10241, 160),
                                              (True, 4161, 65)])
def test_paged_gqa_attention_at_the_trinity_share(one_chip, tpu_mode, ring,
                                                  pages, table):
    """6 query heads on 1 K/V head of 128, bfloat16 pages of 64 tokens, 64
    rows: a full layer over 160 context pages a row, a window layer over
    its ring of 65."""
    from paddle_tpu.ops.pallas.paged_gqa_attention import \
        paged_gqa_decode_attention

    _compile(lambda q, pk, pv, t, p: paged_gqa_decode_attention(
        q, pk, pv, t, p, num_heads=6, num_kv_heads=1, head_dim=128,
        scale=128 ** -0.5, window=4096 if ring else 0, ring=ring),
        one_chip, ((64, 768), F32), ((pages, 64, 128), BF16),
        ((pages, 64, 128), BF16), ((64, table), I32), ((64,), I32))


def test_paged_mla_attention_at_the_kimi_share(one_chip, tpu_mode):
    """64 absorbed query heads on one latent row of 576 values carried in
    640 lanes, values its first 512, bfloat16 pages of 64 tokens, 64 rows
    over 128 pages each of a pool of 8193: the streamed walk, two halves
    of scratch, a DMA semaphore each. A 576-wide pool is what the
    dispatcher must refuse (Mosaic: a page's slice is no whole lane
    tile), and the stock gather is what then compiles."""
    from paddle_tpu.ops.pallas.paged_mla_attention import \
        paged_mla_decode_attention

    def attend(q, pool, t, p):
        return paged_mla_decode_attention(q, pool, t, p, num_heads=64,
                                          value_dim=512, scale=0.1447)

    _compile(attend, one_chip, ((64, 64 * 640), F32),
             ((8193, 64, 640), BF16), ((64, 128), I32), ((64,), I32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((64, 64 * 576), F32), ((8193, 64, 576), BF16), ((64, 128), I32),
        ((64,), I32))]
    assert "tpu_custom_call" not in jax.jit(attend).lower(
        *args).compile().as_text()


@pytest.mark.parametrize("s", [2048, 4096, 6144])
def test_mla_prefill_attention_at_the_kimi_share(one_chip, tpu_mode, s):
    """A prompt bucket of the Kimi cell, 64 heads of 128 + 64 on keys of
    the same and values of 128, bfloat16, through the op on the layer's
    own float32 [1, S, n x d] arrays: the blockwise kernel, 8 heads a
    step, its scores in VMEM. Beside the custom call stand the roundings
    to bfloat16 and the shared rotary key's two lane tiles, and NO
    transpose or copy of an [S, n x d] operand or of the output."""
    import math
    import re

    from paddle_tpu.ops.llm_ops import mla_prefill_attention_op
    from paddle_tpu.ops.pallas import mla_prefill_attention as mpa

    n, nope, rope, dv = 64, 128, 64, 128
    assert mpa._heads_a_step(n, nope, rope, dv, mpa.BLOCK, 2) == 8

    def attend(qn, qr, kv, latent):
        return mla_prefill_attention_op(
            {"QNope": [qn], "QRope": [qr], "KV": [kv], "Latent": [latent]},
            {"num_heads": n, "nope_dim": nope, "rope_dim": rope,
             "scale": 0.1447, "compute_dtype": "bfloat16"})["Out"]

    text = _compile(attend, one_chip, ((1, s, n * nope), F32),
                    ((1, s, n * rope), F32), ((1, s, n * (nope + dv)), F32),
                    ((1, s, 512 + rope), F32))
    assert 'custom_call_target="tpu_custom_call"' in text
    moved = re.findall(r"= \w+\[([\d,]+)\]\S* (transpose|copy)\(", text)
    assert all(math.prod(map(int, dims.split(","))) <= s * 2 * 128
               for dims, _ in moved), moved


def test_routed_experts_grouped_product_at_the_trinity_share(one_chip):
    """64 rows, top-4 of 256, experts 0-31 held at width 3072: the three
    grouped products are the chip's ragged-dot kernel, not a dense product
    over every expert."""
    from paddle_tpu.parallel.moe import routed_experts_share

    text = _compile(
        lambda x, wr, b, w1, w3, w2: routed_experts_share(
            x, wr, b, w1, w3, w2, top_k=4, held_lo=0, route_scale=2.448),
        one_chip, ((64, 3072), F32), ((3072, 256), BF16), ((256,), F32),
        ((32, 3072, 3072), BF16), ((32, 3072, 3072), BF16),
        ((32, 3072, 3072), BF16))
    assert "ragged-dot" in text


@pytest.mark.parametrize("n,h,f,e", [(128, 3072, 3072, 32),
                                     (576, 3072, 3072, 32),
                                     (8256, 3072, 3072, 32),
                                     (64, 7168, 2048, 12),
                                     (3136, 7168, 2048, 12)])
def test_grouped_swiglu_at_the_trinity_and_kimi_shares(one_chip, tpu_mode,
                                                       n, h, f, e):
    """A step's sorted rows and the largest prefill bucket's, at both
    callers' widths: the weight blocks, the row and output tiles and the
    products' temporaries fit the kernel's VMEM limit, the dynamic row
    windows and the loop over them lower."""
    from paddle_tpu.ops.pallas.grouped_swiglu import grouped_swiglu

    _compile(grouped_swiglu, one_chip, ((n, h), BF16), ((e, h, f), BF16),
             ((e, h, f), BF16), ((e, f, h), BF16), ((e,), I32))


def test_ssm_state_update_at_the_falcon_h1_widths(one_chip, tpu_mode):
    """64 rows over 65 slots of 32 heads x [256, 128] float32, the state
    donated: the kernel, and the state aliased to its output (no copy of
    the 273 MB array beside it)."""
    from paddle_tpu.ops.pallas.ssm_state_update import ssm_state_update

    shapes = [((65, 32, 256, 128), F32), ((64,), I32), ((64, 32, 128), F32),
              ((64, 32), F32), ((64, 2, 256), F32), ((64, 2, 256), F32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(ssm_state_update, donate_argnums=(0,)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state = 65 * 32 * 256 * 128 * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 8


def test_gated_delta_state_update_at_the_qwen3_next_share(one_chip,
                                                          tpu_mode):
    """64 rows over 65 slots of 8 value heads x [128, 128] float32, the
    state donated: the kernel, and the state aliased to its output (no
    copy of the 34 MB array beside it)."""
    from paddle_tpu.ops.pallas.gated_delta_state_update import \
        gated_delta_state_update

    shapes = [((65, 8, 128, 128), F32), ((64,), I32), ((64, 8, 128), F32),
              ((64, 8, 128), F32), ((64, 8, 128), F32), ((64, 8), F32),
              ((64, 8), F32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(gated_delta_state_update, donate_argnums=(0,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_state_update" in text
    mem = compiled.memory_analysis()
    state = 65 * 8 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 8


@pytest.mark.parametrize("s", [1024, 16384])
def test_gated_delta_chunk_scan_at_the_qwen3_next_share(one_chip, tpu_mode,
                                                        s):
    """A whole prompt of the shortest and of the longest bucket, 8 value
    heads on 4 key heads x [128, 128], chunks of 64, float32 'highest':
    the kernel, and neither of the stock form's two loops beside it."""
    from paddle_tpu.core import telemetry
    from paddle_tpu.ops.pallas.gated_delta_chunk_scan import \
        gated_delta_chunk_scan

    telemetry.reset()
    shapes = [((1, s, 8, 128), F32)] * 3 + [((1, s, 8), F32)] * 2
    args = [jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
            for shape, d in shapes]
    compiled = jax.jit(lambda *a: gated_delta_chunk_scan(
        *a, 64, heads_per_key=2)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_chunk_scan" in text
    assert "while" not in text
    assert telemetry.counter_get(
        "pallas.gated_delta_chunk_scan_fallbacks") == 0


@pytest.mark.parametrize("n", [384, 81984])
def test_grouped_swiglu_at_the_qwen3_next_share(one_chip, tpu_mode, n):
    """A step's sorted rows (64 rows x top-10, a quarter held) and the
    16,384 bucket's, 128 held experts of 2048 x 512: F in one block, 128
    visits and the row tiles'."""
    from paddle_tpu.ops.pallas.grouped_swiglu import _tiles, grouped_swiglu

    assert _tiles(n, 2048, 512, BF16)[2] == 512
    _compile(grouped_swiglu, one_chip, ((n, 2048), BF16),
             ((128, 2048, 512), BF16), ((128, 2048, 512), BF16),
             ((128, 512, 2048), BF16), ((128,), I32))


def test_paged_gqa_attention_at_the_qwen3_next_share(one_chip, tpu_mode):
    """4 query heads on 1 K/V head of 256, bfloat16 pages of 64 tokens, 64
    rows over 288 context pages each (18,432 positions) of a pool of
    18,433."""
    from paddle_tpu.ops.pallas.paged_gqa_attention import \
        paged_gqa_decode_attention

    _compile(lambda q, pk, pv, t, p: paged_gqa_decode_attention(
        q, pk, pv, t, p, num_heads=4, num_kv_heads=1, head_dim=256,
        scale=256 ** -0.5), one_chip, ((64, 1024), F32),
        ((18433, 64, 256), BF16), ((18433, 64, 256), BF16),
        ((64, 288), I32), ((64,), I32))


def test_paged_gqa_attention_at_the_lfm2_stage(one_chip, tpu_mode):
    """32 query heads on 8 K/V heads of 64 (two K/V heads a lane tile: the
    kernel's packed form, four heads of 128 with eight query rows each),
    bfloat16 pages of 64 tokens, 128 rows over 128 context pages each
    (8,192 positions) of a pool of 16,385."""
    from paddle_tpu.ops.pallas.paged_gqa_attention import \
        paged_gqa_decode_attention

    _compile(lambda q, pk, pv, t, p: paged_gqa_decode_attention(
        q, pk, pv, t, p, num_heads=32, num_kv_heads=8, head_dim=64,
        scale=64 ** -0.5), one_chip, ((128, 2048), F32),
        ((16385, 64, 512), BF16), ((16385, 64, 512), BF16),
        ((128, 128), I32), ((128,), I32))


def test_paged_gqa_attention_at_the_falcon_share(one_chip, tpu_mode):
    """20 query heads on 4 K/V heads of 128 (a group of 5, padded to 8
    sublanes), bfloat16 pages of 64 tokens, 64 rows over 32 context pages
    each (2,048 positions: the table is one chunk, so every copy in flight
    is the next row's) of a pool of 2,049."""
    from paddle_tpu.ops.pallas.paged_gqa_attention import \
        paged_gqa_decode_attention

    _compile(lambda q, pk, pv, t, p: paged_gqa_decode_attention(
        q, pk, pv, t, p, num_heads=20, num_kv_heads=4, head_dim=128,
        scale=128 ** -0.5), one_chip, ((64, 2560), F32),
        ((2049, 64, 512), BF16), ((2049, 64, 512), BF16),
        ((64, 32), I32), ((64,), I32))


@pytest.mark.parametrize("rows", [64, 16384])
def test_mhc_kernels_at_the_motif3_share(one_chip, tpu_mode, rows):
    """Four streams of 4096 float32 a token, 24 maps in a lane tile, 20
    Sinkhorn iterations of lane rotations; a step's 64 rows and the largest
    bucket's 16,384 in tiles of 64."""
    from paddle_tpu.ops.pallas import mhc_mix

    def pre(x, gamma, phi, scale, bias):
        return mhc_mix.mhc_pre(x, gamma, phi, scale, bias, n=4, iters=20,
                               eps=1e-5)

    text = _compile(pre, one_chip, ((rows, 16384), F32), ((16384,), F32),
                    ((16384, 24), BF16), ((3,), F32), ((24,), F32))
    assert "mhc_pre" in text

    def post(x, y, maps):
        return mhc_mix.mhc_post(x, y, maps, n=4, clamp=1e6)

    text = _compile(post, one_chip, ((rows, 16384), F32), ((rows, 4096), F32),
                    ((rows, 128), F32))
    assert "mhc_post" in text


@pytest.mark.parametrize("n", [192, 32832])
def test_grouped_polyglu_at_the_motif3_share(one_chip, tpu_mode, n):
    """48 held PolyNorm experts of 4096 x 1280 in bfloat16, their four
    parameters in SMEM, gate and up of all of F in float32 scratch: a
    step's 192 sorted rows and a 16,384 bucket's 32,832 in tiles of
    1,024."""
    from paddle_tpu.ops.pallas import grouped_swiglu as gs

    assert gs._tiles(n, 4096, 1280, BF16) == (min(n, 1024), 128, 640)

    def experts(xs, w1, w3, w2, pn, sizes):
        return gs.grouped_polyglu(xs, w1, w3, w2, pn, sizes, eps=1e-5,
                                  out_scale=0.5, bias_clamp=0.5)

    text = _compile(experts, one_chip, ((n, 4096), BF16),
                    ((48, 4096, 1280), BF16), ((48, 4096, 1280), BF16),
                    ((48, 1280, 4096), BF16), ((48, 4), F32), ((48,), I32))
    assert "grouped_polyglu" in text


@pytest.mark.parametrize("window", [0, 128])
def test_mla_prefill_attention_at_the_motif3_share(one_chip, tpu_mode,
                                                   window):
    """80 query heads on 16 K/V heads (ten heads, two K/V heads a step), a
    4,096 bucket: the triangle of a full layer and the band of a window
    layer through the op on the layer's own arrays."""
    from paddle_tpu.ops.llm_ops import mla_prefill_attention_op
    from paddle_tpu.ops.pallas import mla_prefill_attention as mpa

    n, nkv, nope, rope, dv, s = 80, 16, 128, 64, 128, 4096
    assert mpa._heads_a_step(n, nope, rope, dv, mpa.BLOCK, 2, group=5) == 10

    def attend(qn, qr, kv, latent):
        attrs = {"num_heads": n, "num_kv_heads": nkv, "nope_dim": nope,
                 "rope_dim": rope, "scale": 192 ** -0.5,
                 "compute_dtype": "bfloat16"}
        if window:
            attrs["window"] = window
        return mla_prefill_attention_op(
            {"QNope": [qn], "QRope": [qr], "KV": [kv], "Latent": [latent]},
            attrs)["Out"]

    _compile(attend, one_chip, ((1, s, n * nope), F32),
             ((1, s, n * rope), F32), ((1, s, nkv * (nope + dv)), F32),
             ((1, s, 512 + rope), F32))


def test_paged_mla_attention_over_a_latent_ring(one_chip, tpu_mode):
    """80 absorbed heads over a slot's ring of 3 pages of a pool of 193:
    the walk of the paged kernel with the ring's mask."""
    from paddle_tpu.ops.pallas.paged_mla_attention import \
        paged_mla_decode_attention

    def attend(q, pool, t, p):
        return paged_mla_decode_attention(q, pool, t, p, num_heads=80,
                                          value_dim=512, scale=0.0722,
                                          window=128)

    _compile(attend, one_chip, ((64, 80 * 640), F32),
             ((193, 64, 640), BF16), ((64, 3), I32), ((64,), I32))


def test_layer_norm_fwd_bwd(one_chip, tpu_mode):
    from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm

    def loss(x, s, b):
        return jnp.sum(fused_layer_norm(x, s, b)[0].astype(F32))

    _compile(jax.grad(loss, (0, 1, 2)), one_chip,
             ((20480, 1024), BF16), ((1024,), F32), ((1024,), F32))


# ERNIE-large attention: batch 40, 16 heads, seq 512, head dim 64, bf16,
# key-padding bias and attention-probs dropout on
_B, _H, _S, _D = 40, 16, 512, 64


@pytest.mark.parametrize("layout", ["bnsd", "packed"])
def test_flash_attention_fwd_bwd(one_chip, tpu_mode, layout):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    if layout == "bnsd":
        qkv, heads = (_B, _H, _S, _D), None
    else:
        qkv, heads = (_B, _S, _H * _D), _H

    def loss(q, k, v, bias, seed):
        out = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                              dropout_seed=seed, num_heads=heads)
        return jnp.sum(out.astype(F32))

    text = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                    (qkv, BF16), (qkv, BF16), (qkv, BF16),
                    ((_B, _S), F32), ((), jnp.uint32))
    assert text.count("tpu_custom_call") >= 2          # fwd and bwd


@pytest.mark.parametrize("n,hd,window,s", [(4, 256, 0, 16384),
                                           (4, 256, 0, 8192),
                                           (6, 128, 0, 8192)])
def test_gqa_prefill_attention_at_the_long_buckets(one_chip, tpu_mode, n, hd,
                                                   window, s):
    """The prompt buckets the op's shape rule hands the kernel: the two
    longest of the Qwen3-Next cell (4 + 1 heads of 256) and the full
    layers' longest of the Trinity cell (6 + 1 heads of 128), bfloat16
    products, through the op on the layer's own float32 [1, S, n x hd]
    arrays: the trainer's forward kernel with a float32 output, no loop
    of XLA products and no [.., 512, S] float32 scores."""
    import math

    from paddle_tpu.ops import llm_ops

    def attend(q, k, v):
        return llm_ops.gqa_prefill_attention_op(
            {"Q": [q], "K": [k], "V": [v]},
            {"num_heads": n, "num_kv_heads": 1, "head_dim": hd,
             "window": window, "compute_dtype": "bfloat16",
             "block_q": 512})["Out"]

    text = _compile(attend, one_chip, ((1, s, n * hd), F32),
                    ((1, s, hd), F32), ((1, s, hd), F32))
    assert "flash_fwd_window" in text and " while(" not in text
    assert re.search(r"ROOT \S+ = \(?f32\[1,%d,%d\]" % (s, n * hd), text)
    held = re.findall(r"= f32\[([\d,]+)\]", text)
    assert all(math.prod(map(int, dims.split(","))) <= s * n * hd
               for dims in held), held


@pytest.mark.parametrize("window", [1024, 0], ids=["sliding", "full"])
def test_flash_window_fwd_bwd_at_the_mellum_share(one_chip, tpu_mode,
                                                  window):
    """2 x 8,192 tokens, 8 query heads of 128 on one K/V head, bfloat16:
    the three window / grouped-head kernels; a sliding layer's grid is 3
    K blocks a query block deep, the full layer's 16."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.flash_window import reach_of

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, num_heads=8,
                              num_kv_heads=1, window=window)
        return jnp.sum(out.astype(F32))

    text = _compile(jax.grad(loss, (0, 1, 2)), one_chip,
                    ((2, 8192, 1024), BF16), ((2, 8192, 128), BF16),
                    ((2, 8192, 128), BF16))
    for name in ("flash_fwd_window", "flash_bwd_window_dkv",
                 "flash_bwd_window_dq"):
        assert name in text
    assert reach_of(8192, 512, window) == (3 if window else 16)


def test_trained_routed_experts_at_the_mellum_share(one_chip, tpu_mode):
    """16,384 tokens, top-8 of 64 by softmax, experts 0-15 held at 2304 x
    896, forward and the hand-written backward: the forward's grouped
    kernel once a branch (the backward's trace of it is dead code and
    gone), the backward's eight products the two `grouped_swiglu_bwd`
    kernels a branch (40,960 rows in tiles of 512 with an expert's
    three matrices whole in VMEM) and no ragged product, the combine the
    `routed_combine` kernel forward and backward a branch and no scatter
    of `[16384, 2304]`, the sorted rows the `routed_spread` kernel (the
    forward's `x`, the backward's `x` again and `dout` with its weighed
    copy, a branch) and no gather of `[40960, 2304]`. The instruction
    names are the kernels' names, which `trace_reduce.op_family` prints
    and `routed_experts_train_roofline` sums by their first letters."""
    from benchmark.trace_reduce import op_family
    from paddle_tpu.parallel.moe import routed_experts_share

    def loss(x, wr, w1, w3, w2):
        out, _counts = routed_experts_share(
            x, wr, jnp.zeros((64,), F32), w1, w3, w2, top_k=8, held_lo=0,
            score_func="softmax", trainable=True)
        return jnp.sum(out)

    text = _compile(jax.value_and_grad(loss, (0, 1, 2, 3, 4)), one_chip,
                    ((16384, 2304), F32), ((2304, 64), BF16),
                    ((16, 2304, 896), BF16), ((16, 2304, 896), BF16),
                    ((16, 896, 2304), BF16))
    assert "ragged-dot" not in text and "f32[16,2304,896]" in text
    # the leading rows' branch and the chunked one
    calls = [op_family(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == ["grouped_swiglu"] * 2 \
        + ["grouped_swiglu_bwd_rows"] * 2 \
        + ["grouped_swiglu_bwd_weights"] * 2 + ["routed_combine"] * 4 \
        + ["routed_spread"] * 6
    assert not re.search(r"= f32\[16384,2304\]\S* scatter\(", text)
    assert not re.search(r"= \w+\[40960,2304\]\S* gather\(", text)


@pytest.mark.parametrize("t,n,h,e", [(16384, 40960, 2304, 16),
                                     (16384, 81984, 2048, 128),
                                     (512, 2624, 2048, 128),
                                     (4096, 2112, 7168, 12),
                                     (4096, 8256, 3072, 32)])
def test_routed_combine_at_the_four_cells_widths(one_chip, tpu_mode, t, n,
                                                 h, e):
    """Mellum's leading rows, Qwen3-Next's largest and smallest prefill
    buckets, Kimi's and Trinity's 4,096 buckets: the two halves of the
    staging buffer and the output tile fit the kernel's VMEM limit, the
    steps' block numbers fit the scalar memory, the transpose of the
    staged tokens and the per-piece copies lower."""
    from paddle_tpu.ops.pallas.routed_combine import routed_combine

    text = _compile(lambda ys, r, w, s: routed_combine(ys, r, w, s, t),
                    one_chip, ((n, h), F32), ((n,), I32), ((n,), F32),
                    ((e,), I32))
    assert "routed_combine" in text and "scatter(" not in text


@pytest.mark.parametrize("t,n,h,e", [(16384, 40960, 2304, 16),
                                     (16384, 81984, 2048, 128),
                                     (512, 2624, 2048, 128),
                                     (4096, 2112, 7168, 12),
                                     (4096, 8256, 3072, 32)])
def test_routed_spread_at_the_four_cells_widths(one_chip, tpu_mode, t, n, h,
                                                e):
    """The combine's five shapes turned round, from the float32 tokens
    every routed layer holds: the source tile's two buffers, its
    bfloat16 parts, the staged rows' two halves and 2 E carried pieces fit
    the kernel's VMEM limit, the per-piece tables fit the scalar memory
    beside the steps' block numbers, and an 8-row piece of bfloat16 is a
    DMA and a dynamic slice. The kernel itself: the dispatcher hands it
    Mellum's runs alone (`RUN_PIECES`), plain and weighted (float32
    cotangent, two outputs), and no gather of `[40960, 2304]` is left."""
    from paddle_tpu.ops.pallas import routed_combine as rc
    from paddle_tpu.ops.pallas import routed_spread as rs

    shapes = (((t, h), F32), ((n,), I32), ((n,), F32), ((e,), I32))
    tile, piece, stage, lanes = rc._tiles(t, n, h)
    text = _compile(lambda x, r, w, s: rs._pallas_routed_spread(
        x, r, w, s, dtype=jnp.dtype(BF16), weighted=False, tile=tile,
        piece=piece, stage=stage, lanes=lanes, interpret=False),
        one_chip, *shapes)
    assert "routed_spread" in text
    if e == 16:
        for weighted in (False, True):
            text = _compile(
                lambda x, r, w, s: rs.routed_spread(x, r, w, s, BF16,
                                                    weighted),
                one_chip, *shapes)
            assert "routed_spread" in text
            assert not re.search(rf"= \w+\[{n},{h}\]\S* gather\(", text)


def test_step_sampler_at_the_xglm_vocabulary(one_chip):
    """The decode step's last stage (serving/sampling.py) over the b8 x
    256,008 logits of the serving cell: plain XLA, no temporaries beyond
    the logits' own size, tokens out."""
    from paddle_tpu.serving.sampling import sample_tokens

    b, v = 8, 256008
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((b, v), (b,), (b,))]
    compiled = jax.jit(sample_tokens).lower(*args).compile()
    assert compiled.out_info.shape == (b,)
    assert compiled.out_info.dtype == I32
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * b * v * 4
    assert "f64" not in compiled.as_text()


@pytest.mark.parametrize("kernel", ["draft_verify", "draft_next"])
def test_draft_tail_at_the_xing4_vocabulary(one_chip, tpu_mode, kernel):
    """The drafting step's tail (ops/pallas/draft_tail.py) at the serving
    cell's 64 slots x 131,072 entries: the logits reach the kernel as a
    bitcast of the head's own array (no copy, no reshape), q is read and
    written at the slot (no gather, no scatter, the state aliased through
    `draft_next`), and no running sum is a loop."""
    from paddle_tpu.ops.pallas import draft_tail as dt

    b, v = 64, 131072
    state = (dt.q_state_shape(b, v), F32)
    if kernel == "draft_verify":
        fn = dt.draft_verify
        shapes = (((2 * b, v), F32), state, ((b,), I32), ((b,), I32),
                  ((b,), jnp.bool_), ((b,), F32), ((b, 3), F32))
    else:
        fn = dt.draft_next
        shapes = (((b, v), F32), state, ((b,), I32), ((b,), F32),
                  ((b,), F32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=(1,) if kernel == "draft_next"
                       else ()).lower(*args).compile()
    text = compiled.as_text()
    assert kernel in text and "tpu_custom_call" in text
    assert " while(" not in text
    assert not re.search(r"= f32\S+ (gather|scatter|copy|reshape)\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    if kernel == "draft_next":
        assert mem.alias_size_in_bytes >= (b + 1) * v * 4


def test_sharded_step_compiles_for_four_chips(topo, tpu_mode):
    """The step jit partitions over a dp=2 x mp=2 mesh of the described
    chips: Mosaic kernels cannot be partitioned automatically, so the
    step must trace on the XLA lowerings (ops/pallas.auto_partitioned) —
    with collectives, without a custom call."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.parallel.mesh import set_mesh

    cfg = bert.ernie_large()
    cfg.num_hidden_layers, cfg.dtype = 1, "bfloat16"
    cfg.use_flash_attention = True
    main, startup, _, fetches = bert.build_pretraining_program(
        cfg, seq_len=512, optimizer_name="adamw",
        max_predictions_per_seq=80)
    exe, scope = pt.Executor(pt.CPUPlace()), pt.Scope()
    exe.run(startup, scope=scope, use_compiled=False)
    data = bert.synthetic_pretraining_batch(cfg, 8, 512,
                                            max_predictions_per_seq=80)
    feed_names = tuple(sorted(data))
    mesh = create_mesh({"dp": 2, "mp": 2}, devices=topo.devices)
    try:
        entry = exe._compile(
            main, main.global_block(), feed_names,
            (fetches["loss"].name,), scope, mesh, None,
            {n: True for n in feed_names})
    finally:
        set_mesh(None)

    def shape(v):
        v = np.asarray(v)
        return jax.ShapeDtypeStruct(
            v.shape, np.int32 if v.dtype == np.int64 else v.dtype)

    text = entry.jitted.lower(
        {n: shape(scope.find_var(n)) for n in entry.state_names},
        {n: shape(scope.find_var(n)) for n in entry.ro_names},
        {n: shape(data[n]) for n in feed_names},
        jax.ShapeDtypeStruct((), I32)).compile().as_text()
    assert "all-reduce" in text and "tpu_custom_call" not in text
