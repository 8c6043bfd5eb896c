"""Pallas serving kernels (tier-1 gate): int8 weight-only MXU GEMM +
paged cached-KV decode attention.

Contracts under test (ops/pallas/int8_gemm.py + paged_attention.py and
the wiring behind the ``int8_matmul`` / ``cached_kv_attention`` op
contracts):

* numpy-oracle OpTests for both kernels run in ``PT_PALLAS=interpret``
  (per-channel scales, bias/act epilogue variants, ragged K/N vs the
  tile shape; partially-filled pages, page-0 scratch masking,
  single-token vs multi-slot batches) — this module is in the conftest
  op-sweep set, so the programs also flow through the static verifier;
* ``PT_PALLAS=off`` takes the counted stock lowering
  (``pallas.*_fallbacks``) bitwise-identically to the pre-kernel path;
* jitted interpret-kernel output is BITWISE-identical to the jitted
  stock lowering in the single-block/single-chunk regime, and the
  multi-chunk online-softmax path matches within float tolerance with
  stale positions contributing exactly zero;
* DECODE ENGINE identity (the PR acceptance pin): generations under
  ``PT_PALLAS=interpret`` equal ``PT_PALLAS=off`` token for token —
  greedy + seeded sampling, fp32 + int8;
* fault injection at decode.step composes with the kernel path
  (per-request errors, zero leaked pages — tools/chaos_check.py
  --decode runs the CLI twin);
* the executor/decode compile caches key on kernels_fingerprint()
  (a PT_PALLAS flip RECOMPILES with cause "pallas_kernels"), and
  /v1-stats-visible dispatch counters land in the decode stats payload.
"""

import contextlib
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import telemetry
from paddle_tpu.core.flags import flag as _flag, set_flags

from op_test import OpTest


@contextlib.contextmanager
def _pallas(mode):
    old = os.environ.get("PT_PALLAS")
    os.environ["PT_PALLAS"] = mode
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("PT_PALLAS", None)
        else:
            os.environ["PT_PALLAS"] = old


def _counter(name):
    return int(telemetry.counter_get(name))


# ---------------------------------------------------------------------------
# numpy oracles
# ---------------------------------------------------------------------------

def _gemm_oracle(x, w8, scale, bias=None, act=None):
    out = (x.astype(np.float64) @ w8.astype(np.float64)) \
        * scale.astype(np.float64)
    if bias is not None:
        out = out + bias.astype(np.float64)
    if act == "relu":
        out = np.maximum(out, 0.0)
    return out.astype(np.float32)


def _paged_attn_oracle(q, k, v, pool_k, pool_v, table, pos, n, hd, scale):
    """cached_kv_attention in numpy: write the step K/V, then per-row
    masked softmax attention over the row's gathered pages."""
    pool_k, pool_v = pool_k.copy(), pool_v.copy()
    b, page = q.shape[0], pool_k.shape[1]
    mp = table.shape[1]
    for i in range(b):
        pool_k[table[i, pos[i] // page], pos[i] % page] = k[i]
        pool_v[table[i, pos[i] // page], pos[i] % page] = v[i]
    out = np.zeros((b, n * hd), np.float32)
    for i in range(b):
        ctx_k = pool_k[table[i]].reshape(mp * page, n, hd)
        ctx_v = pool_v[table[i]].reshape(mp * page, n, hd)
        qh = q[i].reshape(n, hd)
        s = np.einsum("nh,snh->ns", qh, ctx_k).astype(np.float64) * scale
        s[:, np.arange(mp * page) > pos[i]] = -1e9
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out[i] = np.einsum("ns,snh->nh", p, ctx_v).reshape(-1)
    return out, pool_k, pool_v


def _mk_paged_case(rng, b, n, hd, page, mp, npages, pos):
    kvdim = n * hd
    pool_k = rng.randn(npages, page, kvdim).astype(np.float32)
    pool_v = rng.randn(npages, page, kvdim).astype(np.float32)
    table = np.zeros((b, mp), np.int32)
    nxt = 1
    for i in range(b):
        need = pos[i] // page + 1
        table[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    assert nxt <= npages
    q = rng.randn(b, kvdim).astype(np.float32)
    k = rng.randn(b, kvdim).astype(np.float32)
    v = rng.randn(b, kvdim).astype(np.float32)
    return q, k, v, pool_k, pool_v, table, np.asarray(pos, np.int32)


# ---------------------------------------------------------------------------
# OpTests — interpret mode, under the conftest op-sweep (verifier on)
# ---------------------------------------------------------------------------

class _Int8MatmulCase(OpTest):
    op_type = "int8_matmul"
    shape = (6, 64, 128)          # (M, K, N)
    with_bias = False
    act = None
    lead = ()                     # extra leading dims on x

    def setup(self):
        rng = np.random.RandomState(
            sum(map(ord, type(self).__name__)) % 10000)
        m, k, n = self.shape
        x = rng.randn(*self.lead, m, k).astype(np.float32)
        w8 = rng.randint(-127, 128, (k, n)).astype(np.int8)
        scale = ((rng.rand(n) + 0.5) / 127.0).astype(np.float32)
        self.inputs = {"X": x, "Y": w8, "YScale": scale}
        self.attrs = {}
        bias = None
        if self.with_bias:
            bias = rng.randn(n).astype(np.float32)
            self.inputs["Bias"] = bias
        if self.act:
            self.attrs["act"] = self.act
        self.outputs = {"Out": _gemm_oracle(
            x.reshape(-1, k), w8, scale, bias, self.act).reshape(
                *self.lead, m, n)}

    def test_interpret_oracle(self):
        with _pallas("interpret"):
            before = _counter("pallas.int8_gemm_dispatches")
            self.check_output(atol=2e-4, rtol=2e-4)
            assert _counter("pallas.int8_gemm_dispatches") > before


class TestInt8MatmulPerChannel(_Int8MatmulCase):
    pass


class TestInt8MatmulBiasRelu(_Int8MatmulCase):
    # epilogue variants compose: bias-only and act-only are the same
    # _epilogue branches with the other leg skipped
    with_bias = True
    act = "relu"


class TestInt8MatmulRaggedTiledKN(_Int8MatmulCase):
    """Ragged M and K vs the tile shape, N ragged AND spanning two
    output tiles (200 → padded 256, sliced back), bias riding along."""
    shape = (5, 33, 200)
    with_bias = True


class TestInt8Matmul3D(_Int8MatmulCase):
    """The prefill programs feed [B, S, d] activations."""
    shape = (7, 16, 24)
    lead = (2,)


class TestInt8MatmulStaticQuantPreserved(OpTest):
    """The PTQ static-quant mode (act_scale attr) is untouched by the
    weight-only kernel wiring."""
    op_type = "int8_matmul"

    def setup(self):
        rng = np.random.RandomState(11)
        x = rng.randn(4, 32).astype(np.float32)
        w8 = rng.randint(-127, 128, (32, 16)).astype(np.int8)
        scale = ((rng.rand(16) + 0.5) / 127.0).astype(np.float32)
        act_scale = float(np.abs(x).max())
        sx = act_scale / 127.0
        xq = np.clip(np.round(x / sx), -127, 127).astype(np.int8)
        out = (xq.astype(np.int64) @ w8.astype(np.int64)).astype(
            np.float32) * sx * scale
        self.inputs = {"X": x, "Y": w8, "YScale": scale}
        self.attrs = {"act_scale": act_scale}
        self.outputs = {"Out": out}

    def test_interpret_oracle(self):
        with _pallas("interpret"):
            self.check_output(atol=1e-4, rtol=1e-4)


class _PagedAttnCase(OpTest):
    op_type = "cached_kv_attention"
    n, hd, page, mp, npages = 4, 8, 8, 4, 16
    b = 3
    pos = (0, 11, 27)              # page-partial fills on purpose

    def setup(self):
        rng = np.random.RandomState(23)
        n, hd = self.n, self.hd
        q, k, v, pool_k, pool_v, table, pos = _mk_paged_case(
            rng, self.b, n, hd, self.page, self.mp, self.npages,
            list(self.pos))
        scale = hd ** -0.5
        out, pk, pv = _paged_attn_oracle(q, k, v, pool_k, pool_v, table,
                                         pos, n, hd, scale)
        self.inputs = {"Q": q, "K": k, "V": v, "PoolK": pool_k,
                       "PoolV": pool_v, "PageTable": table,
                       "Positions": pos}
        self.attrs = {"num_heads": n, "head_dim": hd, "scale": scale}
        self.outputs = {"Out": out, "PoolKOut": pk, "PoolVOut": pv}

    def expect_from(self, pool_k, pool_v):
        """The oracle over the case's inputs as they stand now, with these
        pools in place of the fed ones."""
        return _paged_attn_oracle(
            self.inputs["Q"], self.inputs["K"], self.inputs["V"], pool_k,
            pool_v, self.inputs["PageTable"], self.inputs["Positions"],
            self.n, self.hd, self.attrs["scale"])

    def test_interpret_oracle(self):
        with _pallas("interpret"):
            before = _counter("pallas.paged_attn_dispatches")
            self.check_output(atol=2e-5, rtol=2e-5)
            assert _counter("pallas.paged_attn_dispatches") > before


class TestPagedAttnPartialPages(_PagedAttnCase):
    pass


class TestPagedAttnSingleToken(_PagedAttnCase):
    """B=1 at position 0 — the first decode step after a 1-token
    prompt."""
    b, pos = 1, (0,)


class TestPagedAttnScratchPageMasked(_PagedAttnCase):
    """An empty slot (all-zero page table) writes to the reserved
    scratch page 0 and attends only over it — the oracle covers that
    row too, proving the write can't corrupt live pages and the row's
    output ignores every stale pool value."""

    def setup(self):
        super().setup()
        # row 0 becomes an empty slot: zero table, position 0
        self.inputs["PageTable"][0] = 0
        self.inputs["Positions"][0] = 0
        # poison every unused pool slot: masked positions must not leak
        q, k, v = (self.inputs[s] for s in ("Q", "K", "V"))
        pool_k = self.inputs["PoolK"]
        pool_v = self.inputs["PoolV"]
        pool_k[8:] = 1e6
        pool_v[8:] = 1e6
        out, pk, pv = _paged_attn_oracle(
            q, k, v, pool_k, pool_v, self.inputs["PageTable"],
            self.inputs["Positions"], self.n, self.hd,
            self.attrs["scale"])
        self.outputs = {"Out": out, "PoolKOut": pk, "PoolVOut": pv}


class TestPagedAttnChunkedOnlineSoftmax(_PagedAttnCase):
    """FLAGS_pallas_kv_chunk_tokens forced below the context length:
    the online-softmax accumulation path, oracle-checked — with every
    stale position poisoned, so a single non-zero masked contribution
    in ANY chunk would blow the comparison (exact-zero masking)."""
    n, hd, page, mp, npages = 2, 8, 8, 4, 12
    b, pos = 2, (20, 30)

    def setup(self):
        super().setup()
        table = self.inputs["PageTable"]
        pos = self.inputs["Positions"]
        pool_v = self.inputs["PoolV"]
        for i in range(self.b):
            for s in range(int(pos[i]) + 1, self.mp * self.page):
                pool_v[table[i, s // self.page], s % self.page] = 1e6
        out, pk, pv = _paged_attn_oracle(
            self.inputs["Q"], self.inputs["K"], self.inputs["V"],
            self.inputs["PoolK"], pool_v, table, pos, self.n, self.hd,
            self.attrs["scale"])
        self.outputs = {"Out": out, "PoolKOut": pk, "PoolVOut": pv}

    def test_interpret_oracle(self):
        from paddle_tpu.core import flags as _flags

        # 2 pages/chunk (typed scoped override, exact restore)
        with _flags.overrides(pallas_kv_chunk_tokens=16):
            with _pallas("interpret"):
                self.check_output(atol=2e-5, rtol=2e-5)


class _PagedAttnWalkCase(_PagedAttnCase):
    """The streamed path's page walk: the chunk forced to 2 pages under a
    table of 8, so a row runs 1 to 4 chunks by its ``pos`` and copies of
    its last chunk only the pages it holds."""
    n, hd, page, mp, npages = 2, 8, 8, 8, 24
    chunk_tokens = 16

    def test_interpret_oracle(self):
        from paddle_tpu.core import flags as _flags

        with _flags.overrides(pallas_kv_chunk_tokens=self.chunk_tokens):
            super().test_interpret_oracle()


class TestPagedAttnWalkFirstMiddleLastChunk(_PagedAttnWalkCase):
    """Rows ending in the first, a middle and the last chunk, in one
    batch: each half of the scratch holds another row's longer chunk when
    a shorter one lands in it."""
    b, pos = 3, (63, 5, 30)


class TestPagedAttnWalkEmptySlotBesideFullRow(_PagedAttnWalkCase):
    """An empty slot (zero table, position 0: one page read) after a row
    that fills every chunk."""
    b, pos = 2, (63, 0)

    def setup(self):
        super().setup()
        self.inputs["PageTable"][1] = 0
        out, pk, pv = self.expect_from(self.inputs["PoolK"],
                                       self.inputs["PoolV"])
        self.outputs = {"Out": out, "PoolKOut": pk, "PoolVOut": pv}


class TestPagedAttnWalkLastChunkOnePage(_PagedAttnWalkCase):
    """Rows whose last chunk holds one page of its two (5 and 3 held
    pages), the second at a page's first token."""
    b, pos = 2, (35, 16)


class _PagedAttnPoisonCase(_PagedAttnWalkCase):
    """What the walk may read and must cancel, or must never read: the
    oracle is computed on a pool whose such tokens are zero, the kernel
    runs on the poisoned pool, and its output equals, bit for bit, its own
    on the clean one."""
    b, pos = 3, (63, 5, 30)
    value = None

    def poisoned(self, table, pos):
        """bool [npages, page]: the pool's tokens to poison (a case may
        re-point the table's unheld tail too)."""
        raise NotImplementedError

    def setup(self):
        super().setup()
        mask = self.poisoned(self.inputs["PageTable"],
                             self.inputs["Positions"])[..., None]
        self.clean = [np.where(mask, np.float32(0), self.inputs[name])
                      for name in ("PoolK", "PoolV")]
        out, pk, pv = self.expect_from(*self.clean)
        for name in ("PoolK", "PoolV"):
            self.inputs[name] = np.where(mask, np.float32(self.value),
                                         self.inputs[name])
        # the pools pass through the op with the step's K/V written
        self.outputs = {
            "Out": out,
            "PoolKOut": np.where(mask, np.float32(self.value), pk),
            "PoolVOut": np.where(mask, np.float32(self.value), pv)}

    def test_bitwise_against_a_clean_pool(self):
        import jax

        from paddle_tpu.core import flags as _flags
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention

        self.setup()
        table, pos = self.inputs["PageTable"], self.inputs["Positions"]
        phys = table[np.arange(self.b), pos // self.page]

        def run(pool_k, pool_v):
            pool_k, pool_v = pool_k.copy(), pool_v.copy()
            pool_k[phys, pos % self.page] = self.inputs["K"]
            pool_v[phys, pos % self.page] = self.inputs["V"]
            return np.asarray(jax.jit(
                lambda *a: paged_decode_attention(
                    *a, num_heads=self.n, head_dim=self.hd,
                    scale=self.attrs["scale"]))(
                        self.inputs["Q"], pool_k, pool_v, table, pos))

        with _flags.overrides(pallas_kv_chunk_tokens=self.chunk_tokens):
            with _pallas("interpret"):
                got = run(self.inputs["PoolK"], self.inputs["PoolV"])
                want = run(*self.clean)
        assert np.isfinite(got).all()
        assert np.array_equal(got, want)


class TestPagedAttnWalkStaleTailIsCancelled(_PagedAttnPoisonCase):
    """The tokens past ``pos`` in a row's last, partly held page are 1e30:
    they are copied, masked before the softmax and multiplied to exact
    zero after it."""
    value = 1e30

    def poisoned(self, table, pos):
        mask = np.zeros((self.npages, self.page), bool)
        for i in range(self.b):
            mask[table[i, pos[i] // self.page], pos[i] % self.page + 1:] = \
                True
        return mask


class TestPagedAttnWalkUnheldPagesAreNeverRead(_PagedAttnPoisonCase):
    """Every page wholly past a row's ``pos`` is NaN, in the table (its
    tail points at NaN pages) and in the pool (page 0 and every unowned
    page): zero times NaN is NaN, so a walk that reads one fails, as the
    kernel that read the table's whole width did."""
    value = np.nan

    def poisoned(self, table, pos):
        mask = np.ones((self.npages, self.page), bool)
        for i in range(self.b):
            held = pos[i] // self.page + 1
            mask[table[i, :held]] = False
            table[i, held:] = self.npages - 1 - i
        return mask


# ---------------------------------------------------------------------------
# off-mode fallback counters + bitwise stock identity
# ---------------------------------------------------------------------------

class TestCountedFallbacks:
    def test_int8_gemm_off_is_counted_stock_bitwise(self):
        from paddle_tpu.ops.pallas.int8_gemm import (int8_weight_only_gemm,
                                                     stock_int8_gemm)
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        x = rng.randn(6, 48).astype(np.float32)
        w8 = rng.randint(-127, 128, (48, 64)).astype(np.int8)
        sc = ((rng.rand(64) + 0.5) / 127.0).astype(np.float32)
        b = rng.randn(64).astype(np.float32)
        with _pallas("off"):
            before = _counter("pallas.int8_gemm_fallbacks")
            got = np.asarray(int8_weight_only_gemm(x, w8, sc, bias=b,
                                                   act="relu"))
            assert _counter("pallas.int8_gemm_fallbacks") == before + 1
        want = np.asarray(stock_int8_gemm(
            jnp.asarray(x), jnp.asarray(w8), jnp.asarray(sc),
            jnp.asarray(b), "relu"))
        assert np.array_equal(got, want)

    def test_paged_attn_off_is_counted_stock_bitwise(self):
        """PT_PALLAS=off must produce byte-identical results to the
        pre-kernel einsum lowering (inlined here as the frozen
        reference)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention

        rng = np.random.RandomState(1)
        n, hd, page, mp = 4, 8, 8, 4
        q, k, v, pool_k, pool_v, table, pos = _mk_paged_case(
            rng, 3, n, hd, page, mp, 16, [3, 14, 30])
        scale = hd ** -0.5
        # the step write, shared by every route
        phys = table[np.arange(3), pos // page]
        pool_k[phys, pos % page] = k
        pool_v[phys, pos % page] = v

        def legacy(q, pool_k, pool_v, table, pos):
            b = q.shape[0]
            ctx_k = pool_k[table].reshape(b, mp * page, -1)
            ctx_v = pool_v[table].reshape(b, mp * page, -1)
            qh = q.reshape(b, n, hd)
            kh = ctx_k.reshape(b, mp * page, n, hd)
            vh = ctx_v.reshape(b, mp * page, n, hd)
            scores = jnp.einsum("bnh,bsnh->bns", qh, kh) * scale
            mask = jnp.arange(mp * page, dtype=jnp.int32)[None, None, :] \
                <= pos[:, None, None]
            scores = jnp.where(mask, scores, -1e9)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bns,bsnh->bnh", probs, vh).reshape(
                b, n * hd)

        with _pallas("off"):
            before = _counter("pallas.paged_attn_fallbacks")
            got = np.asarray(jax.jit(
                lambda *a: paged_decode_attention(
                    *a, num_heads=n, head_dim=hd, scale=scale))(
                        q, pool_k, pool_v, table, pos))
            assert _counter("pallas.paged_attn_fallbacks") == before + 1
        want = np.asarray(jax.jit(legacy)(q, pool_k, pool_v, table, pos))
        assert np.array_equal(got, want)


class TestInterpretBitwise:
    """Jitted interpret kernel == jitted stock lowering, bit for bit,
    in the single-block / single-chunk regime (the decode engine's)."""

    def test_int8_gemm_interpret_bitwise_vs_off(self):
        import functools

        import jax

        from paddle_tpu.ops.pallas.int8_gemm import int8_weight_only_gemm

        rng = np.random.RandomState(2)
        x = rng.randn(8, 64).astype(np.float32)
        w8 = rng.randint(-127, 128, (64, 128)).astype(np.int8)
        sc = ((rng.rand(128) + 0.5) / 127.0).astype(np.float32)
        b = rng.randn(128).astype(np.float32)
        with _pallas("off"):
            off = np.asarray(jax.jit(functools.partial(
                int8_weight_only_gemm, act="relu"))(x, w8, sc, b))
        with _pallas("interpret"):
            it = np.asarray(jax.jit(functools.partial(
                int8_weight_only_gemm, act="relu"))(x, w8, sc, b))
        assert np.array_equal(off, it)

    def test_paged_attn_interpret_bitwise_vs_off(self):
        import jax

        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention

        rng = np.random.RandomState(3)
        n, hd, page, mp = 4, 16, 16, 8
        q, k, v, pool_k, pool_v, table, pos = _mk_paged_case(
            rng, 4, n, hd, page, mp, 24, [0, 17, 63, 99])
        scale = hd ** -0.5
        phys = table[np.arange(4), pos // page]
        pool_k[phys, pos % page] = k
        pool_v[phys, pos % page] = v

        def run(mode):
            with _pallas(mode):
                # fresh closure per mode: jax shares trace caches across
                # jit wrappers of one function object, which would hand
                # the second mode the first mode's lowering
                return np.asarray(jax.jit(
                    lambda *a: paged_decode_attention(
                        *a, num_heads=n, head_dim=hd, scale=scale))(
                            q, pool_k, pool_v, table, pos))

        off, it = run("off"), run("interpret")
        assert np.array_equal(off, it)

# ---------------------------------------------------------------------------
# decode-engine identity: the PR acceptance gate
# ---------------------------------------------------------------------------

def _gen_all(mode, quant, prompts, seed=0):
    """One engine per (mode, quant): greedy AND seeded-sampled
    generations through the same engine (one compile pays for both
    sampling disciplines)."""
    from paddle_tpu.models.decoder_lm import DecoderLMConfig
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    with _pallas(mode):
        cfg = DecodeConfig(max_slots=4, page_size=16, kv_pages=24,
                           weight_quant=quant, prefill_buckets=[32])
        # small vocab/short max_seq/one layer keep the per-mode compiles
        # cheap; d_model/n_head stay at the kernel-relevant defaults and
        # the multi-layer kernel path is covered by the 2-layer chaos
        # engine below
        eng = demo_engine(cfg, model_cfg=DecoderLMConfig(
            vocab_size=128, max_seq_len=64, n_layers=1), seed=seed)
        eng.start()
        try:
            # all requests in flight at once (continuous batching):
            # continuous == sequential is already tier-1-pinned by
            # PR 12, so the interpret-vs-off comparison is unaffected
            # and the engine finishes in ~max_steps instead of Σsteps
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            reqs += [eng.submit(p, max_new_tokens=8, temperature=0.8,
                                seed=100 + i)
                     for i, p in enumerate(prompts)]
            return [np.asarray(r.result(timeout=120)) for r in reqs]
        finally:
            eng.close(drain=True, timeout=10)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(7)
    return [rng.randint(3, 200, rng.randint(3, 20)).astype(np.int32)
            for _ in range(3)]


@pytest.mark.serving
class TestDecodeInterpretIdentity:
    """The acceptance pin: PT_PALLAS=interpret decode output is
    bitwise-identical to PT_PALLAS=off — greedy + seeded sampling,
    fp32 + int8."""

    def test_fp32_greedy_and_sampled(self, prompts):
        off = _gen_all("off", "none", prompts)
        it = _gen_all("interpret", "none", prompts)
        assert all(np.array_equal(a, b) for a, b in zip(off, it))

    def test_int8_greedy_and_sampled(self, prompts):
        off = _gen_all("off", "int8", prompts)
        it = _gen_all("interpret", "int8", prompts)
        assert all(np.array_equal(a, b) for a, b in zip(off, it))


# ---------------------------------------------------------------------------
# chaos composition + cache keys + stats surfaces
# ---------------------------------------------------------------------------

@pytest.mark.serving
@pytest.mark.chaos
def test_step_fault_stats_and_capture_on_kernel_path(scope):
    """One interpret-mode engine session proving three contracts:
    decode.step fault injection composes with the kernel path (typed
    per-request errors, pages back to baseline, engine stays live);
    the /v1/stats decode payload exposes the pallas dispatch counters +
    kernels fingerprint; and the cost capture keys on the kernel
    variant (a second off-mode engine lands under NEW keys)."""
    from paddle_tpu.core import costmodel, faults
    from paddle_tpu.models.decoder_lm import DecoderLMConfig
    from paddle_tpu.serving.decode import DecodeConfig, demo_engine

    set_flags({"cost_capture": "cost"})
    costmodel.reset()
    cfg = DecoderLMConfig(vocab_size=128, d_model=32, n_head=2,
                          n_layers=2, max_seq_len=32, d_inner=64)
    dcfg = dict(max_slots=4, page_size=8, kv_pages=20,
                prefill_buckets=[16])
    try:
        with _pallas("interpret"):
            eng = demo_engine(DecodeConfig(**dcfg), model_cfg=cfg)
            eng.start(warmup=True)
            baseline = eng.pool.free_pages()
            faults.configure("decode.step:@2")
            try:
                rng = np.random.RandomState(5)
                reqs = [eng.submit(
                    rng.randint(3, 120, 5).astype(np.int32),
                    max_new_tokens=6) for _ in range(6)]
                errors = 0
                for r in reqs:
                    try:
                        r.result(timeout=60)
                    except Exception:
                        errors += 1
                assert errors >= 1   # the injected step fault surfaced
                faults.configure("")
                # engine still live on the kernel path after the fault
                out = eng.generate(np.asarray([5, 6, 7], np.int32),
                                   max_new_tokens=4, timeout=60)
                assert np.asarray(out).size == 4
                assert eng.pool.free_pages() == baseline
                stats = eng.stats()
            finally:
                faults.configure("")
                eng.close(drain=True, timeout=10)
        assert stats["pallas"]["kernels"].startswith("interpret")
        assert stats["pallas"].get("paged_attn_dispatches", 0) > 0
        kern_keys = {r.key_id for r in costmodel.programs()
                     if r.kind == "decode"}
        assert kern_keys
        # an off-mode engine's captures land under NEW keys: the pallas
        # fingerprint is part of the capture identity
        with _pallas("off"):
            eng = demo_engine(DecodeConfig(**dcfg), model_cfg=cfg)
            eng.start()
            eng.generate(np.asarray([3, 4], np.int32), max_new_tokens=2,
                         timeout=60)
            off_stats = eng.stats()
            eng.close(drain=True, timeout=10)
        assert off_stats["pallas"]["kernels"].startswith("off")
        off_keys = {r.key_id for r in costmodel.programs()
                    if r.kind == "decode"} - kern_keys
        assert off_keys
    finally:
        set_flags({"cost_capture": "auto"})
        costmodel.reset()


def test_executor_recompiles_on_kernel_mode_flip(scope, tmp_path):
    """kernels_fingerprint() is a compile-cache key component: flipping
    PT_PALLAS between runs of one program RECOMPILES with the cause
    named — reusing the other mode's lowering would silently serve
    stale kernels (and blur per-variant cost capture)."""
    import json

    log = tmp_path / "run.jsonl"
    telemetry.configure(str(log))
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.static_data("x", [4, 8], "float32")
            y = layers.relu(x)
        exe = pt.Executor()
        feed = {"x": np.ones((4, 8), np.float32)}
        before = _counter("executor.compiles")
        with _pallas("off"):
            exe.run(main, feed=feed, fetch_list=[y.name], scope=scope)
        with _pallas("interpret"):
            exe.run(main, feed=feed, fetch_list=[y.name], scope=scope)
        assert _counter("executor.compiles") == before + 2
        telemetry.flush_sink()
        with open(log) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        compiles = [r for r in recs if r.get("kind") == "compile"
                    and r.get("name") == "executor"]
        assert len(compiles) == 2
        assert compiles[1]["attrs"]["cause"] == "pallas_kernels"
        assert compiles[1]["attrs"]["pallas_kernels"].startswith(
            "interpret|")
    finally:
        telemetry.configure(None)


