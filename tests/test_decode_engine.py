"""Generative decode engine tests (paddle_tpu/serving/decode.py +
kv_cache.py + models/decoder_lm.py).

Contracts under test:
* continuous-batched generation is BITWISE-identical to sequential
  one-request-at-a-time decode — greedy and temperature-sampled with
  pinned per-request RNG — because the step program runs at fixed
  slot-array shapes and chooses each row's token from that row alone;
* the KV page pool's alloc/free accounting is exact under admit/retire
  churn (no double allocation, no leak, high-water tracked) and returns
  to baseline after every request resolves;
* a request whose worst-case page need can never fit is refused at
  submit with typed KVCacheExhaustedError (admission, not an OOM), and
  the pool's bytes are visible in the HBM ledger and /v1/stats;
* per-request deadlines are enforced at STEP granularity — an expired
  generation retires mid-flight with DeadlineExceededError and frees
  its pages without draining the batch;
* int8 weight-only serving is a config flip with the same bitwise
  continuous-vs-sequential guarantee;
* injected decode.step faults surface as per-request errors and the
  engine keeps serving (never a wedged queue);
* the HTTP front end exposes /v1/generate, decode stats and the
  pt_decode_* / pt_mem_serving_kv_* live metrics.
"""

import json
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.serving

CFG_KW = dict(vocab_size=97, d_model=32, n_head=2, n_layers=2,
              d_inner=64, max_seq_len=32)
POOL_KW = dict(max_slots=4, page_size=4, kv_pages=28, prefill_buckets=[8])


def _model_cfg(**over):
    from paddle_tpu.models.decoder_lm import DecoderLMConfig

    return DecoderLMConfig(**{**CFG_KW, **over})


@pytest.fixture(scope="module")
def lm_params():
    from paddle_tpu.models.decoder_lm import decoder_lm_params

    return decoder_lm_params(_model_cfg(), seed=0)


@pytest.fixture(scope="module")
def workload():
    rng = np.random.RandomState(7)
    prompts = [rng.randint(3, 96, rng.randint(2, 8)).astype(np.int32)
               for _ in range(6)]
    max_news = [5, 9, 4, 12, 7, 6]
    return prompts, max_news


@pytest.fixture(scope="module")
def engines(lm_params):
    """(continuous, sequential-use) engine pair sharing one param set —
    module-scoped so every test reuses the same jit entries."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cont = DecodeEngine(_model_cfg(), lm_params,
                        DecodeConfig(**POOL_KW)).start()
    seq = DecodeEngine(_model_cfg(), lm_params,
                       DecodeConfig(**POOL_KW)).start()
    yield cont, seq
    cont.close(drain=True, timeout=30)
    seq.close(drain=True, timeout=30)


class TestBitwiseIdentity:
    def test_greedy_continuous_equals_sequential(self, engines, workload):
        """All requests submitted at once (continuous batching across
        admit/retire churn) vs the same requests run one at a time —
        generated token ids must be bitwise identical."""
        from paddle_tpu.core import telemetry

        cont, seq = engines
        prompts, max_news = workload
        steps_before = telemetry.counter_get("decode.steps")
        reqs = [cont.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, max_news)]
        got = [r.result(timeout=120) for r in reqs]
        want = [seq.generate(p, max_new_tokens=m, timeout=120)
                for p, m in zip(prompts, max_news)]
        for i, (g, w) in enumerate(zip(got, want)):
            assert np.array_equal(g, w), \
                f"request {i}: continuous-batched decode diverged from " \
                f"sequential decode"
        # the continuous run actually batched: fewer steps than the
        # total token count (sequential pays one step per token)
        cont_tokens = sum(len(g) for g in got)
        assert telemetry.counter_get("decode.steps") - steps_before \
            < 2 * cont_tokens
        # slot churn left zero pages behind in BOTH pools
        for eng in engines:
            s = eng.pool.stats()
            assert s["pages_used"] == 0
            assert s["pages_free"] == s["pages_total"]
            assert s["high_water_pages"] > 0

    def test_sampled_pinned_rng_equals_sequential(self, engines, workload):
        """Temperature sampling with per-request seeds: token choice is
        a function of the row's own logits, temperature and the request's
        own RNG stream (drawn on the host, one uniform a token), so
        scheduling must not perturb it either."""
        cont, seq = engines
        prompts, max_news = workload
        reqs = [cont.submit(p, max_new_tokens=m, temperature=0.8,
                            seed=100 + i)
                for i, (p, m) in enumerate(zip(prompts, max_news))]
        got = [r.result(timeout=120) for r in reqs]
        want = [seq.generate(p, max_new_tokens=m, temperature=0.8,
                             seed=100 + i, timeout=120)
                for i, (p, m) in enumerate(zip(prompts, max_news))]
        for g, w in zip(got, want):
            assert np.array_equal(g, w)

    def test_sampled_without_seed_rejected(self, engines):
        with pytest.raises(Exception) as ei:
            cont, _ = engines
            cont.generate(np.array([5, 6], np.int32), max_new_tokens=2,
                          temperature=0.7, timeout=30)
        assert "seed" in str(ei.value)


class TestCachedKVOps:
    """Numpy-oracle OpTests for the paged-cache ops
    (ops/attention_ops.py) — the registry-gate coverage for
    cached_kv_attention and kv_cache_write."""

    def test_kv_cache_write_places_tokens_and_masks_padding(self):
        from paddle_tpu.core.registry import lookup

        rng = np.random.RandomState(3)
        B, S, D, N, P, MP = 2, 6, 8, 10, 4, 3
        k = rng.randn(B, S, D).astype(np.float32)
        v = rng.randn(B, S, D).astype(np.float32)
        pool_k = rng.randn(N, P, D).astype(np.float32)
        pool_v = rng.randn(N, P, D).astype(np.float32)
        table = np.array([[3, 4, 0], [7, 2, 0]], np.int32)
        lengths = np.array([6, 3], np.int32)
        out = lookup("kv_cache_write").forward(
            {"K": [k], "V": [v], "PoolK": [pool_k], "PoolV": [pool_v],
             "PageTable": [table], "Lengths": [lengths]}, {})
        got_k = np.asarray(out["PoolKOut"])
        # every valid (b, s) landed at (table[b, s//P], s%P)
        for b in range(B):
            for s in range(int(lengths[b])):
                np.testing.assert_array_equal(
                    got_k[table[b, s // P], s % P], k[b, s])
        # pages NOT owned by either row are untouched (masked prompt
        # tail goes to the reserved scratch page 0)
        for p in set(range(N)) - {0, 2, 3, 4, 7}:
            np.testing.assert_array_equal(got_k[p], pool_k[p])

    def test_cached_kv_attention_matches_numpy_oracle(self):
        from paddle_tpu.core.registry import lookup

        rng = np.random.RandomState(4)
        B, D, N, P, MP, nh = 2, 8, 9, 4, 2, 2
        hd = D // nh
        q = rng.randn(B, D).astype(np.float32)
        k = rng.randn(B, D).astype(np.float32)
        v = rng.randn(B, D).astype(np.float32)
        pool_k = rng.randn(N, P, D).astype(np.float32)
        pool_v = rng.randn(N, P, D).astype(np.float32)
        table = np.array([[1, 2], [5, 6]], np.int32)
        pos = np.array([5, 2], np.int32)     # contexts of 6 and 3 tokens
        out = lookup("cached_kv_attention").forward(
            {"Q": [q], "K": [k], "V": [v], "PoolK": [pool_k],
             "PoolV": [pool_v], "PageTable": [table], "Positions": [pos]},
            {"num_heads": nh, "head_dim": hd})
        got = np.asarray(out["Out"])
        new_pk = np.asarray(out["PoolKOut"])
        new_pv = np.asarray(out["PoolVOut"])
        # the new token's K landed at (table[b, pos//P], pos%P)
        for b in range(B):
            np.testing.assert_array_equal(
                new_pk[table[b, pos[b] // P], pos[b] % P], k[b])
        for b in range(B):
            ctx_k = new_pk[table[b]].reshape(MP * P, nh, hd)
            ctx_v = new_pv[table[b]].reshape(MP * P, nh, hd)
            qh = q[b].reshape(nh, hd)
            scores = np.einsum("nh,snh->ns", qh, ctx_k) / np.sqrt(hd)
            scores[:, pos[b] + 1:] = -1e9    # future + stale masked out
            e = np.exp(scores - scores.max(-1, keepdims=True))
            probs = e / e.sum(-1, keepdims=True)
            want = np.einsum("ns,snh->nh", probs, ctx_v).reshape(-1)
            np.testing.assert_allclose(got[b], want, rtol=2e-5,
                                       atol=2e-6)


class TestPagePool:
    def test_alloc_free_invariants_under_churn(self):
        """Free-list exactness: no double allocation, no loss, high
        water monotone, full return to baseline."""
        from paddle_tpu.serving import KVPagePool

        pool = KVPagePool(n_layers=2, num_pages=17, page_size=4,
                          kv_dim=32)
        assert pool.capacity_pages == 16
        rng = np.random.RandomState(0)
        held = []
        for _ in range(200):
            if held and rng.rand() < 0.5:
                pool.free(held.pop(rng.randint(len(held))))
            else:
                got = pool.try_alloc(int(rng.randint(1, 4)))
                if got:
                    held.append(got)
            flat = [p for h in held for p in h]
            assert len(flat) == len(set(flat)), "page double-allocated"
            assert 0 not in flat, "reserved scratch page handed out"
            assert pool.free_pages() + len(flat) == 16
        for h in held:
            pool.free(h)
        s = pool.stats()
        assert s["pages_free"] == 16 and s["pages_used"] == 0
        assert 0 < s["high_water_pages"] <= 16
        assert s["high_water_bytes"] >= s["used_bytes"]

    def test_double_free_raises(self):
        from paddle_tpu.serving import KVPagePool

        pool = KVPagePool(n_layers=1, num_pages=4, page_size=2, kv_dim=8)
        pages = pool.try_alloc(2)
        pool.free(pages)
        with pytest.raises(AssertionError):
            pool.free(pages)

    def test_pool_gauges_booked(self):
        from paddle_tpu.core import telemetry
        from paddle_tpu.serving import KVPagePool

        pool = KVPagePool(n_layers=2, num_pages=9, page_size=4, kv_dim=16)
        g = telemetry.gauges()
        assert g["mem.serving.kv_pool_bytes"] == pool.pool_bytes
        assert pool.pool_bytes == 2 * 2 * 9 * 4 * 16 * 4


class TestAdmission:
    def test_over_budget_request_refused_typed(self, lm_params):
        """A request that could NEVER fit the pool gets a typed refusal
        at submit — and the engine keeps serving small requests."""
        from paddle_tpu.core import costmodel, telemetry
        from paddle_tpu.serving import (DecodeConfig, DecodeEngine,
                                        KVCacheExhaustedError)

        eng = DecodeEngine(_model_cfg(), lm_params,
                           DecodeConfig(max_slots=2, page_size=4,
                                        kv_pages=4, prefill_buckets=[8]))
        try:
            before = telemetry.counter_get("decode.kv_refusals")
            with pytest.raises(KVCacheExhaustedError) as ei:
                eng.submit(np.arange(3, 11, dtype=np.int32),
                           max_new_tokens=12)   # 20 tokens -> 5 > 3 pages
            assert "KV pages" in str(ei.value)
            assert telemetry.counter_get("decode.kv_refusals") == before + 1
            # the pool's preallocation is on the HBM ledger
            led = costmodel.ledger()
            assert led["serving_kv_pool_bytes"] == eng.pool.pool_bytes
            assert led["total_bytes"] >= eng.pool.pool_bytes
            # a request that fits still serves (engine not wedged)
            eng.start()
            out = eng.generate(np.array([5, 6, 7], np.int32),
                               max_new_tokens=3, timeout=60)
            assert len(out) == 3
        finally:
            eng.close(drain=True, timeout=30)

    def test_queue_backpressure_typed(self, lm_params):
        """Bounded admission: the decode queue rejects past max depth
        with ServerOverloadedError (decode.rejects counts it)."""
        from paddle_tpu.serving import (DecodeConfig, DecodeEngine,
                                        ServerOverloadedError)

        eng = DecodeEngine(_model_cfg(), lm_params,
                           DecodeConfig(max_slots=2, page_size=4,
                                        kv_pages=28, max_queue_depth=2,
                                        prefill_buckets=[8]))
        # never started: submissions sit in the queue
        p = np.array([5, 6], np.int32)
        eng.submit(p, max_new_tokens=2)
        eng.submit(p, max_new_tokens=2)
        with pytest.raises(ServerOverloadedError):
            eng.submit(p, max_new_tokens=2)
        eng.close(drain=False)

    def test_model_length_cap_is_value_error(self, engines):
        cont, _ = engines
        with pytest.raises(ValueError) as ei:
            cont.submit(np.arange(3, 23, dtype=np.int32),
                        max_new_tokens=30)   # 50 > max_seq_len 32
        assert "max_seq_len" in str(ei.value)


class TestDeadline:
    def test_deadline_expires_mid_generation(self, lm_params):
        """A generation whose deadline elapses mid-flight retires at a
        step boundary with DeadlineExceededError and frees its pages —
        without draining the rest of the batch."""
        from paddle_tpu.core import telemetry
        from paddle_tpu.models.decoder_lm import decoder_lm_params
        from paddle_tpu.serving import (DeadlineExceededError,
                                        DecodeConfig, DecodeEngine)

        cfg = _model_cfg(max_seq_len=128)
        eng = DecodeEngine(cfg, decoder_lm_params(cfg, seed=0),
                           DecodeConfig(max_slots=2, page_size=4,
                                        kv_pages=36, prefill_buckets=[8]))
        eng.start()
        try:
            # warm every program OUTSIDE the deadline window
            eng.generate(np.array([5, 6, 7], np.int32), max_new_tokens=2,
                         timeout=60)
            before = telemetry.counter_get("decode.deadline_expired")
            req = eng.submit(np.array([5, 6, 7, 8], np.int32),
                             max_new_tokens=120, deadline_ms=10)
            with pytest.raises(DeadlineExceededError) as ei:
                req.result(timeout=60)
            # step-granularity expiry, not queue-side: the generation
            # was already producing tokens
            assert "generation" in str(ei.value)
            assert len(req.tokens) > 0
            assert telemetry.counter_get("decode.deadline_expired") \
                == before + 1
            s = eng.pool.stats()
            assert s["pages_used"] == 0, "expired request leaked pages"
        finally:
            eng.close(drain=True, timeout=30)


class TestInt8WeightOnly:
    def test_int8_config_bitwise_continuous_vs_sequential(self, lm_params):
        """int8 weight-only serving is a config flip with the same
        continuous-vs-sequential bitwise guarantee; weights really are
        stored int8."""
        from paddle_tpu.serving import DecodeConfig, DecodeEngine

        kw = dict(max_slots=2, page_size=4, kv_pages=20,
                  prefill_buckets=[8], weight_quant="int8")
        cont = DecodeEngine(_model_cfg(), lm_params,
                            DecodeConfig(**kw)).start()
        seq = DecodeEngine(_model_cfg(), lm_params,
                           DecodeConfig(**kw)).start()
        try:
            i8 = [n for n, v in cont._params.items()
                  if n.endswith("_w_i8")]
            assert len(i8) == 2 * 6   # every dense weight, both layers
            assert all(str(cont._params[n].dtype) == "int8" for n in i8)
            prompts = [np.array([5, 6, 7], np.int32),
                       np.array([9, 10, 11, 12], np.int32),
                       np.array([20, 21], np.int32)]
            reqs = [cont.submit(p, max_new_tokens=6) for p in prompts]
            got = [r.result(timeout=120) for r in reqs]
            want = [seq.generate(p, max_new_tokens=6, timeout=120)
                    for p in prompts]
            for g, w in zip(got, want):
                assert np.array_equal(g, w)
        finally:
            cont.close(drain=True, timeout=30)
            seq.close(drain=True, timeout=30)


@pytest.mark.chaos
class TestChaos:
    def test_step_fault_is_per_request_error_not_wedge(self, engines):
        """An injected decode.step fault fails the in-flight generations
        individually, frees their pages, and the engine keeps serving."""
        from paddle_tpu.core import faults, telemetry
        from paddle_tpu.serving import ServingError

        cont, _ = engines
        faults.configure("decode.step:@1")
        try:
            before = telemetry.counter_get("decode.errors")
            reqs = [cont.submit(np.array([5, 6, 7], np.int32),
                                max_new_tokens=6) for _ in range(2)]
            errors = 0
            for r in reqs:
                try:
                    r.result(timeout=60)
                except ServingError:
                    errors += 1
            assert errors >= 1
            assert telemetry.counter_get("decode.errors") > before
        finally:
            faults.configure("")
        # queue not wedged, pool back to baseline
        out = cont.generate(np.array([5, 6, 7], np.int32),
                            max_new_tokens=3, timeout=60)
        assert len(out) == 3
        assert cont.pool.stats()["pages_used"] == 0


class TestHTTP:
    def test_generate_stats_and_live_metrics(self, engines):
        """POST /v1/generate round-trips; /v1/stats carries the decode
        section + KV pool; /metrics exposes pt_decode_* and the
        mem.serving.kv_* gauges; /healthz is ready."""
        from paddle_tpu.serving import ServingHTTPServer

        cont, _ = engines
        srv = ServingHTTPServer(None, decode_engine=cont).start()
        try:
            body = json.dumps({"prompt_ids": [5, 6, 7],
                               "max_new_tokens": 4}).encode()
            doc = json.loads(urllib.request.urlopen(urllib.request.Request(
                srv.url + "/v1/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30).read())
            assert len(doc["tokens"]) == doc["num_tokens"] == 4
            assert doc["ttft_ms"] is not None
            want = cont.generate(np.array([5, 6, 7], np.int32),
                                 max_new_tokens=4, timeout=60)
            assert np.array_equal(np.asarray(doc["tokens"], np.int32),
                                  want)
            stats = json.loads(urllib.request.urlopen(
                srv.url + "/v1/stats", timeout=10).read())
            dc = stats["decode"]
            assert dc["kv_cache"]["pool_bytes"] == cont.pool.pool_bytes
            assert dc["tokens"] > 0 and dc["retired"] > 0
            mtx = urllib.request.urlopen(srv.url + "/metrics",
                                         timeout=10).read().decode()
            assert "pt_decode_tokens_total" in mtx
            assert "pt_mem_serving_kv_pool_bytes" in mtx
            hz = urllib.request.urlopen(srv.url + "/healthz", timeout=10)
            assert hz.status == 200
        finally:
            srv.shutdown()

    def test_generate_error_mapping(self, lm_params):
        """KV over-budget → HTTP 429 with the typed name; bad body →
        400."""
        from paddle_tpu.serving import (DecodeConfig, DecodeEngine,
                                        ServingHTTPServer)

        eng = DecodeEngine(_model_cfg(), lm_params,
                           DecodeConfig(max_slots=2, page_size=4,
                                        kv_pages=4, prefill_buckets=[8]))
        srv = ServingHTTPServer(None, decode_engine=eng).start()
        try:
            body = json.dumps({"prompt_ids": list(range(3, 11)),
                               "max_new_tokens": 12}).encode()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url + "/v1/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=30)
            assert ei.value.code == 429
            payload = json.loads(ei.value.read())
            assert payload["error_type"] == "KVCacheExhaustedError"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url + "/v1/generate", data=b"{}",
                    headers={"Content-Type": "application/json"}),
                    timeout=30)
            assert ei.value.code == 400
        finally:
            srv.shutdown()
            eng.close(drain=False)


class TestReporting:
    def test_perf_report_decode_section(self, tmp_path):
        """A run log from a decode engine renders the Decode section
        with tokens, occupancy and the KV pool lines."""
        import io as _io

        from tools.perf_report import render, summarize_log

        recs = [
            {"ts": 1.0, "kind": "counter", "name": "decode.requests",
             "value": 4, "attrs": {"delta": 4}},
            {"ts": 1.0, "kind": "counter", "name": "decode.prefills",
             "value": 4, "attrs": {"delta": 4}},
            {"ts": 1.1, "kind": "counter", "name": "decode.prefill_tokens",
             "value": 16, "attrs": {"delta": 16}},
            {"ts": 1.2, "kind": "counter", "name": "decode.steps",
             "value": 10, "attrs": {"delta": 10}},
            {"ts": 2.0, "kind": "counter", "name": "decode.tokens",
             "value": 30, "attrs": {"delta": 30}},
            {"ts": 2.0, "kind": "counter", "name": "decode.retired",
             "value": 4, "attrs": {"delta": 4}},
            {"ts": 2.0, "kind": "counter",
             "name": "decode.kv_pages_allocated", "value": 9,
             "attrs": {"delta": 9}},
            {"ts": 2.0, "kind": "counter", "name": "decode.kv_pages_freed",
             "value": 8, "attrs": {"delta": 8}},
            {"ts": 1.5, "kind": "hist", "name": "decode.batch_occupancy",
             "value": 0.75, "attrs": {}},
            {"ts": 1.5, "kind": "timer", "name": "decode.step_ms",
             "value": 1.25, "attrs": {}},
            {"ts": 1.5, "kind": "timer", "name": "decode.prefill_ms",
             "value": 2.5, "attrs": {}},
            {"ts": 1.6, "kind": "gauge",
             "name": "mem.serving.kv_pool_bytes", "value": 4096,
             "attrs": {}},
            {"ts": 1.6, "kind": "gauge",
             "name": "mem.serving.kv_high_water_bytes", "value": 2048,
             "attrs": {}},
        ]
        s = summarize_log(recs)
        dc = s["decode"]
        assert dc["tokens"] == 30 and dc["steps"] == 10
        assert dc["tokens_per_s"] == 30.0   # 30 tokens over 1s of log
        assert dc["kv_pool_bytes"] == 4096
        assert dc["batch_occupancy"]["mean"] == 0.75
        buf = _io.StringIO()
        render(s, out=buf)
        text = buf.getvalue()
        assert "-- decode (continuous-batching generative engine)" in text
        assert "LEAKED 1" in text   # 9 allocated vs 8 freed
        assert "kv page pool" in text

    def test_mem_report_kv_ledger_lines(self):
        import io as _io

        from tools.mem_report import render, summarize_mem

        recs = [
            {"ts": 1.0, "kind": "gauge", "name": "mem.param_bytes",
             "value": 1024, "attrs": {}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_pool_bytes", "value": 8192,
             "attrs": {}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_used_bytes", "value": 4096,
             "attrs": {}},
            {"ts": 1.0, "kind": "gauge",
             "name": "mem.serving.kv_high_water_bytes", "value": 6144,
             "attrs": {}},
        ]
        s = summarize_mem(recs)
        led = s["ledger"]
        assert led["serving_kv_pool_bytes"] == 8192
        assert led["total_bytes"] == 1024 + 8192
        buf = _io.StringIO()
        render(s, out=buf)
        assert "KV page pool" in buf.getvalue()
