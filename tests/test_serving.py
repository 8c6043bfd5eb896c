"""Serving-engine tests: dynamic micro-batching behind admission control.

Contracts under test (paddle_tpu/serving/):
* coalesced + padded batches return responses within 2 ulp of float32 of
  unbatched AnalysisPredictor.run of the same rows, across buckets (a
  bucket's program and the unbatched one are two XLA programs, whose
  matmuls may round the last bit differently), and bitwise-identical
  ones when the same rows go through the same bucket twice;
* partial batches flush on the batch timeout;
* a saturated queue rejects with ServerOverloadedError (never stalls);
* warmup pre-compiles every bucket exactly once;
* the stdlib HTTP front end round-trips JSON on an ephemeral port;
* close(drain=True) serves the backlog before exiting;
* injected serving.handler faults produce per-request error responses
  and the queue keeps moving.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.serving

IN_DIM, OUT_DIM = 6, 4


def _save_mlp(tmp_path, name="m"):
    """Tiny fc net exported as an inference model (fast to compile)."""
    import paddle_tpu as pt
    from paddle_tpu import io, layers

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [IN_DIM])
        h = layers.fc(x, 8, act="relu")
        y = layers.fc(h, OUT_DIM)
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope, use_compiled=False)
    model_dir = str(tmp_path / name)
    io.save_inference_model(model_dir, ["x"], [y],
                            main_program=main, scope=scope)
    return model_dir


def _predictor(model_dir):
    from paddle_tpu.inference import AnalysisConfig, create_predictor

    return create_predictor(AnalysisConfig(model_dir))


def _engine(model_dir, **cfg):
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg.setdefault("max_batch_size", 8)
    cfg.setdefault("batch_timeout_ms", 5.0)
    return ServingEngine(_predictor(model_dir), config=ServingConfig(**cfg))


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, IN_DIM).astype(np.float32)


def _assert_within_2ulp(got, want):
    """Two XLA programs of the same float32 math: equal to 2 ulp at the
    outputs' magnitude."""
    atol = 2 * float(np.spacing(np.float32(np.abs(want).max())))
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


class TestBatchedEquivalence:
    def test_batched_matches_unbatched_across_buckets(self, tmp_path):
        """Requests of 1..8 rows — coalesced, padded to pow2 buckets —
        must equal single-request predictor runs to the last bits, and
        the same rows through the same bucket twice must be BITWISE
        equal."""
        model_dir = _save_mlp(tmp_path)
        reference = _predictor(model_dir)
        engine = _engine(model_dir).start(warmup=True)
        try:
            sizes = [1, 2, 3, 5, 8, 4, 1, 7]
            feeds = [_rows(n, seed=i) for i, n in enumerate(sizes)]
            reqs = [engine.submit({"x": f}) for f in feeds]
            for f, req in zip(feeds, reqs):
                got, = req.result(timeout=30)
                want, = reference.run({"x": f})
                assert got.shape == (f.shape[0], OUT_DIM)
                _assert_within_2ulp(got, want)
            # one request at a time is alone in its batch: the same
            # bucket's program runs it both times
            for f in feeds:
                first, = engine.infer({"x": f}, timeout=30)
                again, = engine.infer({"x": f}, timeout=30)
                assert np.array_equal(first, again), \
                    "one bucket's program gave two answers for the same rows"
        finally:
            engine.close(drain=True, timeout=10)

    def test_concurrent_clients_coalesce(self, tmp_path):
        """8 threads x 1-row requests: all answers right, and the engine
        actually batched (fewer batches than requests)."""
        from paddle_tpu.core import telemetry

        model_dir = _save_mlp(tmp_path)
        reference = _predictor(model_dir)
        engine = _engine(model_dir, batch_timeout_ms=20.0).start(warmup=True)
        before = telemetry.counter_get("serving.batches")
        results = {}
        lock = threading.Lock()

        def client(i):
            f = _rows(1, seed=100 + i)
            got, = engine.infer({"x": f}, timeout=30)
            want, = reference.run({"x": f})
            _assert_within_2ulp(got, want)
            with lock:
                results[i] = True

        try:
            # the 20 ms batch window is far wider than the thread-start
            # skew, so concurrent submits coalesce
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
        finally:
            engine.close(drain=True, timeout=10)
        assert len(results) == 8 and all(results.values())
        batches = telemetry.counter_get("serving.batches") - before
        assert batches < 8, f"no coalescing happened ({batches} batches)"


class TestBatchingPolicy:
    def test_timeout_flushes_partial_batch(self, tmp_path):
        from paddle_tpu.core import telemetry

        engine = _engine(_save_mlp(tmp_path),
                         batch_timeout_ms=15.0).start(warmup=True)
        before_b = telemetry.counter_get("serving.batches")
        before_p = telemetry.counter_get("serving.padded_rows")
        try:
            t0 = time.monotonic()
            out, = engine.infer({"x": _rows(3)}, timeout=30)
            waited = time.monotonic() - t0
        finally:
            engine.close(drain=True, timeout=10)
        assert out.shape == (3, OUT_DIM)
        assert waited < 5.0, "partial batch did not flush on timeout"
        assert telemetry.counter_get("serving.batches") - before_b == 1
        # 3 rows pad to the 4-bucket: exactly one padded row, sliced out
        assert telemetry.counter_get("serving.padded_rows") - before_p == 1

    def test_backpressure_rejects_when_saturated(self, tmp_path):
        from paddle_tpu.core import telemetry
        from paddle_tpu.serving import ServerOverloadedError

        # worker not started -> the queue only fills
        engine = _engine(_save_mlp(tmp_path), max_queue_depth=2)
        before = telemetry.counter_get("serving.rejects")
        r1 = engine.submit({"x": _rows(1)})
        r2 = engine.submit({"x": _rows(2)})
        with pytest.raises(ServerOverloadedError):
            engine.submit({"x": _rows(1)})
        assert telemetry.counter_get("serving.rejects") - before == 1
        engine.start(warmup=False)   # drain the two admitted requests
        try:
            assert r1.result(timeout=30)[0].shape == (1, OUT_DIM)
            assert r2.result(timeout=30)[0].shape == (2, OUT_DIM)
        finally:
            engine.close(drain=True, timeout=10)

    def test_expired_deadline_fails_at_dequeue(self, tmp_path):
        from paddle_tpu.core import telemetry
        from paddle_tpu.serving import DeadlineExceededError

        engine = _engine(_save_mlp(tmp_path))
        before = telemetry.counter_get("serving.deadline_expired")
        req = engine.submit({"x": _rows(1)}, deadline_ms=1)
        ok = engine.submit({"x": _rows(1)})         # no deadline
        time.sleep(0.05)
        engine.start(warmup=False)
        try:
            with pytest.raises(DeadlineExceededError):
                req.result(timeout=30)
            assert ok.result(timeout=30)[0].shape == (1, OUT_DIM)
        finally:
            engine.close(drain=True, timeout=10)
        assert telemetry.counter_get("serving.deadline_expired") - before == 1

    def test_graceful_drain_serves_backlog(self, tmp_path):
        from paddle_tpu.serving import EngineClosedError

        engine = _engine(_save_mlp(tmp_path))
        reqs = [engine.submit({"x": _rows(n, seed=n)}) for n in (1, 2, 3)]
        engine.start(warmup=False)
        engine.close(drain=True, timeout=30)
        for n, req in zip((1, 2, 3), reqs):
            assert req.result(timeout=1)[0].shape == (n, OUT_DIM)
        with pytest.raises(EngineClosedError):
            engine.submit({"x": _rows(1)})


class TestWarmup:
    def test_warmup_compiles_every_bucket_once(self, tmp_path):
        from paddle_tpu.core import telemetry

        engine = _engine(_save_mlp(tmp_path))
        before = telemetry.counter_get("predictor.compiles")
        fresh = engine.warmup()
        # pow2 buckets for max_batch 8: [1, 2, 4, 8]
        assert fresh == 4
        assert telemetry.counter_get("predictor.compiles") - before == 4
        engine.start(warmup=True)    # second warmup: all cache hits
        try:
            for n in (1, 2, 3, 5, 8):
                engine.infer({"x": _rows(n, seed=n)}, timeout=30)
        finally:
            engine.close(drain=True, timeout=10)
        # every request landed in a warmed bucket: zero fresh compiles
        assert telemetry.counter_get("predictor.compiles") - before == 4


class TestHTTP:
    def test_http_round_trip_and_health(self, tmp_path):
        from paddle_tpu.serving import serve

        model_dir = _save_mlp(tmp_path)
        reference = _predictor(model_dir)
        server = serve(model_dir, port=0)    # ephemeral port
        try:
            x = _rows(2, seed=7)
            body = json.dumps({"inputs": {"x": x.tolist()}}).encode()
            req = urllib.request.Request(
                server.url + "/v1/infer", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                doc = json.loads(resp.read())
            want, = reference.run({"x": x})
            name = server.engine.fetch_names[0]
            got = np.asarray(doc["outputs"][name], dtype=np.float32)
            np.testing.assert_array_equal(got, want)
            assert doc["latency_ms"] >= 0
            with urllib.request.urlopen(server.url + "/healthz",
                                        timeout=10) as resp:
                assert resp.status == 200
                assert json.loads(resp.read())["status"] == "ok"
        finally:
            server.shutdown()
            server.engine.close(drain=True, timeout=10)


@pytest.mark.chaos
class TestHandlerFaults:
    def test_injected_fault_is_per_request_not_wedge(self, tmp_path):
        from paddle_tpu.core import faults, telemetry

        engine = _engine(_save_mlp(tmp_path)).start(warmup=True)
        before = telemetry.counter_get("serving.handler_errors")
        faults.configure("serving.handler:@1:RuntimeError")
        try:
            with pytest.raises(RuntimeError):
                engine.infer({"x": _rows(2)}, timeout=30)
            # the very next request sails through — no wedged queue
            out, = engine.infer({"x": _rows(2, seed=1)}, timeout=30)
            assert out.shape == (2, OUT_DIM)
        finally:
            faults.configure("")
            engine.close(drain=True, timeout=10)
        assert telemetry.counter_get("serving.handler_errors") - before >= 1


class TestValidation:
    def test_bad_feeds_rejected_before_queueing(self, tmp_path):
        engine = _engine(_save_mlp(tmp_path))
        with pytest.raises(ValueError, match="missing input"):
            engine.submit({})
        with pytest.raises(ValueError, match="unknown inputs"):
            engine.submit({"x": _rows(1), "bogus": _rows(1)})
        with pytest.raises(ValueError, match="leading batch dim"):
            engine.submit({"x": np.float32(1.0)})
        engine.close(drain=False)

    def test_bucket_boundaries(self):
        from paddle_tpu.serving import ServingConfig

        cfg = ServingConfig(max_batch_size=8)
        assert cfg.buckets == [1, 2, 4, 8]
        assert [cfg.bucket(n) for n in (1, 2, 3, 5, 8, 11)] == \
            [1, 2, 4, 8, 8, 11]
        cfg = ServingConfig(max_batch_size=6, buckets=[2, 6])
        assert [cfg.bucket(n) for n in (1, 2, 3, 6)] == [2, 2, 6, 6]
