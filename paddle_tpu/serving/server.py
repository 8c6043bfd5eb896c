"""HTTP + in-process front ends for the ServingEngine.

Stdlib-only on purpose (http.server + json): a paddle_tpu worker serves
traffic with zero extra dependencies, the same way tools/perf_report.py
renders logs anywhere. The reference's analog is the C++ inference
server samples around AnalysisPredictor; TF-Serving's REST surface is
the API shape being mirrored.

API:
    POST /v1/generate {"prompt_ids": [ints], "max_new_tokens"?,
                      "temperature"?, "seed"?, "deadline_ms"?}
             200 ->  {"tokens": [ints], "num_tokens", "ttft_ms",
                      "token_ms": [each token's ms since submit],
                      "model_version", "latency_ms"} — the generative
                     decode plane (serving/decode.py) when a
                     decode_engine is attached; 429 carries
                     error_type "KVCacheExhaustedError" for the typed
                     would-OOM refusal
    POST /v1/infer   {"inputs": {name: nested lists},
                      "deadline_ms": optional float}
             200 ->  {"outputs": {name: nested lists}, "latency_ms": f,
                      "trace_id": str|null}
             400 bad request (missing/odd inputs)
             429 ServerOverloadedError (admission backpressure)
             503 EngineClosedError (draining / shut down)
             504 DeadlineExceededError
             500 handler failure (per-request, queue keeps serving)
    GET  /healthz    READINESS (health.py state machine): 200
                     {"status": "ok", ...} only when the replica can
                     serve NOW; 503 {"status": "starting"} during
                     warmup, "swapping" during a model swap,
                     "draining"/"stopped" during/after close — a router
                     or external LB polling it never routes to a cold or
                     dying replica
    GET  /livez      LIVENESS: 200 while the process/engine can still
                     make progress (any state but stopped), else 503
    POST /v1/admin/swap {"model_dir": path, "version": int?}
                     zero-downtime model swap: verify the dir's COMMIT
                     manifest when present (PR 5 protocol), build + warm
                     the new predictor on every bucket, atomically flip
                     (engine.swap_predictor) — old version serves until
                     the flip
    GET  /v1/stats   serving.* counters + request/batch latency
                     percentiles + rolling-window rates (engine.stats());
                     when FLAGS_cost_capture is on, a "memory" section
                     with per-warmed-bucket cost/memory footprints and
                     the composed HBM ledger (core/costmodel.py)
    GET  /metrics    Prometheus text exposition of the live registry —
                     cumulative counters, rolling-window rates and
                     p50/p95/p99 over FLAGS_metrics_window_s

Tracing: every /v1/infer request opens a root span (core/trace.py,
sampled by FLAGS_trace_sample_rate) whose context flows through the
admission queue into the engine's batch worker, so one trace_id links
request → queue-wait → batch-assembly → predictor-run. A client-supplied
``X-Request-Id`` header forces sampling and pins the trace id; the
response carries it back as ``trace_id`` + an ``X-Trace-Id`` header.

``serve()`` wires model dir → predictor → engine (with every-bucket
warmup) → bound HTTP server in one call; ``LocalClient`` is the
in-process twin the tier-1 tests and bench harness use (no sockets).
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from ..core import incidents, telemetry, trace
from .admission import (DeadlineExceededError, EngineClosedError,
                        KVCacheExhaustedError, ServerOverloadedError)
from .engine import ServingConfig, ServingEngine


class LocalClient:
    """In-process client: same request/response shape as the HTTP front
    end (outputs keyed by fetch name) without the socket."""

    def __init__(self, engine: ServingEngine):
        self.engine = engine

    def infer(self, inputs: Dict[str, Any],
              deadline_ms: Optional[float] = None,
              timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
        outs = self.engine.infer(inputs, deadline_ms=deadline_ms,
                                 timeout=timeout)
        return dict(zip(self.engine.fetch_names, outs))


def _coerce_inputs(engine: ServingEngine,
                   raw: Dict[str, Any]) -> Dict[str, np.ndarray]:
    specs = engine.predictor.feed_specs()
    feeds = {}
    for name, value in raw.items():
        dtype = specs.get(name, ((), "float32"))[1]
        feeds[name] = np.asarray(value, dtype=np.dtype(dtype))
    return feeds


class _Handler(BaseHTTPRequestHandler):
    # the engine is attached to the server object by make_http_server
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):   # silence per-request stderr spam
        pass

    def _reply(self, code: int, payload: Dict[str, Any],
               headers: Optional[Dict[str, str]] = None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        engine = self.server.engine or self.server.decode_engine
        if self.path == "/healthz":
            # READINESS: 200 iff this replica should receive traffic NOW
            snap = engine.health.snapshot(
                queue_depth=engine.queue.depth(),
                model_version=engine.version)
            self._reply(200 if snap["ready"] else 503, snap)
        elif self.path == "/livez":
            alive = engine.health.is_alive()
            self._reply(200 if alive else 503,
                        {"status": "alive" if alive else "stopped"})
        elif self.path == "/v1/stats":
            stats = self.server.engine.stats() \
                if self.server.engine is not None else {}
            if self.server.decode_engine is not None:
                # the generative plane's counters + KV-cache/pool ledger
                stats["decode"] = self.server.decode_engine.stats()
            # SLO watchdog firing states + incident totals — the plane's
            # "health" verdict next to the raw counters (core/incidents)
            stats["health"] = incidents.health()
            self._reply(200, stats)
        elif self.path == "/metrics":
            body = telemetry.prometheus_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._reply(404, {"error": f"no route {self.path}"})

    def _handle_swap(self, engine: ServingEngine):
        """POST /v1/admin/swap — the replica side of the cluster's
        zero-downtime rolling swap (serving/cluster.py drives it)."""
        try:
            length = int(self.headers.get("Content-Length") or 0)
            doc = json.loads(self.rfile.read(length) or b"{}")
            model_dir = doc["model_dir"]
        except (ValueError, TypeError, KeyError) as e:
            self._reply(400, {"error": f"bad swap request: {e!r}"})
            return
        try:
            from .. import checkpoint as _ckpt
            from ..inference import AnalysisConfig, create_predictor

            version = doc.get("version")
            if os.path.exists(os.path.join(model_dir, _ckpt.MANIFEST_NAME)):
                manifest = _ckpt.verify_model_dir(model_dir)
                if version is None:
                    version = manifest.get("version")
            predictor = create_predictor(AnalysisConfig(model_dir))
            fresh = engine.swap_predictor(predictor, version=version)
        except Exception as e:   # verify/build/warm/injected failure:
            # the old predictor is still live — report, don't die
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        self._reply(200, {"status": "ok", "model_version": engine.version,
                          "warmup_compiles": fresh})

    def _handle_generate(self):
        """POST /v1/generate — the generative decode plane
        (serving/decode.py): {"prompt_ids": [ints], "max_new_tokens"?,
        "temperature"?, "seed"?, "deadline_ms"?} -> {"tokens": [ints],
        "num_tokens", "ttft_ms", "token_ms", "latency_ms",
        "model_version"}."""
        de = self.server.decode_engine
        if de is None:
            self._reply(404, {"error": "no decode engine attached — "
                                       "this replica serves /v1/infer "
                                       "only"})
            return
        try:
            length = int(self.headers.get("Content-Length") or 0)
            doc = json.loads(self.rfile.read(length) or b"{}")
            prompt = doc["prompt_ids"]
        except (ValueError, TypeError, KeyError) as e:
            self._reply(400, {"error": f"bad generate request: {e!r}"})
            return
        t0 = time.perf_counter()
        # session identity: body request_id wins, else the X-Request-Id
        # header the router forwards — either opts the generation into
        # journaling; prior_tokens/rng_state re-admit a journaled
        # session after its replica died (serving/session.py)
        request_id = (doc.get("request_id")
                      or self.headers.get("X-Request-Id"))
        try:
            req = de.submit(prompt,
                            max_new_tokens=doc.get("max_new_tokens"),
                            deadline_ms=doc.get("deadline_ms"),
                            temperature=float(doc.get("temperature", 0.0)),
                            seed=doc.get("seed"),
                            stop_at_eos=bool(doc.get("stop_at_eos", True)),
                            request_id=request_id,
                            prior_tokens=doc.get("prior_tokens"),
                            rng_state=doc.get("rng_state"))
            tokens = req.result()
        except ValueError as e:
            self._reply(400, {"error": str(e)})
        except KVCacheExhaustedError as e:
            # typed would-OOM refusal: the client must shrink or retry
            # against a bigger pool — 429 with the typed name
            self._reply(429, {"error": str(e),
                              "error_type": "KVCacheExhaustedError"})
        except ServerOverloadedError as e:
            self._reply(429, {"error": str(e)},
                        {"Retry-After": "0.05"})
        except EngineClosedError as e:
            self._reply(503, {"error": str(e)})
        except DeadlineExceededError as e:
            self._reply(504, {"error": str(e)})
        except Exception as e:
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
        else:
            payload = {
                "tokens": np.asarray(tokens).tolist(),
                "num_tokens": int(np.asarray(tokens).size),
                "ttft_ms": round(req.ttft_ms, 3)
                if req.ttft_ms is not None else None,
                # each token's time since submit: the first is ttft_ms
                "token_ms": [round((w - req.t_submit) * 1e3, 3)
                             for w in req.token_walls],
                "model_version": de.version,
                "latency_ms": round((time.perf_counter() - t0) * 1e3, 3)}
            if request_id is not None:
                payload["request_id"] = request_id
            if doc.get("prior_tokens"):
                # resumed session: the tokens above are the TAIL only;
                # the router re-joins them with the journaled prefix
                payload["resumed"] = True
            self._reply(200, payload)

    def _handle_prefill(self):
        """POST /v1/prefill — the prefill tier of disaggregated serving
        (serving/disagg.py): {"prompt": [ints]} -> the serialized KV
        page shipment (application/octet-stream, versioned wire format
        with per-page CRCs). Decode-role replicas fetch this and
        install the pages instead of prefilling locally."""
        de = self.server.decode_engine
        if de is None:
            self._reply(404, {"error": "no decode engine attached — "
                                       "nothing to prefill here"})
            return
        try:
            length = int(self.headers.get("Content-Length") or 0)
            doc = json.loads(self.rfile.read(length) or b"{}")
            prompt = doc["prompt"]
        except (ValueError, TypeError, KeyError) as e:
            self._reply(400, {"error": f"bad prefill request: {e!r}"})
            return
        try:
            blob = de.submit_prefill(
                prompt, deadline_ms=doc.get("deadline_ms")).result()
        except ValueError as e:
            self._reply(400, {"error": str(e)})
        except KVCacheExhaustedError as e:
            self._reply(429, {"error": str(e),
                              "error_type": "KVCacheExhaustedError"})
        except ServerOverloadedError as e:
            self._reply(429, {"error": str(e)}, {"Retry-After": "0.05"})
        except EngineClosedError as e:
            self._reply(503, {"error": str(e)})
        except DeadlineExceededError as e:
            self._reply(504, {"error": str(e)})
        except Exception as e:
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
        else:
            body = bytes(blob)
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    def do_POST(self):
        engine: ServingEngine = self.server.engine
        if self.path == "/v1/generate":
            self._handle_generate()
            return
        if self.path == "/v1/prefill":
            self._handle_prefill()
            return
        if self.path == "/v1/admin/swap":
            self._handle_swap(engine)
            return
        if self.path != "/v1/infer":
            self._reply(404, {"error": f"no route {self.path}"})
            return
        if engine is None:
            self._reply(404, {"error": "no micro-batching engine "
                                       "attached — this replica serves "
                                       "/v1/generate only"})
            return
        try:
            length = int(self.headers.get("Content-Length") or 0)
            doc = json.loads(self.rfile.read(length) or b"{}")
            feeds = _coerce_inputs(engine, doc.get("inputs") or {})
        except (ValueError, TypeError) as e:
            self._reply(400, {"error": f"bad request body: {e}"})
            return
        # request root span: an X-Request-Id header pins the trace id and
        # forces sampling; otherwise FLAGS_trace_sample_rate decides. The
        # context captured by engine.submit() inside this block links the
        # whole queue → batch → predictor timeline to one trace_id
        rid = self.headers.get("X-Request-Id")
        code, payload, headers = 500, {"error": "unhandled"}, {}
        t0 = time.perf_counter()
        with trace.root_span("serving.http_request", trace_id=rid,
                             force=bool(rid), path=self.path) as tctx:
            served_version = None
            try:
                req = engine.submit(feeds,
                                    deadline_ms=doc.get("deadline_ms"))
                outs = req.result()
                served_version = req.served_version
            except ValueError as e:      # missing/ragged inputs
                code, payload = 400, {"error": str(e)}
            except ServerOverloadedError as e:
                code, payload = 429, {"error": str(e)}
                headers = {"Retry-After": "0.05"}
            except EngineClosedError as e:
                code, payload = 503, {"error": str(e)}
            except DeadlineExceededError as e:
                code, payload = 504, {"error": str(e)}
            except Exception as e:       # injected / handler failure
                code, payload = 500, {"error": f"{type(e).__name__}: {e}"}
            else:
                code = 200
                payload = {
                    "outputs": {n: np.asarray(o).tolist()
                                for n, o in zip(engine.fetch_names, outs)},
                    "model_version": served_version,
                    "latency_ms": round(
                        (time.perf_counter() - t0) * 1e3, 3)}
        if code == 200 or tctx is not None:
            payload["trace_id"] = tctx.trace_id if tctx else None
        if tctx is not None:
            headers["X-Trace-Id"] = tctx.trace_id
        self._reply(code, payload, headers)


class _HTTPServer(ThreadingHTTPServer):
    # the listen backlog. Callers connect in bursts (a closed loop's 96
    # callers all at once); with socketserver's default of 5 the rest of
    # a burst waits out SYN retries or is reset ~10 s later: 1 to 3
    # requests of a run's first seconds answered with no response at all
    # (my chip runs, PR 28). Sized past any admission queue in use.
    request_queue_size = 1024


class ServingHTTPServer:
    """Bound-but-not-yet-serving HTTP wrapper; start()/shutdown() own the
    acceptor thread. port=0 binds an ephemeral port (tests, CI)."""

    def __init__(self, engine: Optional[ServingEngine],
                 host: str = "127.0.0.1", port: int = 0,
                 decode_engine=None):
        if engine is None and decode_engine is None:
            raise ValueError("ServingHTTPServer needs an engine and/or a "
                             "decode_engine")
        self.engine = engine
        self.decode_engine = decode_engine
        self._httpd = _HTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.engine = engine
        self._httpd.decode_engine = decode_engine
        self._thread: Optional[threading.Thread] = None

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "ServingHTTPServer":
        if self._thread is None:
            # a serving surface is the canonical always-on process: arm
            # the SLO watchdog (FLAGS_slo_watchdog 'auto'); the engine
            # loops drive evaluation via incidents.tick()
            incidents.arm()
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name="pt-serving-http", daemon=True)
            self._thread.start()
        return self

    def shutdown(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        incidents.disarm()


def serve(model_dir: str, host: str = "127.0.0.1", port: int = 0,
          config: Optional[ServingConfig] = None,
          warmup: bool = True) -> ServingHTTPServer:
    """model dir → predictor → warmed engine → started HTTP server."""
    from ..inference import AnalysisConfig, create_predictor

    predictor = create_predictor(AnalysisConfig(model_dir))
    engine = ServingEngine(predictor, config=config)
    engine.start(warmup=warmup)
    # production entry: the pt-incidents-watchdog thread keeps the SLO
    # rules evaluating even while the replica is idle
    incidents.start_watchdog()
    return ServingHTTPServer(engine, host=host, port=port).start()


def serve_decode(model_dir: str, host: str = "127.0.0.1", port: int = 0,
                 config=None, warmup: bool = True) -> ServingHTTPServer:
    """Decoder-LM dir (models/decoder_lm.save_decoder_lm) → started
    generative HTTP server (POST /v1/generate)."""
    from .decode import decode_engine_from_dir

    de = decode_engine_from_dir(model_dir, config=config)
    de.start(warmup=warmup)
    incidents.start_watchdog()
    return ServingHTTPServer(None, host=host, port=port,
                             decode_engine=de).start()
