"""Serving cells: the decode engine behind its HTTP server, under generated load.

The server side is chip_smoke.py's `_serve_one` (run on the chip in PR 21)
without the trip through the disk: weights are made on the device from the
seed in one jitted call and handed to `DecodeEngine(cfg, params, config)`;
`ServingHTTPServer(None, decode_engine=...)` and the watchdog are all that
`serving.server.serve_decode` adds to that. Engine, HTTP threads and the
load generator share this one process, because one process holds the chip.

Clocks: a request's record holds when it was due, when it was sent and when
its response was read, on this process's perf_counter, beside the server's
own `ttft_ms` and `latency_ms` from the response body.
"""

from __future__ import annotations

import itertools
import json
import os
import queue
import threading
import time
import urllib.error
import urllib.request

from .. import flops, generators, reference, trace_reduce
from ..common import held_hbm, log, peak_hbm
from ..generators.requests import FIRST_TOKEN_ID
from . import result

TRACE_AFTER_S = 2.0       # the profiler's sub-window opens this far in
TRACE_SECONDS = 3.0
DRAIN_LIMIT_S = 60.0      # after the window, for requests due inside it
ENGINE_GAP = "engine loop (fetch + sample + feed, unsplit)"


def model_config(config: dict):
    from paddle_tpu.models import decoder_lm as dl

    return dl.DecoderLMConfig(
        vocab_size=config["vocab_size"], d_model=config["d_model"],
        n_head=config["attention_heads"], n_layers=config["num_layers"],
        d_inner=config["ffn_dim"],
        max_seq_len=config["max_position_embeddings"])


def param_specs(cfg):
    """name -> (shape, kind), as models/decoder_lm.decoder_lm_params lays
    them out (a CPU test compares the two)."""
    from paddle_tpu.models import decoder_lm as dl

    specs = {"lm_tok_emb": ((cfg.vocab_size, cfg.d_model), "normal")}
    for i in range(cfg.n_layers):
        for suffix, d_in, d_out in dl._dense_specs(cfg):
            specs[f"lm_l{i}_{suffix}_w"] = ((d_in, d_out), "normal")
            specs[f"lm_l{i}_{suffix}_b"] = ((d_out,), "zeros")
        for ln in ("ln1", "ln2"):
            specs[f"lm_l{i}_{ln}_scale"] = ((cfg.d_model,), "ones")
            specs[f"lm_l{i}_{ln}_bias"] = ((cfg.d_model,), "zeros")
    return specs


def make_params(cfg, seed: int):
    """Seeded float32 weights, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import decoder_lm as dl

    specs = param_specs(cfg)
    names = sorted(specs)
    std = cfg.d_model ** -0.5

    def make(key):
        out = {}
        for j, name in enumerate(names):
            shape, kind = specs[name]
            if kind == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, j), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, float(kind == "ones"),
                                     jnp.float32)
        return out

    params = jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))
    params["lm_pos_enc"] = jnp.asarray(
        dl._sinusoid_table(cfg.max_seq_len, cfg.d_model))
    return params


def post(url: str, doc: dict, timeout: float = 600.0):
    """-> (status, body dict); a refusal's status and body, not a raise."""
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:200]}
    except OSError as e:
        return 0, {"error": repr(e)[:200]}


def send(url: str, req: dict, rec: dict):
    rec["sent"] = time.perf_counter()
    status, body = post(url, {
        "prompt_ids": req["prompt_ids"],
        "max_new_tokens": req["max_new_tokens"],
        "temperature": req["temperature"],
        "seed": req["seed"] if req["temperature"] > 0 else None,
        "stop_at_eos": False})
    rec["done"] = time.perf_counter()
    rec["status"] = status
    rec["asked"] = req["max_new_tokens"]
    rec["prompt"] = len(req["prompt_ids"])
    rec["tokens"] = body.get("num_tokens", 0)
    rec["ttft_ms"] = body.get("ttft_ms")
    rec["latency_ms"] = body.get("latency_ms")
    rec["ok"] = (status == 200 and rec["tokens"] == rec["asked"]
                 and rec["ttft_ms"] is not None)
    return rec


class Load:
    """Drives the generated requests at the server from a few threads and
    keeps one record per request. `t_open` is the window's opening on
    perf_counter; a closed loop's callers run from `t_open - ramp_s` until
    `stop` is set, an open loop's requests go out when they are due."""

    def __init__(self, url: str, plan: dict, t_open: float, seconds: float):
        self.url, self.plan = url, plan
        self.t_open, self.t_close = t_open, t_open + seconds
        self.records = []
        self.stop = threading.Event()
        self._next = itertools.count()
        self._threads = []

    def start(self):
        if self.plan["clients"]:
            targets = [self._closed_client] * self.plan["clients"]
        else:
            self._due = queue.Queue()
            targets = [self._scheduler] + [self._open_worker] * 48
        for fn in targets:
            th = threading.Thread(target=fn, daemon=True)
            th.start()
            self._threads.append(th)

    def _closed_client(self):
        reqs = self.plan["requests"]
        while not self.stop.is_set():
            i = next(self._next)
            rec = {"due": time.perf_counter(), "index": i}
            self.records.append(send(self.url, reqs[i % len(reqs)], rec))

    def _scheduler(self):
        for i, req in enumerate(self.plan["requests"]):
            due = self.t_open + req["due_s"]
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self._due.put((req, {"due": due, "index": i}))
        for _ in self._threads[1:]:
            self._due.put(None)

    def _open_worker(self):
        while True:
            item = self._due.get()
            if item is None:
                return
            self.records.append(send(self.url, *item))

    def finish(self, limit_s: float):
        """Stop the callers and wait for what is in flight."""
        self.stop.set()
        deadline = time.perf_counter() + limit_s
        for th in self._threads:
            th.join(max(0.0, deadline - time.perf_counter()))
        return not any(th.is_alive() for th in self._threads)


def check_correct(url, params, cfg, check: dict, seed: int):
    """Greedy requests over HTTP, teacher-forced through the reference."""
    import numpy as np

    rng = np.random.RandomState((seed + 7919) % (2 ** 32))
    worst, notes, by_prompt = 0.0, [], {}
    for n in check["prompt_tokens"]:
        prompt = rng.randint(FIRST_TOKEN_ID, cfg.vocab_size, n)
        status, body = post(url + "/v1/generate", {
            "prompt_ids": prompt.tolist(), "stop_at_eos": False,
            "max_new_tokens": check["new_tokens"]})
        if status != 200 or body.get("num_tokens") != check["new_tokens"]:
            notes.append(f"check request answered {status}: {body}")
            continue
        ok, gap, by_prompt[n] = reference.check_greedy(
            params, cfg.n_layers, cfg.n_head, prompt, body["tokens"],
            pad_to=check["pad_to"])
        worst = max(worst, gap)
        if not ok:
            notes.append(f"engine's greedy token {gap:.4f} under the "
                         f"reference's maximum logit (margin "
                         f"{reference.MARGIN}) at prompt length {n}")
    return worst, notes, by_prompt


def engine_config(config: dict, traffic: dict) -> dict:
    """DecodeConfig's arguments; refuses a pool, a traffic mix or a check
    that does not fit the configuration's `max_context`."""
    eng = dict(config["engine"], kv_pages=config["kv_pages"])
    per_slot = -(-config["max_context"] // eng["page_size"])
    if eng["kv_pages"] < eng["max_slots"] * per_slot + 1:
        raise ValueError(
            f"kv_pages {eng['kv_pages']} hold no {config['max_context']} "
            f"tokens for each of {eng['max_slots']} slots")
    check = config["check"]
    longest = max(traffic["max_context"],
                  max(check["prompt_tokens"]) + check["new_tokens"])
    if longest > config["max_context"]:
        raise ValueError(f"a context of {longest} tokens is over the "
                         f"configuration's max_context")
    return eng


def run(job):
    import jax

    from paddle_tpu.core import incidents, telemetry
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
    from paddle_tpu.serving.server import ServingHTTPServer

    config, traffic = job.config, job.traffic
    cfg = model_config(config)
    eng = engine_config(config, traffic)
    t0 = time.perf_counter()
    params = make_params(cfg, job.seed)
    jax.block_until_ready(params)
    log("serve.weights", seconds=round(time.perf_counter() - t0, 2))

    t0 = time.perf_counter()
    engine = DecodeEngine(cfg, params, DecodeConfig(**eng))
    engine.start(warmup=True)
    incidents.start_watchdog()
    server = ServingHTTPServer(None, decode_engine=engine).start()
    log("serve.start", seconds=round(time.perf_counter() - t0, 2),
        compiles=telemetry.counter_get("decode.compiles"))
    notes, trace = [], None
    try:
        t0 = time.perf_counter()
        worst_gap, notes, gaps = check_correct(server.url, params, cfg,
                                               config["check"], job.seed)
        log("serve.check", seconds=round(time.perf_counter() - t0, 2),
            worst_gap=worst_gap, margin=reference.MARGIN, gaps=gaps)

        plan = generators.load(traffic["generator"]).make(
            traffic, job.seed, job.seconds, cfg.vocab_size)
        telemetry.reset()              # the window's own counters and hists
        job.watch.mark()
        t_load = time.perf_counter() + 0.05
        t_open = t_load + plan["ramp_s"]
        load = Load(server.url + "/v1/generate", plan, t_open, job.seconds)
        load.start()
        time.sleep(max(0.0, t_open - time.perf_counter()))
        setup_s = job.clock()
        if job.trace:
            trace_dir = os.path.join(job.work_dir, "trace")
            time.sleep(min(TRACE_AFTER_S, 0.1 * job.seconds))
            before = dict(telemetry.counters())
            trace_reduce.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                time.sleep(min(TRACE_SECONDS, 0.5 * job.seconds))
            jax.profiler.stop_trace()
            after = dict(telemetry.counters())
            trace = trace_reduce.reduce_trace(trace_dir, ENGINE_GAP, job.platform)
            trace["counters"] = {
                k: after.get(k, 0) - before.get(k, 0)
                for k in ("decode.steps", "decode.prefills", "decode.tokens")}
        time.sleep(max(0.0, load.t_close - time.perf_counter()))
        window_s = time.perf_counter() - t_open
        snap = telemetry.snapshot()
        held = held_hbm(job.chips)
        backlog = None
        if not plan["clients"]:
            due_in = sum(1 for r in plan["requests"] if r["due_s"] >= 0)
            backlog = due_in - sum(1 for r in list(load.records)
                                   if r["due"] >= t_open)
        compiled = int(snap["counters"].get("decode.compiles", 0))
        if compiled or job.watch.since_mark():
            notes.append(f"compiled inside the window: decode.compiles "
                         f"+{compiled}, backend compiles "
                         f"+{job.watch.since_mark()}")
        if not load.finish(DRAIN_LIMIT_S):
            notes.append(f"requests still in flight {DRAIN_LIMIT_S} s after "
                         f"the window")
    finally:
        server.shutdown()
        engine.close(drain=False, timeout=30)
        incidents.stop_watchdog()

    records = sorted(load.records, key=lambda r: r["index"])
    for r in records:
        if r["ok"]:       # first token, on this clock: the response less the
            #               server's own time from first token to reply
            r["first"] = r["done"] - (r["latency_ms"] - r["ttft_ms"]) / 1e3
    if plan["clients"]:
        # a closed loop counts what was answered inside the window
        counted = [r for r in records
                   if t_open <= r["done"] <= load.t_close]
        attempted = len(counted)
    else:
        # an open loop counts what was due inside it, answered or not
        counted = [r for r in records if r["due"] >= t_open]
        attempted = sum(1 for r in plan["requests"] if r["due_s"] >= 0)
    good = [r for r in counted if r["ok"]]
    failed = attempted - len(good)
    if failed:
        bad = [(r["status"], r["tokens"], r["asked"])
               for r in counted if not r["ok"]][:3]
        notes.append(f"{failed} of {attempted} requests failed or went "
                     f"unanswered, e.g. {bad}")
    log("serve.records", t_open=0.0, t_close=load.t_close - t_open, rows=[
        [round(r[k] - t_open, 4) for k in ("due", "sent", "done")]
        + [r["ok"], r["prompt"], r["tokens"], r["ttft_ms"], r["latency_ms"]]
        for r in records])
    # context a decode step reads: each good request is live for its new
    # tokens' steps at a context growing from prompt to prompt + new
    steps_live = sum(r["tokens"] for r in good) or 1
    mean_ctx = sum(r["tokens"] * (r["prompt"] + (r["tokens"] + 1) / 2)
                   for r in good) / steps_live
    occ = (snap["hists"].get("decode.batch_occupancy") or {}).get("avg", 0)
    live_ctx = occ * eng["max_slots"] * mean_ctx
    sizes = dict(d_model=cfg.d_model, layers=cfg.n_layers, ffn=cfg.d_inner,
                 vocab=cfg.vocab_size)
    log("serve.window", attempted=attempted, failed=failed,
        window_s=round(window_s, 3), backlog_at_close=backlog,
        completed_per_s=round(len(good) / window_s, 4),
        mean_live_context_tokens=round(live_ctx, 1),
        steps=snap["counters"].get("decode.steps"),
        prefills=snap["counters"].get("decode.prefills"))
    return result(
        kind="serve", correct=not notes, attempted=attempted, failed=failed,
        notes=notes, setup_s=setup_s, window_s=window_s, requests=good,
        answered=[r for r in records if r["ok"]],
        window=(t_open, load.t_close),
        telemetry=snap, peak_hbm_bytes=peak_hbm(job.chips),
        window_hbm_bytes=held, trace=trace,
        step_bytes=flops.decoder_step_bytes(
            live_context_tokens=live_ctx, **sizes))
