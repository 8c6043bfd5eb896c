"""Serving cells: the decode engine behind its HTTP server, under generated load.

What is served comes from the configuration's family file
(`<data>/families/<family>.py`): the model's sizes, its seeded weights, its
engine and how many slots it steps, the ids its traffic may draw, its
comparison with its own plain reference, limits and all, and the bytes a
decode step must read. Everything here is the yardstick and the same for
every family: the load, the window, the traced sub-window, the records and
what counts.
`ServingHTTPServer(None, decode_engine=...)` and the watchdog are all that
`serving.server.serve_decode` adds to the family's engine. Engine, HTTP
threads and the load generator share this one process, because one process
holds the chip.

Clocks: a request's record holds when it was due, when it was sent and when
its response was read, on this process's perf_counter, beside the server's
own `ttft_ms` and `latency_ms` from the response body.
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
import time

from .. import generators, trace_reduce
from ..common import held_hbm, log, peak_hbm, post
from . import result

TRACE_AFTER_S = 2.0       # the profiler's sub-window opens this far in
TRACE_SECONDS = 3.0
DRAIN_LIMIT_S = 60.0      # after the window, for requests due inside it
NO_SPAN_GAP = "engine thread outside its loop's spans"


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


def run(job):
    import jax

    from paddle_tpu.core import incidents, telemetry
    from paddle_tpu.serving.server import ServingHTTPServer

    config, traffic = job.config, job.traffic
    family = job.manifest.family(config["family"])
    cfg = family.model_config(config)
    t0 = time.perf_counter()
    params = family.make_params(cfg, job.seed)
    jax.block_until_ready(params)
    log("serve.weights", seconds=round(time.perf_counter() - t0, 2))

    t0 = time.perf_counter()
    engine = family.make_engine(cfg, params, config, traffic)
    engine.start(warmup=True)
    incidents.start_watchdog()
    server = ServingHTTPServer(None, decode_engine=engine).start()
    log("serve.start", seconds=round(time.perf_counter() - t0, 2),
        compiles=telemetry.counter_get("decode.compiles"))
    notes, trace = [], None
    try:
        t0 = time.perf_counter()
        compared, notes, detail = family.check_correct(
            server.url, engine, params, cfg, config["check"], job.seed)
        log("serve.check", seconds=round(time.perf_counter() - t0, 2),
            compared=compared, **detail)

        plan = generators.load(traffic["generator"]).make(
            traffic, job.seed, job.seconds,
            family.traffic_vocab(cfg, config))
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
            trace = trace_reduce.reduce_trace(trace_dir, NO_SPAN_GAP,
                                              job.platform)
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
    live_ctx = occ * family.slots(config) * mean_ctx
    log("serve.window", attempted=attempted, failed=failed,
        window_s=round(window_s, 3), backlog_at_close=backlog,
        completed_per_s=round(len(good) / window_s, 4),
        mean_live_context_tokens=round(live_ctx, 1),
        steps=snap["counters"].get("decode.steps"),
        prefills=snap["counters"].get("decode.prefills"))
    return result(
        kind="serve", correct=not notes, attempted=attempted, failed=failed,
        notes=notes, compared=list(compared) + [
            ["failed_requests", failed, 0],
            ["compiles_in_window", compiled + job.watch.since_mark(), 0]],
        setup_s=setup_s, window_s=window_s, requests=good,
        answered=[r for r in records if r["ok"]],
        window=(t_open, load.t_close),
        telemetry=snap, peak_hbm_bytes=peak_hbm(job.chips),
        window_hbm_bytes=held, trace=trace, live_context_tokens=live_ctx,
        step_bytes=family.step_bytes(cfg, config, live_ctx, snap))
