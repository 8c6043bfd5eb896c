"""Decode-session journal — the state a generation needs to survive the
death of the replica running it.

A decode replica is pure state: the KV pages are rebuildable from the
token ids (chunked prefill is bitwise-identical to the cold run by
construction — serving/decode.py), and sampling is a function of the
row's logits and the request's own RandomState, whose uniforms are drawn on
the host one per token (the survivor chooses its first token on the host in
float64 where the dead replica's step program would have chosen it in
float32: the same softmax at the same uniform, so the same token unless the
uniform lies within ~1e-7 of a CDF boundary). So the ONLY durable facts a
generation owns are tiny and host-side: the prompt, the accepted token
ids, the sampler RNG state after those draws, and the deadline
remainder. This module is that record plus the router-side store it
replicates into.

Protocol (reference analog: the Fluid pserver re-sends a dead trainer's
params — here the ROUTER is the survivor that re-seeds the work):

* The engine snapshots every session-carrying request after every step
  and hands the batch to its
  ``journal_sink`` — in-process a plain callable, cross-process an HTTP
  POST to the router's ``/v1/session/journal``.
* On decode-replica death the router rebuilds the submit from the last
  snapshot: prompt + accepted-so-far as the new prefill prompt, RNG
  state restored verbatim, ``max_new_tokens`` reduced by the accepted
  count, deadline set to the journaled remainder. The survivor's
  prefill either prefix-hits the store (warm) or chunk-re-prefills
  (cold); either way the resumed tail is bitwise-identical to the
  uninterrupted run (pinned by tests/test_orchestrator.py across
  greedy/sampled x fp32/int8 x PT_PALLAS off/interpret).
* The router concatenates journaled accepted tokens with the resumed
  tail, so the client sees ONE uninterrupted token stream.

A model with a draft module (serving/decode.py) delivers one or two tokens a
step and draws four uniforms a row a step: its record is the same cut (every
accepted token, the stream's state before the draws of the step in flight)
plus ``last_step_tokens``, how many tokens the last whole step delivered;
records of other models are unchanged. The resumed tail is decided by the
record and distributed as the uninterrupted one; it is bitwise the
uninterrupted one for greedy sessions only, since the dead replica's
pending draft is not in the record.

Telemetry: session.journaled / session.failovers / session.resumed /
session.resumed_tokens / session.journal_errors / session.evicted —
rendered by tools/perf_report.py's "Sessions" section.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from ..core import telemetry


def pack_rng_state(rng: Optional[np.random.RandomState]) -> Optional[list]:
    """np.random.RandomState -> JSON-able state. The MT19937 key vector
    rides as a plain int list — 624 words, small next to the KV pages it
    replaces."""
    if rng is None:
        return None
    name, key, pos, has_gauss, cached = rng.get_state()
    return [str(name), [int(x) for x in key], int(pos), int(has_gauss),
            float(cached)]


def unpack_rng_state(state) -> Optional[np.random.RandomState]:
    """Inverse of pack_rng_state; None passes through (greedy sessions
    journal no RNG)."""
    if state is None:
        return None
    name, key, pos, has_gauss, cached = state
    rng = np.random.RandomState()
    rng.set_state((str(name), np.asarray(key, np.uint32), int(pos),
                   int(has_gauss), float(cached)))
    return rng


def resume_args(record: Dict[str, Any]) -> Dict[str, Any]:
    """Journal record -> the kwargs of the re-admission submit. The
    resumed request generates only the REMAINING tokens; the caller
    (router) prepends ``record['accepted']`` to the resumed tail."""
    accepted = [int(t) for t in record.get("accepted", [])]
    out = {
        "prompt_ids": [int(t) for t in record["prompt"]],
        "prior_tokens": accepted,
        "max_new_tokens": int(record["max_new_total"]) - len(accepted),
        "temperature": float(record.get("temperature", 0.0)),
        "seed": record.get("seed"),
        "rng_state": record.get("rng_state"),
        "stop_at_eos": bool(record.get("stop_at_eos", True)),
        "request_id": record.get("request_id"),
    }
    rem = record.get("deadline_remaining_ms")
    if rem is not None:
        out["deadline_ms"] = max(1.0, float(rem))
    return out


# sessions the router's journal keeps: a completed one is popped at
# response time, an abandoned one ages out here (session.evicted)
SESSION_CAPACITY = 4096


class SessionJournal:
    """Router-side store of the latest snapshot per request id. Bounded
    LRU (SESSION_CAPACITY): completed sessions are popped
    by the router; abandoned ones age out at the capacity edge
    (session.evicted)."""

    def __init__(self, capacity: int = SESSION_CAPACITY):
        self.capacity = int(capacity)
        self._records: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._lock = threading.Lock()

    def update(self, records: List[Dict[str, Any]]) -> int:
        """Install a batch of snapshots (one POST = one engine step).
        A snapshot with fewer accepted tokens than the stored one is a
        late duplicate from a previous replica life — dropped, the
        journal only moves forward."""
        n = 0
        with self._lock:
            for rec in records:
                rid = rec.get("request_id")
                if not rid:
                    continue
                old = self._records.get(rid)
                if old is not None and (len(old.get("accepted", ()))
                                        > len(rec.get("accepted", ()))):
                    continue
                self._records[rid] = rec
                self._records.move_to_end(rid)
                n += 1
            while self.capacity > 0 and len(self._records) > self.capacity:
                self._records.popitem(last=False)
                telemetry.counter_add("session.evicted", 1)
        if n:
            telemetry.counter_add("session.journaled", n)
        return n

    def get(self, request_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._records.get(request_id)
            return dict(rec) if rec is not None else None

    def pop(self, request_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._records.pop(request_id, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)
