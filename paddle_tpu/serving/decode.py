"""DecodeEngine — continuous-batching autoregressive generation over a
paged KV cache.

The ServingEngine (engine.py) micro-batches single-shot predictors; this
engine is its generative twin for the workload that dominates LLM
serving traffic: many concurrent requests each producing tokens one
step at a time. Orca-style continuous batching + vLLM-style paged KV
caching, on the repo's frozen-program stack:

* **The served model** comes through one seam (served_model.py):
  ``model_cfg.served()`` gives the three program builders, the parameter
  layout and, layer by layer, what the model keeps of a sequence — a
  context's pages, for a window layer a ring of pages, for a latent layer
  one array a token and not K and V (kv_cache.py). models/decoder_lm.py,
  models/afmoe.py and models/kimi_k2.py all come this way; the engine has
  no second path.
* **Phase split.** An admitted request first runs ONE prefill program
  (the model's ``build_prefill_program``, padded to a prompt-length
  bucket) that writes the whole prompt's K/V into its pool pages and
  yields the first sampled token; from then on it only rides the shared
  decode step.
* **Continuous batching.** Decode state lives in a slot array of
  ``max_slots`` recycled slots. Every iteration the scheduler retires
  finished/expired sequences (freeing their pages) and admits queued
  requests into the vacated slots at the step boundary — no
  drain-and-refill: a long generation never holds the batch hostage for
  a short one. One ``jax.jit`` entry per slot-array bucket
  (FLAGS_decode_buckets; the default is a single fixed bucket of
  ``max_slots``, which ALSO pins the step shapes — per-row math is then
  independent of occupancy, keeping continuous-batched generations
  BITWISE-identical to sequential one-request-at-a-time decode).
* **One step ahead.** The loop dispatches step k+1 before it fetches step
  k's tokens, so the host's feed, launch, fetch and accept run while the
  device has a step to work on. A step's tokens stay on the device: the
  step program writes them to ``last_tokens`` (int32 ``[max_slots]``,
  indexed by the slot a seated request keeps until it retires) and the next
  step reads a continuing row's input token from there; the host feeds a
  token only for a row's first step. Which rows step k+1 holds is decided
  from the host's counts: a request whose ``max_new_tokens`` step k reaches
  is left out; one that may end on ``eos_id`` or on its deadline is
  dispatched on speculation, and if step k ended it, its token of k+1 is
  thrown away when k+1 is accepted (``decode.rows_discarded``). An
  admission keeps the pipe full: the prefill is dispatched behind step k,
  step k+1 behind the prefill, k's tokens are accepted, and only then does
  the host wait for the prefill's logits, choose the first token and seat
  the request, under k+1; it joins at k+2. The pipe is drained (the step
  in flight fetched and accepted) where the host must block with nothing
  queued behind: on a second prefill of one poll, on a shipment, before
  the thread sleeps and before it ends. A failed step fails the rows of
  both steps. Depth is one, always.
* **A draft module** (``ServedModel.draft``: models/xing4.py's
  multi-token-prediction module; no flag turns it on, a model that has one
  drafts). The step program then runs TWO positions a slot, the slot's last
  accepted token and the module's draft of the next, verifies the draft by
  the exact rule of speculative sampling (sampling.verify_tokens), runs the
  module on both positions and draws the next draft (`draft_step`): a row
  advances by one or two tokens a step and the host learns by which only
  when it fetches. So a slot's position, its draft and the distribution the
  draft was drawn from live on the device beside ``last_tokens``; the host
  feeds a token and a position for a row's first step alone (which has no
  draft and yields one token), dispatches a row while its count MAY still
  fall short (one token a step in flight at the least), and on the fetch
  (``[slots, 2]`` tokens, ``[slots]`` counts) takes a row's tokens in order
  until the request ends: the surplus TOKENS are thrown away
  (``decode.tokens_discarded``). A rejected draft's latent row is
  overwritten by the next step, which starts at its position; a step
  dispatched ahead may write up to two rows past a request's last kept
  token, which land in its own reserved pages or, behind them, in the
  scratch page (the page tables are that much wider).
* **Paged KV cache.** Pages come from the preallocated
  ``KVPagePool`` (kv_cache.py); the pool arrays are threaded through
  the step program and donated to the jit so XLA updates them in place.
  Pool bytes book into the PR 10 HBM ledger (``mem.serving.kv_*``) and
  a request whose worst-case page need can never fit is refused at
  submit with a typed ``KVCacheExhaustedError`` — admission control,
  not a device OOM.
* **int8 weight-only serving** as a first-class config
  (``weight_quant="int8"`` / FLAGS_decode_weight_quant): dense weights
  are stored int8 with per-output-channel scales and dequantized through
  ops/quant_ops.py ``dequantize_weight`` inside the programs.
* **Deadline-aware scheduling** reusing serving/admission.py: queued
  requests expire at dequeue (AdmissionQueue.poll), running requests
  are checked at STEP granularity — an expired generation retires
  mid-flight with ``DeadlineExceededError`` and frees its pages without
  draining the batch.

Who samples what (a model with a draft module: two paragraphs down): the
decode-step program ends in ``sampling.
sample_tokens`` and returns ``[slots]`` int32 — every token after a
request's first is chosen on the device (greedy argmax, or softmax at the
row's temperature and the inverse CDF at the row's uniform), and the
``[slots, vocab]`` logits never cross to the host. The uniform is drawn on
the host while the step's feed is built, one per token in token order,
from the request's own pinned ``np.random.RandomState``, so the random
stream, the journal's ``rng_state`` and the seed contract are the host's
as before (the draw made for a step in flight is ahead of the accepted
tokens: a journal record holds the state noted before it). A request's FIRST token is still chosen on the host
(``GenerationRequest.sample``) from the logits row that the prefill,
chunked-prefill and shipped-prefill paths hand over. Either way token
selection is a function of the row's own logits, temperature and the
request's own seed — scheduling cannot perturb it.

With a draft module the step program ends in ``sampling.verify_tokens``
and ``sampling.draft_tokens`` (on the chip as ops/pallas/draft_tail.py's two
kernels, which read each logits row once and keep q in place by slot) and
returns ``[slots, 2]`` tokens and ``[slots]`` counts. A row a step takes
FOUR uniforms of the request's stream, drawn on the host while the feed is
built, in the order accept, redraw, second position, next draft, whichever
of them the step comes to use: so the stream's state after a step does not
depend on its outcome, and a request's tokens are a function of its own
logits, temperature and seed.
The delivered tokens are distributed exactly as the one-token sampler's
(the rule is exact); greedy rows accept a draft where it is the model's own
argmax and deliver the model's argmaxes either way. A journal record of a
drafting request holds, as before, every accepted token and the stream's
state before the draws of the step in flight, and ``last_step_tokens``, how
many of them the last whole step delivered; the survivor prefills them,
chooses its next token on the host from the restored stream and drafts on
from there: the record decides the resumed tail (greedy: the uninterrupted
one), which is a sample of the same distribution, not the dead replica's
own continuation, whose pending draft died with it.

Fault sites (core/faults.py, tools/chaos_check.py --decode):
``decode.step`` fails the in-flight step (every affected request gets a
per-request error, pages are freed, the queue keeps moving) and
``decode.kv_alloc`` fails one request's page allocation.

Telemetry: decode.requests/rejects/deadline_expired (admission),
decode.prefills / prefill_tokens / prefill_bucket_tokens / steps / tokens /
retired / errors / kv_refusals / kv_pages_allocated / kv_pages_freed
counters (steps, tokens and batch_occupancy count at accept time what was
delivered),
decode.steps_ahead (steps dispatched while the step before was not yet
fetched: over decode.steps the share of steps that ran ahead; the rest
went into an empty pipe: an engine's first step, one behind a second
prefill of one poll or a shipment) and
decode.rows_discarded (speculative rows whose token was thrown away); for a
model with a draft module decode.draft_proposed and decode.draft_accepted
(drafts verified for rows that were still live, and those the model
accepted), decode.rows_stepped (live rows a step: decode.tokens over it is
the tokens a row took a step, whose per-step mean is the histogram
decode.tokens_per_row_step), decode.tokens_discarded (tokens past
``max_new_tokens`` or ``eos_id``, and every token of a row whose request had
ended), while decode.tokens, decode.token_gap_ms and decode.batch_occupancy
keep their meaning (delivered tokens; rows a step),
decode.prefill_ms + decode.step_ms timers, decode.batch_occupancy
histogram; for a model with ring layers decode.rows_past_window and, for
one with ring or latent layers, decode.kv_tokens_attended (cached tokens
read a step, over rows and layers: a ring layer reads min(context,
window), a latent layer its context's latents, ONCE a row a step whatever
the positions it verifies; with latent RINGS
decode.ring_latent_rows_attended is the rings' part), and whatever counters
the model's step program returns beside its tokens (``ServedModel.step_counters``: the
routed-expert counts of models/afmoe.py), fetched in the step's one fetch; decode.active_slots + decode.queue_depth +
mem.serving.kv_* gauges — rendered by tools/perf_report.py's "Decode"
section and /v1/stats. Every accepted step also records the phases of the
loop since the one before, which add up to it: decode.loop_ms (the step
period, once the loop runs ahead) = decode.admit_ms + decode.feed_ms +
decode.step_ms (the launch of the next step and the wait for this one's
tokens: decode.fetch_ms, where the host waits out what is left of the
step program) + decode.sample_ms (accepting the tokens row by row) +
decode.retire_ms + decode.other_ms; per token decode.token_gap_ms, per request
decode.queue_wait_ms (submit to the start of its prefill). Each timer is
also a ``TraceAnnotation`` of the same name in a running profiler trace.

What an admission costs, one quiet observation a prefill each, all inside
decode.admit_ms: decode.prefill_feed_ms (the prefix lookup, the pages, the
slot, the numpy feed, up to the dispatch), decode.prefill_wait_ms (the
host's wait for the prefill program with no step's tokens left to accept:
the time every live slot stands still; the second half of decode.prefill_ms)
and decode.seat_ms (the first token's host sample, the seat or the retire);
the counter decode.prefill_bucket_tokens beside decode.prefill_tokens (the
tokens the prefill programs computed, padding and all, against the tokens
asked for). With every accepted step decode.cpu_ms, the engine thread's own
CPU time (``time.thread_time``) over the iterations decode.loop_ms covers:
loop less cpu less the two waits for the device (decode.fetch_ms,
decode.prefill_wait_ms) is time the thread waited for the interpreter lock
or a core. ``stats()`` gives the shares (``admission_cost``). In a trace a
request's spans (decode.prefill_ms both halves, with ``bucket=`` and
``tokens=``; the feed and seat parts, which are decode.admit_ms spans with
``part=``; decode.retire_ms) carry its ``rid=``; a step's spans belong to
every row and stay bare.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from ..core import costmodel, faults, telemetry
from ..core import flags as _flags
from ..core.flags import flag as _flag
from .admission import (AdmissionQueue, DeadlineExceededError,
                        EngineClosedError, InferenceRequest,
                        KVCacheExhaustedError, ServingError)
from .health import DRAINING, READY, STOPPED, HealthState
from .kv_cache import PagedKVCache
from .served_model import DRAFT_SPARE_TOKENS
from .prefix_store import PrefixStore


# the phases that tile one iteration of DecodeEngine._loop, with
# decode.other_ms for what lies between them (decode.fetch_ms is a child of
# decode.step_ms, decode.prefill_ms one of decode.admit_ms; the spans of
# decode.retire_ms lie inside decode.sample_ms's, whose histogram holds the
# accepting of the step's tokens without them)
_LOOP_PHASES = ("decode.admit_ms", "decode.feed_ms", "decode.step_ms",
                "decode.sample_ms", "decode.retire_ms")

# a request's process-unique integer: what its spans share in a trace
_REQUEST_IDS = itertools.count(1)


@contextlib.contextmanager
def _admission_part(part: str, req: "GenerationRequest"):
    """One request's share of an admission outside its prefill's own span:
    ``prefill_feed`` before the dispatch, ``seat`` after the wait. Yields
    the dict that takes its ms. In a trace it is a decode.admit_ms span
    INSIDE the loop's (``part=``, ``rid=``), not one of a name of its own:
    a fill's one decode.admit_ms span lasts seconds and a span that
    straddles an edge of a traced window is not in the trace, so without
    these the device's gaps between a fill's prefills are under no span at
    all; under the phase's name they are where a steady loop's are, and
    the names the device may idle under stay the ones
    tests/benchmark_suite/test_bench_phase_readers.py holds them to. The
    histograms are decode.<part>_ms, the caller's to observe."""
    ms: Dict[str, float] = {}
    with telemetry.timer("decode.admit_ms", into=ms, part=part, rid=req.rid):
        yield ms


def _pow2_ladder(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return sorted(set(out))


def admission_cost(counters: Dict[str, Any],
                   hists: Dict[str, Any]) -> Dict[str, float]:
    """What admissions cost the loop, in % (``stats()``, /v1/stats and
    tools/perf_report.py's "Decode" section; the benchmark's readers take
    the same histograms over their window): ``prefill_wait_share``, the
    share of the loop's wall time in which every live slot stood still for a
    prefill program (decode.prefill_wait_ms over decode.loop_ms);
    ``engine_cpu_share``, the engine thread's own CPU time over that wall
    time (decode.cpu_ms: 100 is a loop the host bounds);
    ``prefill_padded_token_share``, the share of the tokens the prefill
    programs computed that were a bucket's padding. A share whose histogram
    or counter has nothing yet is left out."""
    out: Dict[str, float] = {}
    loop_ms = (hists.get("decode.loop_ms") or {}).get("total")
    for key, name in (("prefill_wait_share", "decode.prefill_wait_ms"),
                      ("engine_cpu_share", "decode.cpu_ms")):
        h = hists.get(name)
        if loop_ms and h and h["count"]:
            out[key] = round(100.0 * h["total"] / loop_ms, 2)
    computed = counters.get("decode.prefill_bucket_tokens")
    if computed:
        out["prefill_padded_token_share"] = round(
            100.0 * (1.0 - counters.get("decode.prefill_tokens", 0)
                     / computed), 2)
    return out


class DecodeConfig:
    """Decode-engine knobs; defaults come from the FLAGS_decode_*
    registry. ``continuous=False`` turns the scheduler into the
    drain-and-refill static-batching baseline (admit a wave, run it to
    completion, only then admit the next) — the control arm of
    tools/bench_serving.py --generate."""

    def __init__(self, max_slots: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 kv_ring_pages: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 max_new_tokens: Optional[int] = None,
                 weight_quant: Optional[str] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 continuous: bool = True,
                 prefix_cache: Optional[bool] = None,
                 role: Optional[str] = None,
                 prefill_urls: Optional[Any] = None):
        self.max_slots = int(_flag("decode_max_slots") if max_slots is None
                             else max_slots)
        # strict typed parse (core/flags.py): zero-valued or
        # non-monotonic lists raise BucketConfigError; the set must end
        # exactly at max_slots (the fixed-step-shape contract).
        # default: ONE fixed bucket — constant step shapes keep
        # continuous batching bitwise-identical to sequential decode
        if buckets is None:
            buckets = _flags.parse_buckets(_flag("decode_buckets"),
                                           "FLAGS_decode_buckets",
                                           cover=self.max_slots,
                                           cover_exact=True)
        else:
            buckets = _flags.parse_buckets(buckets, "buckets",
                                           cover=self.max_slots,
                                           cover_exact=True)
        self.buckets = buckets or [self.max_slots]
        self.page_size = int(_flag("decode_page_size") if page_size is None
                             else page_size)
        self.kv_pages = int(_flag("decode_kv_pages") if kv_pages is None
                            else kv_pages)
        # pages of the ring class, for a model with window layers
        # (kv_cache.PagedKVCache); None: a ring for every slot
        self.kv_ring_pages = None if kv_ring_pages is None \
            else int(kv_ring_pages)
        self.max_queue_depth = int(
            _flag("decode_max_queue_depth") if max_queue_depth is None
            else max_queue_depth)
        self.default_deadline_ms = float(
            _flag("decode_default_deadline_ms") if default_deadline_ms is None
            else default_deadline_ms)
        self.max_new_tokens = int(
            _flag("decode_max_new_tokens") if max_new_tokens is None
            else max_new_tokens)
        self.weight_quant = str(
            _flag("decode_weight_quant") if weight_quant is None
            else weight_quant).lower()
        if self.weight_quant not in ("none", "int8"):
            raise ValueError(f"decode weight_quant must be 'none' or "
                             f"'int8', got {self.weight_quant!r}")
        self.prefill_buckets = sorted(set(int(b) for b in prefill_buckets)) \
            if prefill_buckets else None   # None -> pow2 up to max_seq_len
        self.continuous = bool(continuous)
        # prefix sharing + disaggregated-serving role (serving/
        # prefix_store.py, serving/disagg.py)
        self.prefix_cache = bool(
            _flag("decode_prefix_cache") if prefix_cache is None
            else prefix_cache)
        self.role = str(_flag("decode_role") if role is None
                        else role).lower()
        if self.role not in ("unified", "prefill", "decode"):
            raise ValueError(f"decode role must be 'unified', 'prefill' "
                             f"or 'decode', got {self.role!r}")
        if prefill_urls is None:
            prefill_urls = _flag("disagg_prefill_urls")
        if isinstance(prefill_urls, str):
            prefill_urls = [u.strip() for u in prefill_urls.split(",")
                            if u.strip()]
        self.prefill_urls = [str(u) for u in prefill_urls]

    def bucket(self, active: int) -> int:
        for b in self.buckets:
            if active <= b:
                return b
        return self.buckets[-1]


class GenerationRequest(InferenceRequest):
    """One queued/running generation: prompt + sampling params + the
    engine-side decode state. Rides the shared AdmissionQueue (deadline
    at dequeue, typed backpressure); ``result()`` returns the generated
    token ids as an int32 array. ``ttft_ms`` / ``token_walls`` expose
    time-to-first-token and per-token arrival times for the bench
    harness."""

    __slots__ = ("prompt", "max_new_tokens", "temperature", "seed",
                 "eos_id", "tokens", "token_walls", "t_submit", "t_first",
                 "pages", "table_row", "pos_next", "last_token",
                 "shared_blocks", "_rng", "session_id", "prior", "seq",
                 "stop_at_eos", "ring_pages", "ring_row", "first_logits",
                 "slot", "carried", "ahead", "_rng_cut", "final_state",
                 "final_pages", "rid", "steps", "last_step_tokens",
                 "step_outputs")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 deadline: Optional[float], temperature: float = 0.0,
                 seed: Optional[int] = None, eos_id: Optional[int] = None,
                 trace: Optional[Any] = None,
                 session_id: Optional[str] = None,
                 prior: Optional[np.ndarray] = None):
        super().__init__({"prompt": prompt}, 1, deadline, trace=trace)
        self.prompt = prompt
        # ``rid=`` of this request's spans in a profiler trace (its prefill's
        # two halves, what the admission does before and after them, its
        # retiring): they tie a ``prefill_p<bucket>`` program run of the
        # device plane to the request that caused it
        self.rid = next(_REQUEST_IDS)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = seed
        self.eos_id = eos_id
        self.stop_at_eos = eos_id is not None
        self.tokens: List[int] = []
        self.token_walls: List[float] = []
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        # session-failover identity (serving/session.py): ``prior`` is
        # the accepted tokens from a previous replica life — the engine
        # prefills ``seq`` (prompt + prior) and generates only the
        # remainder; the router re-joins the full stream
        self.session_id = session_id
        self.prior = (np.zeros(0, np.int32) if prior is None
                      else np.asarray(prior, np.int32).reshape(-1))
        self.seq = (prompt if self.prior.size == 0
                    else np.concatenate([prompt, self.prior]))
        # engine-side slot state (worker-thread-owned once admitted)
        self.pages: List[int] = []
        self.table_row: Optional[np.ndarray] = None
        # the ring class's pages and table row (window layers), if any
        self.ring_pages: List[int] = []
        self.ring_row: Optional[np.ndarray] = None
        # False, or True to keep the prefill's logits row (float32) here:
        # how a check compares logits where the engine gives them out
        self.first_logits: Any = False
        # False, or True to keep the slot's per-slot state arrays as they
        # stand when the request retires (a model with recurrent state)
        self.final_state: Any = False
        # False, or True to keep the request's own pages of the context
        # class as they stand when it retires: how a check compares what
        # the cache holds, not only what was computed from it
        self.final_pages: Any = False
        # a drafting model's: the steps accepted so far (the first has no
        # draft), the tokens the last of them delivered, and False or the
        # list that takes a record of every step (`_keep_step`)
        self.steps = 0
        self.last_step_tokens = 0
        self.step_outputs: Any = False
        # the position and, for a request's first step, the token its next
        # step is fed; ``pos_next`` moves on when a step is DISPATCHED
        self.pos_next = 0
        self.last_token = 0
        # the step entry's ``last_tokens`` index while seated; whether the
        # request's input token lives there (it has been in a dispatched
        # step); tokens of steps in flight, dispatched and not yet accepted
        self.slot: Optional[int] = None
        self.carried = False
        self.ahead = 0
        # a journaled request's sampler state before the draw made for a
        # step in flight (journal_record)
        self._rng_cut: Any = None
        # prefix-store block hashes this request holds a reference on
        # (serving/prefix_store.py) — released at retirement
        self.shared_blocks: List[str] = []
        self._rng = np.random.RandomState(seed) if seed is not None \
            else None

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.t_first is None:
            return None
        return (self.t_first - self.t_submit) * 1e3

    def sample(self, logits_row: np.ndarray) -> int:
        """Host-side choice of this request's FIRST token, from the logits
        row a prefill (local, chunked or shipped) hands over; every later
        token is chosen inside the step program by
        ``sampling.sample_tokens``, which states the same mathematics in
        float32. Greedy when temperature <= 0 (argmax, lowest index on
        ties); else softmax-at-temperature inverse-CDF driven by the
        pinned per-request RandomState, whose next draws ``_run_step``
        feeds to the device one per token."""
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        if self._rng is None:
            raise ValueError("sampled decoding (temperature > 0) needs a "
                             "per-request seed for reproducible serving")
        z = logits_row.astype(np.float64) / self.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        # clamp: a draw past the fp cumsum tail must not index vocab+1
        idx = np.searchsorted(np.cumsum(p), self._rng.random_sample())
        return int(min(idx, len(p) - 1))

    def finished(self) -> bool:
        return bool(self.tokens) and (
            len(self.tokens) >= self.max_new_tokens
            or (self.eos_id is not None and self.tokens[-1] == self.eos_id))

    def journal_record(self, page_size: int,
                       now: Optional[float] = None) -> Dict[str, Any]:
        """Snapshot everything a survivor needs to continue this
        generation bitwise-identically (serving/session.py): the prompt
        (plus its page-chain hash for affinity), EVERY accepted token —
        prior lives included — the sampler RNG state after those draws,
        and the deadline remainder. The draw made ahead for a step in
        flight is not among them: the state is the one noted before it
        (``_rng_cut``). Engine-thread-only (reads _rng)."""
        from .prefix_store import prefix_chain_hash

        rem = None
        if self.deadline is not None:
            rem = max(0.0, (self.deadline
                            - (time.monotonic() if now is None else now))
                      * 1e3)
        from .session import pack_rng_state

        rng = self._rng
        if self.ahead and self.temperature > 0:
            rng = np.random.RandomState()
            rng.set_state(self._rng_cut)
        return {
            "request_id": self.session_id,
            "prompt": [int(t) for t in self.prompt],
            "prefix_hash": prefix_chain_hash(self.prompt, page_size),
            "accepted": [int(t) for t in self.prior] + list(self.tokens),
            "max_new_total": int(self.prior.size) + self.max_new_tokens,
            "temperature": self.temperature,
            "seed": self.seed,
            "stop_at_eos": self.stop_at_eos,
            "rng_state": pack_rng_state(rng)
            if self.temperature > 0 else None,
            "deadline_remaining_ms": rem,
            # a drafting model's step delivers one or two tokens: how many
            # of `accepted` the last whole step brought (0: no such model)
            **({"last_step_tokens": self.last_step_tokens}
               if self.last_step_tokens else {}),
        }


class ShipPrefillRequest(InferenceRequest):
    """Disaggregated-serving prefill work item (serving/disagg.py): a
    prefill-tier replica runs the prompt's prefill, reads the finished
    KV pages back to host, and resolves with the serialized shipment
    bytes (versioned wire format, per-page CRC). Rides the same
    AdmissionQueue as generations so every program run stays on the
    worker thread that owns the donated pool arrays."""

    __slots__ = ("prompt",)

    def __init__(self, prompt: np.ndarray, deadline: Optional[float]):
        super().__init__({"prompt": prompt}, 1, deadline)
        self.prompt = prompt


class _Prefilled(NamedTuple):
    """A request whose prefill is dispatched and whose logits row the host
    has not fetched yet."""

    req: "GenerationRequest"
    pages: List[int]                # what _admit allocated, to free if it fails
    seat: Any                       # () -> None: wait for the row, seat req


class _StepInFlight(NamedTuple):
    """A dispatched decode step whose tokens the host has not fetched."""

    rows: List[GenerationRequest]   # in the program's row order
    bucket: int
    chosen: Any                     # device int32 [bucket] (+ step_counts)
    positions: np.ndarray           # the rows' positions, int32 [len(rows)]
    kept: Any = None                # a drafting step's device outputs, if a
    #                                 row keeps them, and its uniforms
    uniforms: Any = None


def run_program(block, params, pools, feed):
    """Run one program's block over the parameters, the pools and its feed:
    (its environment, the pools with every array the program wrote back,
    ``<name>_out``, in the place of the one it read)."""
    from ..core.executor import run_block

    env = dict(params)
    env.update(pools)
    env.update(feed)
    run_block(block, env)
    return env, dict(pools, **{n: env[n + "_out"] for n in pools
                               if n + "_out" in env})


def draft_step(model, kv, weight_quant: str, bucket: int, step_block):
    """The step of a model with a draft module, one jitted program:
    (params, pools, feed, last_tokens, spec) -> (fetch, pools,
    last_tokens, spec, kept). A row is fed its last accepted token and
    the draft of the next (both from the device once the row is
    carried; its first step has a token and a position from the host
    and no draft), the held layers run both positions, the acceptance
    rule (sampling.verify_tokens) yields one or two tokens, and the
    module runs on both positions with the tokens AFTER them, so that
    its own latent layer has a row for every accepted position, and
    draws the next draft from its distribution at the newest accepted
    one (a first step's module pass starts one position back, on the
    prefill's last hidden state and the first token). ``fetch`` is
    int32: the tokens [bucket x 2], the counts [bucket], the two
    programs' counters; ``kept`` stays on the device unless a row asked
    for it (``keep_step_outputs``). ``step_block`` is the block of the
    model's step program at this bucket (tools/rehearse_served.py compiles
    this same step for a described chip)."""
    import jax.numpy as jnp

    from ..ops.pallas.draft_tail import draft_next, draft_verify

    program, _feeds, _fetches = model.build_draft_program(bucket, kv,
                                                          weight_quant)
    draft_block = program.global_block()

    def pairs(a, b):                # [B, ...] x 2 -> [2B, ...]
        return jnp.stack([a, b], axis=1).reshape((-1,) + a.shape[1:])

    def step(params, pools, feed, last_tokens, spec):
        slot, carried = feed["carry"][:, 0], feed["carry"][:, 1] > 0

        def of_slot(a):
            return a.at[slot].get(mode="clip")

        token = jnp.where(carried, of_slot(last_tokens), feed["tokens"])
        pos = jnp.where(carried, of_slot(spec["pos"]), feed["positions"])
        draft = jnp.where(carried, of_slot(spec["draft"]), 0)
        table = jnp.repeat(feed["page_table"], 2, axis=0)
        live = feed["page_table"][:, 0] > 0
        env, pools = run_program(step_block, params, pools, {
            "tokens": pairs(token, draft), "positions": pairs(pos, pos + 1),
            "page_table": table, "live": pairs(live, live & carried)})
        hidden = env["hidden"].reshape(bucket, 2, -1)
        temperature = feed["sampling"][:, 0]
        # the rule on the logits as the head left them, q read at the slot
        tokens, count, q_read = draft_verify(
            env["logits"], spec["q"], slot, draft, carried, temperature,
            feed["sampling"][:, 1:4])
        # the module: rows (pos, pos + 1) with the tokens after them; a
        # first step's (pos - 1, pos), from the prefill's hidden state
        first = ~carried
        start = pos - first.astype(jnp.int32)
        fed = jnp.where(first[:, None],
                        jnp.stack([token, tokens[:, 0]], axis=1), tokens)
        state = jnp.where(
            first[:, None, None],
            jnp.stack([of_slot(spec["hidden"]), hidden[:, 0]], axis=1),
            hidden)
        newest = jnp.where(first, 1, count - 1)
        env2, pools = run_program(draft_block, params, pools, {
            "tokens": fed.reshape(-1), "positions": pairs(start, start + 1),
            "page_table": table, "live": pairs(live, live & (newest > 0)),
            "hidden": state.reshape(2 * bucket, -1),
            "pick": 2 * jnp.arange(bucket, dtype=jnp.int32) + newest})
        # q written where the slot's last one was read
        next_draft, q = draft_next(env2["draft_logits"], spec["q"], slot,
                                   temperature, feed["sampling"][:, 4])
        last = jnp.take_along_axis(tokens, (count - 1)[:, None],
                                   axis=1)[:, 0]
        last_tokens = last_tokens.at[slot].set(last, mode="drop")
        spec = dict(
            spec, pos=spec["pos"].at[slot].set(pos + count, mode="drop"),
            draft=spec["draft"].at[slot].set(next_draft, mode="drop"), q=q)
        fetch = jnp.concatenate([
            tokens.reshape(-1), count,
            env["step_counts"].astype(jnp.int32),
            env2["step_counts"].astype(jnp.int32)])
        # arrays the step has anyway, as it has them (`_keep_step` indexes
        # them for the rows that asked): the logits [2B, V], q's rows as
        # the rule read them, in the state's order
        kept = {"logits": env["logits"], "draft": draft, "pos": pos,
                "q": q_read, "draft_logits": env2["draft_logits"]}
        return fetch, pools, last_tokens, spec, kept

    return step


class DecodeEngine:
    """Thread-safe generative front end over a frozen decoder-LM param
    set. Lifecycle mirrors ServingEngine: ``start()`` → concurrent
    ``submit``/``generate`` → ``close(drain=True)``. One worker thread
    owns the slot array, the pools and every program run."""

    def __init__(self, model_cfg: Any, params: Dict[str, Any],
                 config: Optional[DecodeConfig] = None, version: int = 0):
        import jax.numpy as jnp

        self.model_cfg = model_cfg
        self.model = model_cfg.served()
        self.config = config or DecodeConfig()
        params = self.model.prepare_params(params, self.config.weight_quant)
        if self.config.weight_quant == "int8":
            telemetry.counter_add("decode.int8_weight_tensors",
                                  sum(1 for n in params
                                      if n.endswith("_w_i8")))
        self._params = {n: jnp.asarray(v) for n, v in params.items()}
        # one pool a class of pages; `pool` is the context class, which is
        # the only one a model without window layers has
        self.kv = PagedKVCache(self.model.cache_layout(),
                               self.config.page_size, self.config.kv_pages,
                               self.config.kv_ring_pages,
                               dtype=self.model.kv_dtype,
                               slots=self.config.max_slots)
        self.pool = self.kv.context
        # a model that keeps other than K and V of every token in a
        # context's pages (a window's ring, latent pages): what a step
        # attends is counted for it (decode.kv_tokens_attended)
        self._ring_or_latent = self.kv.ring is not None \
            or any(self.kv.context.latent)
        # what a step advances a live row, by kind of state-class layer:
        # rows of a RECURRENT state, and rows of a conv tail that is a
        # layer's whole state (a gated short convolution)
        tails = len(self.kv.tail_layers)
        self._state_row_counters = [
            (name, n) for name, n in (
                ("decode.state_rows_updated",
                 len(self.kv.state_layers) - tails),
                ("decode.conv_rows_updated", tails)) if n]
        if self.kv.has_state and (
                self.config.prefix_cache or self.config.role != "unified"):
            # what a slot keeps (a recurrent state, a conv tail, or a tail
            # alone) has no per-token pages: the prefix store has nothing
            # of it to share and no chunk program resumes one (a prompt's
            # pages without the state after them are half a prefix), and a
            # shipment carries pages alone. A tail alone is a few rows a
            # layer, so a snapshot of it beside a prefix's pages and a
            # chunk program fed the tail its predecessor left are the
            # shorter way there; both are later work
            raise ValueError(
                "a model with per-slot state (a recurrent state and a conv "
                "tail a slot, or a conv tail alone) runs unified and "
                "without the prefix store: what a slot keeps has no "
                "per-token pages to share or to ship, and no chunk program "
                "carries a state or a tail over")
        if self._ring_or_latent and (
                self.config.prefix_cache or self.config.role != "unified"):
            # the prefix store shares a prompt's full pages between
            # requests and a shipment installs a prompt's pages; a ring is
            # a slot's own and is overwritten as its window slides, and no
            # chunk of a prompt attends a latent prefix yet
            raise ValueError(
                "a model with ring (window) or latent layers runs unified "
                "and without the prefix store: neither the prefix store "
                "nor disaggregated prefill handles ring or latent pages "
                "yet")
        self._pools = self.kv.make_arrays()
        # a model with a draft module is stepped two positions a slot and
        # advances by one or two tokens; what the host can no longer count
        # lives on the device (``_spec``), and its page tables reach the
        # rows a step ahead may write past a request's last kept token
        self._draft = bool(self.model.draft)
        # a model whose step hands its logits out beside the tokens, for a
        # request that keeps a record of its steps (`keep_step_outputs`)
        self._keeps_logits = bool(self.model.keeps_step_logits) \
            and not self._draft
        self._mp = -(-(model_cfg.max_seq_len + (
            DRAFT_SPARE_TOKENS if self._draft else 0))
            // self.config.page_size)
        self._feed_names: Dict[Any, Any] = {}   # (phase, bucket) -> names
        self.queue = AdmissionQueue(self.config.max_queue_depth,
                                    self.config.default_deadline_ms,
                                    metric_prefix="decode")
        if self.config.prefill_buckets is None:
            self.config.prefill_buckets = _pow2_ladder(
                min(8, model_cfg.max_seq_len), model_cfg.max_seq_len)
        # content-addressed prefix sharing: admission consults the store
        # for the longest cached prefix and prefills only the suffix
        # through the page-chunked prefill program
        self.prefix_store = PrefixStore(self.pool) \
            if self.config.prefix_cache else None
        self._active: List[GenerationRequest] = []
        # a seated request's slot: its index into ``_last_tokens``, the
        # device's own copy of every slot's latest token (int32
        # [max_slots]), which each step program reads its continuing rows'
        # input from and writes its chosen tokens to
        self._free_slots = list(range(self.config.max_slots))[::-1]
        self._last_tokens = jnp.zeros((self.config.max_slots,), jnp.int32)
        # a drafting model's slots, beside ``_last_tokens`` (by slot, on the
        # device, read and written by the step program): the position the
        # slot's last accepted token is fed at, the draft of the token after
        # it and the distribution q it was drawn from; ``hidden`` is the
        # prefill's, the held layers' output at the prompt's last position,
        # which the module's pass of the slot's first step reads
        self._spec: Dict[str, Any] = {}
        if self._draft:
            from ..ops.pallas.draft_tail import q_state

            n = self.config.max_slots
            self._spec = {
                "pos": jnp.zeros((n,), jnp.int32),
                "draft": jnp.zeros((n,), jnp.int32),
                "q": q_state(n, model_cfg.vocab_size),
                "hidden": jnp.zeros((n, model_cfg.hidden_size),
                                    jnp.float32)}
        # the step that was dispatched and whose tokens are not fetched yet
        self._inflight: Optional[_StepInFlight] = None
        # the prefill that was dispatched and whose request is not seated
        self._prefilled: Optional[_Prefilled] = None
        self._entries: Dict[Any, Any] = {}   # (phase, bucket) -> jitted fn
        # the set-up record of each entry (telemetry.CompileRecord), as
        # stats()["warmup"] shows them
        self._warmup: List[Dict[str, Any]] = []
        self._thread: Optional[threading.Thread] = None
        self.health = HealthState()
        self.version = int(version)
        # session-failover journal (serving/session.py): a callable
        # taking a list of journal records — in-process the router's
        # SessionJournal.update, cross-process an HTTP POST. None (the
        # default) disables journaling entirely.
        self.journal_sink = None

    # -- client surface ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               temperature: float = 0.0, seed: Optional[int] = None,
               stop_at_eos: bool = True,
               request_id: Optional[str] = None,
               prior_tokens: Optional[Sequence[int]] = None,
               rng_state: Optional[Any] = None,
               keep_first_logits: bool = False,
               keep_final_state: bool = False,
               keep_final_pages: bool = False,
               keep_step_outputs: bool = False) -> GenerationRequest:
        """Enqueue one generation (non-blocking). ``prompt`` is a 1-D
        int token-id array. Raises ValueError (malformed / over the
        model length), KVCacheExhaustedError (can never fit the KV
        pool), ServerOverloadedError, EngineClosedError.

        ``request_id`` opts the request into session journaling
        (serving/session.py). ``prior_tokens``/``rng_state`` re-admit a
        journaled session after its replica died: the engine prefills
        prompt+prior (prefix-hit or chunked cold re-prefill — bitwise
        the same KV either way), restores the sampler RNG mid-stream
        and generates only the remaining ``max_new_tokens``.
        ``keep_first_logits`` leaves the prefill's float32 logits row on
        the request (``first_logits``) beside its tokens;
        ``keep_final_state`` leaves its slot's per-slot state arrays as they
        stand when it retires (``final_state``: name -> the slot's entry;
        for a request that ends on its count, the state after its last FED
        token, the one before its last chosen one); ``keep_final_pages``
        leaves its own pages of the context class likewise
        (``final_pages``: pool array name -> [its pages, page, kv_dim] in
        the order of its page table, token t at ``[t // page, t % page]``;
        for a model with rings also its ring's pages, in the ring's order:
        token t at index ``t mod (ring pages x page)``);
        ``keep_step_outputs`` (a model with a draft module) leaves a record
        of each of its steps (``step_outputs``: the position, the draft and
        the q it was drawn from, the logits of both positions, the module's
        logits, the step's uniforms and tokens); of a model whose step
        program hands its logits out (``ServedModel.keeps_step_logits``)
        the record is the step's position, its float32 logits row and the
        token chosen from it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt needs at least one token")
        prior = (np.zeros(0, np.int32) if prior_tokens is None
                 else np.asarray(prior_tokens, np.int32).reshape(-1))
        if max_new_tokens is None:
            max_new_tokens = self.config.max_new_tokens
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        total = int(prompt.size) + int(prior.size) + max_new_tokens
        if total > self.model_cfg.max_seq_len:
            raise ValueError(
                f"prompt ({prompt.size + prior.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the model's max_seq_len "
                f"({self.model_cfg.max_seq_len})")
        # typed would-OOM refusal BEFORE the request enters the queue
        self.kv.check_fits(total)
        req = GenerationRequest(
            prompt, max_new_tokens, self.queue.deadline_for(deadline_ms),
            temperature=temperature, seed=seed,
            eos_id=self.model_cfg.eos_id if stop_at_eos else None,
            session_id=request_id, prior=prior)
        req.first_logits = bool(keep_first_logits)
        req.final_state = bool(keep_final_state)
        req.final_pages = bool(keep_final_pages)
        if keep_step_outputs and (self._draft or self._keeps_logits):
            req.step_outputs = []
        if rng_state is not None:
            from .session import unpack_rng_state

            req._rng = unpack_rng_state(rng_state)
        if prior.size:
            telemetry.counter_add("session.resumed", 1)
            telemetry.counter_add("session.resumed_tokens",
                                  int(prior.size))
        self.queue.submit_request(req)
        return req

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kw) -> np.ndarray:
        """Blocking submit-and-wait; returns the generated int32 ids."""
        return self.submit(prompt, **kw).result(timeout)

    def submit_prefill(self, prompt,
                       deadline_ms: Optional[float] = None
                       ) -> ShipPrefillRequest:
        """Disaggregated serving (serving/disagg.py): enqueue a
        prefill-and-ship work item. ``result()`` returns the serialized
        KV page shipment bytes for the prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt needs at least one token")
        if int(prompt.size) > self.model_cfg.max_seq_len:
            raise ValueError(
                f"prompt ({prompt.size}) exceeds the model's max_seq_len "
                f"({self.model_cfg.max_seq_len})")
        self.pool.check_fits(int(prompt.size))
        req = ShipPrefillRequest(prompt,
                                 self.queue.deadline_for(deadline_ms))
        self.queue.submit_request(req)
        return req

    def stats(self) -> Dict[str, Any]:
        """decode.* counters + KV pool accounting + latency percentiles
        + rolling-window token rate + ``warmup`` (where the engine's start
        went, by program) — the /v1/stats "decode" payload."""
        c = telemetry.counters()
        out = {k.split(".", 1)[1]: int(v) for k, v in c.items()
               if k.startswith("decode.") and isinstance(v, (int, float))}
        out["queue_depth"] = self.queue.depth()
        out["model_version"] = self.version
        out["status"] = self.health.state
        out["role"] = self.config.role
        out["kv_cache"] = self.kv.stats()
        if self.prefix_store is not None:
            out["prefix_store"] = self.prefix_store.stats()
            out["prefix_store"].update(
                {k.split(".", 1)[1]: int(v) for k, v in c.items()
                 if k.startswith("kv.") and isinstance(v, (int, float))})
        dis = {k.split(".", 1)[1]: int(v) for k, v in c.items()
               if k.startswith("disagg.") and isinstance(v, (int, float))}
        if dis:
            out["disagg"] = dis
        from ..ops import pallas as _pallas

        # per-kernel dispatch/fallback counters (counted at lowering
        # time) + the live kernel fingerprint — which code path this
        # engine's programs actually compiled
        out["pallas"] = dict(
            {k.split(".", 1)[1]: int(v) for k, v in c.items()
             if k.startswith("pallas.") and isinstance(v, (int, float))},
            kernels=_pallas.kernels_fingerprint())
        hists = telemetry.snapshot()["hists"]
        for key in ("decode.step_ms", "decode.prefill_ms",
                    "decode.prefill_feed_ms", "decode.prefill_wait_ms",
                    "decode.seat_ms", "decode.request_ms"):
            h = hists.get(key)
            if h:
                out[key.split(".", 1)[1]] = {
                    "count": h["count"], "avg": h["avg"], "p50": h["p50"],
                    "p95": h["p95"], "p99": h["p99"], "max": h["max"]}
        occ = hists.get("decode.batch_occupancy")
        if occ:
            out["batch_occupancy"] = {"avg": occ["avg"], "p50": occ["p50"]}
        # every token after a request's first is chosen by the step program
        if "tokens" in out:
            out["tokens_device_sampled"] = out["tokens"]
        if out.get("draft_proposed"):
            # a model with a draft module: the share of drafts the model
            # accepted, and the tokens a stepped row took a step
            out["draft_accept_share"] = round(
                100.0 * out.get("draft_accepted", 0) / out["draft_proposed"],
                2)
            per = hists.get("decode.tokens_per_row_step")
            if per:
                out["tokens_per_row_step"] = {"avg": per["avg"],
                                              "p50": per["p50"]}
        out.update(admission_cost(c, hists))
        # where this engine's start went: the set-up record of each of its
        # programs, and their sum by phase
        programs = list(self._warmup)
        if programs:
            out["warmup"] = {
                "programs": programs,
                "total": {f: round(sum(r[f] for r in programs), 3)
                          for f in telemetry.COMPILE_RECORD_SECONDS}}
        win = telemetry.windowed()
        wout = {"seconds": win["window_s"]}
        for name, key in (("decode.tokens", "tokens_per_s"),
                          ("decode.steps", "steps_per_s")):
            wc = win["counters"].get(name)
            if wc:
                wout[key] = wc["rate"]
        out["window"] = wout
        return out

    # -- lifecycle -----------------------------------------------------------
    def start(self, warmup: bool = False) -> "DecodeEngine":
        if self._thread is not None:
            return self
        if self.queue.closed:
            raise EngineClosedError("decode engine was closed; "
                                    "build a new one")
        if warmup:
            self.warmup()
        self._thread = threading.Thread(target=self._loop,
                                        name="pt-decode-engine",
                                        daemon=True)
        self._thread.start()
        self.health.set(READY)
        return self

    def warmup(self) -> int:
        """Pre-compile every decode bucket and every prefill bucket so
        no request ever pays a compile mid-load (a mid-generation
        compile stalls the WHOLE slot array, not just one request).
        Returns the number of fresh compiles."""
        before = telemetry.counter_get("decode.compiles")
        for b in self.config.buckets:
            self._entry("step", b)
        for b in self.config.prefill_buckets:
            self._entry("prefill", b)
        if self.prefix_store is not None:
            # the ONE chunked-prefill entry (chunk length == page size)
            self._entry("chunk", self.config.page_size)
        return int(telemetry.counter_get("decode.compiles") - before)

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        self.health.set(DRAINING)
        self.queue.close(drain=drain)
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        self.health.set(STOPPED)

    # -- program compilation -------------------------------------------------
    def _entry(self, phase: str, bucket: int):
        """One jitted entry per (phase, bucket), pools donated so XLA
        updates the KV arrays in place. A prefill is (params, pools, feed)
        -> (first-token logits row, new_pools). The step is (params, pools,
        feed, last_tokens) -> (chosen, new_pools, last_tokens): a row whose
        ``carry`` says so takes its input token from ``last_tokens[slot]``,
        where the step before left it, and not from the host's ``tokens``
        (a request's first step: its first token was chosen on the host);
        ``chosen`` is the sampled tokens, int32 [bucket] (the logits stay
        on the device), which the program also writes to the rows' slots of
        ``last_tokens``. So a step can be dispatched before the one before
        it is fetched, and its shape does not depend on that step's bucket.
        Compile wall time + XLA cost capture accounted like the predictor's
        cache."""
        key = (phase, bucket)
        entry = self._entries.get(key)
        if entry is not None:
            return entry
        import jax
        import jax.numpy as jnp

        from ..ops import pallas as _pallas
        from .sampling import sample_tokens

        # the program's own name in the profiler's trace, in the compile
        # cache's key and on its set-up record: decode_step_b8,
        # prefill_p256, chunk_p128
        name = {"step": "decode_step_b", "prefill": "prefill_p",
                "chunk": "chunk_p"}[phase] + str(bucket)
        # the Pallas kernel fingerprint (PT_PALLAS mode + tile/chunk
        # geometry) keys the cost capture so flops/bytes attribute to
        # the kernel VARIANT actually compiled — the roofline verdict of
        # the stock gather+einsum lowering and the paged kernel are
        # different programs, not one blurred row
        pallas_fp = _pallas.kernels_fingerprint()
        # the set-up record (telemetry.CompileRecord) of this program:
        # from before its construction to the throw-away run's return
        with telemetry.CompileRecord("decode", name,
                                      pallas_kernels=pallas_fp) as record:
            with record.phase("build_s"):
                cc = self.config
                build = {"step": self.model.build_step_program,
                         "chunk": self.model.build_chunk_prefill_program,
                         "prefill": self.model.build_prefill_program}[phase]
                program, feeds, fetches = build(bucket, self.kv,
                                                cc.weight_quant)
                # a program's arguments are part of its compiled form: it
                # is fed exactly the names its builder lists (and the
                # sampler's) (a step's `state_slots` are `carry`'s slots,
                # taken on the device)
                by_slot = phase == "step" and "state_slots" in feeds
                self._feed_names[key] = tuple(
                    n for n in feeds
                    if not (by_slot and n == "state_slots")) + (
                    ("sampling", "carry") if phase == "step" else ())
                block = program.global_block()
                counted = "step_counts" in fetches
                keeps_logits = self._keeps_logits
                run = functools.partial(run_program, block)

                def prefill(params, pools, feed):
                    env, pools = run(params, pools, feed)
                    return env["logits"], pools

                def step(params, pools, feed, last_tokens):
                    slot, carried = feed["carry"][:, 0], feed["carry"][:, 1]
                    # a padding row names no slot (max_slots): it reads the
                    # last one, unused, and its token is dropped from the
                    # scatter
                    tokens = jnp.where(carried > 0,
                                       last_tokens.at[slot].get(mode="clip"),
                                       feed["tokens"])
                    feed = dict(feed, tokens=tokens)
                    if by_slot:
                        # a model with per-slot state finds a row's state
                        # by the slot its request keeps; a padding row's is
                        # the scratch slot, max_slots
                        feed["state_slots"] = slot
                    env, pools = run(params, pools, feed)
                    chosen = sample_tokens(env["logits"],
                                           feed["sampling"][:, 0],
                                           feed["sampling"][:, 1])
                    last_tokens = last_tokens.at[slot].set(chosen,
                                                           mode="drop")
                    if counted:
                        # the model's counters ride behind the tokens: one
                        # int32 vector, one fetch
                        chosen = jnp.concatenate(
                            [chosen, env["step_counts"].astype(jnp.int32)])
                    if keeps_logits:
                        # the array the sampler read, as the head left it
                        return (chosen, pools, last_tokens,
                                {"logits": env["logits"]})
                    return chosen, pools, last_tokens

                donate = (1,)
                if self._draft:
                    donate = (1, 4) if phase == "step" else (1, 3)
                    if phase == "step":
                        self._feed_names[key] = (
                            "tokens", "positions", "page_table", "sampling",
                            "carry")
                        step = self._draft_step(bucket, block)
                    else:
                        self._feed_names[key] += ("state_slots",)

                        def prefill(params, pools, feed, spec):
                            # the module's rows were written beside the
                            # layers'; the last position's hidden state
                            # waits in the slot for the first step's module
                            # pass
                            env, pools = run(params, pools, feed)
                            hidden = spec["hidden"].at[
                                feed["state_slots"]].set(env["hidden"],
                                                         mode="drop")
                            return (env["logits"], pools,
                                    dict(spec, hidden=hidden))

                fn = step if phase == "step" else prefill
                fn.__name__ = fn.__qualname__ = name
                entry = jax.jit(fn, donate_argnums=donate)
                self._entries[key] = entry
                record.ops = len(block.ops)
            t0 = time.perf_counter()
            args = (self._zero_feed(phase, bucket),)
            if phase == "step":
                args += (jnp.zeros_like(self._last_tokens),)
            if self._draft:
                args += (self._spec,)
            if costmodel.capture_mode() != "off":
                with record.phase("capture_s"):
                    costmodel.capture(
                        lambda: entry.lower(self._params, dict(self._pools),
                                            *args),
                        key_id=costmodel.key_id_for(
                            (phase, bucket, cc.weight_quant, pallas_fp)),
                        kind="decode", program=f"{phase}_b{bucket}")
            # compile through a throwaway execution on zero feeds (the
            # predictor's measure-through-first-run discipline), on the
            # engine's OWN pools, taken back as the program returns them
            # (donation consumes whatever is passed in): a zero feed's page
            # tables name the scratch page 0 alone, so no request's page is
            # written, and no second pool is held beside the first (3.4 GB of
            # latent pages beside 7 GB of weights did not fit twice)
            out = entry(self._params, self._pools, *args)
            self._pools = out[1]
            if self._draft:
                # donated like the pools: a zero feed names no slot
                self._spec = out[3 if phase == "step" else 2]
            setup = record.close()
            ms = round((time.perf_counter() - t0) * 1e3, 3)
            telemetry.counter_add("decode.compiles", 1)
            telemetry.event("compile", "decode", ms,
                            dict(setup, cause="decode_bucket", phase=phase,
                                 bucket=bucket, cache_size=len(self._entries)))
            self._warmup.append(setup)
            return entry

    def _draft_step(self, bucket: int, step_block):
        """`draft_step` for this engine's model (a method so that a check
        can plant a fault around it)."""
        return draft_step(self.model, self.kv, self.config.weight_quant,
                          bucket, step_block)

    def _feed(self, phase: str, bucket: int, parts: Dict[str, Any]):
        """The program's feed: of ``parts`` (host arrays by feed name) the
        names its builder listed, as device arrays."""
        import jax.numpy as jnp

        return {n: jnp.asarray(parts[n])
                for n in self._feed_names[(phase, bucket)]}

    def _zero_feed(self, phase: str, bucket: int):
        rows = bucket if phase == "step" else 1
        tables = {"page_table": np.zeros((rows, self._mp), np.int32),
                  "ring_table": np.zeros((rows, self.kv.ring_slot_pages),
                                         np.int32)}
        if phase == "step":
            return self._feed(phase, bucket, dict(
                tables, tokens=np.zeros((bucket,), np.int32),
                positions=np.zeros((bucket,), np.int32),
                sampling=np.zeros((bucket, 5 if self._draft else 2),
                                  np.float32),
                # no row names a slot: a model's per-slot state is touched
                # at the scratch slot alone, whenever this compiles
                carry=np.full((bucket, 2), (self.config.max_slots, 0),
                              np.int32)))
        oh = np.zeros((1, bucket), np.float32)
        oh[0, 0] = 1.0
        return self._feed(phase, bucket, dict(
            tables, tokens=np.zeros((1, bucket), np.int32),
            positions=np.zeros((1, bucket), np.int32),
            chunk_start=np.zeros((1,), np.int32),
            state_slots=np.full((1,), self.config.max_slots, np.int32),
            next_tokens=np.zeros((1, bucket), np.int32),
            next_lengths=np.zeros((1,), np.int32),
            lengths=np.ones((1,), np.int32), last_onehot=oh))

    # -- scheduler loop ------------------------------------------------------
    def _loop(self):
        # the phase times in ms, by histogram name, since the last step
        # whose tokens were accepted; they reach the histograms when the
        # next is, one record a step
        it: Dict[str, float] = {}
        while True:
            # a step in flight is fetched before the thread sleeps or ends
            if not self._active and self._inflight is None:
                has_work = self.queue.wait_for_work(0.05)
                if not has_work:
                    if self.queue.closed:
                        return
                    continue
            with telemetry.timer("decode.loop_ms", into=it):
                cpu0 = time.thread_time()
                try:
                    with telemetry.timer("decode.admit_ms", into=it):
                        self._admit(it)
                    if self._active or self._inflight is not None:
                        self._run_step(it)
                        self._journal_tick()
                    # the prefill that _admit dispatched runs behind the
                    # step that was in flight and ahead of the one just
                    # launched: its request is seated under that one
                    with telemetry.timer("decode.admit_ms", into=it):
                        self._seat_prefilled()
                except BaseException as e:   # the loop must outlive any step
                    self._fail_seated(e)
                    it = {}
                telemetry.gauge_set("decode.active_slots", len(self._active))
                # the step's one hook: whoever subscribed (the SLO watchdog's
                # queue-saturation and step-time rules) runs on this cadence
                telemetry.tick()
                # this thread's own CPU time beside the loop's wall time:
                # what is left of the wall once the waits for the device
                # (decode.fetch_ms, decode.prefill_wait_ms) are taken off
                # too, the thread spent off the CPU elsewhere: waiting for
                # the interpreter lock behind the callers' threads, for a
                # core, in a transfer or a launch. Where the thread's clock
                # ticks coarsely (10 ms on the chip's machine) one sample
                # says little and the sum is an estimate
                it["decode.cpu_ms"] = it.get("decode.cpu_ms", 0.0) \
                    + (time.thread_time() - cpu0) * 1e3
            if "decode.fetch_ms" in it:
                self._observe_phases(it)
                it = {}
            elif self._inflight is None:
                it = {}       # no step ran: nothing of one to record
            # else a step was dispatched into an empty pipe and none
            # fetched: these times join the iteration that fetches it

    @staticmethod
    def _observe_phases(it: Dict[str, float]):
        """One accepted step's record: decode.loop_ms and the phases that
        tile it, decode.other_ms for what lies between them (the deadline
        scan, the journal tick, the gauge, the watchdog, the timers), and
        beside them decode.cpu_ms, the thread's CPU time over the same
        iterations (no phase: it overlaps them all)."""
        for name in _LOOP_PHASES:     # a step that only drained fed nothing
            it.setdefault(name, 0.0)
        it["decode.sample_ms"] -= it["decode.retire_ms"]
        it["decode.other_ms"] = it["decode.loop_ms"] - sum(
            it[name] for name in _LOOP_PHASES)
        # decode.step_ms stays in the run log as it was; the other phases
        # are histograms only (eight more records a step would crowd the
        # log and the flight recorder out)
        telemetry.observe("decode.step_ms", it.pop("decode.step_ms"),
                          kind="timer")
        for name, ms in it.items():
            telemetry.observe_quiet(name, ms)

    def _fail_seated(self, e: BaseException):
        """A failed step fails every seated request, the rows of a step in
        flight among them (its tokens are lost with it), and the request
        whose prefill is dispatched and not yet seated."""
        telemetry.counter_add("decode.errors", max(1, len(self._active)),
                              exc=type(e).__name__)
        err = e if isinstance(e, ServingError) else ServingError(
            f"decode step failed: {e!r}")
        for req in self._active:
            self._retire(req, error=err)
        self._active = []
        self._inflight = None
        pending, self._prefilled = self._prefilled, None
        if pending is not None:
            self._prefill_failed(pending.req, pending.pages, err)

    def _admit(self, it: Dict[str, float]):
        """Admit queued requests into free slots at the step boundary.
        Non-continuous (drain-and-refill baseline) only admits into an
        EMPTY slot array. A local prefill is dispatched here, behind the
        step in flight, and left in ``_prefilled``: ``_loop`` launches the
        next step behind it and seats the request under that step
        (``_seat_prefilled``), so an admission leaves the device no gap.
        One prefill at a time is left so: a second request of one poll
        first seats the one before it, in the order (a prompt's pages enter
        the prefix store before the next lookup) and at the cost the loop
        had before it ran ahead. Before the host blocks there, or on a
        shipment, the step in flight is fetched and accepted (``_drain``):
        a finished step's tokens do not wait behind a prefill."""
        if not self.config.continuous and self._active:
            return
        free = self.config.max_slots - len(self._active)
        if free <= 0:
            return
        unseated: List[GenerationRequest] = []
        for req in self.queue.poll(free):
            if self._prefilled is not None:
                self._drain(it)
                self._seat_prefilled()
            if isinstance(req, ShipPrefillRequest):
                self._drain(it)
                self._ship_prefill(req)
                continue
            # disaggregated decode role: try to install a shipped
            # prefill from the prefill tier; ANY failure (connection,
            # CRC reject) falls back to a local prefill
            if self.config.role == "decode" and self.config.prefill_urls:
                self._drain(it)
                if self._admit_shipped(req):
                    continue
            # what precedes the prefill's dispatch, from the seat (or the
            # drained step) of the request before: with decode.prefill_ms
            # and decode.seat_ms it tiles this request's part of the
            # admission, the host's gap between two prefill programs
            launch = None
            with _admission_part("prefill_feed", req) as feed_ms:
                got = self._reserve(req, unseated)
                if got is not None:
                    pages = got[0]
                    try:
                        launch = self._prefill(req, *got)
                    except BaseException as e:
                        self._prefill_failed(req, pages, e)
            if launch is None:
                continue
            telemetry.observe_quiet("decode.prefill_feed_ms",
                                    feed_ms["decode.admit_ms"])
            try:
                self._prefilled = _Prefilled(req, pages, launch())
            except BaseException as e:
                self._prefill_failed(req, pages, e)
        self.queue.requeue(unseated)

    def _reserve(self, req: GenerationRequest,
                 unseated: List[GenerationRequest]):
        """What an admission takes before its prefill: the longest cached
        prefix, pages of both classes, a slot. -> (private pages, the shared
        prefix's hashes, its pages), or None: the request failed (a
        per-request error), or the pools cannot seat it now and it joins
        ``unseated`` to wait for frees."""
        # prefix sharing: acquire the longest cached prefix chain;
        # a lookup fault is a per-request error, nothing acquired
        hashes: List[str] = []
        shared: List[int] = []
        if self.prefix_store is not None:
            try:
                hashes, shared = self.prefix_store.lookup(req.seq)
            except Exception as e:
                telemetry.counter_add("decode.errors", 1,
                                      exc=type(e).__name__)
                req.fail(e if isinstance(e, ServingError)
                         else ServingError(
                             f"prefix lookup failed: {e!r}"))
                return None
        need, ring_need = self.kv.pages_for_tokens(
            int(req.seq.size) + req.max_new_tokens)
        need -= len(hashes)
        try:
            # seated only if BOTH classes of pages can seat it
            got = self.kv.try_alloc(need, ring_need)
            if got is None and self.prefix_store is not None:
                # ledger pressure: reclaim idle refcount-zero
                # chains LRU-first, then retry once
                short = need - self.pool.free_pages()
                if short > 0 and self.prefix_store.reclaim(short):
                    got = self.kv.try_alloc(need, ring_need)
        except Exception as e:   # injected decode.kv_alloc fault
            if hashes:
                self.prefix_store.release(hashes)
            telemetry.counter_add("decode.errors", 1,
                                  exc=type(e).__name__)
            req.fail(e if isinstance(e, ServingError) else ServingError(
                f"KV page allocation failed: {e!r}"))
            return None
        if got is None:
            if hashes:
                self.prefix_store.release(hashes)
            unseated.append(req)   # no headroom NOW — wait for frees
            return None
        pages, req.ring_pages = got
        telemetry.observe("decode.queue_wait_ms",
                          (time.monotonic() - req.t_submit) * 1e3)
        # the slot is taken before the prefill, which writes a stateful
        # model's state and tail there over what the slot's last owner
        # left (a row dispatched on speculation for a request that
        # ended ran before this prefill on the device)
        self._take_slot(req)
        return pages, hashes, shared

    def _seat_prefilled(self):
        """Wait for the dispatched prefill's logits row, if there is one,
        and seat its request. A prefill that fails is that request's
        error."""
        pending, self._prefilled = self._prefilled, None
        if pending is None:
            return
        try:
            pending.seat()
        except BaseException as e:
            self._prefill_failed(pending.req, pending.pages, e)

    def _prefill_failed(self, req: GenerationRequest, pages: List[int],
                        e: BaseException):
        """A per-request error: give back what the admission took."""
        self.kv.free(req.pages if req.pages else pages, req.ring_pages)
        req.pages, req.ring_pages = [], []
        self._release_slot(req)
        if req.shared_blocks:
            self.prefix_store.release(req.shared_blocks)
            req.shared_blocks = []
        telemetry.counter_add("decode.errors", 1, exc=type(e).__name__)
        req.fail(e if isinstance(e, ServingError) else ServingError(
            f"prefill failed: {e!r}"))

    def _prefill(self, req: GenerationRequest, pages: List[int],
                 hashes: Optional[List[str]] = None,
                 shared: Optional[List[int]] = None):
        """PREFILL phase: build the request's prefill and return the call
        that dispatches it, which returns the call that waits for its logits
        row and seats the request (``_Prefilled.seat``): what is built here
        is the admission's host work before the device has the program
        (decode.prefill_feed_ms), what the first call times is the dispatch
        (decode.prefill_ms). With the prefix store on, EVERY prefill runs
        page-aligned chunks through the one chunked entry (a cache hit
        just skips the cached leading chunks — bitwise identity with
        the cold run holds by construction: same program, same fixed
        shape, same order). Otherwise the classic one-pass causal
        prefill over the padded prompt."""
        if self.prefix_store is not None:
            return self._prefill_chunked(req, pages, hashes or [],
                                         shared or [])
        L = int(req.seq.size)
        bucket = next(b for b in self.config.prefill_buckets if b >= L)
        req.pages = pages
        row = np.zeros(self._mp, np.int32)
        row[:len(pages)] = pages
        req.table_row = row
        req.ring_row = np.zeros(self.kv.ring_slot_pages, np.int32)
        req.ring_row[:len(req.ring_pages)] = req.ring_pages
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :L] = req.seq
        oh = np.zeros((1, bucket), np.float32)
        oh[0, L - 1] = 1.0
        entry = self._entry("prefill", bucket)
        parts = {
            "tokens": tokens, "lengths": np.asarray([L], np.int32),
            "last_onehot": oh,
            "positions": np.arange(bucket, dtype=np.int32)[None, :],
            "state_slots": np.asarray([req.slot], np.int32),
            "page_table": row[None, :], "ring_table": req.ring_row[None, :]}
        if self._draft:
            # the module's row of position i is made of token i + 1
            parts["next_tokens"] = np.roll(tokens, -1, axis=1)
            parts["next_lengths"] = np.asarray([L - 1], np.int32)
        feed = self._feed("prefill", bucket, parts)
        # the program computes the bucket, padding and all
        span = dict(rid=req.rid, bucket=bucket, tokens=L)

        def launch():
            ms: Dict[str, float] = {}
            with telemetry.timer("decode.prefill_ms", into=ms, **span):
                if self._draft:
                    logits, self._pools, self._spec = entry(
                        self._params, self._pools, feed, self._spec)
                else:
                    logits, self._pools = entry(self._params, self._pools,
                                                feed)
            return lambda: self._seat(req, self._logits_row(logits, ms, span))

        return launch

    def _logits_row(self, logits, ms: Dict[str, float],
                    span: Dict[str, int]) -> np.ndarray:
        """Wait for a dispatched prefill's logits row. decode.prefill_ms is
        the dispatch (``ms`` so far) and this wait, not what the loop did
        between them. The wait alone is decode.prefill_wait_ms: the loop has
        accepted the step that was in flight before the prefill (or drained
        it), so the host has nothing left to do but wait for the prefill
        program, and every live slot stands still meanwhile. It reads UNDER
        the program's device time by the host's accept of that step
        (decode.sample_ms: tenths of a ms against prefills of 15-175 ms),
        during which the program already ran. ``span`` is the prefill's
        ``rid``, the ``tokens`` it was asked for and the ``bucket`` of
        tokens its programs computed: decode.prefill_tokens over
        decode.prefill_bucket_tokens is the share that was no padding,
        counted here, both for the prefills that were seated."""
        dispatch_ms = ms["decode.prefill_ms"]
        with telemetry.timer("decode.prefill_ms", into=ms, **span):
            row = np.asarray(logits)[0]
        telemetry.observe("decode.prefill_ms", ms["decode.prefill_ms"],
                          kind="timer")
        telemetry.observe_quiet("decode.prefill_wait_ms",
                                ms["decode.prefill_ms"] - dispatch_ms)
        telemetry.counter_add("decode.prefills", 1)
        telemetry.counter_add("decode.prefill_tokens", span["tokens"])
        telemetry.counter_add("decode.prefill_bucket_tokens", span["bucket"])
        return row

    def _prefill_chunked(self, req: GenerationRequest, pages: List[int],
                         hashes: List[str], shared: List[int]):
        """Chunked prefill (prefix store on): the page table splices
        the ``len(hashes)`` shared prefix pages in front of the private
        pages, then each UNCACHED page-sized chunk runs through the one
        fixed-shape chunk entry. Writes land only in private pages (the
        lookup's match cap keeps the final chunk — the one producing
        first-token logits — always recomputed); afterwards the store
        adopts this prompt's full pages so the next request shares
        them."""
        L = int(req.seq.size)
        P = self.config.page_size
        k = len(hashes)
        req.pages = pages
        req.shared_blocks = list(hashes)
        row = np.zeros(self._mp, np.int32)
        row[:k] = shared
        row[k:k + len(pages)] = pages
        req.table_row = row
        req.ring_row = np.zeros(self.kv.ring_slot_pages, np.int32)
        n_chunks = -(-L // P)
        entry = self._entry("chunk", P)
        # the chunks that run, whole, are what the programs compute
        span = dict(rid=req.rid, bucket=(n_chunks - k) * P, tokens=L - k * P)

        def launch():
            logits = None
            ms: Dict[str, float] = {}
            with telemetry.timer("decode.prefill_ms", into=ms, **span):
                for ci in range(k, n_chunks):
                    lo = ci * P
                    n = min(L, lo + P) - lo
                    tokens = np.zeros((1, P), np.int32)
                    tokens[0, :n] = req.seq[lo:lo + n]
                    positions = np.clip(lo + np.arange(P, dtype=np.int32), 0,
                                        self.model_cfg.max_seq_len - 1)
                    oh = np.zeros((1, P), np.float32)
                    if ci == n_chunks - 1:
                        oh[0, L - 1 - lo] = 1.0
                    feed = self._feed("chunk", P, {
                        "tokens": tokens, "positions": positions[None, :],
                        "chunk_start": np.asarray([lo], np.int32),
                        "lengths": np.asarray([n], np.int32),
                        "last_onehot": oh, "page_table": row[None, :],
                        "ring_table": req.ring_row[None, :]})
                    logits, self._pools = entry(self._params, self._pools,
                                                feed)

            def seat():
                logits_row = self._logits_row(logits, ms, span)
                # the store adopts every FULL prompt page (strictly before
                # the page receiving decode writes); repoint the table at
                # the canonical pages and keep only the tail pages private
                n_full = L // P
                if n_full > k:
                    held, canon = self.prefix_store.insert(
                        req.seq, [int(p) for p in row[:n_full]],
                        start_block=k)
                    row[k:n_full] = canon
                    req.shared_blocks.extend(held)
                    req.pages = pages[n_full - k:]
                self._seat(req, logits_row)

            return seat

        return launch

    def _ship_prefill(self, req: ShipPrefillRequest):
        """Prefill-tier work (serving/disagg.py): run the prompt's
        prefill, read the finished pages back to host, pack the
        versioned per-page-CRC shipment, free the pages, resolve with
        the bytes. ``disagg.ship`` faults inject here — a failure is a
        per-request error; the pool stays clean."""
        from . import disagg

        pages: List[int] = []
        try:
            faults.maybe_fail("disagg.ship", tokens=int(req.prompt.size))
            L = int(req.prompt.size)
            n_pages = self.pool.pages_for_tokens(L)
            pages = self.pool.try_alloc(n_pages)
            if not pages:
                raise KVCacheExhaustedError(
                    f"prefill tier cannot seat {n_pages} pages right now")
            bucket = next(b for b in self.config.prefill_buckets
                          if b >= L)
            row = np.zeros(self._mp, np.int32)
            row[:n_pages] = pages
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :L] = req.prompt
            oh = np.zeros((1, bucket), np.float32)
            oh[0, L - 1] = 1.0
            entry = self._entry("prefill", bucket)
            feed = self._feed("prefill", bucket, {
                "tokens": tokens, "lengths": np.asarray([L], np.int32),
                "last_onehot": oh,
                "positions": np.arange(bucket, dtype=np.int32)[None, :],
                "page_table": row[None, :]})
            with telemetry.timer("decode.prefill_ms"):
                logits, self._pools = entry(self._params, self._pools,
                                            feed)
                logits = np.asarray(logits)
            idx = np.asarray(pages, np.int64)
            layer_pages = {name: np.asarray(self._pools[name])[idx]
                           for name in sorted(self._pools)}
            blob = disagg.pack_shipment(req.prompt, self.config.page_size,
                                        layer_pages, logits[0])
            self.pool.free(pages)
            pages = []
            telemetry.counter_add("disagg.ships", 1)
            telemetry.counter_add("disagg.ship_bytes", len(blob))
            req.resolve(blob)
        except BaseException as e:
            if pages:
                self.pool.free(pages)
            telemetry.counter_add("decode.errors", 1, exc=type(e).__name__)
            req.fail(e if isinstance(e, ServingError) else ServingError(
                f"prefill shipment failed: {e!r}"))

    def _admit_shipped(self, req: GenerationRequest) -> bool:
        """Decode-tier admission (serving/disagg.py): fetch the
        prompt's KV page shipment from a prefill replica, CRC-verify,
        install the pages into the pool arrays and seat the request
        with its first token sampled from the SHIPPED logits. Returns
        False on ANY failure — connection, CRC reject, no pool
        headroom — so the caller falls back to a local prefill
        (``disagg.fallback_prefills``); a corrupted shipment is
        re-prefilled, never served."""
        from . import disagg

        import zlib

        urls = self.config.prefill_urls
        pages: List[int] = []
        try:
            url = urls[zlib.crc32(req.seq.tobytes()) % len(urls)]
            blob = disagg.fetch_prefill(url, req.seq)
            ship = disagg.unpack_shipment(blob)   # raises on CRC reject
            L = int(req.seq.size)
            if (ship["page_size"] != self.config.page_size
                    or ship["tokens"] != [int(t) for t in req.seq]):
                raise disagg.ShipmentError(
                    "shipment does not match the request")
            need = self.pool.pages_for_tokens(L + req.max_new_tokens)
            pages = self.pool.try_alloc(need)
            if not pages:
                return False
            n_ship = ship["n_pages"]
            idx = np.asarray(pages[:n_ship], np.int64)
            for name, arr in ship["layers"].items():
                self._pools[name] = self._pools[name].at[idx].set(arr)
            req.pages = pages
            pages = []
            row = np.zeros(self._mp, np.int32)
            row[:len(req.pages)] = req.pages
            req.table_row = row
            req.ring_row = np.zeros(0, np.int32)
            telemetry.counter_add("disagg.installs", 1)
            telemetry.counter_add("decode.prefills", 1)
            telemetry.observe("decode.queue_wait_ms",
                              (time.monotonic() - req.t_submit) * 1e3)
            self._take_slot(req)
            self._seat(req, np.asarray(ship["logits"]))
            return True
        except Exception as e:
            if pages:
                self.pool.free(pages)
            self._release_slot(req)
            telemetry.counter_add("disagg.fallback_prefills", 1,
                                  exc=type(e).__name__)
            return False

    def _run_step(self, it: Dict[str, float]):
        """DECODE phase, one step ahead: dispatch the next fixed-shape step
        over the padded slot array, THEN fetch and accept the step in
        flight, so the host's feed, launch, fetch and accept run while the
        device has a step to work on. Which rows the next step holds is
        decided from the host's counts: a request whose ``max_new_tokens``
        the step in flight reaches is left out; one that may end on
        ``eos_id`` is dispatched and, if it did end, its token of the next
        step is thrown away when that step is accepted (``_finish``).
        Per-request deadlines are checked here, at step granularity. The
        phases' times go into ``it`` (see ``_loop``): decode.step_ms is the
        launch of one step and the wait for the one before."""
        delay_ms = float(_flag("decode_step_delay_ms"))
        if delay_ms > 0:   # chaos/bench pacing knob — off by default
            time.sleep(delay_ms / 1e3)
        now = time.monotonic()
        for req in [r for r in self._active if r.expired(now)]:
            self._active.remove(req)
            telemetry.counter_add("decode.deadline_expired", 1,
                                  phase="generation")
            self._retire(req, error=DeadlineExceededError(
                f"generation deadline elapsed after {len(req.tokens)} of "
                f"{req.max_new_tokens} tokens"))
        flight, self._inflight = self._inflight, None
        rows = [r for r in self._active
                if len(r.tokens) + r.ahead < r.max_new_tokens]
        if rows:
            self._inflight = self._launch(rows, it)
            if flight is not None:
                telemetry.counter_add("decode.steps_ahead", 1)
        if flight is not None:
            self._finish(flight, it)

    def _finish(self, flight: _StepInFlight, it: Dict[str, float]):
        """Fetch and accept a dispatched step: one token a row, or a
        drafting model's one or two."""
        if self._draft:
            self._finish_drafted(flight, it)
        else:
            self._finish_one(flight, it)

    def _launch(self, rows: List[GenerationRequest],
                it: Dict[str, float]) -> _StepInFlight:
        """Build one step's feed from what the host knows without the
        tokens of the step in flight, and dispatch it."""
        bucket = self.config.bucket(len(rows))
        faults.maybe_fail("decode.step", active=len(rows), bucket=bucket)
        entry = self._entry("step", bucket)
        with telemetry.timer("decode.feed_ms", into=it):
            tokens = np.zeros(bucket, np.int32)
            positions = np.zeros(bucket, np.int32)
            table = np.zeros((bucket, self._mp), np.int32)
            # a row's (temperature, uniform) for the program's sampler: the
            # request's own stream gives one draw per sampled token, here,
            # in token order (a first token's draw came before, on the host)
            sampling = np.zeros((bucket, 5 if self._draft else 2),
                                np.float32)
            ring = np.zeros((bucket, self.kv.ring_slot_pages), np.int32)
            # a row's (slot, whether its token is last_tokens[slot]); a
            # padding row's slot is none of them
            carry = np.zeros((bucket, 2), np.int32)
            carry[len(rows):, 0] = self.config.max_slots
            for i, req in enumerate(rows):
                if not req.carried:   # its first token, chosen on the host
                    tokens[i] = req.last_token
                carry[i] = (req.slot, req.carried)
                positions[i] = req.pos_next
                table[i] = req.table_row
                ring[i] = req.ring_row
                if req.temperature > 0.0:
                    if req.session_id is not None:
                        req._rng_cut = req._rng.get_state()
                    if self._draft:
                        # a drafting step's four: accept, redraw, second
                        # position, next draft (sampling.verify_tokens)
                        sampling[i, 0] = req.temperature
                        sampling[i, 1:] = req._rng.random_sample(4)
                    else:
                        sampling[i] = (req.temperature,
                                       req._rng.random_sample())
            feed = self._feed("step", bucket, {
                "tokens": tokens, "positions": positions,
                "page_table": table, "ring_table": ring,
                "sampling": sampling, "carry": carry})
        kept = None
        with telemetry.timer("decode.step_ms", into=it):
            if self._draft:
                chosen, self._pools, self._last_tokens, self._spec, kept = \
                    entry(self._params, self._pools, feed, self._last_tokens,
                          self._spec)
            elif self._keeps_logits:
                chosen, self._pools, self._last_tokens, kept = entry(
                    self._params, self._pools, feed, self._last_tokens)
            else:
                chosen, self._pools, self._last_tokens = entry(
                    self._params, self._pools, feed, self._last_tokens)
            chosen.copy_to_host_async()
        for req in rows:
            req.pos_next += 1
            req.carried = True
            req.ahead += 1
        if kept is not None and not any(r.step_outputs is not False
                                        for r in rows):
            kept = None
        return _StepInFlight(rows, bucket, chosen, positions[:len(rows)],
                             kept, sampling if kept is not None else None)

    def _finish_one(self, flight: _StepInFlight, it: Dict[str, float]):
        """Fetch a dispatched step's tokens and accept them row by row. A
        row whose request ended meanwhile (on ``eos_id``, on its deadline)
        was dispatched on speculation: its token is thrown away."""
        rows, bucket = flight.rows, flight.bucket
        with telemetry.timer("decode.step_ms", into=it):
            # [bucket] int32 (and the model's counters behind them); the
            # host waits out what is left of the step program here
            with telemetry.timer("decode.fetch_ms", into=it):
                chosen = np.asarray(flight.chosen)
        for name, value in zip(self.model.step_counters, chosen[bucket:]):
            telemetry.counter_add(name, int(value))
        for name, layers_ in self._state_row_counters:
            telemetry.counter_add(name, len(rows) * layers_)
        if self._ring_or_latent or self.kv.has_state:
            # keys (a latent layer's cached tokens) a step reads: a ring
            # layer's rows read their window
            ctx = flight.positions.astype(np.int64) + 1
            attended = len(self.kv.context.layers) * ctx.sum()
            if self.kv.ring is not None:
                telemetry.counter_add("decode.rows_past_window",
                                      int(np.sum(ctx > self.kv.window)))
                in_rings = len(self.kv.ring.layers) \
                    * np.minimum(ctx, self.kv.window).sum()
                attended += in_rings
                if any(self.kv.ring.latent):
                    # latent rows read from rings, apart from those read
                    # from a context's latent pages
                    telemetry.counter_add(
                        "decode.ring_latent_rows_attended", int(in_rings))
            telemetry.counter_add("decode.kv_tokens_attended",
                                  int(attended))
        # one span for accepting the step's tokens. A request that finishes
        # is retired at once, in a child span
        delivered, retired = 0, False
        with telemetry.timer("decode.sample_ms", into=it):
            for i, req in enumerate(rows):
                req.ahead -= 1
                if req.done():
                    continue
                delivered += 1
                if req.step_outputs is not False:
                    req.step_outputs.append({
                        "position": int(flight.positions[i]),
                        "logits": np.asarray(flight.kept["logits"][i]),
                        "token": int(chosen[i])})
                self._accept_token(req, int(chosen[i]))
                if req.finished():
                    retired = True
                    with telemetry.timer("decode.retire_ms", into=it,
                                         rid=req.rid):
                        self._retire(req)
            if retired:
                self._active = [r for r in self._active if not r.done()]
        telemetry.counter_add("decode.steps", 1)
        telemetry.counter_add("decode.tokens", delivered)
        if delivered < len(rows):
            telemetry.counter_add("decode.rows_discarded",
                                  len(rows) - delivered)
        telemetry.observe("decode.batch_occupancy", delivered / bucket)

    def _finish_drafted(self, flight: _StepInFlight, it: Dict[str, float]):
        """`_finish_one` for a model with a draft module: the fetch brings
        [bucket, 2] tokens and a count of 1 or 2 a row, and the host learns
        only now how far each row went. A row takes its tokens in order
        until it ends (``max_new_tokens``, ``eos_id``): what is left of the
        step's, and every token of a row whose request had ended before
        (dispatched on speculation), is thrown away
        (decode.tokens_discarded; the latter rows also count in
        decode.rows_discarded). A row's first step had no draft."""
        rows, bucket = flight.rows, flight.bucket
        with telemetry.timer("decode.step_ms", into=it):
            with telemetry.timer("decode.fetch_ms", into=it):
                fetched = np.asarray(flight.chosen)
        tokens = fetched[:2 * bucket].reshape(bucket, 2)
        counts = fetched[2 * bucket:3 * bucket]
        for name, value in zip(self.model.step_counters,
                               fetched[3 * bucket:]):
            telemetry.counter_add(name, int(value))
        # a row's latents are read once a step for both positions, up to
        # the second one: its prompt, its tokens but the last, the pair
        telemetry.counter_add(
            "decode.kv_tokens_attended", len(self.kv.context.layers) * sum(
                int(r.seq.size) + len(r.tokens) + 1 for r in rows))
        delivered = stepped = proposed = accepted = discarded = 0
        retired = False
        with telemetry.timer("decode.sample_ms", into=it):
            for i, req in enumerate(rows):
                req.ahead -= 1
                n = int(counts[i])
                if req.done():
                    discarded += n
                    continue
                stepped += 1
                if req.steps:
                    proposed += 1
                    accepted += n - 1
                req.steps += 1
                took = 0
                for tok in tokens[i, :n]:
                    self._accept_token(req, int(tok))
                    took += 1
                    if req.finished():
                        break
                req.last_step_tokens = took
                delivered += took
                discarded += n - took
                if req.step_outputs is not False:
                    self._keep_step(req, flight, i, tokens[i, :n], took)
                if req.finished():
                    retired = True
                    with telemetry.timer("decode.retire_ms", into=it,
                                         rid=req.rid):
                        self._retire(req)
            if retired:
                self._active = [r for r in self._active if not r.done()]
        telemetry.counter_add("decode.steps", 1)
        telemetry.counter_add("decode.tokens", delivered)
        telemetry.counter_add("decode.rows_stepped", stepped)
        telemetry.counter_add("decode.draft_proposed", proposed)
        telemetry.counter_add("decode.draft_accepted", accepted)
        if discarded:
            telemetry.counter_add("decode.tokens_discarded", discarded)
        if stepped < len(rows):
            telemetry.counter_add("decode.rows_discarded",
                                  len(rows) - stepped)
        telemetry.observe("decode.batch_occupancy", stepped / bucket)
        if stepped:
            telemetry.observe_quiet("decode.tokens_per_row_step",
                                    delivered / stepped)

    @staticmethod
    def _keep_step(req: GenerationRequest, flight: _StepInFlight, i: int,
                   tokens: np.ndarray, took: int):
        """One step's record of a request that asked for them
        (``keep_step_outputs``): what the acceptance rule read and gave."""
        from ..ops.pallas.draft_tail import vocab_rows

        kept = flight.kept
        logits = np.asarray(kept["logits"][2 * i:2 * i + 2])
        req.step_outputs.append({
            "position": int(kept["pos"][i]), "draft": int(kept["draft"][i]),
            "had_draft": req.steps > 1, "logits": logits,
            "q": vocab_rows(np.asarray(kept["q"][i]), logits.shape[1]),
            "draft_logits": np.asarray(kept["draft_logits"][i]),
            "uniforms": np.array(flight.uniforms[i, 1:], np.float32),
            "tokens": [int(t) for t in tokens], "delivered": took})

    def _drain(self, it: Dict[str, float]):
        """Fetch and accept the step in flight, if there is one, before the
        host blocks on something queued behind it (``_admit``: a second
        prefill of one poll, a shipment). A step that fails here
        fails as one does in ``_loop``: every seated request, and the
        engine goes on."""
        flight, self._inflight = self._inflight, None
        if flight is None:
            return
        t0 = time.perf_counter()
        try:
            self._finish(flight, it)
        except Exception as e:
            self._fail_seated(e)
        # the step was timed under its own names: the admission's span,
        # inside which this runs, gives that time up, so that the
        # histograms hold disjoint phases
        it["decode.admit_ms"] = it.get("decode.admit_ms", 0.0) \
            - (time.perf_counter() - t0) * 1e3

    def _journal_tick(self):
        """Replicate session snapshots to the router after every step
        (serving/session.py): a failover never replays more than the
        step in flight. Runs on the worker thread right after a step —
        the snapshot is a consistent cut: every accepted token is in it,
        the RNG state has consumed exactly those draws. A sink failure
        (router briefly down) only costs replay depth, never the
        generation (session.journal_errors)."""
        sink = self.journal_sink
        if sink is None:
            return
        now = time.monotonic()
        records = [req.journal_record(self.config.page_size, now)
                   for req in self._active
                   if req.session_id is not None and req.tokens]
        if not records:
            return
        try:
            sink(records)
        except Exception as e:
            telemetry.counter_add("session.journal_errors", 1,
                                  exc=type(e).__name__)

    def _seat(self, req: GenerationRequest, logits_row: np.ndarray):
        """A prefilled request gets its first token and, unless that ends
        it, keeps the slot its admission took until it retires: the host's
        work between the prefill's logits and the next dispatch
        (decode.seat_ms, one observation a seated request)."""
        with _admission_part("seat", req) as ms:
            self._first_token(req, logits_row)
            req.pos_next = int(req.seq.size)
            if req.finished():
                self._retire(req)
            else:
                if self.kv.has_state:   # its prefill wrote the slot's state
                    telemetry.counter_add("decode.state_slots_seated", 1)
                self._active.append(req)
        telemetry.observe_quiet("decode.seat_ms", ms["decode.admit_ms"])

    def _take_slot(self, req: GenerationRequest):
        req.slot = self._free_slots.pop()
        self.kv.note_state_slots(
            self.config.max_slots - len(self._free_slots))

    def _release_slot(self, req: GenerationRequest):
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None
            self.kv.note_state_slots(
                self.config.max_slots - len(self._free_slots))

    def _first_token(self, req: GenerationRequest, logits_row: np.ndarray):
        """A request's first token, chosen on the host from the logits row
        its prefill handed over."""
        tok = req.sample(logits_row)
        if req.first_logits is True:
            req.first_logits = np.array(logits_row, np.float32)
        telemetry.counter_add("decode.tokens_host_sampled", 1)
        self._accept_token(req, tok)

    def _accept_token(self, req: GenerationRequest, tok: int):
        now = time.monotonic()
        if req.t_first is None:
            req.t_first = now
        else:
            telemetry.observe_quiet("decode.token_gap_ms",
                                    (now - req.token_walls[-1]) * 1e3)
        req.tokens.append(tok)
        req.token_walls.append(now)
        req.last_token = tok

    def _retire(self, req: GenerationRequest, error: Optional[BaseException]
                = None):
        """Slot recycling: free the request's PRIVATE pages, drop its
        prefix-store references and resolve/fail it — finished
        sequences leave WITHOUT draining the batch. Shared pages stay
        resident in the store (that is the cache)."""
        if req.final_state is True and req.slot is not None:
            # queued behind the steps dispatched so far, none of which
            # holds a request that ended on its count
            req.final_state = {n: self._pools[n][req.slot]
                               for n in self.kv.state_names()}
        if req.final_pages is True:
            # a device gather of its own pages, queued like the state's
            pages = np.asarray(req.pages, np.int32)
            req.final_pages = {n: self._pools[n][pages]
                               for n in self.pool.array_names()}
            if self.kv.ring is not None:    # and its ring, in ring order
                ring = np.asarray(req.ring_pages, np.int32)
                req.final_pages.update(
                    (n, self._pools[n][ring])
                    for n in self.kv.ring.array_names())
        self._release_slot(req)
        # A slot's recurrent state needs no clearing, here or with a step in
        # flight that still advances it (a row dispatched on speculation):
        # the successor's prefill is queued behind that step on the device
        # and overwrites the slot's state and tail whole.
        # Freeing here is safe with a step in flight that still holds this
        # request as a row (dispatched on speculation past its EOS or its
        # deadline): that step's K/V write lands on a page the request
        # reserved at admission (pages_for_tokens(prompt + max_new_tokens)),
        # and a later owner's prefill is queued behind it on the device.
        if req.pages or req.ring_pages:
            self.kv.free(req.pages, req.ring_pages)
            req.pages, req.ring_pages = [], []
        if req.shared_blocks:
            self.prefix_store.release(req.shared_blocks)
            req.shared_blocks = []
        telemetry.counter_add("decode.retired", 1)
        telemetry.observe("decode.request_ms",
                          (time.monotonic() - req.t_submit) * 1e3,
                          kind="timer")
        if error is not None:
            req.fail(error)
        else:
            req.resolve(np.asarray(req.tokens, np.int32))


def decode_engine_from_dir(model_dir: str,
                           config: Optional[DecodeConfig] = None,
                           version: int = 0) -> DecodeEngine:
    """Servable dir (models/decoder_lm.save_decoder_lm) -> engine — the
    frozen-model path the HTTP server and cluster plane use."""
    from ..models.decoder_lm import load_decoder_lm

    cfg, params = load_decoder_lm(model_dir)
    return DecodeEngine(cfg, params, config=config, version=version)


def demo_engine(config: Optional[DecodeConfig] = None,
                model_cfg: Optional[Any] = None,
                seed: int = 0) -> DecodeEngine:
    """Deterministically-initialised small LM engine (tests/bench)."""
    from ..models.decoder_lm import DecoderLMConfig, decoder_lm_params

    cfg = model_cfg or DecoderLMConfig()
    return DecodeEngine(cfg, decoder_lm_params(cfg, seed), config=config)
