"""The seam between `DecodeEngine` and the model it serves.

The engine owns admission, batching, the loop, the device sampler, the
pools and every ``decode.*`` span; a model module owns its block. What
the engine asks of a model is this class: its three program builders
(decode step, whole-prompt prefill, page-chunked prefill), how its
parameters are laid out and prepared, and what each layer keeps of a
sequence (`kv_cache.LayerCache`: the width of its K/V and whether its
pages are a context's or a ring, or that it keeps ONE latent array a
token and no K and V; and what it keeps a SLOT beside them, or INSTEAD
of them: a recurrent state and a conv tail). models/decoder_lm.py,
models/afmoe.py, models/kimi_k2.py, models/falcon_h1.py,
models/qwen3_next.py, models/motif3.py, models/xing4.py and models/lfm2.py
each give one;
``cfg.served()`` builds it.

A builder returns ``(program, feeds, fetches)``. ``feeds`` are the names
the program reads beside parameters and pools, out of what the engine can
give:

  step     tokens [B], positions [B], page_table [B, MP], ring_table [B, R],
           state_slots [B]
  prefill  tokens [1, S], lengths [1], last_onehot [1, S], positions [1, S],
           page_table [1, MP], ring_table [1, R], state_slots [1]
  chunk    tokens, positions [1, C], chunk_start [1], lengths [1],
           last_onehot [1, C], page_table, ring_table

and the engine feeds exactly those (a program's arguments are part of its
compiled form). Beside them a program is fed the pool arrays its layout
names (`kv_cache.pool_array_names`: ``kv_k_<l>`` and ``kv_v_<l>``, or a
latent layer's one ``kv_c_<l>``) and writes each back as ``<name>_out``:
the engine threads and donates exactly `PagedKVCache.make_arrays()`'s
names. A latent layer so feeds ``kv_c_<l>`` [pages, page, row width] and
fetches ``kv_c_<l>_out``; it reads ``page_table``, or with a window
(`LayerCache(latent=True, window=w)`, a LATENT RING: models/motif3.py)
its ``kv_c_<l>`` is of the ring class, [ring pages, page, row width], and
it reads ``ring_table``; its model builds no chunk program (the engine
refuses the prefix store for it). A layer with per-slot state (`LayerCache.ssm_state`) also feeds
``ssm_state_<l>`` [slots + 1, heads, d_state, head_dim] and
``conv_tail_<l>`` [slots + 1, d_conv - 1, conv_dim]
(`kv_cache.state_array_names`) and writes each back likewise; a
STATE-ONLY layer (`LayerCache(0, ssm_state=..., conv_tail=...)`: a
linear-attention layer of a model whose other layers attend,
models/qwen3_next.py) feeds and fetches those two ALONE: no ``kv_*_<l>``
array exists for it, the context pool holds the attending layers' arrays
only, and ``page_table`` is read by those layers;
a TAIL-ONLY layer (`LayerCache(0, conv_tail=...)` and no `ssm_state`: a
layer whose whole mixer is a gated short convolution, models/lfm2.py)
feeds and fetches ``conv_tail_<l>`` ALONE
(`kv_cache.state_array_names(l, tail_only=True)`), and the engine counts
its rows as ``decode.conv_rows_updated``, not as
``decode.state_rows_updated`` (rows of a RECURRENT state);
``state_slots`` names each row's slot (a step's from the slot its seated
request keeps, which ``carry`` holds on the device; a padding row's and a
warm-up feed's is the scratch slot, the arrays' last). Its prefill WRITES
the slot's state and tail, its step advances them in place; its model
builds no chunk program (a state has no pages to share: the engine refuses
the prefix store and the disaggregated roles for it). Every program writes
``logits``; a step program may also write
``step_counts``, int32 [len(step_counters)], which the engine fetches in
the same fetch as the step's tokens and adds to the telemetry counters
named in ``step_counters``.

A model with a DRAFT MODULE (``draft = True``; models/xing4.py) is stepped
two positions a slot: its step program's rows are pairs (row ``2s`` slot s
at its position, row ``2s + 1`` the position after it, fed the slot's last
accepted token and the draft of the next), it is fed ``live`` [2B] bool
beside ``tokens``, ``positions`` and ``page_table`` (each [2B, ...]) and
also writes ``hidden`` [2B, hidden]; ``build_draft_program`` gives the
module's program over the same pairs (fed ``hidden``, the tokens AFTER the
rows' positions, and ``pick`` [B], the row of each pair whose
``draft_logits`` [B, vocab] it writes; its ``step_counts`` follow the step
program's in ``step_counters``). The engine runs both in ONE jitted step
with the acceptance rule between them (serving/sampling.py) and carries a
slot's position, draft and draft distribution on the device. Its prefill
is also fed ``next_tokens`` [1, S] (the prompt moved left by one) and
``next_lengths`` [1] (its length less one), fills the module's own latent
layer and also writes ``hidden`` [1, hidden] of the last real position. A
step may write latent rows up to DRAFT_SPARE_TOKENS positions past what a
request may reach (tokens that are thrown away): its page tables are that
much wider than ``max_seq_len``, and the columns behind a request's pages
name the scratch page.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from .kv_cache import LayerCache, PagedKVCache


# positions past `max_seq_len` that a drafting model's page tables hold: a
# step dispatched before the one before it was fetched may write this far
# beyond a request's last kept token
DRAFT_SPARE_TOKENS = 2


class ServedModel:
    kv_dtype: str = "float32"
    step_counters: Tuple[str, ...] = ()
    draft: bool = False         # has a draft module: `build_draft_program`
    # the jitted step returns its float32 logits [B, vocab] beside the
    # tokens (the array the sampler read: no work more), and a request
    # submitted with `keep_step_outputs` keeps its row of every step: how a
    # check reads the logits of STEPS where greedy tokens say nothing (a
    # head tied to a unit-scale embedding repeats the last token)
    keeps_step_logits: bool = False

    def __init__(self, cfg: Any):
        self.cfg = cfg          # max_seq_len, eos_id, vocab_size

    def cache_layout(self) -> List[LayerCache]:
        raise NotImplementedError

    def prepare_params(self, params: Dict[str, Any],
                       weight_quant: str) -> Dict[str, Any]:
        """The parameter dict as the programs read it (e.g. quantized)."""
        if weight_quant != "none":
            raise ValueError(f"{type(self).__name__} has no "
                             f"weight_quant {weight_quant!r}")
        return params

    def build_step_program(self, batch: int, kv: PagedKVCache,
                           weight_quant: str):
        raise NotImplementedError

    def build_draft_program(self, batch: int, kv: PagedKVCache,
                            weight_quant: str):
        raise NotImplementedError

    def build_prefill_program(self, prompt_len: int, kv: PagedKVCache,
                              weight_quant: str):
        raise NotImplementedError

    def build_chunk_prefill_program(self, chunk_len: int, kv: PagedKVCache,
                                    weight_quant: str):
        raise NotImplementedError
