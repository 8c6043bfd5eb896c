"""LFM2-MoE decoder (LiquidAI, `model_type: lfm2_moe`) — the eighth served
model behind `DecodeEngine`, and the first in which a convolution is a
layer's ONLY mixer: three layers of four keep a two-row conv tail a SLOT
and nothing else (no pages, no recurrent state, no state kernel), the
fourth is grouped attention at head 64 on a context's pages.

The block (benchmark/reference_lfm2.py is its plain float32 statement):

* ``h0 = E[ids]``; layer l: ``h += Op_l(RMS(h))``, ``h += F_l(RMS(h))``
  (two RMS norms with plain gains); ``logits = RMS_f(h) E^T``: the head is
  the embedding's own array, read a second time, not a copy.
* ``layer_types[l]`` says which mixer, ``l < num_dense_layers`` which
  feed-forward; the two are independent of each other.
* ``conv``: ``[B | C | X] = u W_in``; ``z = B * X``; ``c_t = sum_k w[k]
  z_{t-(K-1)+k}`` (depthwise, causal, ``conv_L_cache`` taps, no bias, NO
  activation); ``Op = (C * c) W_out`` (ops/short_conv_ops.py).
* ``full_attention``: q, k, v without bias; an RMS norm with a gain of
  ``head_dim`` on every head of q and of k, then rotary positions over the
  whole head (rotate-half); grouped causal softmax at ``head_dim^-0.5``;
  ``W_o``. No gate.
* ``F`` of a dense layer: SwiGLU at ``intermediate_size``. Of the others: a
  dropless routed layer, ``s = sigmoid(x W_r)`` in float32, the ``top_k``
  largest of ``s + b`` kept (``b`` the expert bias, for SELECTION only),
  weights ``s_kept / (sum s_kept + 1e-6) x routed_scaling_factor``, every
  expert a SwiGLU at ``moe_intermediate_size``; no shared expert
  (parallel/moe.py ``routed_experts_share`` with ``norm_eps=1e-6``).

A configuration may hold one STAGE of a pipeline: ``layer_types`` are the
held layers' own, ``num_dense_layers`` the dense ones among them, and the
stage carries the embedding (and, tied to it, the final norm and the head,
so that a token can be sampled). ``experts_held`` may be a range of the
routed experts as in models/afmoe.py; a stage holds all of them.

Cache (`cache_layout`): an attention layer is `LayerCache(kv_dim)`, a
context's pages; a convolution layer is TAIL-ONLY, `LayerCache(0,
conv_tail=(K - 1, hidden))`: ``conv_tail_<l>`` [slots + 1, K - 1, hidden]
is all that exists of it. The step shifts a row's tail by one token in
place at the row's slot (``state_slots``); the whole-prompt prefill writes
the slot's tail as it stands after the last REAL token (zeros before a
prompt shorter than the tail).

Weights, pages and tails are bfloat16; activations between products, norms,
the router's scores, the softmax, the convolution's sum and the logits are
float32, every product accumulates in float32.

Whole-prompt attention at head 64 is `gqa_prefill_attention`'s XLA form at
every length: the flash forward kernel tiles heads of 128 lanes
(`flash_window.window_route`), so the op counts
``pallas.gqa_prefill_fallbacks{reason=short|shape}`` and the query block is
sized here so that a block's float32 scores stay at 128 MiB (PREFILL_SCORES).

There is no chunked prefill (a chunk would have to be fed the tail its
predecessor left), so `build_chunk_prefill_program` refuses, and the engine
refuses the prefix store and the disaggregated roles for a model with
per-slot state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

from .. import layers
from ..core.ir import Program, program_guard
from ..serving.kv_cache import (LayerCache, PagedKVCache,
                                state_array_names)
from ..serving.served_model import ServedModel
from .program_block import (Block, named_out as _named_out, op as _op,
                            seeded_params)

CONV, FULL = "conv", "full_attention"
# float32 scores one query block of a whole-prompt attention may hold
# (heads x block_q x prompt): 32 Mi of them, 128 MiB, past which the XLA
# form slows to 19 ps a score (ops/llm_ops.py GQA_PREFILL_KERNEL_FROM's
# table)
PREFILL_SCORES = 1 << 25


@dataclass
class Lfm2Config:
    vocab_size: int = 512
    hidden_size: int = 64
    layer_types: Tuple[str, ...] = (CONV, CONV, FULL, CONV)   # those held
    num_dense_layers: int = 1         # leading held layers with a dense MLP
    intermediate_size: int = 128      # dense SwiGLU width
    moe_intermediate_size: int = 32   # width of every expert
    num_experts: int = 16             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 16)   # first held, how many
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    head_dim: int = 16
    num_heads: int = 4
    num_kv_heads: int = 2
    conv_L_cache: int = 3             # the convolution's taps
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights, K/V pages, the conv tails
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        if set(self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types {self.layer_types}")
        if FULL not in self.layer_types:
            raise ValueError("a model needs at least one attention layer "
                             "(a context's pages)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} K/V heads")
        lo, count = self.experts_held
        if lo < 0 or count < 1 or lo + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if self.conv_L_cache < 2:
            raise ValueError("a convolution of one tap keeps no tail")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == FULL

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def served(self) -> "Lfm2Served":
        return Lfm2Served(self)


# what the seeded expert bias is drawn at (mean, std): not zero, so that a
# check can tell selection (scores + bias) from weighting (scores alone),
# and small enough that a step's rows still reach every expert: a trained
# bias is what BALANCES the experts' load, a drawn one unbalances it. At
# 0.1 a step of 128 rows hit 49.8 of 64 experts a layer on the chip (my chip
# run, PR 58: a bias of -0.1 is 0.85 of a router logit's standard deviation
# at the scores that are kept); at 0.03 a drawn router hits 63.6
EXPERT_BIAS = (0.0, 0.03)


def param_specs(cfg: Lfm2Config) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (`init_std`), a
    ``(mean, std)`` draw, or the constant that fills it. Matrices are in
    ``cfg.dtype``; gains, the convolution's taps and the expert bias are
    float32. There is no head: the programs read `lf_tok_emb` twice."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    specs = {"lf_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "lf_norm_f": ((d,), 1.0, "float32")}
    for i in range(cfg.n_layers):
        p = f"lf_l{i}_"
        specs[p + "norm_op"] = ((d,), 1.0, "float32")
        specs[p + "norm_ffn"] = ((d,), 1.0, "float32")
        if cfg.is_attention(i):
            specs[p + "q_norm"] = ((hd,), 1.0, "float32")
            specs[p + "k_norm"] = ((hd,), 1.0, "float32")
            mats = (("q_w", (d, nq)), ("k_w", (d, nkv)), ("v_w", (d, nkv)),
                    ("o_w", (nq, d)))
        else:
            specs[p + "conv_w"] = ((cfg.conv_L_cache, d), "normal",
                                   "float32")
            mats = (("in_w", (d, 3 * d)), ("out_w", (d, d)))
        if cfg.is_moe(i):
            f, eh = cfg.moe_intermediate_size, cfg.experts_held[1]
            specs[p + "expert_bias"] = ((cfg.num_experts,), EXPERT_BIAS,
                                        "float32")
            mats += (("router_w", (d, cfg.num_experts)),
                     ("ex_w1", (eh, d, f)), ("ex_w3", (eh, d, f)),
                     ("ex_w2", (eh, f, d)))
        else:
            f = cfg.intermediate_size
            mats += (("w1", (d, f)), ("w3", (d, f)), ("w2", (f, d)))
        for name, shape in mats:
            specs[p + name] = (shape, "normal", dt)
    return specs


def init_std(name: str, shape: tuple) -> float:
    """fan_in^-0.5: the second-to-last axis of a matrix, the last of the
    embedding, the taps of the convolution."""
    if name == "lf_tok_emb":
        return shape[-1] ** -0.5
    return shape[0 if name.endswith("_conv_w") else -2] ** -0.5


def lfm2_params(cfg: Lfm2Config, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    return seeded_params(param_specs(cfg), init_std, seed)


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program. Parameters by name, the norms, the
    projections and the SwiGLU are models/program_block.py's, the same in
    every phase; how an attention layer attends and how a convolution layer
    meets its tail are the phase's own."""

    def __init__(self, cfg: Lfm2Config, kv: PagedKVCache):
        super().__init__(cfg, kv, param_specs(cfg))

    def tail(self, i):
        """ConvTail, ConvTailOut of convolution layer i."""
        cfg = self.cfg
        (tail,), (tail_out,) = self.arrays(
            state_array_names(i, tail_only=True),
            [[self.kv.state_slots, cfg.conv_L_cache - 1, cfg.hidden_size]],
            [cfg.dtype])
        return tail, tail_out

    def attn_attrs(self):
        cfg = self.cfg
        return {"num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.head_dim, "scale": cfg.head_dim ** -0.5,
                "window": 0, "ring": False}

    def attention(self, x, i, positions, attend):
        cfg, p = self.cfg, f"lf_l{i}_"
        q, k = _op("qk_norm_rope",
                   {"Q": self.linear(x, p + "q_w"),
                    "K": self.linear(x, p + "k_w"),
                    "QScale": self.param(p + "q_norm"),
                    "KScale": self.param(p + "k_norm"),
                    "Positions": positions},
                   {"QOut": None, "KOut": None},
                   {"head_dim": cfg.head_dim, "epsilon": cfg.rms_norm_eps,
                    "rope": True, "theta": cfg.rope_theta})
        return self.linear(attend(i, q, k, self.linear(x, p + "v_w")),
                           p + "o_w")

    def short_conv(self, x, i, conv):
        p = f"lf_l{i}_"
        tail, tail_out = self.tail(i)
        y = conv({"BCX": self.linear(x, p + "in_w"), "ConvTail": tail,
                  "W": self.param(p + "conv_w")},
                 {"Y": None, "ConvTailOut": tail_out})[0]
        return self.linear(y, p + "out_w")

    def ffn(self, x, i, live):
        cfg, p = self.cfg, f"lf_l{i}_"
        if not cfg.is_moe(i):
            return self.swiglu(x, p, "w1", "w3", "w2")
        ins = {"X": x, "RouterW": self.param(p + "router_w"),
               "SelectBias": self.param(p + "expert_bias"),
               "W1": self.param(p + "ex_w1"), "W3": self.param(p + "ex_w3"),
               "W2": self.param(p + "ex_w2")}
        if live is not None:
            ins["Live"] = live
        routed, counts = _op(
            "routed_experts", ins, {"Out": None, "Counts": None},
            {"top_k": cfg.num_experts_per_tok,
             "held_lo": cfg.experts_held[0],
             "route_scale": cfg.routed_scaling_factor,
             "route_norm": cfg.norm_topk_prob, "norm_eps": 1e-6})
        self.counts = counts if self.counts is None \
            else self.counts + counts
        return routed

    def layer(self, h, i, positions, attend, conv, live):
        p = f"lf_l{i}_"
        x = self.norm(h, p + "norm_op")
        if self.cfg.is_attention(i):
            h = h + self.attention(x, i, positions, attend)
        else:
            h = h + self.short_conv(x, i, conv)
        return h + self.ffn(self.norm(h, p + "norm_ffn"), i, live)

    def embed(self, tokens):
        return _op("embed_scaled",
                   {"W": self.param("lf_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"scale": 1.0})

    def logits(self, x):
        """The head tied to the embedding: its array read as [out, in]."""
        _op("linear_acc32", {"X": self.norm(x, "lf_norm_f"),
                             "W": self.param("lf_tok_emb")},
            {"Out": _named_out("logits")}, {"transpose_Y": True})


class Lfm2Served(ServedModel):
    # the int32s of the step program's `step_counts`, in order
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit")
    # a head tied to the embedding follows the last token: a check reads
    # the logits of steps, not greedy tokens (serving/served_model.py)
    keeps_step_logits = True

    def __init__(self, cfg: Lfm2Config):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        cfg = self.cfg
        tail_only = LayerCache(
            0, conv_tail=(cfg.conv_L_cache - 1, cfg.hidden_size))
        pages = LayerCache(cfg.num_kv_heads * cfg.head_dim)
        return [pages if cfg.is_attention(i) else tail_only
                for i in range(cfg.n_layers)]

    def _table(self, kv, batch):
        mp = -(-self.cfg.max_seq_len // kv.page_size)
        return layers.static_data("page_table", [batch, mp], "int32")

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One decode step at a fixed [batch] slot array: every row one
        token through its pages (attention layers) and its slot's tail
        (convolution layers; `state_slots` [batch] names each row's slot,
        the engine gives it from `carry`). Fetches `logits`, the pools and
        tails, and `step_counts` int32 [3] (the routed pairs of live rows,
        those on held experts, the held experts hit, summed over the routed
        layers)."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [batch], "int32")
            positions = layers.static_data("positions", [batch], "int32")
            slots = layers.static_data("state_slots", [batch], "int32")
            table = self._table(kv, batch)
            blk = _Block(cfg, kv)
            live = _op("rows_live", {"PageTable": table}, {"Live": None},
                       dtype="bool")

            def attend(i, q, k, v):
                (pk, pv), (pk_out, pv_out) = blk.pools(i)
                return _op("cached_kv_attention",
                           {"Q": q, "K": k, "V": v, "PoolK": pk,
                            "PoolV": pv, "PageTable": table,
                            "Positions": positions},
                           {"Out": None, "PoolKOut": pk_out,
                            "PoolVOut": pv_out}, blk.attn_attrs())[0]

            def conv(ins, outs):
                return _op("gated_short_conv_update",
                           dict(ins, Slots=slots), outs)

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, live)
            blk.logits(h)
            fetches = ["logits"] + blk.pool_outs
            if blk.counts is not None:
                _op("assign", {"X": blk.counts},
                    {"Out": _named_out("step_counts", "int32")})
                fetches.append("step_counts")
        return (main, ["tokens", "positions", "state_slots", "page_table"],
                fetches)

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt: every real
        token's K/V into the attention layers' pages, the slot's tails
        written as they stand after the last real token, the last real
        position's logits out."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [1, prompt_len], "int32")
            positions = layers.static_data("positions", [1, prompt_len],
                                           "int32")
            lengths = layers.static_data("lengths", [1], "int32")
            slots = layers.static_data("state_slots", [1], "int32")
            table = self._table(kv, 1)
            blk = _Block(cfg, kv)
            # the padded tail routes nowhere (parallel/moe.py)
            live = _op("prompt_rows_live",
                       {"Tokens": tokens, "Lengths": lengths},
                       {"Live": None}, dtype="bool")
            block_q = prefill_block_q(cfg.num_heads, prompt_len)

            def attend(i, q, k, v):
                (pk, pv), (pk_out, pv_out) = blk.pools(i)
                _op("kv_cache_write",
                    {"K": k, "V": v, "PoolK": pk, "PoolV": pv,
                     "PageTable": table, "Lengths": lengths},
                    {"PoolKOut": pk_out, "PoolVOut": pv_out},
                    {"ring": False})
                return _op("gqa_prefill_attention",
                           {"Q": q, "K": k, "V": v}, {"Out": None},
                           dict(blk.attn_attrs(), compute_dtype=cfg.dtype,
                                block_q=block_q))

            def conv(ins, outs):
                return _op("gated_short_conv_prefill",
                           dict(ins, Slots=slots, Lengths=lengths), outs)

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, live)
            last = _op("last_token_rows", {"X": h, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return (main, ["tokens", "positions", "lengths", "state_slots",
                       "page_table"], ["logits"] + blk.pool_outs)

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise NotImplementedError(
            "lfm2 has no chunked prefill: a chunk would have to be fed the "
            "conv tails its predecessor left, and the prefix store shares "
            "pages, not tails")


def prefill_block_q(num_heads: int, prompt_len: int) -> int:
    """The query block of a whole-prompt attention: the largest divisor of
    the prompt's length up to 512 whose float32 scores (heads x block x
    prompt) stay within PREFILL_SCORES, and no smaller than 64 for it."""
    for bq in range(min(512, prompt_len), 0, -1):
        if prompt_len % bq == 0 and (
                num_heads * bq * prompt_len <= PREFILL_SCORES or bq <= 64):
            return bq
    return prompt_len
