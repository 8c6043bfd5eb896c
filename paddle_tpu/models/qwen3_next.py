"""Qwen3-Next decoder (Qwen, `model_type: qwen3_next`) — the fifth served
model behind `DecodeEngine`, and the first whose layers are of two KINDS of
cache: three of four are Gated-DeltaNet layers that keep a matrix state and
a conv tail a SLOT and NO pages at all, the fourth is gated softmax
attention that keeps pages and no state.

The block (benchmark/reference_qwen3_next.py is its plain float32
statement):

* ``norm(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (gains stored around
  zero). ``h0 = E[ids]``; layer l: ``h += Mixer_l(norm(h))``,
  ``h += MoE(norm(h))``; ``logits = norm(h) @ W_head`` (untied). Layer l is
  full attention where ``(l + 1) % full_attention_interval == 0``, else
  Gated DeltaNet.
* ``Attn``: ``q_proj`` gives each query head ``2 x head_dim`` values, its
  query and an output gate; q and k normed per head by ``norm``; rotary
  positions (rotate-half) on the FIRST ``rotary_dim`` dimensions of a head
  only; causal softmax with each K/V head shared by its group of query
  heads; ``a = (o * sigmoid(g)) W_o``.
* ``DeltaNet``: ``in_proj_qkvz`` and ``in_proj_ba`` laid out BY KEY HEAD
  (ops/linear_attention_ops.py `gdn_split`); q | k | v through a causal
  depthwise convolution without a bias, then SiLU; the gated delta rule of
  ops/linear_attention_ops.py in float32 on l2-normed q and k;
  ``y = w * RMS_head(o) * silu(z)`` (a plain gain); ``out_proj``.
* ``MoE``: softmax over all experts in float32, top-k, weights renormed
  over the kept (``norm_topk_prob``); plus ``sigmoid(x w_sg) *
  SwiGLU_shared(x)``.

A configuration may hold one chip's SHARE of a deployment that divides
every layer over several chips: ``num_heads`` / ``num_kv_heads`` are the
attention heads held, ``linear_key_heads`` / ``linear_value_heads`` the
DeltaNet heads held (whole key heads with their value heads),
``experts_held`` the range of routed experts held (the router keeps its
published width), ``vocab_size`` the rows of embedding and head held. What
the absent heads and experts would add is left out and that partial result
goes on; router, shared expert and norms are whole on every chip.

Cache (`cache_layout`): an attention layer is `LayerCache(kv_dim)`, a
context's pages; a DeltaNet layer is STATE-ONLY, `LayerCache(0,
ssm_state=(value heads, key_dim, value_dim), conv_tail=(d_conv - 1, 2 x
key width + value width))`: no pool array exists for it. The decode step
advances state and tail one token a row, in place, at the row's slot
(``state_slots``); the whole-prompt prefill runs the chunked rule from a
zero state and WRITES the slot's state and tail as they stand after the
last REAL token.

Weights, pages and the conv tail are bfloat16; the state, activations
between matmuls, norms, softmax, the router, the convolution and the rule
are float32, every product accumulates in float32.

There is no chunked prefill (a chunk would have to resume the state its
predecessor left), so `build_chunk_prefill_program` refuses, and the
engine refuses the prefix store and the disaggregated roles for a model
with state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .. import layers
from ..core.ir import Program, program_guard
from ..serving.kv_cache import (LayerCache, PagedKVCache,
                                state_array_names)
from ..serving.served_model import ServedModel
from .program_block import Block, named_out as _named_out, op as _op


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 512             # rows of embedding and head held
    hidden_size: int = 64
    n_layers: int = 4
    full_attention_interval: int = 4
    head_dim: int = 32
    num_heads: int = 2                # query heads held
    num_kv_heads: int = 1             # K/V heads held
    partial_rotary_factor: float = 0.25
    linear_key_heads: int = 2         # DeltaNet key heads held
    linear_value_heads: int = 4       # their value heads
    linear_key_head_dim: int = 16
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 64       # the prefill's chunk
    moe_intermediate_size: int = 32   # width of every routed expert
    shared_expert_intermediate_size: int = 32
    num_experts: int = 16             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 8)    # first held, how many
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights, K/V pages, the conv tail
    linear_state_dtype: str = "float32"   # a configuration key, not a knob
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.experts_held = tuple(int(v) for v in self.experts_held)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} K/V heads")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError(f"{self.linear_value_heads} value heads do not "
                             f"divide over {self.linear_key_heads} key "
                             f"heads")
        lo, count = self.experts_held
        if lo < 0 or count < 1 or lo + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary part {self.rotary_dim} of a head of "
                             f"{self.head_dim}")
        if not any(self.is_attention(i) for i in range(self.n_layers)):
            raise ValueError("a model needs at least one attention layer "
                             "(a context's pages)")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: q, k and v of every held head."""
        return 2 * self.key_dim + self.value_dim

    def served(self) -> "Qwen3NextServed":
        return Qwen3NextServed(self)


# kinds of `param_specs` beside "normal" and a constant
A_LOG, DT_BIAS = "a_log", "dt_bias"
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)
# what the seeded query norm's gain (1 + w) is: a row's attention scores
# spread by about this, so the softmax picks keys and does not average its
# context (as models/falcon_h1.py QUERY_GAIN)
QUERY_GAIN = 4.0
# what the seeded router's columns are scaled by beyond fan_in^-0.5: the
# kept ten's weights then spread as a sharp router's do (the first about a
# third, the tenth about a fiftieth of their sum; at 1 they run from 0.2 to
# 0.06), so a choice between the tenth and the eleventh that turns on
# bfloat16's rounding moves a fiftieth of the routed sum and not a
# fifteenth: with ten of 512 kept in every layer some layer's choice turns
# at about every second position
ROUTER_GAIN = 3.0


def param_specs(cfg: Qwen3NextConfig) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (std fan_in^-0.5, the
    router's ROUTER_GAIN times that; the fan-in is the second-to-last
    axis, or the last of the embedding), a
    constant, or A_LOG (log of A over A_RANGE) and DT_BIAS (the inverse
    softplus of dt log-spaced over DT_RANGE): the linear-attention
    family's public initialisation's ranges, on a grid over the held heads
    (`seeded_value`). Matrices are in ``cfg.dtype``; gains
    (stored around zero but the gated norm's), the convolution and the
    per-head scalars are float32."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    f, fs, eh = cfg.moe_intermediate_size, \
        cfg.shared_expert_intermediate_size, cfg.experts_held[1]
    r = cfg.linear_value_heads // cfg.linear_key_heads
    specs = {"qn_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "qn_head_w": ((d, cfg.vocab_size), "normal", dt),
             "qn_norm_f": ((d,), 0.0, "float32")}
    for i in range(cfg.n_layers):
        p = f"qn_l{i}_"
        specs[p + "norm_in"] = ((d,), 0.0, "float32")
        specs[p + "norm_post"] = ((d,), 0.0, "float32")
        if cfg.is_attention(i):
            specs[p + "q_norm"] = ((hd,), QUERY_GAIN - 1.0, "float32")
            specs[p + "k_norm"] = ((hd,), 0.0, "float32")
            mats = (("q_w", (d, 2 * nq)), ("k_w", (d, nkv)),
                    ("v_w", (d, nkv)), ("o_w", (nq, d)))
        else:
            nv = cfg.linear_value_heads
            specs[p + "conv_w"] = ((cfg.linear_conv_kernel_dim,
                                    cfg.conv_dim), "normal", "float32")
            specs[p + "a_log"] = ((nv,), A_LOG, "float32")
            specs[p + "dt_bias"] = ((nv,), DT_BIAS, "float32")
            specs[p + "gn_w"] = ((cfg.linear_value_head_dim,), 1.0,
                                 "float32")
            mats = (("qkvz_w", (d, 2 * cfg.key_dim + 2 * cfg.value_dim)),
                    ("ba_w", (d, 2 * r * cfg.linear_key_heads)),
                    ("out_w", (cfg.value_dim, d)))
        mats += (("router_w", (d, cfg.num_experts)),
                 ("sh_w1", (d, fs)), ("sh_w3", (d, fs)), ("sh_w2", (fs, d)),
                 ("sh_gate_w", (d, 1)), ("ex_w1", (eh, d, f)),
                 ("ex_w3", (eh, d, f)), ("ex_w2", (eh, f, d)))
        for name, shape in mats:
            specs[p + name] = (shape, "normal", dt)
    return specs


def fan_in(name: str, shape: tuple) -> int:
    return shape[-1] if name == "qn_tok_emb" else shape[-2]


def seeded_value(name, spec, normal, xp=np):
    """One parameter's float32 values: `normal(shape)` draws the matrices,
    `xp` is numpy or jax.numpy. A_LOG and DT_BIAS are no draw: the held
    value heads stand on a grid over the family's ranges, head j of n at
    u = (j + 0.5) / n with A = lo + (hi - lo) u and dt log-spaced, so that
    every seed and every layer has the whole spread of memories (a head
    forgets in about 1 / (A dt) tokens: ~400 down to ~1). Eight heads
    DRAWN leave some seeds without a long memory, and what rounding the
    state costs follows the longest."""
    shape, kind, _ = spec
    if kind == "normal":
        gain = ROUTER_GAIN if name.endswith("_router_w") else 1.0
        return normal(shape) * (gain * fan_in(name, shape) ** -0.5)
    if kind in (A_LOG, DT_BIAS):
        u = (xp.arange(shape[0], dtype=xp.float32) + 0.5) / shape[0]
        if kind == A_LOG:
            lo, hi = A_RANGE
            return xp.log(lo + (hi - lo) * u)
        lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
        dt = xp.exp(lo + (hi - lo) * u)
        return dt + xp.log(-xp.expm1(-dt))          # softplus^-1(dt)
    return xp.full(shape, kind, xp.float32)


def qwen3_next_params(cfg: Qwen3NextConfig, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    import ml_dtypes

    rng = np.random.RandomState(seed)
    out = {}
    for name, spec in sorted(param_specs(cfg).items()):
        v = seeded_value(name, spec, lambda s: rng.normal(0.0, 1.0, s))
        out[name] = np.asarray(v).astype(
            ml_dtypes.bfloat16 if spec[2] == "bfloat16" else spec[2])
    return out


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program. Parameters by name and the projections
    are models/program_block.py's, the same in every phase; how an
    attention layer attends and how a DeltaNet layer convolves and applies
    its rule are the phase's own."""

    def __init__(self, cfg: Qwen3NextConfig, kv: PagedKVCache):
        super().__init__(cfg, kv, param_specs(cfg))

    def norm(self, x, name):
        return _op("rms_norm", {"X": x, "Scale": self.param(name)},
                   {"Y": None}, {"epsilon": self.cfg.rms_norm_eps,
                                 "scale_offset": 1.0})

    def states(self, i):
        """(State, ConvTail), (StateOut, ConvTailOut) of DeltaNet layer i."""
        cfg, slots = self.cfg, self.kv.state_slots
        return self.arrays(
            state_array_names(i),
            [[slots, cfg.linear_value_heads, cfg.linear_key_head_dim,
              cfg.linear_value_head_dim],
             [slots, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim]],
            [cfg.linear_state_dtype, cfg.dtype])

    def attn_attrs(self):
        cfg = self.cfg
        return {"num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.head_dim, "scale": cfg.head_dim ** -0.5,
                "window": 0, "ring": False}

    def gdn_attrs(self):
        cfg = self.cfg
        return {"key_heads": cfg.linear_key_heads,
                "key_dim": cfg.linear_key_head_dim,
                "value_heads": cfg.linear_value_heads,
                "value_dim": cfg.linear_value_head_dim}

    def conv_attrs(self):
        """The convolution's three parts, in `ssm_conv_*`'s words: q
        (`n_heads` x `head_dim`), k (`n_groups` x `d_state`), the rest v."""
        cfg = self.cfg
        return {"n_heads": cfg.linear_key_heads,
                "head_dim": cfg.linear_key_head_dim,
                "n_groups": cfg.linear_key_heads,
                "d_state": cfg.linear_key_head_dim}

    def attention(self, x, i, positions, attend):
        cfg, p = self.cfg, f"qn_l{i}_"
        q, gate = _op("split_head_pairs", {"X": self.linear(x, p + "q_w")},
                      {"First": None, "Second": None},
                      {"head_dim": cfg.head_dim})
        q, k = _op("qk_norm_rope",
                   {"Q": q, "K": self.linear(x, p + "k_w"),
                    "QScale": self.param(p + "q_norm"),
                    "KScale": self.param(p + "k_norm"),
                    "Positions": positions},
                   {"QOut": None, "KOut": None},
                   {"head_dim": cfg.head_dim, "epsilon": cfg.rms_norm_eps,
                    "rope": True, "theta": cfg.rope_theta,
                    "rotary_dim": cfg.rotary_dim, "scale_offset": 1.0})
        o = attend(i, q, k, self.linear(x, p + "v_w"))
        gated = _op("sigmoid_gate", {"X": o, "Gate": gate}, {"Out": None})
        return self.linear(gated, p + "o_w")

    def delta_net(self, x, i, conv, rule):
        cfg, p = self.cfg, f"qn_l{i}_"
        qkv, z, b, a = _op("gdn_split",
                           {"QKVZ": self.linear(x, p + "qkvz_w"),
                            "BA": self.linear(x, p + "ba_w")},
                           {"QKV": None, "Z": None, "B": None, "A": None},
                           self.gdn_attrs())
        (state, tail), (state_out, tail_out) = self.states(i)
        q, k, v = conv({"XBC": qkv, "ConvTail": tail,
                        "W": self.param(p + "conv_w")},
                       {"X": None, "B": None, "C": None,
                        "ConvTailOut": tail_out})[:3]
        y = rule({"Q": q, "K": k, "V": v, "A": a, "B": b,
                  "ALog": self.param(p + "a_log"),
                  "DtBias": self.param(p + "dt_bias"), "State": state},
                 {"Y": None, "StateOut": state_out})[0]
        y = _op("gated_head_rms_norm",
                {"X": y, "Gate": z, "Scale": self.param(p + "gn_w")},
                {"Y": None}, {"head_dim": cfg.linear_value_head_dim,
                              "epsilon": cfg.rms_norm_eps})
        return self.linear(y, p + "out_w")

    def moe(self, x, i, live):
        cfg, p = self.cfg, f"qn_l{i}_"
        ins = {"X": x, "RouterW": self.param(p + "router_w"),
               "W1": self.param(p + "ex_w1"), "W3": self.param(p + "ex_w3"),
               "W2": self.param(p + "ex_w2")}
        if live is not None:
            ins["Live"] = live
        routed, counts = _op(
            "routed_experts", ins, {"Out": None, "Counts": None},
            {"top_k": cfg.num_experts_per_tok,
             "held_lo": cfg.experts_held[0], "score_func": "softmax",
             "route_norm": cfg.norm_topk_prob})
        self.counts = counts if self.counts is None \
            else self.counts + counts
        shared = _op("sigmoid_gate",
                     {"X": self.swiglu(x, p, "sh_w1", "sh_w3", "sh_w2"),
                      "Gate": self.linear(x, p + "sh_gate_w")},
                     {"Out": None})
        return shared + routed

    def layer(self, h, i, positions, attend, conv, rule, live):
        p = f"qn_l{i}_"
        x = self.norm(h, p + "norm_in")
        if self.cfg.is_attention(i):
            h = h + self.attention(x, i, positions, attend)
        else:
            h = h + self.delta_net(x, i, conv, rule)
        return h + self.moe(self.norm(h, p + "norm_post"), i, live)

    def embed(self, tokens):
        return _op("embed_scaled",
                   {"W": self.param("qn_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"scale": 1.0})

    def logits(self, x):
        _op("linear_acc32", {"X": self.norm(x, "qn_norm_f"),
                             "W": self.param("qn_head_w")},
            {"Out": _named_out("logits")})


class Qwen3NextServed(ServedModel):
    # the int32s of the step program's `step_counts`, in order
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit")

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        cfg = self.cfg
        state_only = LayerCache(
            0, ssm_state=(cfg.linear_value_heads, cfg.linear_key_head_dim,
                          cfg.linear_value_head_dim),
            conv_tail=(cfg.linear_conv_kernel_dim - 1, cfg.conv_dim),
            state_dtype=cfg.linear_state_dtype)
        pages = LayerCache(cfg.num_kv_heads * cfg.head_dim)
        return [pages if cfg.is_attention(i) else state_only
                for i in range(cfg.n_layers)]

    def _table(self, kv, batch):
        mp = -(-self.cfg.max_seq_len // kv.page_size)
        return layers.static_data("page_table", [batch, mp], "int32")

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One decode step at a fixed [batch] slot array: every row one
        token through its pages (attention layers) and its slot's state
        (DeltaNet layers; `state_slots` [batch] names each row's slot, the
        engine gives it from `carry`). Fetches `logits`, the pools and
        `step_counts` int32 [3] (the routed pairs of live rows, those on
        held experts, the held experts hit, summed over the layers)."""
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
                return _op("ssm_conv_update", dict(ins, Slots=slots), outs,
                           blk.conv_attrs())

            def rule(ins, outs):
                return _op("gated_delta_state_update",
                           dict(ins, Slots=slots), outs, blk.gdn_attrs())

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, rule, live)
            blk.logits(h)
            _op("assign", {"X": blk.counts},
                {"Out": _named_out("step_counts", "int32")})
        return (main, ["tokens", "positions", "state_slots", "page_table"],
                ["logits"] + blk.pool_outs + ["step_counts"])

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt: every real
        token's K/V into the attention layers' pages, the slot's states
        and conv tails written as they stand after the last real token,
        the last real position's logits out."""
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
                                block_q=min(512, prompt_len)))

            def conv(ins, outs):
                return _op("ssm_conv_prefill",
                           dict(ins, Slots=slots, Lengths=lengths), outs,
                           blk.conv_attrs())

            def rule(ins, outs):
                return _op("gated_delta_chunk_scan",
                           dict(ins, Slots=slots, Lengths=lengths), outs,
                           dict(blk.gdn_attrs(),
                                chunk=cfg.linear_chunk_size))

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, rule, live)
            last = _op("last_token_rows", {"X": h, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return (main, ["tokens", "positions", "lengths", "state_slots",
                       "page_table"], ["logits"] + blk.pool_outs)

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise NotImplementedError(
            "qwen3_next has no chunked prefill: a chunk would have to "
            "resume the matrix state and conv tail its predecessor left, "
            "and the prefix store shares pages, not states")
