"""Falcon-H1 decoder (tiiuae, `model_type: falcon_h1`) — the fourth served
model behind `DecodeEngine`, and the first whose layers keep a RECURRENT
STATE beside their K/V pages: every block runs a Mamba-2 mixer in parallel
with grouped attention on the same normed input.

The block (benchmark/reference_falcon_h1.py is its plain float32
statement; the multipliers are the published muP constants):

* ``h0 = E[ids] * embedding_multiplier``; each layer ``x = RMS_in(h)``,
  ``h += Mixer(x) * ssm_out_multiplier + Attn(x * attention_in_multiplier)
  * attention_out_multiplier``, then ``h += MLP(RMS_ff(h))``;
  ``logits = (RMS_f(h) @ W_head) * lm_head_multiplier`` (untied).
* ``MLP``: ``down(up(x) * silu(gate(x) * mlp_multipliers[0])) *
  mlp_multipliers[1]``.
* ``Attn``: q, k, v without bias; ``k *= key_multiplier``; rotary positions
  over the whole head (rotate-half) on q and k; causal softmax with each
  K/V head shared by ``num_heads / num_kv_heads`` query heads; ``o_proj``.
* ``Mixer``: ``u = in_proj(x * ssm_in_multiplier) * mup_vector`` (the five
  ``ssm_multipliers`` over the columns of z, x, B, C, dt), split
  ``z | xBC | dt``; ``xBC = silu(causal depthwise conv1d(xBC) + bias)``,
  split into x (heads x head_dim), B and C (groups x d_state; a head reads
  its group's); the selective recurrence of ops/ssm_ops.py in float32;
  ``y = RMS_grouped(y * silu(z))`` (the gate first, each group of
  ``d_ssm / groups`` normed alone); ``out_proj``.

Cache: a layer keeps a context's K/V pages AND a slot's state
(`LayerCache(kv_dim, ssm_state=..., conv_tail=...)`): the recurrent state
[heads, d_state, head_dim] in ``ssm_state_dtype`` (float32: a running sum
over a request's whole life) and the conv tail, the last ``d_conv - 1``
inputs of the convolution. The decode step advances both one token a row,
in place, at the row's slot (``state_slots``, which the engine derives from
``carry``); the whole-prompt prefill runs the chunked scan from a zero
state and WRITES the slot's state and tail (the state after the last REAL
token, the inputs of the last real tokens), whatever the slot's last owner
left there.

Weights and pages are bfloat16; activations between matmuls, norms,
softmax, the convolution, the recurrence and logits are float32, every
product accumulates in float32 (ops/llm_ops.py).

There is no chunked prefill: a chunk would have to resume the state its
predecessor left, and the prefix store shares pages, not states. So
`build_chunk_prefill_program` refuses, and the engine refuses the prefix
store and the disaggregated roles for this model.
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
class FalconH1Config:
    vocab_size: int = 512             # rows of embedding and head held
    hidden_size: int = 64
    n_layers: int = 2
    num_heads: int = 5                # query heads
    num_kv_heads: int = 1
    head_dim: int = 16
    intermediate_size: int = 128
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_d_state: int = 32
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 16
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights, K/V pages, the conv tail
    ssm_state_dtype: str = "float32"  # a configuration key, not a knob
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.ssm_multipliers = tuple(float(v) for v in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(v) for v in self.mlp_multipliers)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} K/V heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} mixer heads do not "
                             f"divide over {self.mamba_n_groups} groups")
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if len(self.ssm_multipliers) != 5:
            raise ValueError("ssm_multipliers: one each for z, x, B, C, dt")

    @property
    def d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, then B and C of every group."""
        return self.d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.mamba_n_heads

    def mup_vector(self) -> np.ndarray:
        """`ssm_multipliers` over the in-projection's columns: z, x, B, C,
        dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        widths = (self.d_ssm, self.d_ssm, gn, gn, self.mamba_n_heads)
        return np.concatenate([np.full(w, m, np.float32) for w, m
                               in zip(widths, self.ssm_multipliers)])

    def served(self) -> "FalconH1Served":
        return FalconH1Served(self)


# kinds of `param_specs` beside "normal" and a constant
MUP, A_LOG, DT_BIAS = "mup_vector", "a_log", "dt_bias"
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)


def param_specs(cfg: FalconH1Config) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (`init_scale`), a
    constant, or one of MUP (the muP vector, from the configuration), A_LOG
    (log of A uniform in A_RANGE) and DT_BIAS (the inverse softplus of dt
    log-uniform in DT_RANGE), the family's public initialisation. Matrices
    are in ``cfg.dtype``; gains, the convolution and the per-head scalars
    are float32."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv, f = cfg.num_heads * hd, cfg.num_kv_heads * hd, \
        cfg.intermediate_size
    h = cfg.mamba_n_heads
    specs = {"fh_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "fh_head_w": ((d, cfg.vocab_size), "normal", dt),
             "fh_norm_f": ((d,), 1.0, "float32"),
             "fh_mup_vector": ((cfg.in_proj_dim,), MUP, "float32")}
    for i in range(cfg.n_layers):
        p = f"fh_l{i}_"
        for name, shape, kind in (
                ("norm_in", (d,), 1.0), ("norm_ff", (d,), 1.0),
                ("mixer_norm", (cfg.d_ssm,), 1.0),
                ("conv_w", (cfg.mamba_d_conv, cfg.conv_dim), "normal"),
                ("conv_b", (cfg.conv_dim,), 0.0),
                ("a_log", (h,), A_LOG), ("dt_bias", (h,), DT_BIAS),
                ("d_skip", (h,), 1.0)):
            specs[p + name] = (shape, kind, "float32")
        for name, shape in (
                ("in_w", (d, cfg.in_proj_dim)), ("out_w", (cfg.d_ssm, d)),
                ("q_w", (d, nq)), ("k_w", (d, nkv)), ("v_w", (d, nkv)),
                ("o_w", (nq, d)), ("gate_w", (d, f)), ("up_w", (d, f)),
                ("down_w", (f, d))):
            specs[p + name] = (shape, "normal", dt)
    return specs


# a row's attention scores spread by about this with seeded weights, so the
# softmax picks keys and does not average its context
QUERY_GAIN = 4.0


def init_scale(cfg: FalconH1Config, name: str, shape: tuple):
    """Standard deviation of a seeded ``normal`` parameter (a scalar, or one
    a column). fan_in^-0.5, DIVIDED by the published multiplier that scales
    what the matrix produces, so that with the multipliers kept every
    branch hands on unit-scale activations and mixer, attention and MLP
    each write to the residual at a comparable gain (at fan_in^-0.5 alone
    the multipliers 0.088, 0.0375 and 0.011 would leave the logits to the
    embedding). A checkpoint brings its own scales: these are the seeded
    weights'."""
    if name == "fh_tok_emb":
        return 1.0 / cfg.embedding_multiplier
    std = shape[-2] ** -0.5
    part = name.split("_", 2)[-1] if name.startswith("fh_l") else name
    if part == "in_w":
        return std / (cfg.ssm_in_multiplier * cfg.mup_vector())
    return std / {
        "fh_head_w": cfg.lm_head_multiplier,
        "out_w": cfg.ssm_out_multiplier,
        "q_w": cfg.attention_in_multiplier / QUERY_GAIN,
        "k_w": cfg.attention_in_multiplier * cfg.key_multiplier,
        "v_w": cfg.attention_in_multiplier,
        "o_w": cfg.attention_out_multiplier,
        "gate_w": cfg.mlp_multipliers[0],
        "down_w": cfg.mlp_multipliers[1]}.get(part, 1.0)


def seeded_value(cfg, name, spec, normal, uniform, xp=np):
    """One parameter's float32 values: `normal(shape)` and `uniform(shape)`
    ([0, 1)) draw them, `xp` is numpy or jax.numpy."""
    shape, kind, _ = spec
    if kind == "normal":
        return normal(shape) * xp.asarray(init_scale(cfg, name, shape),
                                          xp.float32)
    if kind == MUP:
        return xp.asarray(cfg.mup_vector())
    if kind == A_LOG:
        lo, hi = A_RANGE
        return xp.log(lo + (hi - lo) * uniform(shape))
    if kind == DT_BIAS:
        lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
        dt = xp.exp(lo + (hi - lo) * uniform(shape))
        return dt + xp.log(-xp.expm1(-dt))          # softplus^-1(dt)
    return xp.full(shape, kind, xp.float32)


def falcon_h1_params(cfg: FalconH1Config, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    import ml_dtypes

    rng = np.random.RandomState(seed)
    out = {}
    for name, spec in sorted(param_specs(cfg).items()):
        v = seeded_value(cfg, name, spec, lambda s: rng.normal(0.0, 1.0, s),
                         lambda s: rng.uniform(0.0, 1.0, s))
        out[name] = np.asarray(v).astype(
            ml_dtypes.bfloat16 if spec[2] == "bfloat16" else spec[2])
    return out


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program. Parameters by name, norms and projections
    are models/program_block.py's (`param`, `norm`, `linear`), the same in
    every phase; how a layer attends and how its mixer convolves and scans
    are the phase's own."""

    def __init__(self, cfg: FalconH1Config, kv: PagedKVCache):
        super().__init__(cfg, kv, param_specs(cfg))

    @staticmethod
    def scaled(x, by: float):
        if by == 1.0:
            return x
        return _op("scale", {"X": x}, {"Out": None}, {"scale": float(by)})

    def states(self, i):
        """(State, ConvTail), (StateOut, ConvTailOut) of layer i."""
        cfg, slots = self.cfg, self.kv.state_slots
        return self.arrays(
            state_array_names(i),
            [[slots, cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_head],
             [slots, cfg.mamba_d_conv - 1, cfg.conv_dim]],
            [cfg.ssm_state_dtype, cfg.dtype])

    def attn_attrs(self):
        cfg = self.cfg
        return {"num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.head_dim, "scale": cfg.head_dim ** -0.5,
                "window": 0, "ring": False}

    def ssm_attrs(self):
        cfg = self.cfg
        return {"n_heads": cfg.mamba_n_heads, "head_dim": cfg.mamba_d_head,
                "n_groups": cfg.mamba_n_groups,
                "d_state": cfg.mamba_d_state}

    def mixer(self, x, i, conv, scan):
        cfg, p = self.cfg, f"fh_l{i}_"
        u = self.linear(self.scaled(x, cfg.ssm_in_multiplier), p + "in_w")
        z, xbc, dt = _op("ssm_split",
                         {"U": u, "Mup": self.param("fh_mup_vector")},
                         {"Z": None, "XBC": None, "Dt": None},
                         {"d_ssm": cfg.d_ssm, "conv_dim": cfg.conv_dim})
        (state, tail), (state_out, tail_out) = self.states(i)
        xs, bm, cm = conv({"XBC": xbc, "ConvTail": tail,
                           "W": self.param(p + "conv_w"),
                           "Bias": self.param(p + "conv_b")},
                          {"X": None, "B": None, "C": None,
                           "ConvTailOut": tail_out})[:3]
        y = scan({"X": xs, "B": bm, "C": cm, "Dt": dt,
                  "ALog": self.param(p + "a_log"),
                  "D": self.param(p + "d_skip"),
                  "DtBias": self.param(p + "dt_bias"), "State": state},
                 {"Y": None, "StateOut": state_out})[0]
        y = _op("gated_group_rms_norm",
                {"X": y, "Gate": z, "Scale": self.param(p + "mixer_norm")},
                {"Y": None}, {"groups": cfg.mamba_n_groups,
                              "epsilon": cfg.rms_norm_eps})
        return self.scaled(self.linear(y, p + "out_w"),
                           cfg.ssm_out_multiplier)

    def attention(self, x, i, positions, attend):
        cfg, p = self.cfg, f"fh_l{i}_"
        a_in = self.scaled(x, cfg.attention_in_multiplier)
        q, k = _op("qk_rope", {"Q": self.linear(a_in, p + "q_w"),
                               "K": self.linear(a_in, p + "k_w"),
                               "Positions": positions},
                   {"QOut": None, "KOut": None},
                   {"head_dim": cfg.head_dim, "theta": cfg.rope_theta,
                    "k_scale": cfg.key_multiplier})
        o = attend(i, q, k, self.linear(a_in, p + "v_w"))
        return self.scaled(self.linear(o, p + "o_w"),
                           cfg.attention_out_multiplier)

    def mlp(self, x, i):
        cfg, p = self.cfg, f"fh_l{i}_"
        gate = self.scaled(self.linear(x, p + "gate_w"),
                           cfg.mlp_multipliers[0])
        mid = _op("swiglu", {"Gate": gate, "Up": self.linear(x, p + "up_w")},
                  {"Out": None})
        return self.scaled(self.linear(mid, p + "down_w"),
                           cfg.mlp_multipliers[1])

    def layer(self, h, i, positions, attend, conv, scan):
        p = f"fh_l{i}_"
        x = self.norm(h, p + "norm_in")
        h = h + self.mixer(x, i, conv, scan) \
            + self.attention(x, i, positions, attend)
        return h + self.mlp(self.norm(h, p + "norm_ff"), i)

    def embed(self, tokens):
        return _op("embed_scaled",
                   {"W": self.param("fh_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"scale": self.cfg.embedding_multiplier})

    def logits(self, x):
        out = _op("linear_acc32", {"X": self.norm(x, "fh_norm_f"),
                                   "W": self.param("fh_head_w")},
                  {"Out": None})
        _op("scale", {"X": out}, {"Out": _named_out("logits")},
            {"scale": float(self.cfg.lm_head_multiplier)})


class FalconH1Served(ServedModel):
    def __init__(self, cfg: FalconH1Config):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        cfg = self.cfg
        return [LayerCache(
            cfg.num_kv_heads * cfg.head_dim,
            ssm_state=(cfg.mamba_n_heads, cfg.mamba_d_state,
                       cfg.mamba_d_head),
            conv_tail=(cfg.mamba_d_conv - 1, cfg.conv_dim),
            state_dtype=cfg.ssm_state_dtype) for _ in range(cfg.n_layers)]

    def _table(self, kv, batch):
        mp = -(-self.cfg.max_seq_len // kv.page_size)
        return layers.static_data("page_table", [batch, mp], "int32")

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One decode step at a fixed [batch] slot array: every row one
        token through pages and state. `state_slots` [batch] names each
        row's slot (the engine gives it from `carry`)."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [batch], "int32")
            positions = layers.static_data("positions", [batch], "int32")
            slots = layers.static_data("state_slots", [batch], "int32")
            table = self._table(kv, batch)
            blk = _Block(cfg, kv)

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
                           blk.ssm_attrs())

            def scan(ins, outs):
                return _op("ssm_state_update", dict(ins, Slots=slots), outs,
                           blk.ssm_attrs())

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, scan)
            blk.logits(h)
        return (main, ["tokens", "positions", "state_slots", "page_table"],
                ["logits"] + blk.pool_outs)

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt: every real
        token's K/V into the layer's pages, the slot's state and conv tail
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
                           blk.ssm_attrs())

            def scan(ins, outs):
                return _op("ssm_chunk_scan",
                           dict(ins, Slots=slots, Lengths=lengths), outs,
                           dict(blk.ssm_attrs(),
                                chunk=cfg.mamba_chunk_size))

            h = blk.embed(tokens)
            for i in range(cfg.n_layers):
                h = blk.layer(h, i, positions, attend, conv, scan)
            last = _op("last_token_rows", {"X": h, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return (main, ["tokens", "positions", "lengths", "state_slots",
                       "page_table"], ["logits"] + blk.pool_outs)

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise NotImplementedError(
            "falcon_h1 has no chunked prefill: a chunk would have to resume "
            "the recurrent state and conv tail its predecessor left, and the "
            "prefix store shares pages, not states")
