"""AFMoE decoder (arcee-ai Trinity family, `model_type: afmoe`) — the
second served model, behind the same `DecodeEngine` as decoder_lm.py.

The block (benchmark/reference_afmoe.py is its plain float32 statement):

* ``h0 = E[ids] * sqrt(hidden)`` (muP); each layer
  ``h += RMS_post_attn(Attn(RMS_in(h)))``,
  ``h += RMS_post_mlp(MLP(RMS_pre_mlp(h)))``; ``logits = RMS_f(h) @ W_head``
  (untied).
* ``Attn``: q, k, v and a gate g projected from x; q and k RMS-normed per
  head with learned gains; rotary positions on SLIDING layers only (full
  layers carry no positions); grouped heads (query head j on K/V head
  ``j // group``); sliding layers attend keys at ``t - window < s <= t``;
  ``a = (o * sigmoid(g)) Wo``.
* ``MLP``: SwiGLU. The first ``num_dense_layers`` layers are dense; the
  rest add a shared expert to a dropless top-k routed layer with sigmoid
  scores (parallel/moe.py ``routed_experts_share``).

A configuration may hold one chip's SHARE of a deployment that divides
every layer over several chips: ``num_heads`` / ``num_kv_heads`` are the
heads held, ``experts_held`` the range of routed experts held (the router
keeps its published width ``num_experts``), ``vocab_size`` the rows of the
embedding and of the head that are held. What the absent heads and experts
would add is left out and that partial result goes on to the next layer;
nothing here stands in for the other chips.

Cache: a full layer keeps a context's pages; a sliding layer keeps a ring
of ``window / page + 1`` pages a slot (serving/kv_cache.py). Weights and
pages are bfloat16; activations between matmuls, norms, softmax, router
scores and logits are float32, every product accumulates in float32
(ops/llm_ops.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .. import layers
from ..core.ir import Program, program_guard
from ..serving.kv_cache import LayerCache, PagedKVCache
from ..serving.served_model import ServedModel
from .program_block import Block, named_out as _named_out, op as _op

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    vocab_size: int = 512             # rows of embedding and head held
    hidden_size: int = 64
    head_dim: int = 16
    num_heads: int = 4                # query heads held
    num_kv_heads: int = 1             # K/V heads held
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)
    num_dense_layers: int = 1         # leading layers with a dense MLP
    intermediate_size: int = 128      # dense MLP width
    moe_intermediate_size: int = 32   # width of every expert
    num_experts: int = 32             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 8)    # first held, how many
    route_scale: float = 2.448
    route_norm: bool = True
    sliding_window: int = 32
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights and K/V pages
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} K/V heads")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types}")
        lo, count = self.experts_held
        if lo < 0 or count < 1 or lo + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def window_of(self, layer: int) -> int:
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else 0

    def served(self) -> "AfmoeServed":
        return AfmoeServed(self)


def param_specs(cfg: AfmoeConfig) -> Dict[str, Tuple[tuple, str, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (std fan_in^-0.5;
    the fan-in is the second-to-last axis, or the last of the embedding)
    or the constant that fills it. Matrices are in ``cfg.dtype``; norm
    gains and the selection bias are float32."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    specs = {"af_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "af_head_w": ((d, cfg.vocab_size), "normal", dt),
             "af_norm_f": ((d,), 1.0, "float32")}
    for i in range(cfg.n_layers):
        p = f"af_l{i}_"
        for norm, width in (("norm_in", d), ("norm_pre_mlp", d),
                            ("norm_post_attn", d), ("norm_post_mlp", d),
                            ("q_norm", hd), ("k_norm", hd)):
            specs[p + norm] = ((width,), 1.0, "float32")
        for name, shape in (("q_w", (d, nq)), ("k_w", (d, nkv)),
                            ("v_w", (d, nkv)), ("g_w", (d, nq)),
                            ("o_w", (nq, d))):
            specs[p + name] = (shape, "normal", dt)
        if not cfg.is_moe(i):
            f = cfg.intermediate_size
            for name, shape in (("w1", (d, f)), ("w3", (d, f)),
                                ("w2", (f, d))):
                specs[p + name] = (shape, "normal", dt)
            continue
        f, eh = cfg.moe_intermediate_size, cfg.experts_held[1]
        specs[p + "router_w"] = ((d, cfg.num_experts), "normal", dt)
        specs[p + "select_bias"] = ((cfg.num_experts,), 0.0, "float32")
        for name, shape in (("sh_w1", (d, f)), ("sh_w3", (d, f)),
                            ("sh_w2", (f, d)), ("ex_w1", (eh, d, f)),
                            ("ex_w3", (eh, d, f)), ("ex_w2", (eh, f, d))):
            specs[p + name] = (shape, "normal", dt)
    return specs


def fan_in(name: str, shape: tuple) -> int:
    return shape[-1] if name == "af_tok_emb" else shape[-2]


def afmoe_params(cfg: AfmoeConfig, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    import ml_dtypes

    rng = np.random.RandomState(seed)
    out = {}
    for name, (shape, kind, dtype) in sorted(param_specs(cfg).items()):
        if kind == "normal":
            v = rng.normal(0.0, fan_in(name, shape) ** -0.5, shape)
        else:
            v = np.full(shape, kind)
        out[name] = v.astype(ml_dtypes.bfloat16 if dtype == "bfloat16"
                             else dtype)
    return out


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program: parameters by name, the norms, the
    projections and the MLPs (models/program_block.py), which are the same
    in every phase; how a layer attends is the phase's own (`attend`)."""

    def __init__(self, cfg: AfmoeConfig, kv: PagedKVCache):
        super().__init__(cfg, kv, param_specs(cfg))

    def pools(self, i):
        """(PoolK, PoolV, PoolKOut, PoolVOut, table name) of layer i."""
        cfg, kv = self.cfg, self.kv
        ring = cfg.window_of(i) > 0
        pool = kv.ring if ring else kv.context
        shape = [pool.num_pages, pool.page_size,
                 cfg.num_kv_heads * cfg.head_dim]
        pk = layers.static_data(f"kv_k_{i}", shape, cfg.dtype)
        pv = layers.static_data(f"kv_v_{i}", shape, cfg.dtype)
        outs = (_named_out(f"kv_k_{i}_out", cfg.dtype),
                _named_out(f"kv_v_{i}_out", cfg.dtype))
        self.pool_outs += [o.name for o in outs]
        return (pk, pv) + outs

    def attn_attrs(self, i):
        cfg = self.cfg
        return {"num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.head_dim, "scale": cfg.head_dim ** -0.5,
                "window": cfg.window_of(i), "ring": cfg.window_of(i) > 0}

    def layer(self, x, i, positions, attend, live=None):
        cfg, p = self.cfg, f"af_l{i}_"
        a_in = self.norm(x, p + "norm_in")
        q, k = _op("qk_norm_rope",
                   {"Q": self.linear(a_in, p + "q_w"),
                    "K": self.linear(a_in, p + "k_w"),
                    "QScale": self.param(p + "q_norm"),
                    "KScale": self.param(p + "k_norm"),
                    "Positions": positions},
                   {"QOut": None, "KOut": None},
                   {"head_dim": cfg.head_dim, "epsilon": cfg.rms_norm_eps,
                    "rope": cfg.window_of(i) > 0, "theta": cfg.rope_theta})
        o = attend(i, q, k, self.linear(a_in, p + "v_w"))
        gated = _op("sigmoid_gate",
                    {"X": o, "Gate": self.linear(a_in, p + "g_w")},
                    {"Out": None})
        x = x + self.norm(self.linear(gated, p + "o_w"),
                          p + "norm_post_attn")
        m_in = self.norm(x, p + "norm_pre_mlp")
        if not cfg.is_moe(i):
            m = self.swiglu(m_in, p, "w1", "w3", "w2")
        else:
            ins = {"X": m_in, "RouterW": self.param(p + "router_w"),
                   "SelectBias": self.param(p + "select_bias"),
                   "W1": self.param(p + "ex_w1"),
                   "W3": self.param(p + "ex_w3"),
                   "W2": self.param(p + "ex_w2")}
            if live is not None:
                ins["Live"] = live
            routed, counts = _op(
                "routed_experts", ins, {"Out": None, "Counts": None},
                {"top_k": cfg.num_experts_per_tok,
                 "held_lo": cfg.experts_held[0],
                 "route_scale": cfg.route_scale,
                 "route_norm": cfg.route_norm})
            self.counts = counts if self.counts is None \
                else self.counts + counts
            m = self.swiglu(m_in, p, "sh_w1", "sh_w3", "sh_w2") + routed
        return x + self.norm(m, p + "norm_post_mlp")

    def embed(self, tokens):
        return _op("embed_scaled",
                   {"W": self.param("af_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"scale": self.cfg.hidden_size ** 0.5})

    def logits(self, x):
        _op("linear_acc32",
            {"X": self.norm(x, "af_norm_f"), "W": self.param("af_head_w")},
            {"Out": _named_out("logits")})


class AfmoeServed(ServedModel):
    # the int32s of the step program's `step_counts`, in order
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit")

    def __init__(self, cfg: AfmoeConfig):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        cfg = self.cfg
        return [LayerCache(cfg.num_kv_heads * cfg.head_dim,
                           cfg.window_of(i)) for i in range(cfg.n_layers)]

    def _tables(self, kv, batch):
        mp = -(-self.cfg.max_seq_len // kv.page_size)
        table = layers.static_data("page_table", [batch, mp], "int32")
        feeds, ring = ["page_table"], None
        if kv.ring is not None:
            ring = layers.static_data(
                "ring_table", [batch, kv.ring_slot_pages], "int32")
            feeds.append("ring_table")
        return table, ring, feeds

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One decode step at a fixed [batch] slot array: `logits`
        [B, vocab held], the pools, and `step_counts` int32 [3]: the
        routed pairs of live rows, those on held experts, and the held
        experts hit, each summed over the MoE layers."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [batch], "int32")
            positions = layers.static_data("positions", [batch], "int32")
            table, ring_table, table_feeds = self._tables(kv, batch)
            blk = _Block(cfg, kv)
            live = _op("rows_live", {"PageTable": table}, {"Live": None},
                       dtype="bool")

            def attend(i, q, k, v):
                pk, pv, pk_out, pv_out = blk.pools(i)
                tab = ring_table if cfg.window_of(i) else table
                return _op("cached_kv_attention",
                           {"Q": q, "K": k, "V": v, "PoolK": pk,
                            "PoolV": pv, "PageTable": tab,
                            "Positions": positions},
                           {"Out": None, "PoolKOut": pk_out,
                            "PoolVOut": pv_out}, blk.attn_attrs(i))[0]

            x = blk.embed(tokens)
            for i in range(cfg.n_layers):
                x = blk.layer(x, i, positions, attend, live)
            blk.logits(x)
            fetches = ["logits"] + blk.pool_outs
            if blk.counts is not None:
                _op("assign", {"X": blk.counts},
                    {"Out": _named_out("step_counts", "int32")})
                fetches.append("step_counts")
        return main, ["tokens", "positions"] + table_feeds, fetches

    def _prompt_program(self, length, kv, chunked):
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [1, length], "int32")
            positions = layers.static_data("positions", [1, length],
                                           "int32")
            lengths = layers.static_data("lengths", [1], "int32")
            feeds = ["tokens", "positions", "lengths"]
            if chunked:
                start = layers.static_data("chunk_start", [1], "int32")
                feeds.append("chunk_start")
            table, ring_table, table_feeds = self._tables(kv, 1)
            blk = _Block(cfg, kv)
            # the padded tail routes nowhere (parallel/moe.py)
            live = _op("prompt_rows_live",
                       {"Tokens": tokens, "Lengths": lengths},
                       {"Live": None}, dtype="bool")

            def attend(i, q, k, v):
                pk, pv, pk_out, pv_out = blk.pools(i)
                tab = ring_table if cfg.window_of(i) else table
                attrs = blk.attn_attrs(i)
                if chunked:
                    return _op("chunk_cached_attention",
                               {"Q": q, "K": k, "V": v, "PoolK": pk,
                                "PoolV": pv, "PageTable": tab,
                                "ChunkStart": start, "Lengths": lengths},
                               {"Out": None, "PoolKOut": pk_out,
                                "PoolVOut": pv_out}, attrs)[0]
                _op("kv_cache_write",
                    {"K": k, "V": v, "PoolK": pk, "PoolV": pv,
                     "PageTable": tab, "Lengths": lengths},
                    {"PoolKOut": pk_out, "PoolVOut": pv_out},
                    {"ring": attrs["ring"]})
                return _op("gqa_prefill_attention",
                           {"Q": q, "K": k, "V": v}, {"Out": None},
                           dict(attrs, compute_dtype=cfg.dtype,
                                block_q=min(512, length)))

            x = blk.embed(tokens)
            for i in range(cfg.n_layers):
                x = blk.layer(x, i, positions, attend, live)
            last = _op("last_token_rows", {"X": x, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return main, feeds + table_feeds, ["logits"] + blk.pool_outs

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt: every real
        token's K/V into its layer's pages (a ring keeps the last of
        them), the last real position's logits out."""
        return self._prompt_program(prompt_len, kv, chunked=False)

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        """One page-aligned chunk of a prompt against the pool's prefix
        (`chunk_cached_attention`); `lengths` is the chunk's valid tokens
        and the logits are those of its last valid position. For a model
        of full layers only: the op refuses a window layer's ring, and
        the engine refuses the prefix store for such a model before."""
        return self._prompt_program(chunk_len, kv, chunked=True)
