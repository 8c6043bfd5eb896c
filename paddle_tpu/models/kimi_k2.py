"""Kimi-K2 decoder (moonshotai, `model_type: kimi_k2`: the DeepSeek-V3
block) — the third served model, behind the same `DecodeEngine` as
decoder_lm.py and afmoe.py, and the first whose layers cache a LATENT.

The block (benchmark/reference_kimi_k2.py is its plain float32 statement):

* ``h0 = E[ids]``; each layer ``h += Attn(RMS(h))``, ``h += MLP(RMS(h))``;
  ``logits = RMS(h) @ W_head`` (untied).
* ``Attn`` (multi-head latent attention): ``c_q = RMS(x W_qa)``; per head
  ``[q_n, q_r] = c_q W_qb``; ``[c, k_r] = x W_kva`` with ``c = RMS(c)``
  and ``k_r`` ONE rotary key for all heads; YaRN rotary positions on q_r
  and k_r only (interleaved pairs); per head ``[k_n, v] = c W_kvb``;
  ``score = (q_n . k_n + q_r . k_r) x (nope + rope)^-0.5 x m^2`` with
  ``m = 0.1 x mscale_all_dim x ln(factor) + 1``; causal softmax;
  ``a = concat_heads(sum p v) W_o``.
* Only ``[c, k_r]`` is cached: `LayerCache(latent=True)`, one array of
  ``kv_lora_rank + qk_rope_head_dim`` values a token (carried in whole
  lane tiles: `latent_row_width`). The PREFILL attends in the expanded
  form above (`mla_prefill_attention`: a blockwise kernel on the chip,
  ops/pallas/mla_prefill_attention.py, which reads ``q_n``, ``q_r`` and
  ``c W_kvb`` as [S, heads x width] where the projections left them,
  several heads and one block pair of the causal triangle a grid step,
  masks only the diagonal blocks, and writes [S, heads x v] rounded once
  to `dtype`, what ``W_o``'s product takes); the DECODE STEP in the absorbed
  form: with ``W_kvb`` split by head into ``W_uk`` and ``W_uv``,
  ``q_c = q_n W_uk``, ``score = (q_c . c + q_r . k_r) x scale``,
  ``o = (sum p c) W_uv`` (`mla_absorb_query`, `cached_latent_attention`,
  `mla_expand_output`; the paged kernel ops/pallas/paged_mla_attention.py),
  so a step reads each cached row once for all heads and expands nothing.
  The two forms are the same mathematics.
* ``MLP``: the first ``first_k_dense`` layers a SwiGLU; the rest a shared
  expert beside a dropless top-k routed layer with sigmoid scores, a
  selection-only bias, normalised and scaled weights
  (parallel/moe.py ``routed_experts_share``, as models/afmoe.py).

A configuration may hold one chip's SHARE of a deployment whose routed
experts are spread over chips while attention is data-parallel:
``experts_held`` is the range of routed experts held (the router keeps
its published width), ``vocab_size`` the rows of embedding and head held;
every head is held. What the absent experts would add is left out and
that partial result goes on to the next layer.

Weights and pages are bfloat16; activations between matmuls, norms,
softmax, router scores and logits are float32, every product accumulates
in float32 (ops/llm_ops.py).

There is no chunked prefill: absorbed attention of a chunk against a
latent prefix is not built, so `build_chunk_prefill_program` refuses and
with it the prefix store.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple


from .. import layers
from ..core.ir import Program, program_guard
from ..ops.llm_ops import yarn_mscale
from ..serving.kv_cache import (LayerCache, PagedKVCache,
                                pool_array_names)
from ..serving.served_model import ServedModel
from .program_block import (Block, named_out as _named_out, op as _op,
                            seeded_params)

LANES = 128


@dataclass
class KimiK2Config:
    vocab_size: int = 512             # rows of embedding and head held
    hidden_size: int = 64
    num_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    n_layers: int = 3
    first_k_dense: int = 1            # leading layers with a dense MLP
    intermediate_size: int = 128      # dense MLP width
    moe_intermediate_size: int = 32   # width of every expert
    n_shared_experts: int = 1
    num_experts: int = 32             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 8)    # first held, how many
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0         # YaRN; 1 turns it off
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights and latent pages
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.experts_held = tuple(int(v) for v in self.experts_held)
        lo, count = self.experts_held
        if lo < 0 or count < 1 or lo + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions need an even rope width")

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense

    @property
    def latent_dim(self) -> int:
        """Values a latent page holds of a token: the latent, the key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The pool array's row: `latent_dim` in whole lane tiles (576 in
        640; a 576-wide bfloat16 array is laid out in 640 lanes on the
        chip whatever its shape says, and a page copy wants whole tiles)."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    def served(self) -> "KimiK2Served":
        return KimiK2Served(self)


def layer_specs(cfg, p: str, moe: bool) -> Dict[str, Tuple[tuple, str, str]]:
    """One layer's parameters under the prefix `p` (``k2_l3_``): the latent
    attention's norms and five matrices, then a dense SwiGLU or, `moe`, the
    router, its selection bias, the shared expert and the held experts
    (models/xing4.py takes its layers' and its draft module's from here)."""
    d, n, dt = cfg.hidden_size, cfg.num_heads, cfg.dtype
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    specs = {}
    for norm, width in (("norm_in", d), ("norm_mlp", d),
                        ("q_a_norm", cfg.q_lora_rank),
                        ("kv_a_norm", cfg.kv_lora_rank)):
        specs[p + norm] = ((width,), 1.0, "float32")
    for name, shape in (
            ("q_a_w", (d, cfg.q_lora_rank)),
            ("q_b_w", (cfg.q_lora_rank, n * qk)),
            ("kv_a_w", (d, cfg.latent_dim)),
            ("kv_b_w", (cfg.kv_lora_rank,
                        n * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            ("o_w", (n * cfg.v_head_dim, d))):
        specs[p + name] = (shape, "normal", dt)
    if not moe:
        f = cfg.intermediate_size
        for name, shape in (("w1", (d, f)), ("w3", (d, f)),
                            ("w2", (f, d))):
            specs[p + name] = (shape, "normal", dt)
        return specs
    f, eh = cfg.moe_intermediate_size, cfg.experts_held[1]
    fs = f * cfg.n_shared_experts
    specs[p + "router_w"] = ((d, cfg.num_experts), "normal", dt)
    specs[p + "select_bias"] = ((cfg.num_experts,), 0.0, "float32")
    for name, shape in (("sh_w1", (d, fs)), ("sh_w3", (d, fs)),
                        ("sh_w2", (fs, d)), ("ex_w1", (eh, d, f)),
                        ("ex_w3", (eh, d, f)), ("ex_w2", (eh, f, d))):
        specs[p + name] = (shape, "normal", dt)
    return specs


def param_specs(cfg: KimiK2Config) -> Dict[str, Tuple[tuple, str, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (`init_std`) or the
    constant that fills it. Matrices are in ``cfg.dtype``; norm gains and
    the selection bias are float32."""
    d, dt = cfg.hidden_size, cfg.dtype
    specs = {"k2_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "k2_head_w": ((d, cfg.vocab_size), "normal", dt),
             "k2_norm_f": ((d,), 1.0, "float32")}
    for i in range(cfg.n_layers):
        specs.update(layer_specs(cfg, f"k2_l{i}_", cfg.is_moe(i)))
    return specs


def init_std(name: str, shape: tuple) -> float:
    """Standard deviation of a seeded ``normal`` parameter: fan_in^-0.5
    (the fan-in is the second-to-last axis, or the last of the embedding)."""
    return shape[-1 if name.endswith("_tok_emb") else -2] ** -0.5


def kimi_k2_params(cfg: KimiK2Config, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    return seeded_params(param_specs(cfg), init_std, seed)


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program. Parameters by name, norms, projections
    and SwiGLU are models/program_block.py's (`param`, `norm`, `linear`,
    `swiglu`), the same in every phase; how a layer attends is the
    phase's own (`attend`)."""

    def __init__(self, cfg: KimiK2Config, kv: PagedKVCache, specs=None):
        super().__init__(cfg, kv, specs or param_specs(cfg))

    def pool(self, i):
        """(Pool, PoolOut) of layer i: its one latent array."""
        cfg, pool = self.cfg, self.kv.context
        name, = pool_array_names(i, latent=True)
        var = layers.static_data(
            name, [pool.num_pages, pool.page_size, cfg.latent_row_width],
            cfg.dtype)
        out = _named_out(name + "_out", cfg.dtype)
        self.pool_outs.append(out.name)
        return var, out

    def head_attrs(self):
        cfg = self.cfg
        return {"num_heads": cfg.num_heads,
                "nope_dim": cfg.qk_nope_head_dim,
                "rope_dim": cfg.qk_rope_head_dim}

    def attention(self, a_in, p, i, positions, attend):
        """The latent-attention sublayer of the layer whose parameters
        start with `p` and whose pool is layer `i`'s, over its normed
        input; how it attends is `attend`'s."""
        cfg = self.cfg
        c_q = self.norm(self.linear(a_in, p + "q_a_w"), p + "q_a_norm")
        q_nope, q_rope, c, latent = _op(
            "mla_rope_split",
            {"Q": self.linear(c_q, p + "q_b_w"),
             "KVA": self.linear(a_in, p + "kv_a_w"),
             "KVScale": self.param(p + "kv_a_norm"),
             "Positions": positions},
            {"QNope": None, "QRope": None, "C": None, "Latent": None},
            dict(self.head_attrs(), epsilon=cfg.rms_norm_eps,
                 theta=cfg.rope_theta, yarn_factor=cfg.rope_factor,
                 yarn_original_max=cfg.rope_original_max,
                 yarn_beta_fast=cfg.rope_beta_fast,
                 yarn_beta_slow=cfg.rope_beta_slow))
        o = attend(i, q_nope, q_rope, c, latent, self.param(p + "kv_b_w"))
        return self.linear(o, p + "o_w")

    def mlp(self, m_in, p, moe, live=None):
        """The MLP sublayer's terms over its normed input, in the order
        they are added: a dense SwiGLU, or the shared expert and the routed
        layer (whose counts join `counts`)."""
        cfg = self.cfg
        if not moe:
            return [self.swiglu(m_in, p, "w1", "w3", "w2")]
        ins = {"X": m_in, "RouterW": self.param(p + "router_w"),
               "SelectBias": self.param(p + "select_bias"),
               "W1": self.param(p + "ex_w1"), "W3": self.param(p + "ex_w3"),
               "W2": self.param(p + "ex_w2")}
        if live is not None:
            ins["Live"] = live
        routed, counts = _op(
            "routed_experts", ins, {"Out": None, "Counts": None},
            {"top_k": cfg.num_experts_per_tok,
             "held_lo": cfg.experts_held[0],
             "route_scale": cfg.routed_scaling_factor,
             "route_norm": cfg.norm_topk_prob})
        self.counts = counts if self.counts is None \
            else self.counts + counts
        return [self.swiglu(m_in, p, "sh_w1", "sh_w3", "sh_w2"), routed]

    def layer(self, x, i, positions, attend, live=None):
        cfg, p = self.cfg, f"k2_l{i}_"
        x = x + self.attention(self.norm(x, p + "norm_in"), p, i, positions,
                               attend)
        for term in self.mlp(self.norm(x, p + "norm_mlp"), p, cfg.is_moe(i),
                             live):
            x = x + term
        return x

    def embed(self, tokens):
        return _op("embed_scaled",
                   {"W": self.param("k2_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"scale": 1.0})

    def logits(self, x):
        _op("linear_acc32",
            {"X": self.norm(x, "k2_norm_f"), "W": self.param("k2_head_w")},
            {"Out": _named_out("logits")})


class KimiK2Served(ServedModel):
    # the int32s of the step program's `step_counts`, in order
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit")

    def __init__(self, cfg: KimiK2Config):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        return [LayerCache(self.cfg.latent_row_width, latent=True)
                for _ in range(self.cfg.n_layers)]

    def _table(self, batch, kv):
        mp = -(-self.cfg.max_seq_len // kv.page_size)
        return layers.static_data("page_table", [batch, mp], "int32")

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One decode step at a fixed [batch] slot array, every layer in
        the absorbed form over its latent pages: `logits` [B, vocab held],
        the pools, and `step_counts` int32 [3] (models/afmoe.py)."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [batch], "int32")
            positions = layers.static_data("positions", [batch], "int32")
            table = self._table(batch, kv)
            blk = _Block(cfg, kv)
            heads = blk.head_attrs()
            live = _op("rows_live", {"PageTable": table}, {"Live": None},
                       dtype="bool")

            def attend(i, q_nope, q_rope, c, latent, w_kvb):
                pool, pool_out = blk.pool(i)
                q = _op("mla_absorb_query",
                        {"QNope": q_nope, "QRope": q_rope, "W": w_kvb},
                        {"Q": None}, heads)
                o_c = _op("cached_latent_attention",
                          {"Q": q, "Latent": latent, "Pool": pool,
                           "PageTable": table, "Positions": positions},
                          {"Out": None, "PoolOut": pool_out},
                          {"num_heads": cfg.num_heads,
                           "value_dim": cfg.kv_lora_rank,
                           "scale": cfg.softmax_scale})[0]
                return _op("mla_expand_output", {"X": o_c, "W": w_kvb},
                           {"Out": None}, heads)

            x = blk.embed(tokens)
            for i in range(cfg.n_layers):
                x = blk.layer(x, i, positions, attend, live)
            blk.logits(x)
            fetches = ["logits"] + blk.pool_outs
            if blk.counts is not None:
                _op("assign", {"X": blk.counts},
                    {"Out": _named_out("step_counts", "int32")})
                fetches.append("step_counts")
        return main, ["tokens", "positions", "page_table"], fetches

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt in the
        expanded form: every real token's latent row into its layer's
        pages, the last real position's logits out."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [1, prompt_len], "int32")
            positions = layers.static_data("positions", [1, prompt_len],
                                           "int32")
            lengths = layers.static_data("lengths", [1], "int32")
            table = self._table(1, kv)
            blk = _Block(cfg, kv)
            # the padded tail routes nowhere (parallel/moe.py)
            live = _op("prompt_rows_live",
                       {"Tokens": tokens, "Lengths": lengths},
                       {"Live": None}, dtype="bool")

            def attend(i, q_nope, q_rope, c, latent, w_kvb):
                pool, pool_out = blk.pool(i)
                _op("latent_cache_write",
                    {"Latent": latent, "Pool": pool, "PageTable": table,
                     "Lengths": lengths}, {"PoolOut": pool_out})
                kv_heads = _op("linear_acc32", {"X": c, "W": w_kvb},
                               {"Out": None})
                return _op("mla_prefill_attention",
                           {"QNope": q_nope, "QRope": q_rope,
                            "KV": kv_heads, "Latent": latent},
                           {"Out": None},
                           dict(blk.head_attrs(), scale=cfg.softmax_scale,
                                compute_dtype=cfg.dtype))

            x = blk.embed(tokens)
            for i in range(cfg.n_layers):
                x = blk.layer(x, i, positions, attend, live)
            last = _op("last_token_rows", {"X": x, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return main, ["tokens", "positions", "lengths", "page_table"], \
            ["logits"] + blk.pool_outs

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise ValueError(
            "kimi_k2 has no chunked prefill: absorbed attention of a chunk "
            "against a latent prefix is not built, so it runs without the "
            "prefix store (DecodeConfig.prefix_cache=False)")
