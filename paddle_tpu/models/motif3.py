"""Motif-3 decoder (Motif-Technologies, `model_type: Motif`; catalog row
Motif-3-Beta) — the sixth served model behind `DecodeEngine`, and the first
whose layers keep TWO classes of latent cache: three layers of four are
window layers on a LATENT RING, the fourth a full layer on latent pages.

The block (benchmark/reference_motif3.py is its plain float32 statement;
``n`` streams of width ``C``):

* ``X_0 = [E[id]] x n``. Around each sublayer F in (Attn, MLP), with its
  own parameters (manifold-constrained hyper-connections,
  ops/pallas/mhc_mix.py): ``xt = RMS_gamma(vec(X))``; ``[l_pre, l_post,
  l_res] = xt Phi``; ``H_pre = sigmoid(a_pre l_pre + b_pre)``, ``H_post = 2
  sigmoid(a_post l_post + b_post)``, ``H_res = Sinkhorn_20(exp(a_res l_res
  + B_res))``; ``u = sum_i H_pre[i] X[i]``; ``y = F(RMS(u))``; ``X'[i] =
  sum_j H_res[i, j] X[j] + H_post[i] y``, clamped. Ops `mhc_pre` (X -> u
  and the three maps) and `mhc_post`, a kernel each. After the last layer
  ``logits = RMS(sum_i X[i]) W_head`` (untied).
* ``Attn`` (grouped differential latent attention): Kimi-K2's latent
  projections (``c_q = RMS(x W_qa)``, ``[q_n, q_r] = c_q W_qb`` a query
  head, ``[c, k_r] = x W_kva``, ``c = RMS(c)``, ONE rotary key, plain
  rotary positions) with FEWER K/V heads than query heads: ``[k_n, v] = c
  W_kvb`` a K/V head g, read by query heads ``g x group .. (g + 1) x
  group``, of which the last is the group's NOISE head: ``d_{g,j} =
  o_{g,j} - sigmoid(x W_lam)_{g,j} o_{g,noise}``; ``a = (concat(d) *
  sigmoid(x W_g)) W_o``. Layer i of the PUBLISHED numbering is a full
  layer where ``(i + 1) % sliding_window_period == 0``, else it attends
  its last ``sliding_window`` keys.
* Only ``[c, k_r]`` is cached: `LayerCache(latent=True)`, and with a
  `window` the latent RING (serving/kv_cache.py). The PREFILL attends
  expanded (`mla_prefill_attention` with `num_kv_heads` and, in a window
  layer, `window`: the kernel visits the band's block pairs only) and
  subtracts in value space; the DECODE STEP attends absorbed
  (`mla_absorb_query`, `cached_latent_attention` over the full layer's
  page table or the window layer's ring table, the same paged kernel),
  subtracts in LATENT space (`diff_head_combine` at the latent's width)
  and expands the signal heads alone by their group's ``W_uv``
  (`mla_expand_output`). The two forms are the same mathematics.
* ``MLP``: ``(PN(x W1) * (x W3)) W2`` with PolyNorm ``PN(z) = s (w1 z /
  r(z) + w2 z^2 / r(z^2) + w3 z^3 / r(z^3) + clip(b))``, ``r`` a ROW
  statistic over the whole width. The leading dense layers at
  ``intermediate_size``; the rest a shared expert beside the dropless
  routed layer (sigmoid scores, top-k, normalised and scaled weights
  applied after the experts; parallel/moe.py `routed_experts_share` with
  its ``poly`` given: the grouped kernel ``grouped_polyglu``),
  every expert with PolyNorm parameters of its own.

A configuration may hold one chip's SHARE, as models/kimi_k2.py:
``experts_held``, ``vocab_size``, and ``layer_ids`` (the published indices
of the layers held, which decide window or full, dense or routed).

Weights, pages and rings are bfloat16; the streams, activations between
matmuls, norms, maps, softmax, router scores and logits are float32, every
product accumulates in float32.

There is no chunked prefill and no multi-token prediction head
(`num_nextn_predict_layers` is carried by the configuration, not built).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple


from .. import layers
from ..core.ir import Program, program_guard
from ..serving.kv_cache import (LayerCache, PagedKVCache,
                                pool_array_names)
from ..serving.served_model import ServedModel
from .program_block import (Block, named_out as _named_out, op as _op,
                            seeded_params)

LANES = 128


@dataclass
class Motif3Config:
    vocab_size: int = 512             # rows of embedding and head held
    hidden_size: int = 64
    num_heads: int = 10               # query heads: signal and noise
    num_kv_heads: int = 2             # K/V heads = groups = noise heads
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    layer_ids: Tuple[int, ...] = (1, 2, 3)    # published indices held
    n_dense_first_layers: int = 2
    sliding_window: int = 16
    sliding_window_period: int = 4
    intermediate_size: int = 128      # dense MLP width
    moe_intermediate_size: int = 32   # width of every expert
    n_shared_experts: int = 1
    num_experts: int = 32             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 8)    # first held, how many
    route_scale: float = 2.0
    route_norm: bool = True
    n_streams: int = 4                # mhc_expansion_rate
    mhc_sinkhorn_iters: int = 20
    hidden_clamp: float = 1e6
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_seq_len: int = 256            # positions a request may reach
    dtype: str = "bfloat16"           # weights, latent pages and rings
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        self.layer_ids = tuple(int(v) for v in self.layer_ids)
        self.experts_held = tuple(int(v) for v in self.experts_held)
        lo, count = self.experts_held
        if lo < 0 or count < 1 or lo + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary positions need an even rope width")
        if self.num_heads % self.num_kv_heads \
                or self.num_heads // self.num_kv_heads < 2:
            raise ValueError(
                f"{self.num_heads} query heads are no groups of signal "
                f"heads and a noise head over {self.num_kv_heads} K/V heads")
        if not any(self.window_of(i) == 0 for i in range(self.n_layers)):
            raise ValueError("a model needs a full layer: the page table "
                             "is read for the rows that are live")

    @property
    def n_layers(self) -> int:
        return len(self.layer_ids)

    @property
    def group(self) -> int:
        """Query heads a K/V head: its signal heads and its noise head."""
        return self.num_heads // self.num_kv_heads

    @property
    def num_signal_heads(self) -> int:
        return self.num_heads - self.num_kv_heads

    def is_moe(self, layer: int) -> bool:
        return self.layer_ids[layer] >= self.n_dense_first_layers

    def window_of(self, layer: int) -> int:
        """0 for a full layer, else the keys a query of it reaches."""
        full = (self.layer_ids[layer] + 1) % self.sliding_window_period == 0
        return 0 if full else self.sliding_window

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """`latent_dim` in whole lane tiles (models/kimi_k2.py)."""
        return -(-self.latent_dim // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def n_maps(self) -> int:
        """H_pre, H_post and H_res of a token."""
        return 2 * self.n_streams + self.n_streams ** 2

    def served(self) -> "Motif3Served":
        return Motif3Served(self)


def param_specs(cfg: Motif3Config) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (`init_std`), a
    constant that fills it, or ``(mean, std)`` of a normal draw (the maps'
    biases and PolyNorm's parameters, which must differ from sublayer to
    sublayer and expert to expert for a check to see them). Matrices are
    in ``cfg.dtype``; gains, the maps' scalars and PolyNorm's are
    float32."""
    d, dt = cfg.hidden_size, cfg.dtype
    n, nkv, ns = cfg.num_heads, cfg.num_kv_heads, cfg.n_streams
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    pn = ((4,), (1.0 / 3.0, 0.25), "float32")
    specs = {"m3_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "m3_head_w": ((d, cfg.vocab_size), "normal", dt),
             "m3_norm_f": ((d,), 1.0, "float32")}
    for i in range(cfg.n_layers):
        p = f"m3_l{i}_"
        for norm, width in (("norm_in", d), ("norm_mlp", d),
                            ("q_a_norm", cfg.q_lora_rank),
                            ("kv_a_norm", cfg.kv_lora_rank)):
            specs[p + norm] = ((width,), 1.0, "float32")
        for sub in ("mhc_a_", "mhc_m_"):
            specs[p + sub + "norm"] = ((ns * d,), 1.0, "float32")
            specs[p + sub + "phi"] = ((ns * d, cfg.n_maps), "normal", dt)
            specs[p + sub + "scale"] = ((3,), 1.0, "float32")
            specs[p + sub + "bias"] = ((cfg.n_maps,), (0.0, 1.0), "float32")
        for name, shape in (
                ("q_a_w", (d, cfg.q_lora_rank)),
                ("q_b_w", (cfg.q_lora_rank, n * qk)),
                ("kv_a_w", (d, cfg.latent_dim)),
                ("kv_b_w", (cfg.kv_lora_rank,
                            nkv * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                ("lam_w", (d, cfg.num_signal_heads)),
                ("g_w", (d, cfg.num_signal_heads * cfg.v_head_dim)),
                ("o_w", (cfg.num_signal_heads * cfg.v_head_dim, d))):
            specs[p + name] = (shape, "normal", dt)
        if not cfg.is_moe(i):
            f = cfg.intermediate_size
            for name, shape in (("w1", (d, f)), ("w3", (d, f)),
                                ("w2", (f, d))):
                specs[p + name] = (shape, "normal", dt)
            specs[p + "pn"] = pn
            continue
        f, eh = cfg.moe_intermediate_size, cfg.experts_held[1]
        fs = f * cfg.n_shared_experts
        specs[p + "router_w"] = ((d, cfg.num_experts), "normal", dt)
        for name, shape in (("sh_w1", (d, fs)), ("sh_w3", (d, fs)),
                            ("sh_w2", (fs, d)), ("ex_w1", (eh, d, f)),
                            ("ex_w3", (eh, d, f)), ("ex_w2", (eh, f, d))):
            specs[p + name] = (shape, "normal", dt)
        specs[p + "sh_pn"] = pn
        specs[p + "ex_pn"] = ((eh, 4), pn[1], "float32")
    return specs


def init_std(name: str, shape: tuple) -> float:
    """Standard deviation of a seeded ``normal`` parameter: fan_in^-0.5
    (the fan-in is the second-to-last axis, or the last of the embedding)."""
    return shape[-1 if name == "m3_tok_emb" else -2] ** -0.5


def motif3_params(cfg: Motif3Config, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    return seeded_params(param_specs(cfg), init_std, seed)


# ---------------------------------------------------------------------------
# program builders

class _Block(Block):
    """The layers of one program (models/program_block.py); how a layer
    attends is the phase's own (`attend`)."""

    def __init__(self, cfg: Motif3Config, kv: PagedKVCache):
        super().__init__(cfg, kv, param_specs(cfg))

    def pool(self, i):
        """(Pool, PoolOut) of layer i: its one latent array, of the ring
        class for a window layer."""
        cfg = self.cfg
        pool = self.kv.ring if cfg.window_of(i) else self.kv.context
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

    def poly_attrs(self):
        cfg = self.cfg
        return {"epsilon": cfg.rms_norm_eps,
                "out_scale": cfg.polynorm_output_scale,
                "bias_clamp": cfg.polynorm_bias_clamp}

    def polyglu(self, x, p, w1, w3, w2, pn):
        mid = _op("polyglu", {"Gate": self.linear(x, p + w1),
                              "Up": self.linear(x, p + w3),
                              "PN": self.param(p + pn)}, {"Out": None},
                  self.poly_attrs())
        return self.linear(mid, p + w2)

    def mhc_attrs(self):
        cfg = self.cfg
        return ({"n_streams": cfg.n_streams,
                 "sinkhorn_iters": cfg.mhc_sinkhorn_iters,
                 "epsilon": cfg.rms_norm_eps},
                {"n_streams": cfg.n_streams, "clamp": cfg.hidden_clamp})

    def attention(self, x, i, positions, attend):
        cfg, p = self.cfg, f"m3_l{i}_"
        c_q = self.norm(self.linear(x, p + "q_a_w"), p + "q_a_norm")
        q_nope, q_rope, c, latent = _op(
            "mla_rope_split",
            {"Q": self.linear(c_q, p + "q_b_w"),
             "KVA": self.linear(x, p + "kv_a_w"),
             "KVScale": self.param(p + "kv_a_norm"),
             "Positions": positions},
            {"QNope": None, "QRope": None, "C": None, "Latent": None},
            dict(self.head_attrs(), epsilon=cfg.rms_norm_eps,
                 theta=cfg.rope_theta))
        # the heads' outputs, a group's signal heads then its noise head:
        # latents in the step, values in the prefill
        o, absorbed = attend(i, q_nope, q_rope, c, latent,
                             self.param(p + "kv_b_w"))
        d = _op("diff_head_combine",
                {"X": o, "LambdaLogits": self.linear(x, p + "lam_w")},
                {"Out": None},
                {"num_groups": cfg.num_kv_heads,
                 "width": cfg.kv_lora_rank if absorbed else cfg.v_head_dim})
        if absorbed:                    # the step: one W_uv a group
            d = _op("mla_expand_output",
                    {"X": d, "W": self.param(p + "kv_b_w")}, {"Out": None},
                    {"num_heads": cfg.num_signal_heads,
                     "num_kv_heads": cfg.num_kv_heads,
                     "nope_dim": cfg.qk_nope_head_dim})
        gated = _op("sigmoid_gate",
                    {"X": d, "Gate": self.linear(x, p + "g_w")},
                    {"Out": None})
        return self.linear(gated, p + "o_w")

    def mlp(self, x, i, live):
        cfg, p = self.cfg, f"m3_l{i}_"
        if not cfg.is_moe(i):
            return self.polyglu(x, p, "w1", "w3", "w2", "pn")
        ins = {"X": x, "RouterW": self.param(p + "router_w"),
               "W1": self.param(p + "ex_w1"), "W3": self.param(p + "ex_w3"),
               "W2": self.param(p + "ex_w2"), "PN": self.param(p + "ex_pn")}
        if live is not None:
            ins["Live"] = live
        poly = self.poly_attrs()
        routed, counts = _op(
            "routed_experts", ins, {"Out": None, "Counts": None},
            {"top_k": cfg.num_experts_per_tok,
             "held_lo": cfg.experts_held[0],
             "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
             "activation": "poly_norm", "pn_eps": poly["epsilon"],
             "pn_out_scale": poly["out_scale"],
             "pn_bias_clamp": poly["bias_clamp"]})
        self.counts = counts if self.counts is None \
            else self.counts + counts
        return self.polyglu(x, p, "sh_w1", "sh_w3", "sh_w2", "sh_pn") \
            + routed

    def layer(self, xs, i, positions, attend, live=None):
        p = f"m3_l{i}_"
        xs = self.around(xs, p + "mhc_a_", p + "norm_in",
                         lambda x: self.attention(x, i, positions, attend))
        return self.around(xs, p + "mhc_m_", p + "norm_mlp",
                           lambda x: self.mlp(x, i, live))

    def embed(self, tokens):
        """The embedding row in each of the streams: [..., n x C]."""
        return _op("embed_streams",
                   {"W": self.param("m3_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"n_streams": self.cfg.n_streams})

    def logits(self, xs):
        h = _op("sum_streams", {"X": xs}, {"Out": None},
                {"n_streams": self.cfg.n_streams})
        _op("linear_acc32",
            {"X": self.norm(h, "m3_norm_f"), "W": self.param("m3_head_w")},
            {"Out": _named_out("logits")})


class Motif3Served(ServedModel):
    # the int32s of the step program's `step_counts`, in order
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit")

    def __init__(self, cfg: Motif3Config):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        cfg = self.cfg
        return [LayerCache(cfg.latent_row_width, cfg.window_of(i),
                           latent=True) for i in range(cfg.n_layers)]

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
        """One decode step at a fixed [batch] slot array, every layer in
        the absorbed form: a full layer over its latent pages, a window
        layer over its slot's latent ring. `logits` [B, vocab held], the
        pools, and `step_counts` int32 [3] (models/afmoe.py)."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [batch], "int32")
            positions = layers.static_data("positions", [batch], "int32")
            table, ring_table, table_feeds = self._tables(kv, batch)
            blk = _Block(cfg, kv)
            heads = dict(blk.head_attrs(), num_kv_heads=cfg.num_kv_heads)
            live = _op("rows_live", {"PageTable": table}, {"Live": None},
                       dtype="bool")

            def attend(i, q_nope, q_rope, c, latent, w_kvb):
                pool, pool_out = blk.pool(i)
                window = cfg.window_of(i)
                q = _op("mla_absorb_query",
                        {"QNope": q_nope, "QRope": q_rope, "W": w_kvb},
                        {"Q": None}, heads)
                attrs = {"num_heads": cfg.num_heads,
                         "value_dim": cfg.kv_lora_rank,
                         "scale": cfg.softmax_scale}
                if window:
                    attrs["window"] = window
                o_c = _op("cached_latent_attention",
                          {"Q": q, "Latent": latent, "Pool": pool,
                           "PageTable": ring_table if window else table,
                           "Positions": positions},
                          {"Out": None, "PoolOut": pool_out}, attrs)[0]
                return o_c, True

            xs = blk.embed(tokens)
            for i in range(cfg.n_layers):
                xs = blk.layer(xs, i, positions, attend, live)
            blk.logits(xs)
            fetches = ["logits"] + blk.pool_outs
            if blk.counts is not None:
                _op("assign", {"X": blk.counts},
                    {"Out": _named_out("step_counts", "int32")})
                fetches.append("step_counts")
        return main, ["tokens", "positions"] + table_feeds, fetches

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt in the
        expanded form: every real token's latent row into its layer's
        pages (a ring keeps the last of them), the last real position's
        logits out."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens = layers.static_data("tokens", [1, prompt_len], "int32")
            positions = layers.static_data("positions", [1, prompt_len],
                                           "int32")
            lengths = layers.static_data("lengths", [1], "int32")
            table, ring_table, table_feeds = self._tables(kv, 1)
            blk = _Block(cfg, kv)
            # the padded tail routes nowhere (parallel/moe.py)
            live = _op("prompt_rows_live",
                       {"Tokens": tokens, "Lengths": lengths},
                       {"Live": None}, dtype="bool")

            def attend(i, q_nope, q_rope, c, latent, w_kvb):
                pool, pool_out = blk.pool(i)
                window = cfg.window_of(i)
                _op("latent_cache_write",
                    {"Latent": latent, "Pool": pool,
                     "PageTable": ring_table if window else table,
                     "Lengths": lengths}, {"PoolOut": pool_out},
                    {"ring": True} if window else {})
                kv_heads = _op("linear_acc32", {"X": c, "W": w_kvb},
                               {"Out": None})
                attrs = dict(blk.head_attrs(), scale=cfg.softmax_scale,
                             compute_dtype=cfg.dtype,
                             num_kv_heads=cfg.num_kv_heads)
                if window:
                    attrs["window"] = window
                o = _op("mla_prefill_attention",
                        {"QNope": q_nope, "QRope": q_rope,
                         "KV": kv_heads, "Latent": latent},
                        {"Out": None}, attrs)
                return o, False

            xs = blk.embed(tokens)
            for i in range(cfg.n_layers):
                xs = blk.layer(xs, i, positions, attend, live)
            last = _op("last_token_rows", {"X": xs, "Lengths": lengths},
                       {"Out": None})
            blk.logits(last)
        return main, ["tokens", "positions", "lengths"] + table_feeds, \
            ["logits"] + blk.pool_outs

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise ValueError(
            "motif3 has no chunked prefill: absorbed attention of a chunk "
            "against a latent prefix or ring is not built, so it runs "
            "without the prefix store (DecodeConfig.prefix_cache=False)")
