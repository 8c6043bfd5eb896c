"""Xing4.0 decoder (XingChen-AGI, `model_type: xing4_0`; catalog row
Xing4.0-29B-A4B) — the seventh served model behind `DecodeEngine`, and the
first with a DRAFT MODULE: its multi-token-prediction module is built, and a
decode step drafts one token and verifies two a slot (serving/decode.py,
serving/sampling.py).

The block (benchmark/reference_xing4.py is its plain float32 statement) is
models/kimi_k2.py's latent attention, router and routed layer, imported,
inside models/motif3.py's residual of ``n`` streams (``mhc_pre`` /
``mhc_post``, ops/pallas/mhc_mix.py), with two additions to the maps: the
residual map's logits are clamped to ``[hc_res_clamp_min, _max]`` before
the exponential, and ``hc_eps`` is the epsilon of the streams' root mean
square and joins every Sinkhorn denominator:

* ``X_0 = [E[id]] x n``; around each sublayer F in (Attn, MLP):
  ``u, maps = mhc_pre(X)``; ``X' = mhc_post(X, F(RMS(u)), maps)``;
  ``h = sum_i X[i]``; ``logits = RMS(h) W_head`` (untied).
* ``Attn``: `kimi_k2._Block.attention` (latent attention with YaRN);
  ``MLP``: `kimi_k2._Block.mlp` (a dense SwiGLU in the leading layers, else
  the shared expert beside the sigmoid top-k routed layer).
* The draft module (DeepSeek-V3's MTP module, arXiv:2412.19437 sec. 2.2),
  at position i with the NEXT token known: ``x = [RMS_e(E[tok_{i+1}]),
  RMS_h(h_i)] W_eh`` (``h_i`` the sum of the streams after the last layer,
  before the final norm); ``X = [x] x n``; one MoE decoder layer of the
  model's own kind with maps of its own; ``logits = RMS_mtp(sum_i X[i])
  W_head`` with the model's OWN embedding and head: the distribution of
  token i + 2. Its layer is one more latent layer of the same cache
  (index ``n_layers``): position i's row is written from ``h_i`` and
  ``tok_{i+1}``.

Programs (rows of a step are PAIRS: row ``2s`` is slot s at its position,
row ``2s + 1`` the position after it, with slot s's page table):

* `build_step_program(batch)`: the held layers over ``2 x batch`` rows;
  every layer writes both rows' latents and attends them with ONE read of
  the slot's pages (`cached_latent_attention` with ``queries`` 2: the
  second position sees the first's fresh row). `logits` [2B, vocab],
  `hidden` [2B, hidden] (``h``), the pools, `step_counts`.
* `build_draft_program(batch)`: the module over ``2 x batch`` rows fed
  `hidden`, the tokens AFTER them and their positions; `draft_logits`
  [B, vocab] of the row `pick` names in each pair, the module's pool,
  `step_counts` of its routed layer.
* `build_prefill_program(prompt_len)`: the whole prompt through the held
  layers (models/kimi_k2.py's expanded form), then the module's latent
  rows for positions ``0 .. length - 2`` from ``h_i`` and `next_tokens`
  (the prompt moved left by one; the module's attention output and MLP add
  nothing to a row and are not computed); `logits` [1, vocab] and `hidden`
  [1, hidden] of the last real position, which the first step's module
  pass reads.

A configuration may hold one chip's share as models/kimi_k2.py's:
``experts_held``, ``vocab_size``, and ``first_k_dense`` of the ``n_layers``
held. Weights and pages bfloat16; streams, maps, norms, softmax, router
scores and logits float32. No chunked prefill.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple


from .. import layers
from ..core.ir import Program, program_guard
from ..layer_helper import LayerHelper
from ..serving.kv_cache import LayerCache
from ..serving.served_model import DRAFT_SPARE_TOKENS, ServedModel
from . import kimi_k2
from .program_block import (named_out as _named_out, op as _op,
                            seeded_params)

PAIR = 2        # positions a slot a step: the last accepted token, its draft


@dataclass
class Xing4Config(kimi_k2.KimiK2Config):
    """`KimiK2Config`'s fields (the block's) and the streams'."""

    routed_scaling_factor: float = 2.0
    n_streams: int = 4                # hc_mult
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    def __post_init__(self):
        super().__post_init__()
        self.hc_res_clamp = tuple(float(v) for v in self.hc_res_clamp)

    @property
    def n_maps(self) -> int:
        return 2 * self.n_streams + self.n_streams ** 2

    @property
    def mtp_layer(self) -> int:
        """The draft module's layer in the cache layout: behind the held."""
        return self.n_layers

    def served(self) -> "Xing4Served":
        return Xing4Served(self)


def _mhc_specs(cfg, p):
    """The maps' parameters of the two sublayers of the layer under `p`
    (models/motif3.py `param_specs`)."""
    d, ns, dt = cfg.hidden_size, cfg.n_streams, cfg.dtype
    specs = {}
    for sub in ("mhc_a_", "mhc_m_"):
        specs[p + sub + "norm"] = ((ns * d,), 1.0, "float32")
        specs[p + sub + "phi"] = ((ns * d, cfg.n_maps), "normal", dt)
        specs[p + sub + "scale"] = ((3,), 1.0, "float32")
        specs[p + sub + "bias"] = ((cfg.n_maps,), (0.0, 1.0), "float32")
    return specs


def param_specs(cfg: Xing4Config) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype); kinds as models/motif3.py's
    (``normal``, a constant, or ``(mean, std)``)."""
    d, dt = cfg.hidden_size, cfg.dtype
    specs = {"x4_tok_emb": ((cfg.vocab_size, d), "normal", dt),
             "x4_head_w": ((d, cfg.vocab_size), "normal", dt),
             "x4_norm_f": ((d,), 1.0, "float32"),
             "x4_mtp_enorm": ((d,), 1.0, "float32"),
             "x4_mtp_hnorm": ((d,), 1.0, "float32"),
             "x4_mtp_eh_proj": ((2 * d, d), "normal", dt),
             "x4_mtp_norm": ((d,), 1.0, "float32")}
    for p, moe in [(f"x4_l{i}_", cfg.is_moe(i))
                   for i in range(cfg.n_layers)] + [("x4_mtp_", True)]:
        specs.update(kimi_k2.layer_specs(cfg, p, moe))
        specs.update(_mhc_specs(cfg, p))
    return specs


init_std = kimi_k2.init_std


def xing4_params(cfg: Xing4Config, seed: int = 0):
    """Deterministic parameters for tests and demos, as numpy arrays in
    the dtypes `param_specs` states."""
    return seeded_params(param_specs(cfg), init_std, seed)


# ---------------------------------------------------------------------------
# program builders

class _Block(kimi_k2._Block):
    """`kimi_k2._Block`'s sublayers inside the streams' residual path."""

    def __init__(self, cfg: Xing4Config, kv):
        super().__init__(cfg, kv, param_specs(cfg))

    def mhc_attrs(self):
        cfg = self.cfg
        return ({"n_streams": cfg.n_streams,
                 "sinkhorn_iters": cfg.hc_sinkhorn_iters,
                 "epsilon": cfg.hc_eps, "sinkhorn_eps": cfg.hc_eps,
                 "res_clamp_min": cfg.hc_res_clamp[0],
                 "res_clamp_max": cfg.hc_res_clamp[1]},
                {"n_streams": cfg.n_streams})

    def layer(self, xs, i, positions, attend, live=None, p=None, moe=None):
        """Layer i of the held ones, or with `p` the layer under that
        prefix on pool i (the draft module's)."""
        p = p or f"x4_l{i}_"
        moe = self.cfg.is_moe(i) if moe is None else moe
        xs = self.around(
            xs, p + "mhc_a_", p + "norm_in",
            lambda x: self.attention(x, p, i, positions, attend))

        def mlp(x):
            terms = self.mlp(x, p, moe, live)
            return terms[0] if len(terms) == 1 else terms[0] + terms[1]

        return self.around(xs, p + "mhc_m_", p + "norm_mlp", mlp)

    def embed(self, tokens):
        return _op("embed_streams",
                   {"W": self.param("x4_tok_emb"), "Ids": tokens},
                   {"Out": None}, {"n_streams": self.cfg.n_streams})

    def hidden(self, xs):
        return _op("sum_streams", {"X": xs}, {"Out": None},
                   {"n_streams": self.cfg.n_streams})

    def head(self, h, norm, out):
        _op("linear_acc32",
            {"X": self.norm(h, norm), "W": self.param("x4_head_w")},
            {"Out": _named_out(out)})

    def draft_streams(self, hidden, next_tokens):
        """The module's input in every stream: ``[RMS_e(E[next]),
        RMS_h(hidden)] W_eh``."""
        e = _op("embed_scaled",
                {"W": self.param("x4_tok_emb"), "Ids": next_tokens},
                {"Out": None}, {"scale": 1.0})
        helper = LayerHelper("concat")
        both = helper.create_variable_for_type_inference("float32")
        helper.append_op(
            "concat", {"X": [self.norm(e, "x4_mtp_enorm"),
                             self.norm(hidden, "x4_mtp_hnorm")]},
            {"Out": [both]}, {"axis": -1})
        return _op("tile_streams",
                   {"X": self.linear(both, "x4_mtp_eh_proj")}, {"Out": None},
                   {"n_streams": self.cfg.n_streams})


class Xing4Served(ServedModel):
    # the int32s of `step_counts`: the step program's, then the draft
    # program's (the module's routed layer, counted apart: the readers of
    # decode.moe_experts_hit divide by the held MoE layers)
    step_counters = ("decode.moe_pairs_total", "decode.moe_pairs_held",
                     "decode.moe_experts_hit",
                     "decode.draft_moe_pairs_total",
                     "decode.draft_moe_pairs_held",
                     "decode.draft_moe_experts_hit")
    draft = True

    def __init__(self, cfg: Xing4Config):
        super().__init__(cfg)
        self.kv_dtype = cfg.dtype

    def cache_layout(self) -> List[LayerCache]:
        return [LayerCache(self.cfg.latent_row_width, latent=True)
                for _ in range(self.cfg.n_layers + 1)]

    def _table(self, rows, kv):
        mp = -(-(self.cfg.max_seq_len + DRAFT_SPARE_TOKENS) // kv.page_size)
        return layers.static_data("page_table", [rows, mp], "int32")

    def _paired_attend(self, blk, table, positions):
        """A step's attention: both rows of a pair written, the slot's
        latents read once for the two."""
        cfg, heads = self.cfg, blk.head_attrs()

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
                       "scale": cfg.softmax_scale, "queries": PAIR})[0]
            return _op("mla_expand_output", {"X": o_c, "W": w_kvb},
                       {"Out": None}, heads)

        return attend

    def _step_feeds(self, batch, kv):
        rows = PAIR * batch
        return (layers.static_data("tokens", [rows], "int32"),
                layers.static_data("positions", [rows], "int32"),
                self._table(rows, kv),
                layers.static_data("live", [rows], "bool"))

    @staticmethod
    def _counts(blk, fetches):
        _op("assign", {"X": blk.counts},
            {"Out": _named_out("step_counts", "int32")})
        return fetches + blk.pool_outs + ["step_counts"]

    def build_step_program(self, batch, kv, weight_quant="none"):
        """One step of the held layers over [2 x batch] rows (module
        docstring): `logits`, `hidden`, the pools, `step_counts`."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens, positions, table, live = self._step_feeds(batch, kv)
            blk = _Block(cfg, kv)
            attend = self._paired_attend(blk, table, positions)
            xs = blk.embed(tokens)
            for i in range(cfg.n_layers):
                xs = blk.layer(xs, i, positions, attend, live)
            h = blk.hidden(xs)
            _op("assign", {"X": h}, {"Out": _named_out("hidden")})
            blk.head(h, "x4_norm_f", "logits")
            fetches = self._counts(blk, ["logits", "hidden"])
        return main, ["tokens", "positions", "page_table", "live"], fetches

    def build_draft_program(self, batch, kv, weight_quant="none"):
        """The draft module over [2 x batch] rows: `tokens` are the tokens
        AFTER the rows' positions, `hidden` the held layers' ``h`` at them;
        `draft_logits` [batch, vocab] at the row `pick` [batch] names."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            tokens, positions, table, live = self._step_feeds(batch, kv)
            hidden = layers.static_data(
                "hidden", [PAIR * batch, cfg.hidden_size], "float32")
            pick = layers.static_data("pick", [batch], "int32")
            blk = _Block(cfg, kv)
            xs = blk.draft_streams(hidden, tokens)
            xs = blk.layer(xs, cfg.mtp_layer, positions,
                           self._paired_attend(blk, table, positions), live,
                           p="x4_mtp_", moe=True)
            h = _op("gather", {"X": blk.hidden(xs), "Index": pick},
                    {"Out": None}, {"axis": 0})
            blk.head(h, "x4_mtp_norm", "draft_logits")
            fetches = self._counts(blk, ["draft_logits"])
        return main, ["tokens", "positions", "page_table", "live", "hidden",
                      "pick"], fetches

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        """Causal pass over a [1, prompt_len] padded prompt (module
        docstring): `logits` and `hidden` of the last real position."""
        cfg = self.cfg
        main, startup = Program(), Program()
        with program_guard(main, startup):
            shape = [1, prompt_len]
            tokens = layers.static_data("tokens", shape, "int32")
            positions = layers.static_data("positions", shape, "int32")
            lengths = layers.static_data("lengths", [1], "int32")
            next_tokens = layers.static_data("next_tokens", shape, "int32")
            next_lengths = layers.static_data("next_lengths", [1], "int32")
            table = self._table(1, kv)
            blk = _Block(cfg, kv)
            live = _op("prompt_rows_live",
                       {"Tokens": tokens, "Lengths": lengths},
                       {"Live": None}, dtype="bool")

            def attend_over(rows):
                def attend(i, q_nope, q_rope, c, latent, w_kvb):
                    pool, pool_out = blk.pool(i)
                    _op("latent_cache_write",
                        {"Latent": latent, "Pool": pool, "PageTable": table,
                         "Lengths": rows}, {"PoolOut": pool_out})
                    kv_heads = _op("linear_acc32", {"X": c, "W": w_kvb},
                                   {"Out": None})
                    return _op("mla_prefill_attention",
                               {"QNope": q_nope, "QRope": q_rope,
                                "KV": kv_heads, "Latent": latent},
                               {"Out": None},
                               dict(blk.head_attrs(),
                                    scale=cfg.softmax_scale,
                                    compute_dtype=cfg.dtype))
                return attend

            xs = blk.embed(tokens)
            for i in range(cfg.n_layers):
                xs = blk.layer(xs, i, positions, attend_over(lengths), live)
            h = blk.hidden(xs)
            last = _op("last_token_rows", {"X": h, "Lengths": lengths},
                       {"Out": None})
            _op("assign", {"X": last}, {"Out": _named_out("hidden")})
            blk.head(last, "x4_norm_f", "logits")
            # the module's rows: its layer's attention input alone decides
            # them, so nothing behind the cache write is kept
            p = "x4_mtp_"
            u, _maps = blk.streams_in(blk.draft_streams(h, next_tokens),
                                      p + "mhc_a_")
            blk.attention(blk.norm(u, p + "norm_in"), p, cfg.mtp_layer,
                          positions, attend_over(next_lengths))
        return main, ["tokens", "positions", "lengths", "page_table",
                      "next_tokens", "next_lengths"], \
            ["logits", "hidden"] + blk.pool_outs

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        raise ValueError(
            "xing4 has no chunked prefill: absorbed attention of a chunk "
            "against a latent prefix is not built, so it runs without the "
            "prefix store (DecodeConfig.prefix_cache=False)")
