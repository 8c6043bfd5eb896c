"""Decoder-only transformer LM — the generative-serving workload.

The decode-mode counterpart of models/transformer.py: the same
post-LN transformer block stack, restructured around the KV cache so the
serving engine (paddle_tpu/serving/decode.py) can run autoregressive
generation as two op-desc programs instead of re-running the full
sequence every token (the reference's analog is the beam_search /
while-op inference decoding programs around
paddle/fluid/operators/beam_search_op*):

* ``build_prefill_program`` — one causal pass over the (padded) prompt
  that ALSO writes every token's K/V into the paged pool
  (``kv_cache_write`` op) and emits the last valid position's logits:
  the PREFILL phase, run once per admitted request;
* ``build_step_program`` — a single-token step at a fixed slot-array
  shape: embed the last sampled token, run every layer through the
  ``cached_kv_attention`` op (write-then-attend against the pool) and
  emit next-token logits: the DECODE phase, run once per generated
  token for the whole batch.

Both programs declare every parameter as a ``static_data`` feed (or a
``layer_norm`` parameter) resolved BY NAME from the engine's frozen
param dict, so one weight set serves every bucket's jit entry — the
frozen-predictor discipline without a per-program scope copy.

int8 weight-only serving: ``weight_quant="int8"`` makes every dense
weight a pair of (int8 tensor, per-output-channel scale) feeds joined by
the ``dequantize_weight`` op (ops/quant_ops.py) — XLA fuses the dequant
into the consuming matmul read, halving weight bytes; activations, KV
cache and layer norms stay fp32. ``quantize_decoder_lm_params``
converts a trained fp32 param dict into that layout.

Number format: THIS block's weights, activations and K/V pages are
float32 (int8 weights apart), and its heads are plain multi-head
(``kvdim == n_head x head_dim``), every layer holding a context's pages.
That is this model's stated format, not the engine's limit: the engine
takes any model through ``serving/served_model.py`` (``DecoderLMServed``
below is this model's side of it), and models/afmoe.py serves bfloat16
weights and pages, grouped heads and window layers on a ring of pages
through the same engine.
"""

from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass

import numpy as np

from .. import layers
from ..core.ir import Program, program_guard
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from ..serving.kv_cache import LayerCache
from ..serving.served_model import ServedModel

PARAMS_FILE = "decoder_lm_params.npz"
CONFIG_FILE = "decoder_lm_config.json"


@dataclass
class DecoderLMConfig:
    vocab_size: int = 1024
    d_model: int = 64
    n_head: int = 4
    n_layers: int = 2
    d_inner: int = 128
    max_seq_len: int = 128        # positions the model (and KV cache) holds
    bos_id: int = 1
    eos_id: int = 2

    def __post_init__(self):
        if self.d_model % self.n_head:
            raise ValueError(f"d_model {self.d_model} not divisible by "
                             f"n_head {self.n_head}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    def served(self) -> "DecoderLMServed":
        return DecoderLMServed(self)


# dense sublayers per block, in program order: (suffix, d_in, d_out)
def _dense_specs(cfg: DecoderLMConfig):
    d, di = cfg.d_model, cfg.d_inner
    return [("q", d, d), ("k", d, d), ("v", d, d), ("o", d, d),
            ("fc1", d, di), ("fc2", di, d)]


def _param(name, shape, dtype="float32"):
    return layers.static_data(name, list(shape), dtype)


def _dense(x, name, d_in, d_out, quant: bool):
    """x @ W + b with the weight either an fp32 feed or an (int8, scale)
    pair lowered through the weight-only ``int8_matmul`` op contract
    (ops/quant_ops.py): the weight stays int8 in HBM and the
    per-channel dequant + bias fuse into the matmul epilogue — the
    Pallas MXU kernel (ops/pallas/int8_gemm.py) under PT_PALLAS, the
    counted stock lowering otherwise."""
    b = _param(f"{name}_b", (d_out,))
    if quant:
        w8 = _param(f"{name}_w_i8", (d_in, d_out), "int8")
        ws = _param(f"{name}_w_scale", (d_out,))
        helper = LayerHelper("int8_matmul")
        out = helper.create_variable_for_type_inference("float32")
        helper.append_op("int8_matmul",
                         {"X": [x], "Y": [w8], "YScale": [ws],
                          "Bias": [b]},
                         {"Out": [out]}, {})
        return out
    w = _param(f"{name}_w", (d_in, d_out))
    return layers.linear(x, w, b)


def _post_ln(x, residual, name):
    return layers.layer_norm(x + residual, begin_norm_axis=len(x.shape) - 1,
                             param_attr=ParamAttr(name=f"{name}_scale"),
                             bias_attr=ParamAttr(name=f"{name}_bias"))


def _sinusoid_table(max_len: int, d: int) -> np.ndarray:
    pos = np.arange(max_len)[:, None].astype(np.float64)
    i = np.arange(d)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * (i // 2) / d)
    table = np.zeros((max_len, d), np.float32)
    table[:, 0::2] = np.sin(angle[:, 0::2])
    table[:, 1::2] = np.cos(angle[:, 1::2])
    return table


def decoder_lm_params(cfg: DecoderLMConfig, seed: int = 0):
    """Deterministic fp32 parameter dict for the program builders'
    names — the 'trained model' of tests/bench (a real training run
    would land the same names via its scope)."""
    rng = np.random.RandomState(seed)
    std = cfg.d_model ** -0.5
    p = {"lm_tok_emb": rng.normal(0.0, std, (cfg.vocab_size, cfg.d_model))
         .astype(np.float32),
         "lm_pos_enc": _sinusoid_table(cfg.max_seq_len, cfg.d_model)}
    for i in range(cfg.n_layers):
        for suffix, d_in, d_out in _dense_specs(cfg):
            p[f"lm_l{i}_{suffix}_w"] = rng.normal(
                0.0, std, (d_in, d_out)).astype(np.float32)
            p[f"lm_l{i}_{suffix}_b"] = np.zeros(d_out, np.float32)
        for ln in ("ln1", "ln2"):
            p[f"lm_l{i}_{ln}_scale"] = np.ones(cfg.d_model, np.float32)
            p[f"lm_l{i}_{ln}_bias"] = np.zeros(cfg.d_model, np.float32)
    return p


def quantize_decoder_lm_params(params, cfg: DecoderLMConfig):
    """fp32 param dict -> weight-only int8 layout: every dense weight
    becomes (<name>_w_i8 int8, <name>_w_scale fp32 per-output-channel
    abs-max / 127); embeddings, positions, norms and biases stay fp32.
    The symmetric per-channel scheme of ops/quant_ops.py
    fake_channel_wise_quantize_dequantize_abs_max, materialised."""
    out = {}
    for name, v in params.items():
        if name.endswith("_w") and v.ndim == 2 and name != "lm_tok_emb":
            scale = np.maximum(np.abs(v).max(axis=0), 1e-8) / 127.0
            q = np.clip(np.round(v / scale[None, :]), -127, 127)
            out[name + "_i8"] = q.astype(np.int8)
            out[name + "_scale"] = scale.astype(np.float32)
        else:
            out[name] = v
    return out


def save_decoder_lm(model_dir: str, cfg: DecoderLMConfig, params) -> str:
    """Persist config + fp32 params as a servable model dir (the decode
    twin of io.save_inference_model; checkpoint.publish_model can wrap
    the dir in a COMMIT manifest for the cluster plane)."""
    os.makedirs(model_dir, exist_ok=True)
    from .. import io as _io

    _io.atomic_write_json(os.path.join(model_dir, CONFIG_FILE), asdict(cfg))
    _io.atomic_savez(os.path.join(model_dir, PARAMS_FILE), **params)
    return model_dir


def load_decoder_lm(model_dir: str):
    """(cfg, params) from a save_decoder_lm dir."""
    with open(os.path.join(model_dir, CONFIG_FILE)) as f:
        cfg = DecoderLMConfig(**json.load(f))
    with np.load(os.path.join(model_dir, PARAMS_FILE)) as z:
        params = {k: z[k] for k in z.files}
    return cfg, params


def _embed_step(tokens, positions, cfg):
    """[B] token + position ids -> [B, d] embeddings (gather lookups —
    the single-token twin of the [B, S] prompt embedding)."""
    emb = _param("lm_tok_emb", (cfg.vocab_size, cfg.d_model))
    pos = _param("lm_pos_enc", (cfg.max_seq_len, cfg.d_model))
    x = layers.scale(layers.gather(emb, tokens), scale=cfg.d_model ** 0.5)
    return x + layers.gather(pos, positions), emb


def _pool_vars(cfg, layer, num_pages, page_size):
    return (_param(f"kv_k_{layer}", (num_pages, page_size, cfg.d_model)),
            _param(f"kv_v_{layer}", (num_pages, page_size, cfg.d_model)))


def _named_out(name, dtype="float32"):
    from ..core.ir import default_main_program

    return default_main_program().current_block().create_var(
        name=name, dtype=dtype, stop_gradient=True)


def build_step_program(cfg: DecoderLMConfig, batch: int, num_pages: int,
                       page_size: int, weight_quant: str = "none"):
    """One decode step at a FIXED [batch] slot-array shape.

    Feeds: tokens [B] int32 (last sampled token per slot), positions [B]
    int32 (where its K/V lands; context = 0..pos), page_table [B, MP]
    int32 (physical pages per slot; empty slots all-zero), plus the
    kv_k_<l>/kv_v_<l> pools threaded in and out. Fetches: ``logits``
    [B, vocab] and kv_k_<l>_out/kv_v_<l>_out.

    The fixed shape is what keeps continuous batching bitwise-identical
    to sequential decode: per-row results depend only on the row (XLA
    kernel selection is a function of shapes, not slot occupancy)."""
    quant = weight_quant == "int8"
    mp = -(-cfg.max_seq_len // page_size)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        tokens = layers.static_data("tokens", [batch], "int32")
        positions = layers.static_data("positions", [batch], "int32")
        table = layers.static_data("page_table", [batch, mp], "int32")
        x, emb = _embed_step(tokens, positions, cfg)
        pool_outs = []
        for i in range(cfg.n_layers):
            name = f"lm_l{i}"
            q = _dense(x, f"{name}_q", cfg.d_model, cfg.d_model, quant)
            k = _dense(x, f"{name}_k", cfg.d_model, cfg.d_model, quant)
            v = _dense(x, f"{name}_v", cfg.d_model, cfg.d_model, quant)
            pk, pv = _pool_vars(cfg, i, num_pages, page_size)
            attn = _named_out(f"lm_l{i}_attn")
            pk_out = _named_out(f"kv_k_{i}_out")
            pv_out = _named_out(f"kv_v_{i}_out")
            LayerHelper("cached_kv_attention").append_op(
                "cached_kv_attention",
                {"Q": [q], "K": [k], "V": [v], "PoolK": [pk], "PoolV": [pv],
                 "PageTable": [table], "Positions": [positions]},
                {"Out": [attn], "PoolKOut": [pk_out], "PoolVOut": [pv_out]},
                {"num_heads": cfg.n_head, "head_dim": cfg.head_dim,
                 "scale": cfg.head_dim ** -0.5})
            pool_outs += [pk_out.name, pv_out.name]
            o = _dense(attn, f"{name}_o", cfg.d_model, cfg.d_model, quant)
            x = _post_ln(o, x, f"{name}_ln1")
            h = layers.relu(_dense(x, f"{name}_fc1", cfg.d_model,
                                   cfg.d_inner, quant))
            f = _dense(h, f"{name}_fc2", cfg.d_inner, cfg.d_model, quant)
            x = _post_ln(f, x, f"{name}_ln2")
        logits = _named_out("logits")
        LayerHelper("matmul").append_op(
            "matmul", {"X": [x], "Y": [emb]}, {"Out": [logits]},
            {"transpose_Y": True})
    feeds = ["tokens", "positions", "page_table"]
    return main, feeds, ["logits"] + pool_outs


def build_chunk_prefill_program(cfg: DecoderLMConfig, batch: int,
                                chunk_len: int, num_pages: int,
                                page_size: int,
                                weight_quant: str = "none"):
    """PAGE-CHUNKED prefill: one pass over a [batch, chunk_len] slice of
    the prompt starting at a page-aligned global position, attending over
    the already-written pool prefix + the chunk causally
    (``chunk_cached_attention``) and writing the chunk's K/V into the
    row's pages. Running the prompt chunk by chunk through this ONE
    fixed-shape program is the prefix-store's prefill discipline
    (serving/prefix_store.py): a cache hit skips the cached chunks and
    replays only the suffix — bit-identical to the cold run because
    every chunk's compute is a pure function of (chunk tokens, prior
    pool bytes) at one fixed jit shape.

    Feeds: tokens [B, C] int32 (right-padded chunk), positions [B, C]
    int32 (global positions, for the position encoding), chunk_start [B]
    int32, lengths [B] int32 (valid tokens in the chunk), last_onehot
    [B, C] fp32 (one-hot of the last valid chunk position — the logits
    read, meaningful on the prompt's final chunk), page_table [B, MP]
    int32, and the kv pools. Fetches: ``logits`` + kv_*_out."""
    quant = weight_quant == "int8"
    mp = -(-cfg.max_seq_len // page_size)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        tokens = layers.static_data("tokens", [batch, chunk_len], "int32")
        positions = layers.static_data("positions", [batch, chunk_len],
                                       "int32")
        start = layers.static_data("chunk_start", [batch], "int32")
        lengths = layers.static_data("lengths", [batch], "int32")
        last_oh = layers.static_data("last_onehot", [batch, chunk_len],
                                     "float32")
        table = layers.static_data("page_table", [batch, mp], "int32")
        emb = _param("lm_tok_emb", (cfg.vocab_size, cfg.d_model))
        pos = _param("lm_pos_enc", (cfg.max_seq_len, cfg.d_model))
        x = layers.scale(layers.gather(emb, tokens),
                         scale=cfg.d_model ** 0.5)
        x = x + layers.gather(pos, positions)
        pool_outs = []
        for i in range(cfg.n_layers):
            name = f"lm_l{i}"
            q = _dense(x, f"{name}_q", cfg.d_model, cfg.d_model, quant)
            k = _dense(x, f"{name}_k", cfg.d_model, cfg.d_model, quant)
            v = _dense(x, f"{name}_v", cfg.d_model, cfg.d_model, quant)
            pk, pv = _pool_vars(cfg, i, num_pages, page_size)
            attn = _named_out(f"lm_l{i}_attn")
            pk_out = _named_out(f"kv_k_{i}_out")
            pv_out = _named_out(f"kv_v_{i}_out")
            LayerHelper("chunk_cached_attention").append_op(
                "chunk_cached_attention",
                {"Q": [q], "K": [k], "V": [v], "PoolK": [pk], "PoolV": [pv],
                 "PageTable": [table], "ChunkStart": [start],
                 "Lengths": [lengths]},
                {"Out": [attn], "PoolKOut": [pk_out], "PoolVOut": [pv_out]},
                {"num_heads": cfg.n_head, "head_dim": cfg.head_dim,
                 "scale": cfg.head_dim ** -0.5})
            pool_outs += [pk_out.name, pv_out.name]
            o = _dense(attn, f"{name}_o", cfg.d_model, cfg.d_model, quant)
            x = _post_ln(o, x, f"{name}_ln1")
            h = layers.relu(_dense(x, f"{name}_fc1", cfg.d_model,
                                   cfg.d_inner, quant))
            f = _dense(h, f"{name}_fc2", cfg.d_inner, cfg.d_model, quant)
            x = _post_ln(f, x, f"{name}_ln2")
        h_last = layers.reduce_sum(x * layers.unsqueeze(last_oh, [2]),
                                   dim=1)
        logits = _named_out("logits")
        LayerHelper("matmul").append_op(
            "matmul", {"X": [h_last], "Y": [emb]}, {"Out": [logits]},
            {"transpose_Y": True})
    feeds = ["tokens", "positions", "chunk_start", "lengths",
             "last_onehot", "page_table"]
    return main, feeds, ["logits"] + pool_outs


def build_prefill_program(cfg: DecoderLMConfig, batch: int, prompt_len: int,
                          num_pages: int, page_size: int,
                          weight_quant: str = "none"):
    """Causal pass over a [batch, prompt_len] padded prompt that writes
    every real token's K/V into the paged pool and emits the LAST valid
    position's logits.

    Feeds: tokens [B, S] int32 (right-padded), lengths [B] int32,
    last_onehot [B, S] fp32 (one-hot of lengths-1 — host-computed so the
    last-position read is one masked reduce, no dynamic gather),
    page_table [B, MP] int32, and the kv pools. Causal masking already
    keeps queries at positions < length away from padded keys, and
    kv_cache_write routes padded positions to the pool's scratch page,
    so no key-padding bias is needed."""
    quant = weight_quant == "int8"
    mp = -(-cfg.max_seq_len // page_size)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        tokens = layers.static_data("tokens", [batch, prompt_len], "int32")
        lengths = layers.static_data("lengths", [batch], "int32")
        last_oh = layers.static_data("last_onehot", [batch, prompt_len],
                                     "float32")
        table = layers.static_data("page_table", [batch, mp], "int32")
        emb = _param("lm_tok_emb", (cfg.vocab_size, cfg.d_model))
        pos = _param("lm_pos_enc", (cfg.max_seq_len, cfg.d_model))
        x = layers.scale(layers.gather(emb, tokens),
                         scale=cfg.d_model ** 0.5)
        x = x + layers.slice(pos, [0], [0], [prompt_len])
        pool_outs = []
        for i in range(cfg.n_layers):
            name = f"lm_l{i}"
            q = _dense(x, f"{name}_q", cfg.d_model, cfg.d_model, quant)
            k = _dense(x, f"{name}_k", cfg.d_model, cfg.d_model, quant)
            v = _dense(x, f"{name}_v", cfg.d_model, cfg.d_model, quant)
            pk, pv = _pool_vars(cfg, i, num_pages, page_size)
            pk_out = _named_out(f"kv_k_{i}_out")
            pv_out = _named_out(f"kv_v_{i}_out")
            LayerHelper("kv_cache_write").append_op(
                "kv_cache_write",
                {"K": [k], "V": [v], "PoolK": [pk], "PoolV": [pv],
                 "PageTable": [table], "Lengths": [lengths]},
                {"PoolKOut": [pk_out], "PoolVOut": [pv_out]}, {})
            pool_outs += [pk_out.name, pv_out.name]
            ctx = layers.flash_attention(q, k, v, causal=True,
                                         scale=cfg.head_dim ** -0.5,
                                         num_heads=cfg.n_head, is_test=True)
            o = _dense(ctx, f"{name}_o", cfg.d_model, cfg.d_model, quant)
            x = _post_ln(o, x, f"{name}_ln1")
            h = layers.relu(_dense(x, f"{name}_fc1", cfg.d_model,
                                   cfg.d_inner, quant))
            f = _dense(h, f"{name}_fc2", cfg.d_inner, cfg.d_model, quant)
            x = _post_ln(f, x, f"{name}_ln2")
        # last valid position's hidden state: [B,S,d] * [B,S,1] summed
        # over S — one masked reduce instead of a dynamic index
        h_last = layers.reduce_sum(x * layers.unsqueeze(last_oh, [2]),
                                   dim=1)
        logits = _named_out("logits")
        LayerHelper("matmul").append_op(
            "matmul", {"X": [h_last], "Y": [emb]}, {"Out": [logits]},
            {"transpose_Y": True})
    feeds = ["tokens", "lengths", "last_onehot", "page_table"]
    return main, feeds, ["logits"] + pool_outs


class DecoderLMServed(ServedModel):
    """This model's side of the engine's seam (serving/served_model.py):
    the three builders above, float32 pages, every layer a context's
    pages of width d_model, int8 weight-only as the one quantization."""

    def cache_layout(self):
        return [LayerCache(self.cfg.d_model)] * self.cfg.n_layers

    def prepare_params(self, params, weight_quant: str):
        if weight_quant == "int8":
            return quantize_decoder_lm_params(params, self.cfg)
        return params

    def build_step_program(self, batch, kv, weight_quant="none"):
        return build_step_program(self.cfg, batch, kv.context.num_pages,
                                  kv.page_size, weight_quant)

    def build_prefill_program(self, prompt_len, kv, weight_quant="none"):
        return build_prefill_program(self.cfg, 1, prompt_len,
                                     kv.context.num_pages, kv.page_size,
                                     weight_quant)

    def build_chunk_prefill_program(self, chunk_len, kv,
                                    weight_quant="none"):
        return build_chunk_prefill_program(
            self.cfg, 1, chunk_len, kv.context.num_pages, kv.page_size,
            weight_quant)
