"""Mellum decoder (JetBrains Mellum 2, `model_type: mellum`) as a causal
language model's PRETRAINING program: the second architecture the trainer
lowers, beside models/bert.py.

The block (benchmark/reference_mellum.py is its plain float32 statement):

* ``h0 = E[ids]``; each layer ``h += Attn(RMS(h))``, ``h += MoE(RMS(h))``;
  ``logits = RMS_f(h) @ W_head`` (untied); the loss is the mean next-token
  cross-entropy.
* ``Attn``: q, k, v projected from x; q and k RMS-normed per head with
  learned gains; rotary positions on every layer (half-split pairs over
  the whole head), plain on sliding layers and with YaRN frequencies and
  cos/sin times ``attention_factor`` on full ones; grouped heads (query
  head j on K/V head ``j // group``); sliding layers attend keys at
  ``t - window < s <= t``; ``a = o Wo``. No bias, no gate.
* ``MoE``: ``p = softmax(x Wr)`` over all experts, the top k by p,
  weights ``p / sum of the kept p``; a dropless routed layer of SwiGLU
  experts (parallel/moe.py ``routed_experts_share``, ``trainable``). No
  shared expert, no dense layer, no auxiliary loss.

A configuration may hold one chip's SHARE of a deployment that divides
every layer over several chips, as models/afmoe.py: ``num_heads`` /
``num_kv_heads`` are the heads held, ``experts_held`` the routed experts
held (the router keeps its published width), ``vocab_size`` the rows of
embedding and head that are held; ids and labels come from the slice and
the loss is over it. What the absent heads and experts would add is left
out; nothing stands in for the other chips or their exchange.

The program is layers + ops appended by name (models/program_block.py),
`optimizer.minimize` appends the backward (the flash op's and the loss
op's own grad ops, `jax.vjp` of the other lowerings) and AdamW, and
`Executor.run` lowers it to one jitted, donated step. Weights, gradients
and the two products' inputs are ``cfg.dtype``; activations between
matmuls, norms, softmax, router scores and the loss are float32, and every
product accumulates in float32 (ops/llm_ops.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

from .. import layers
from ..core.ir import Program, program_guard
from ..initializer import Constant, Initializer, Normal
from ..param_attr import ParamAttr
from .program_block import named_out as _named_out, op as _op

SLIDING, FULL = "sliding_attention", "full_attention"
# what a step tells the registry (Program.telemetry_fetches)
COUNTS_VAR, MAX_ROWS_VAR = "moe_train_counts", "moe_train_max_group_rows"


@dataclass
class MellumConfig:
    vocab_size: int = 256             # rows of embedding and head held
    hidden_size: int = 64
    head_dim: int = 16
    num_heads: int = 4                # query heads held
    num_kv_heads: int = 1             # K/V heads held
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    moe_intermediate_size: int = 32   # width of every expert
    num_experts: int = 16             # the router's width, as published
    num_experts_per_tok: int = 4
    experts_held: Tuple[int, int] = (0, 4)    # first held, how many
    norm_topk_prob: bool = True
    sliding_window: int = 16
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    # YaRN on the full layers: factor, original_max, beta_fast, beta_slow,
    # attention_factor (rope_parameters.full_attention); {} for plain
    yarn: Dict[str, float] = field(default_factory=dict)
    dtype: str = "float32"            # weights, gradients, product inputs
    embedding_std: float = 1.0        # seeded weights: E ~ N(0, std^2)
    loss_chunk: int = 2048            # rows of logits alive at a time

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
        if self.num_experts % 2 or lo % 2 or count % 2:
            raise ValueError(
                f"the seeded router's columns come in pairs (_PairedRouter): "
                f"experts_held {self.experts_held} of {self.num_experts} "
                f"cuts one")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def window_of(self, layer: int) -> int:
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else 0


def chosen_var(layer: int) -> str:
    """The variable that holds layer `layer`'s chosen experts, int32
    [batch, seq, top_k]: fetched by whoever compares the routing."""
    return f"ml_l{layer}_chosen"


class _PairedRouter(Initializer):
    """Seeded router weights [H, E] whose columns are unit vectors in
    pairs ``w[:, 2j + 1] = -w[:, 2j]``, the direction of each pair drawn
    as `Normal`'s would be (a column of N(0, 1/H) is a unit vector to
    1.5% at H 2304: the scale of the logits is the plain draw's).

    Why: nothing balances an untrained router. With plain Gaussian
    columns an expert's share of the tokens follows its column's length
    and its product with whatever direction the hidden states share, and
    a rank's 16 of 64 experts took 1.98-2.04 pairs a token where an even
    router gives 2 (by seed; the step's time follows it). A pair's two
    experts split the tokens whose |score| is large by its sign, so a
    shared direction moves tokens between the two and a rank that holds
    whole pairs keeps its load, to first order; equal lengths take the
    other cause away. Single experts still differ as before (the largest
    group a fifth to two fifths over the even share)."""

    def __call__(self, var, block):
        h, e = (int(d) for d in var.shape)

        def tmp(tag, shape):
            return block.create_var(name=f"{var.name}@{tag}", shape=shape,
                                    dtype="float32")

        half, unit, neg = (tmp(t, (h, e // 2)) for t in ("half", "unit",
                                                         "neg"))
        both, flat = tmp("pairs", (h, e // 2, 2)), tmp("flat", (h, e))
        block.append_op("gaussian_random", {}, {"Out": [half.name]},
                        {"shape": [h, e // 2], "mean": 0.0, "std": 1.0,
                         "seed": block.program.next_op_seed(),
                         "dtype": "float32"})
        block.append_op("norm", {"X": [half.name]}, {"Out": [unit.name]},
                        {"axis": 0})
        block.append_op("scale", {"X": [unit.name]}, {"Out": [neg.name]},
                        {"scale": -1.0})
        block.append_op("stack", {"X": [unit.name, neg.name]},
                        {"Y": [both.name]}, {"axis": 2})
        block.append_op("reshape2", {"X": [both.name]}, {"Out": [flat.name]},
                        {"shape": [h, e]})
        block.append_op("cast", {"X": [flat.name]}, {"Out": [var.name]},
                        {"out_dtype": str(var.dtype)})


def param_specs(cfg: MellumConfig) -> Dict[str, Tuple[tuple, object, str]]:
    """name -> (shape, kind, dtype). Kind: ``normal`` (std fan_in^-0.5,
    the fan-in the second-to-last axis), ``router`` (`_PairedRouter`: the
    same scale), a float std (the embedding's), or ``one`` (norm gains,
    float32)."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    f, eh = cfg.moe_intermediate_size, cfg.experts_held[1]
    specs = {"ml_tok_emb": ((cfg.vocab_size, d), cfg.embedding_std, dt),
             "ml_head_w": ((d, cfg.vocab_size), "normal", dt),
             "ml_norm_f": ((d,), "one", "float32")}
    for i in range(cfg.n_layers):
        p = f"ml_l{i}_"
        for norm, width in (("norm_attn", d), ("norm_moe", d),
                            ("q_norm", hd), ("k_norm", hd)):
            specs[p + norm] = ((width,), "one", "float32")
        for name, shape in (("q_w", (d, nq)), ("k_w", (d, nkv)),
                            ("v_w", (d, nkv)), ("o_w", (nq, d)),
                            ("ex_w1", (eh, d, f)), ("ex_w3", (eh, d, f)),
                            ("ex_w2", (eh, f, d))):
            specs[p + name] = (shape, "normal", dt)
        specs[p + "router_w"] = ((d, cfg.num_experts), "router", dt)
    return specs


class _Block:
    """The parameters by name and the layers built from them."""

    def __init__(self, cfg: MellumConfig):
        self.cfg = cfg
        self.params = {}
        for name, (shape, kind, dtype) in param_specs(cfg).items():
            if kind == "one":
                init = Constant(1.0)
            elif kind == "router":
                init = _PairedRouter()
            else:
                init = Normal(0.0, shape[-2] ** -0.5 if kind == "normal"
                              else float(kind))
            self.params[name] = layers.create_parameter(
                list(shape), dtype, attr=ParamAttr(name=name,
                                                   initializer=init))
        self.counts, self.max_rows = None, None

    def norm(self, x, name):
        return _op("rms_norm", {"X": x, "Scale": self.params[name]},
                   {"Y": None}, {"epsilon": self.cfg.rms_norm_eps})

    def linear(self, x, name):
        return _op("linear_acc32", {"X": x, "W": self.params[name]},
                   {"Out": None})

    def attention(self, x, i):
        cfg, p = self.cfg, f"ml_l{i}_"
        rope = {"head_dim": cfg.head_dim, "epsilon": cfg.rms_norm_eps,
                "rope": True, "theta": cfg.rope_theta}
        if not cfg.window_of(i) and cfg.yarn:
            y = cfg.yarn
            rope.update(yarn_factor=y["factor"],
                        yarn_original_max=y["original_max"],
                        yarn_beta_fast=y["beta_fast"],
                        yarn_beta_slow=y["beta_slow"],
                        attention_factor=y["attention_factor"])
        q, k = _op("qk_norm_rope",
                   {"Q": self.linear(x, p + "q_w"),
                    "K": self.linear(x, p + "k_w"),
                    "QScale": self.params[p + "q_norm"],
                    "KScale": self.params[p + "k_norm"]},
                   {"QOut": None, "KOut": None}, rope)
        q, k, v = (layers.cast(t, cfg.dtype)
                   for t in (q, k, self.linear(x, p + "v_w")))
        out, _lse = _op("flash_attention", {"Q": q, "K": k, "V": v},
                        {"Out": None, "Lse": None},
                        {"causal": True, "scale": cfg.head_dim ** -0.5,
                         "head_dim": cfg.head_dim,
                         "num_heads": cfg.num_heads,
                         "num_kv_heads": cfg.num_kv_heads,
                         "window": cfg.window_of(i)}, dtype=cfg.dtype)
        return self.linear(out, p + "o_w")

    def experts(self, x, i):
        cfg, p = self.cfg, f"ml_l{i}_"
        select_bias = layers.fill_constant([cfg.num_experts], "float32", 0.0)
        out, counts, _chosen = _op(
            "routed_experts",
            {"X": x, "RouterW": self.params[p + "router_w"],
             "SelectBias": select_bias, "W1": self.params[p + "ex_w1"],
             "W3": self.params[p + "ex_w3"], "W2": self.params[p + "ex_w2"]},
            {"Out": None, "Counts": None,
             "Chosen": _named_out(chosen_var(i), "int32")},
            {"top_k": cfg.num_experts_per_tok,
             "held_lo": cfg.experts_held[0], "route_scale": 1.0,
             "route_norm": cfg.norm_topk_prob, "score_func": "softmax",
             "trainable": True})
        counts.stop_gradient = True
        three = layers.slice(counts, [0], [0], [3])
        rows = layers.slice(counts, [0], [3], [4])
        self.counts = three if self.counts is None else self.counts + three
        self.max_rows = rows if self.max_rows is None \
            else layers.elementwise_max(self.max_rows, rows)
        return out


def build_pretraining_program(cfg: MellumConfig, batch: int, seq_len: int,
                              optimizer_name: str = "adamw",
                              lr: float = 1e-4, seed: int = 0,
                              with_optimizer: bool = True,
                              weight_decay: float = 0.01):
    """Next-token pretraining on fixed, unpacked [batch, seq_len] rows.

    Feeds: ``tokens`` and ``labels`` int64 [batch, seq_len] (a row's labels
    are its tokens shifted by one). Fetches: ``loss``. The seeded weights
    are `seed`'s. Returns (main, startup, feeds, fetches); `main`'s
    `telemetry_fetches` name the routed layers' counters (summed over the
    layers of a step: kept pairs, held pairs, held experts hit; and the
    largest group's rows), which `Executor.run` publishes as
    ``moe.train.*``."""
    main, startup = Program(), Program()
    startup.random_seed = int(seed) % (2 ** 31 - 1)
    with program_guard(main, startup):
        tokens = layers.static_data("tokens", [batch, seq_len], "int64")
        labels = layers.static_data("labels", [batch, seq_len], "int64")
        blk = _Block(cfg)
        x = _op("embed_scaled", {"W": blk.params["ml_tok_emb"],
                                 "Ids": tokens}, {"Out": None})
        for i in range(cfg.n_layers):
            p = f"ml_l{i}_"
            x = x + blk.attention(blk.norm(x, p + "norm_attn"), i)
            x = x + blk.experts(blk.norm(x, p + "norm_moe"), i)
        loss, _dx, _dw = _op(
            "head_cross_entropy",
            {"X": blk.norm(x, "ml_norm_f"), "W": blk.params["ml_head_w"],
             "Label": labels},
            {"Loss": None, "XGrad": None, "WGrad": None},
            {"chunk": cfg.loss_chunk})
        _op("assign", {"X": blk.counts},
            {"Out": _named_out(COUNTS_VAR, "int32")})
        _op("assign", {"X": blk.max_rows},
            {"Out": _named_out(MAX_ROWS_VAR, "int32")})
        one = _op("fill_constant", {}, {"Out": _named_out(
            "moe_train_steps", "int32")},
            {"shape": [1], "value": 1.0, "dtype": "int32"})
        main.telemetry_fetches = {
            COUNTS_VAR: (("moe.train.pairs", "counter"),
                         ("moe.train.pairs_held", "counter"),
                         ("moe.train.experts_hit", "counter")),
            MAX_ROWS_VAR: (("moe.train.max_group_rows", "hist"),),
            one.name: (("moe.train.steps", "counter"),)}
        if with_optimizer:
            from .. import optimizer as opt_mod

            if optimizer_name != "adamw":
                raise ValueError(f"optimizer {optimizer_name!r}")
            opt_mod.AdamWOptimizer(
                lr, weight_decay=weight_decay).minimize(loss)
    return main, startup, dict(tokens=tokens, labels=labels), \
        dict(loss=loss)


def synthetic_batch(cfg: MellumConfig, batch: int, seq_len: int,
                    seed: int = 0):
    """Seeded ids uniform over the held rows; labels are the ids shifted
    by one."""
    import numpy as np

    ids = np.random.RandomState(seed % (2 ** 32)).randint(
        0, cfg.vocab_size, (batch, seq_len + 1)).astype(np.int64)
    return dict(tokens=ids[:, :-1], labels=ids[:, 1:])
