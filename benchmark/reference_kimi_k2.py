"""Plain reference of the Kimi-K2 block (moonshotai, `model_type: kimi_k2`:
the DeepSeek-V3 block), independent of the code under test: straightforward
`jax.numpy` in float32 under `jax.default_matmul_precision("highest")`, no
cache, no pages, no kernels, no batching, no absorbed products. It imports
nothing of the program; it reads the same parameter dict by the same names
(models/kimi_k2.py `param_specs`) and upcasts whatever dtype it finds. The
routed layer, SwiGLU and RMS norm are reference_afmoe's equations, letter
for letter, stated again here because a control's rounding has to survive
the compiler (`_f32`).

The block, from the published `config.json` (catalog row Kimi-K2.7-Code)
and, where the config is silent, as the configuration file lists under
`assumed`:

  h0 = E[ids];  h = h + Attn(RMS_in(h));  h = h + MLP(RMS_mlp(h))
  logits = RMS_f(h) @ W_head                                   (untied)

  Attn(x), multi-head latent attention in its EXPANDED form:
    c_q = RMS(x W_qa);   [q_n (nope), q_r (rope)]_h = c_q W_qb     per head
    [c (rank), k_r (rope)] = x W_kva;  c = RMS(c)
    q_r, k_r rotated by position (YaRN frequencies, interleaved pairs);
      k_r is ONE key for all heads
    [k_n (nope), v]_h = c W_kvb                                    per head
    score = (q_n . k_n + q_r . k_r) x (nope + rope)^-0.5 x m^2,
      m = 0.1 x mscale_all_dim x ln(factor) + 1; the tables unscaled
      (mscale = mscale_all_dim)
    causal softmax;  a = concat_heads(sum p v) W_o
  Dense MLP (leading `first_k_dense` layers): (silu(x W1) * (x W3)) W2.
  MoE: s = sigmoid(x Wr) over all experts; the top-k of s + b (b: the
    selection bias, n_group 1); w_i = s_i / sum of the kept s x
    routed_scaling_factor; m = Shared(x) + sum_i w_i Expert_i(x). Dropless.

The share. `cfg["experts_held"]` says which routed experts the parameters
hold, the rows of `k2_tok_emb` which of the vocabulary; every head is held
(the deployment's attention is data-parallel). The router always scores all
experts; experts that are not held add nothing, and that partial result goes
on to the next layer, here as in the program. With every expert held this is
the uncut model (tests/test_kimi_k2_share.py adds the shares up to it).

Attention runs in blocks of queries (`block`) so that a prompt of six
thousand tokens fits beside a serving engine on one chip.

How a run's numbers are held against this reference is in
`families/kimi_k2.py`; routing decides discretely and is handled as
reference_afmoe's docstring says (ROUTE_EPS: a prompt is cut to end on a
position whose top-k the reference decides by more than that; decoded
positions under it are held to a wider margin).

Limits. Each lies between two readings on the chip (v5e, the configuration
kimi_k2_dp_ep32 at its real widths, my chip runs, PR 33; PERF.md section 4
has the table). The one reading is the engine against this reference: five
prompt lengths (300 to 6,100 tokens, one in each prefill bucket) with 59
sampled requests decoding beside them, in 26 runs of as many seeds. The
other is a control (`benchmark/readings_kimi_k2.py`, seeds 3000000711,
-712, -735 and -777): the SAME engine outputs judged, by the same
`families/kimi_k2.judge`, against this reference with a part of it rounded
to 8 bits (float8 e4m3, the nearest precision below bfloat16): the latent
rows as a page holds them (`latent`), W_kvb alone, which is the absorbed
form's W_uk and W_uv (`kvb`), every weight matrix (`weights`); or, for
UNDECIDED_MARGIN, the planted fault of the CPU test read at the timed size
(`--plant page_table`, seeds -735 and -777). Each control has to come out
as not correct, by one of the limits; one prompt over a limit makes a run
not correct, so a run's reading is its worst prompt's.
  LOGIT_ERR 0.09  largest |engine - reference| over a prefill's logits
              row, as a share of that row's root mean square. Engine: a
              run's worst 0.027-0.042 in nineteen runs and 0.049 to 0.060
              in seven, always a prompt of 300 to 1,500 tokens (0.015-0.022
              at 6,100). What parts the two groups is routing that turns
              on rounding INSIDE the prompt, read on two seeds of the
              second group (`--routing`): the engine's prefill keeps
              another HELD expert than this reference at 25 of a
              700-token prompt's 2,800 (position, layer) pairs (0.0559)
              and at 7 of a 300-token prompt's 1,200 (0.0525), each at a
              gap of 0.00006 to 0.0038 between the k-th and the (k+1)-th
              score, none at the last position (the prompt is cut to a
              decided one); held against this reference made to keep the
              ENGINE's experts the same logits read 0.0302 and 0.0418,
              and the same seeds' other short prompt 0.0352 -> 0.0299
              (1 pair) and 0.0322 -> 0.0230 (9 pairs). So rounding alone
              is 0.023-0.042 of a short prompt's row and a whole expert's
              output kept or dropped earlier in the prompt adds up to
              0.026 (every matrix writes to the residual stream at its
              fan-in scale here, so bfloat16's rounding is a larger share
              of the logits than in cell 3, whose post-norms start at
              0.09). Controls, a run's worst over four seeds: latent
              0.175-0.182, kvb 0.149-0.177 (their 6,100-token prompts
              alone read 0.048-0.060), weights 0.73-0.91. The limit is 1.5
              times over the engine's worst of 26 runs and 1.65 times
              under the partial controls' lowest. (0.065 for the first
              seven runs, set from five: the next six read 0.049 and 0.053
              twice, too near it for seeds the driver draws; the fourteen
              runs after it was moved read up to 0.060.)
  MARGIN 0.06   a greedy token's reference logit may lie this far under
              the reference's maximum (logits are unit scale), at
              positions whose routing is decided. Engine: 0 to 0.015 in
              twenty-two runs, 0.019-0.022 in four. Controls: weights
              0.55-0.91; kvb 0.062-0.186, latent 0.047-0.111: the partial
              controls lie at the limit or over it and fail LOGIT_ERR in
              any case (as cell 3's do). 2.7 times over the engine's
              worst, nine times under the control it is for.
  ROUTE_EPS 0.012  in units of the selection score, as reference_afmoe:
              of ~4,000 decoded positions that route by more than this in
              26 runs none chose another token by more than 0.022, and the
              prefill's kept experts differ from these at gaps of at most
              0.0038 (`--routing`), so the engine's selection scores
              differ from these by well under it; 29-53 of a run's 200
              decoded positions route by less.
  UNDECIDED_MARGIN 1.5, UNDECIDED_SHARE 0.7  the positions ROUTE_EPS takes
              from MARGIN are not left out: their worst gap is held to
              1.5. Engine: under 0.13 in twenty-two runs, 0.452 twice,
              0.616 and 0.803 once each, where the engine kept another
              expert and a whole expert's output moved the maximum. The
              upper reading is the planted fault at the timed size: with
              every later live row of a step fed the first row's page
              table, the worst undecided position lies 2.63 and 3.06
              under the maximum (the worst decided one 3.29 and 2.89, so
              MARGIN fails first; the prefills read as ever). 1.9 times
              over the engine's worst, 1.75 times under the fault's
              lower reading. The undecided positions may be at most 0.7 of
              the decoded ones, as in reference_afmoe, so at least 60 are
              held to MARGIN; that share has no control of its own.
"""

from __future__ import annotations

import math

from benchmark.reference_afmoe import (decided_prefix,  # noqa: F401
                                       greedy_gaps, logit_error, padded)

LOGIT_ERR = 0.09
MARGIN = 0.06
ROUTE_EPS = 0.012
UNDECIDED_MARGIN = 1.5
UNDECIDED_SHARE = 0.7

CONTROLS = ("latent", "kvb", "weights")


def _f32(a, via=None):
    """`a` in float32; with `via`, rounded to that dtype on the way (the
    lower-precision control: "float8_e4m3fn" makes 8-bit values of it).
    The barrier keeps the rounding: the chip's compiler allows itself
    excess precision and drops a narrowing conversion that is widened
    again at once where that feeds a product (on the v5e it dropped
    W_kvb's, and the `kvb` control read the reference's own numbers to
    the last digit: my chip run, PR 33)."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if via is not None:
        a = jax.lax.optimization_barrier(a.astype(via))
    return a.astype(jnp.float32)


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(gain)


def swiglu(x, w1, w3, w2, via=None):
    import jax

    return (jax.nn.silu(x @ _f32(w1, via)) * (x @ _f32(w3, via))) \
        @ _f32(w2, via)


def route(params, p, x, cfg, via=None, forced=None):
    """-> (weights [T, experts], zero off the kept experts; gap [T]: how
    far the k-th selection score lies above the (k+1)-th, infinite where
    neither of the two experts is held). `forced` [T, k] keeps those
    experts in place of the k largest (benchmark/readings_kimi_k2.py: the
    reference on the engine's own routing)."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    lo, count = cfg["experts_held"]
    s = jax.nn.sigmoid(x @ _f32(params[p + "router_w"], via))
    sel = s + _f32(params[p + "select_bias"])
    top, idx = jax.lax.top_k(sel, k + 1)
    keep = idx[:, :k] if forced is None else forced
    kept = jnp.take_along_axis(s, keep, axis=1)
    w = kept
    if cfg["norm_topk_prob"]:
        w = kept / (jnp.sum(kept, axis=1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    weights = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], keep].set(w)
    edge = idx[:, k - 1:k + 1]                  # the k-th and the (k+1)-th
    held = jnp.any((edge >= lo) & (edge < lo + count), axis=1)
    gap = jnp.where(held, top[:, k - 1] - top[:, k], jnp.inf)
    return weights, gap


def routed(params, p, x, weights, cfg, via=None):
    """sum over the HELD experts of weight x Expert(x): every held expert
    computes every token, the weight decides what is kept."""
    import jax
    import jax.numpy as jnp

    lo, count = cfg["experts_held"]
    w_held = weights[:, lo:lo + count].T                    # [E_held, T]

    def one(acc, ex):
        w1, w3, w2, w = ex
        return acc + w[:, None] * swiglu(x, w1, w3, w2, via), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (params[p + "ex_w1"], params[p + "ex_w3"], params[p + "ex_w2"],
         w_held))
    return acc


def yarn_inv_freq(cfg):
    """[rope/2] frequencies: plain `theta^(-2i/rope)` for pairs that turn
    more than beta_fast times over the original context, that over
    `factor` for pairs that turn fewer than beta_slow times, a linear
    blend between (the published YaRN of this family)."""
    import jax.numpy as jnp

    dim, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    factor = cfg["rope_factor"]
    if factor <= 1:
        return plain

    def index_of(turns):
        return dim * math.log(cfg["rope_original_max"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_of(cfg["rope_beta_fast"])), 0)
    high = min(math.ceil(index_of(cfg["rope_beta_slow"])), dim - 1)
    blend = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - blend) + plain / factor * blend


def softmax_scale(cfg) -> float:
    m = 1.0
    if cfg["rope_factor"] > 1:
        m = 0.1 * cfg["rope_mscale_all_dim"] * math.log(cfg["rope_factor"]) \
            + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, positions, inv_freq):
    """x [T, ..., rope]; the pairs (2i, 2i+1) rotated by pos x inv_freq[i]."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def attention(params, p, x, cfg, block: int = 256, via=None, kvb_via=None,
              latent_via=None):
    """The attention sublayer's output of layer prefix `p` for x
    [T, hidden]. `latent_via` rounds the normed latent and the rotated
    shared key as a page would hold them; `kvb_via` rounds W_kvb."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    n, nope = cfg["num_heads"], cfg["qk_nope_head_dim"]
    rd, rank = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t, dtype=jnp.int32)
    inv = yarn_inv_freq(cfg)
    c_q = rms_norm(x @ _f32(params[p + "q_a_w"], via),
                   params[p + "q_a_norm"], eps)
    q = (c_q @ _f32(params[p + "q_b_w"], via)).reshape(t, n, nope + rd)
    q_n, q_r = q[..., :nope], rope(q[..., nope:], pos, inv)
    kva = x @ _f32(params[p + "kv_a_w"], via)
    c = rms_norm(kva[:, :rank], params[p + "kv_a_norm"], eps)
    k_r = rope(kva[:, rank:], pos, inv)
    c, k_r = _f32(c, latent_via), _f32(k_r, latent_via)
    kv = (c @ _f32(params[p + "kv_b_w"], kvb_via)).reshape(t, n, -1)
    k_n, v = kv[..., :nope], kv[..., nope:]
    bq = block if t % block == 0 else t
    scale = softmax_scale(cfg)

    def one_block(q0):
        qn = jax.lax.dynamic_slice_in_dim(q_n, q0, bq, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, q0, bq, axis=0)
        sc = (jnp.einsum("qhd,shd->hqs", qn, k_n)
              + jnp.einsum("qhr,sr->hqs", qr, k_r)) * scale
        tq = q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        prob = jax.nn.softmax(jnp.where(pos[None, :] <= tq, sc, -jnp.inf),
                              axis=-1)
        return jnp.einsum("hqs,shv->qhv", prob, v)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    return o.reshape(t, -1) @ _f32(params[p + "o_w"], via)


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 256, via=None, only: str = "weights",
            on_route=None, forced=None):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; route_gap [T], the smallest routing
    gap of each position over the MoE layers). Causal, so a padded tail is
    harmless. `via` is the lower-precision control: it rounds to that
    dtype, by `only`, every weight matrix (`weights`; norm gains and the
    selection bias stay), W_kvb alone (`kvb`: the W_uk and W_uv of the
    absorbed form), or the latent rows alone, as pages hold them
    (`latent`). `on_route(layer, weights, gap)` sees each MoE layer's
    routing weights [T, experts] and `route`'s gap [T]
    (benchmark/readings_kimi_k2.py reads a sequence's share of pairs on
    the held experts from them); `forced` {layer: [T, k] experts} replaces
    those layers' own choice (`route`)."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    kvb_via = via if only in ("weights", "kvb") else None
    latent_via = via if only == "latent" else None
    via = via if only == "weights" else None
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _f32(params["k2_tok_emb"][tokens], via)
        gap = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        for i in range(cfg["n_layers"]):
            p = f"k2_l{i}_"
            h = h + attention(params, p,
                              rms_norm(h, params[p + "norm_in"], eps), cfg,
                              block, via, kvb_via, latent_via)
            x = rms_norm(h, params[p + "norm_mlp"], eps)
            if i < cfg["first_k_dense"]:
                m = swiglu(x, params[p + "w1"], params[p + "w3"],
                           params[p + "w2"], via)
            else:
                weights, g = route(params, p, x, cfg, via,
                                   (forced or {}).get(i))
                gap = jnp.minimum(gap, g)
                if on_route is not None:
                    on_route(i, weights, g)
                m = swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                           params[p + "sh_w2"], via) \
                    + routed(params, p, x, weights, cfg, via)
            h = h + m
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = rms_norm(h, params["k2_norm_f"], eps) \
            @ _f32(params["k2_head_w"], via)
        return logits, gap


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n)` ->
    (logits [n, vocab], route_gap [len(seq)]). One compile a `pad_to`.
    `via` (a dtype) and `only` make it a lower-precision control
    (`forward`)."""

    def __init__(self, params, cfg: dict, via=None, only: str = "weights"):
        import jax

        self.params, self.cfg = params, dict(cfg)

        def fn(params, tokens, first, rows):
            return forward(params, tokens, self.cfg, first, rows,
                           via=via, only=only)

        self._fn = jax.jit(fn, static_argnums=(3,))

    def rows(self, seq, pad_to: int, first: int, n: int):
        import jax.numpy as jnp
        import numpy as np

        seq = np.asarray(seq, np.int32).reshape(-1)
        logits, gap = self._fn(self.params, jnp.asarray(padded(seq, pad_to)),
                               first, n)
        return np.asarray(logits), np.asarray(gap)[:seq.size]
