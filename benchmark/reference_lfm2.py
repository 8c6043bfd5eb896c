"""Plain reference of the LFM2-MoE block (LiquidAI, `model_type: lfm2_moe`),
independent of the code under test: straightforward `jax.numpy`, every
product in float32 under `jax.default_matmul_precision("highest")`, the
full forward over a whole sequence: no cache, no pages, no slots, no
kernels, no grouped products. The convolution is a padded sum over shifted
copies of the sequence, attention a masked softmax, and EVERY expert
computes every token, the routing weight deciding what is kept. It imports
nothing of the program; it reads the same parameter dict by the same names
(models/lfm2.py `param_specs`).

The block, from the published `config.json` (catalog row LFM2-24B-A2B) and,
where the config is silent, the family's public model code, as the
configuration file lists under `assumed`:

  rms(x; w) = x / sqrt(mean(x^2) + eps) * w
  h0 = E[ids];  h = h + Op_l(rms(h));  h = h + F_l(rms(h))
  logits = rms_f(h) E^T                      (the head tied to the embedding)
  conv(u): [B | C | X] = u W_in;  z = B * X;
    c_t = sum_{k=0..K-1} w[k] z_{t-(K-1)+k}   (depthwise, causal, no bias,
    no activation);  Op = (C * c) W_out
  attn(u): q, k, v = u W_q, u W_k, u W_v; every head of q and of k
    RMS-normed over head_dim with a gain, then rotary positions over the
    whole head (theta, rotate-half); causal softmax(q k^T / sqrt(head_dim))
    v, query head j on K/V head j // (heads / kv heads);  Op = o W_o
  F of the first `num_dense_layers` layers: W_2 (silu(W_1 x) * W_3 x)
  F of the others: s = sigmoid(x W_r); the top k of s + b kept (b the
    expert bias: selection only); weights s_kept / (sum s_kept + 1e-6) x
    routed_scaling_factor; sum over the kept of weight x Expert(x), an
    expert the same SwiGLU at its own width; no shared expert.

`cfg` (`families/lfm2.reference_config`) says which layers are held
(`layer_types`, `num_dense_layers` of them dense), which experts
(`experts_held`; what an absent expert would add is left out, here as in
the program) and the `dtype` weights, pages and tails are stored in.

THE NUMBER FORMAT is the configuration's (`assumed.number_format`), stated
here in plain `jax.numpy` and not taken from the program: what enters a
product is rounded to the dtype the product's weights are stored in (`_times`;
in attention q, the cached K and V and the softmax's probabilities), the
ROUTER's product alone takes its input in float32; everything between
products is float32; a position that was fed as one token reads its
predecessors' z from the tail, in the tail's dtype (`short_conv`,
`cached_from`); tails and pages come out in the dtype they are stored in.
With float32 weights nothing is rounded and this is the plain float32
forward. Two bfloat16 values multiply exactly in float32, so the dense
layers agree with the engine to the last bit on the chip (the first
layer's tail reads 0.0, the first attention layer's K and V 0.0 at the
median position); the rotary angles and the softmax's sums are where the
two first part, by a float32 rounding, and every later rounding to
bfloat16 doubles such a difference until it is bfloat16's own (0.46% of a
position's K and V after four routed layers, where the float32 reference of
this file's first form read 0.83%).

Routing decides discretely: where the engine's and the reference's
selection scores differ by more than the gap between the k-th and the
(k+1)-th, they keep different experts, a change of a whole expert's output
and not of a rounding, and what the turned position left in the tails and
the pages reaches the rows after it (a burst of several rows that decays; in
a prompt of one or two tokens every later row, which attends it). On the
chip 6-8% of the cached positions turn in one of the four routed layers
before the last attention layer (18-22% against the float32 form) and an
eighth to a fifth of a run's logits rows lie in a burst. So no
single row is held: the rows by their median and by each prompt's own lower
quartile, the cached positions by their median and by the share of them
that turned (`families/lfm2.py judge`), and what no routed layer precedes
(the first layer's tail, the first attention layer's pages) by its worst.

Limits: each lies between two readings on the chip (below): the engine
against this reference over its seeds, and the SAME engine outputs judged
against this reference built wrong or in a lower precision
(`benchmark/readings_lfm2.py`), each of which has to come out as not
correct by at least one limit. The lower-precision control (`via`: the
router's scores and the convolution's sum through bfloat16, where the
configuration states float32) turns half of the cached positions' routing
where the engine turns a twelfth, and comes out not correct by three limits
(`LOGIT_ERR` 2.6 times over at the least, `KV_TURNED_SHARE` 2.3, `KV_LAST_ERR`
1.45).
"""

from __future__ import annotations

# Limits, each beside the two readings it lies between (v5e, lfm2_24b_pp5 at
# its published widths, my chip runs, PR 58; PERF.md section 4 has the
# table): the engine's LARGEST over its seeds (`benchmark/readings_lfm2.py`
# seeds 11, 23, 37 and 53, every verdict printed at these limits; the cell's
# own check in two sets of six, 3000058601-606 and 3000058701-706, and a
# traced run: seventeen in all) | the LEAST reading among the faults that
# the limit fails, and which (readings_lfm2, the four seeds; `--plant
# conv_tail` seeds 11, 23 and 37)
LOGIT_ERR = 0.042             # median of all 246 rows: 0.0264 | 0.0669
#                               (`bias_weighted`; the control `bf16` 0.110)
PROMPT_LOGIT_ERR = 0.16       # a prompt's OWN lower quartile, the worst
#                               prompt's: 0.0471 (a prompt of one or two
#                               tokens of which one turned: every later row
#                               attends it; 0.031 and less else) | 0.666
#                               (`--plant conv_tail`; `bf16` 0.079-0.28)
PREFILL_LOGIT_ERR = 0.3       # the second nearest of the six prefills' own
#                               rows: 0.0198 | 4.45 (`gates_swapped`)
FIRST_STEPS_LOGIT_ERR = 0.35  # median of each prompt's first two steps, the
#                               only ones that read the tail its prefill
#                               wrote: 0.0264 | 5.09 (`--plant conv_tail`)
TAIL_ERR = 0.05               # median over prompts and convolution layers:
#                               0.0041 | 1.41 (`gates_swapped`)
TAIL_ERR_FIRST = 0.01         # the first layer's, the worst prompt's: 0.0
#                               (to the last bit, every seed) | 1.45
#                               (`gates_swapped`)
KV_ERR = 0.025                # first attention layer, the WORST of all
#                               ~4,400 cached positions: 0.0053 | 0.120
#                               (`no_qk_norm`; `--plant conv_tail` 1.08)
KV_LAST_ERR = 0.0068          # last attention layer, median position:
#                               0.00465 (0.00457-0.00465 on every seed) |
#                               0.00989 (`bf16`; `bias_weighted` 0.0113)
KV_TURNED = 0.01      # a cached position's K and V in the last attention
#                       layer off by more than this of their norm: more than
#                       rounding leaves (0.0046 at the median position,
#                       0.0059 at the 90th percentile; a turned choice 0.03
#                       and more)
KV_TURNED_SHARE = 0.21        # the share of ~4,400 cached positions over
#                               KV_TURNED: 0.0927 (0.060-0.093) | 0.475
#                               (`bf16`, the lower-precision control;
#                               `bias_weighted` 0.77)
ROW_TURNED = 0.06     # logged, no limit: a logits row over it lies in a
#                       turned position's burst (a sound row reads
#                       0.015-0.04: 31 to 54 of a run's 246 rows are over)
FAULTS = ("gates_swapped", "bias_weighted", "no_qk_norm")
ROUTE_NORM_EPS = 1e-6


def _f32(a):
    import jax.numpy as jnp

    return jnp.asarray(a).astype(jnp.float32)


def _through(a, via):
    """`a` rounded to dtype `via` and back, float32 again (nothing where
    `via` is None or float32). The barrier keeps the rounding: the chip's
    compiler allows itself excess precision and drops a narrowing
    conversion that is widened again at once (PERF.md, PR 33)."""
    import jax
    import jax.numpy as jnp

    if via is None or jnp.dtype(via) == jnp.float32:
        return a
    return jax.lax.optimization_barrier(a.astype(via)).astype(jnp.float32)


def _times(x, w):
    """x @ w as the stated number format has a product: x rounded to the
    dtype w is stored in, then plain float32 at "highest" (two bfloat16
    values multiply exactly in float32, so what is left between this and
    any other float32 accumulation is the order of the sum)."""
    import jax.numpy as jnp

    return _through(x, jnp.asarray(w).dtype) @ _f32(w)


def rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(w)


def rope(x, positions, theta):
    """x [T, heads, hd]: the whole head rotated, pairs (i, i + hd / 2) by
    pos * theta^(-2i/hd)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def swiglu(x, w1, w3, w2):
    import jax

    return _times(jax.nn.silu(_times(x, w1)) * _times(x, w3), w2)


def short_conv(params, p, x, cfg, cached_from=None, via=None, fault=None):
    """-> (the convolution sublayer's output for x [T, hidden], z [T,
    hidden]: what a slot's tail holds of a position, before the tail's
    dtype rounds it). A position from `cached_from` on was fed as ONE
    token: it read its predecessors' z from the slot's tail, in the dtype
    the tails are stored in; a position before it lay in the prompt and
    read them in float32."""
    import jax.numpy as jnp

    t, taps = x.shape[0], cfg["conv_L_cache"]
    b, c, xx = jnp.split(_times(x, params[p + "in_w"]), 3, axis=-1)
    if fault == "gates_swapped":
        b, c = c, b
    z = b * xx
    w = _f32(params[p + "conv_w"])                          # [taps, hidden]
    zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    zc = jnp.pad(_through(z, cfg["dtype"]), ((taps - 1, 0), (0, 0)))
    cached = (jnp.arange(t) >= (t if cached_from is None
                                else cached_from))[:, None]
    # the last tap is the position's own z: never through a tail
    conv = w[taps - 1] * z + sum(
        w[k] * jnp.where(cached, zc[k:k + t], zp[k:k + t])
        for k in range(taps - 1))
    return _times(c * _through(conv, via), params[p + "out_w"]), z


def attention(params, p, x, cfg, block: int = 256, fault=None):
    """-> (the attention sublayer's output for x [T, hidden]; K and V as a
    page holds them, [T, 2, kv heads x hd]: normed and rotated keys, then
    values, in the pages' dtype). Both products of the attention take their
    inputs in that dtype: q, the cached K and V, and the softmax's
    probabilities."""
    import jax
    import jax.numpy as jnp

    t, dt = x.shape[0], cfg["dtype"]
    hd, nq, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t, dtype=jnp.int32)
    q = _times(x, params[p + "q_w"]).reshape(t, nq, hd)
    k = _times(x, params[p + "k_w"]).reshape(t, nkv, hd)
    v = _through(_times(x, params[p + "v_w"]).reshape(t, nkv, hd), dt)
    if fault != "no_qk_norm":
        q = rms(q, params[p + "q_norm"], eps)
        k = rms(k, params[p + "k_norm"], eps)
    q = _through(rope(q, pos, cfg["rope_theta"]), dt)
    k = _through(rope(k, pos, cfg["rope_theta"]), dt)
    qh = q.reshape(t, nkv, nq // nkv, hd)
    bq = block if t % block == 0 else t

    def one_block(q0):
        qb = jax.lax.dynamic_slice_in_dim(qh, q0, bq)
        s = jnp.einsum("qkgh,skh->kgqs", qb, k) * hd ** -0.5
        ok = pos[None, :] <= (q0 + jnp.arange(bq))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", _through(pr, dt), v)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    return _times(o.reshape(t, nq * hd), params[p + "o_w"]), \
        jnp.stack([k.reshape(t, nkv * hd), v.reshape(t, nkv * hd)], axis=1)


def route(params, p, x, cfg, via=None, fault=None, forced=None):
    """-> (weights [T, num_experts], zero off the kept experts; the experts
    kept [T, k]). The router's product is the one that takes its input in
    float32, as the scores are. `forced` [T, k] keeps those experts
    instead of the reference's own choice (`readings_lfm2 --routing`: the
    engine's), weighed by the reference's scores."""
    import jax
    import jax.numpy as jnp

    s = _through(jax.nn.sigmoid(x @ _f32(params[p + "router_w"])), via)
    chosen_by = s + _f32(params[p + "expert_bias"])
    idx = forced if forced is not None else jax.lax.top_k(
        chosen_by, cfg["num_experts_per_tok"])[1]
    # the bias selects; the scores alone weigh
    kept = jnp.take_along_axis(
        chosen_by if fault == "bias_weighted" else s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, axis=1, keepdims=True)
                       + ROUTE_NORM_EPS)
    kept = kept * cfg["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                idx].set(kept), idx


def routed(params, p, x, weights, cfg):
    """sum over the HELD experts of weight x Expert(x): every held expert
    computes every token, the weight decides what is kept."""
    import jax
    import jax.numpy as jnp

    lo, count = cfg["experts_held"]
    w_held = weights[:, lo:lo + count].T                    # [E_held, T]

    def one(acc, ex):
        w1, w3, w2, w = ex
        return acc + w[:, None] * swiglu(x, w1, w3, w2), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (params[p + "ex_w1"], params[p + "ex_w3"], params[p + "ex_w2"],
         w_held))
    return acc


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            tail_at=None, block: int = 256, via=None, fault=None,
            cached_from=None, forced=None, on_route=None):
    """[T] token ids -> (float32 logits of the `rows` positions from `first`
    on, or of every position; with `tail_at`, every convolution layer's
    z at the ``K - 1`` positions up to and with that one [conv layers,
    K - 1, hidden] (zeros before the sequence's start: what a slot's tail
    holds after that position) and every attention layer's K and V
    [attention layers, T, 2, kv heads x hd], both as stored: in
    ``cfg["dtype"]``; else None twice). Causal, so a padded tail is
    harmless. The positions from `cached_from` on were fed one token at a
    time (`short_conv`). `via` is the lower-precision control: the router's
    scores and the convolution's sum through that dtype. `fault` is one of
    FAULTS: what the check would read of an engine built so. `forced`
    (routed layer -> experts [T, k]) keeps those experts; `on_route(layer,
    experts kept [T, k])` sees each routed layer's choice."""
    import jax
    import jax.numpy as jnp

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault={fault!r}: one of {FAULTS}")
    taps = cfg["conv_L_cache"]
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _f32(params["lf_tok_emb"][tokens])
        tails, pages = [], []
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"lf_l{i}_"
            x = rms(h, params[p + "norm_op"], eps)
            if kind == "full_attention":
                a, kv = attention(params, p, x, cfg, block, fault)
                pages.append(kv)
                h = h + a
            else:
                c, z = short_conv(params, p, x, cfg, cached_from, via,
                                  fault)
                if tail_at is not None:
                    zp = jnp.pad(_through(z, cfg["dtype"]),
                                 ((taps - 1, 0), (0, 0)))
                    tails.append(jax.lax.dynamic_slice_in_dim(
                        zp, tail_at + 1, taps - 1))
                h = h + c
            x = rms(h, params[p + "norm_ffn"], eps)
            if i < cfg["num_dense_layers"]:
                h = h + swiglu(x, params[p + "w1"], params[p + "w3"],
                               params[p + "w2"])
            else:
                weights, kept = route(params, p, x, cfg, via, fault,
                                      (forced or {}).get(i))
                if on_route is not None:
                    on_route(i, kept)
                h = h + routed(params, p, x, weights, cfg)
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = _times(rms(h, params["lf_norm_f"], eps),
                        params["lf_tok_emb"].T)
        if tail_at is None:
            return logits, None, None
        return logits, jnp.stack(tails), jnp.stack(pages)


def padded(seq, pad_to: int):
    import numpy as np

    seq = np.asarray(seq, np.int32).reshape(-1)
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to "
                         f"{pad_to}")
    out = np.zeros(pad_to, np.int32)
    out[:seq.size] = seq
    return out


def logit_errors(got, want):
    """Largest |got - want| of each row [rows, vocab] as a share of that
    row of want's root mean square."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want), axis=-1) / np.maximum(
        np.sqrt(np.mean(np.square(want), axis=-1)), 1e-12)


def kv_errors(got, want):
    """||got - want|| / ||want|| over each position's K and V, [T, 2,
    width] each -> [T]."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.sqrt(np.sum(np.square(got - want), axis=(1, 2)))
    return err / np.maximum(
        np.sqrt(np.sum(np.square(want), axis=(1, 2))), 1e-30)


def tail_errors(got, want):
    """||got - want|| / ||want|| of each layer over [layers, K - 1,
    hidden]."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (np.sqrt(np.sum(np.square(got - want), axis=(1, 2)))
            / np.maximum(np.sqrt(np.sum(np.square(want), axis=(1, 2))),
                         1e-30)).tolist()


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n,
    tail_at)` -> (logits [n, vocab], tails, K/V) of the `n` rows from
    `first` on, of which the first is the prompt's last position and the
    others were fed one token at a time (`forward`'s `cached_from` is
    ``first + 1``). One compile a `pad_to`. `via` (a dtype) makes it the
    lower-precision control, `fault` a planted fault (`forward`).
    `routed_rows` is `rows` with `forced` experts (routed layer ->
    [pad_to, k], or None) and one result more: the experts kept [routed
    layers, tokens, k]."""

    def __init__(self, params, cfg: dict, via=None, fault=None):
        import jax
        import jax.numpy as jnp

        self.params, self.cfg = params, dict(cfg)

        def fn(params, tokens, first, tail_at, forced, rows):
            seen = []
            out = forward(params, tokens, self.cfg, first, rows,
                          tail_at=tail_at, via=via, fault=fault,
                          cached_from=first + 1, forced=forced,
                          on_route=lambda _i, kept: seen.append(kept))
            return out + (jnp.stack(seen),)

        self._fn = jax.jit(fn, static_argnums=(5,))

    def routed_rows(self, seq, pad_to: int, first: int, n: int,
                    tail_at: int, forced=None):
        import jax.numpy as jnp
        import numpy as np

        seq = np.asarray(seq, np.int32).reshape(-1)
        logits, tails, pages, kept = self._fn(
            self.params, jnp.asarray(padded(seq, pad_to)), first,
            int(tail_at), forced, n)
        return np.asarray(logits), np.asarray(tails), \
            np.asarray(pages)[:, :seq.size], np.asarray(kept)[:, :seq.size]

    def rows(self, seq, pad_to: int, first: int, n: int, tail_at: int):
        return self.routed_rows(seq, pad_to, first, n, tail_at)[:3]
