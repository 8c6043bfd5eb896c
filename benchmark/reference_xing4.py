"""Plain reference of the Xing4.0 block and its multi-token-prediction
module (XingChen-AGI, `model_type: xing4_0`; catalog row Xing4.0-29B-A4B),
independent of the code under test: straightforward `jax.numpy` in float32
under `jax.default_matmul_precision("highest")`, no cache, no pages, no
kernels, no batching, no absorbed products, no pairs of positions; and the
acceptance rule of speculative sampling in plain `numpy`, float64. It
imports nothing of the program; it reads the same parameter dict by the
same names (models/xing4.py `param_specs`) and upcasts whatever dtype it
finds. The latent attention's pieces, the router and the routed layer are
reference_kimi_k2's equations (the same published block), imported from
that file, not from the program.

The block, from the published `config.json` and, where it is silent, as the
configuration file lists under `assumed` (`n` streams of width `C`):

  X_0 = [E[id]] x n
  for each layer, for each sublayer F in (Attn, MLP), its own parameters:
    xt = RMS_gamma(vec(X)) over all nC values, epsilon hc_eps
    [l_pre (n), l_post (n), l_res (n x n)] = xt Phi
    H_pre = sigmoid(a_pre l_pre + b_pre);  H_post = 2 sigmoid(a_post l_post
    + b_post);  H_res = SK(exp(clip(a_res l_res + B_res, -30, 30))): rows,
    then columns, divided by their sum + hc_eps, `hc_sinkhorn_iters` times
    u = sum_i H_pre[i] X[i];  y = F(RMS(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
  h = sum_i X[i];  logits = RMS_f(h) W_head                        (untied)
  Attn, MLP: reference_kimi_k2's (latent attention with YaRN, expanded
  form; a dense SwiGLU in the leading layers, else the shared expert beside
  the sigmoid top-k routed layer with a selection-only bias).

  The module (arXiv:2412.19437 sec. 2.2), at position i of a sequence whose
  token i + 1 is known:
    x_i = [RMS_e(E[tok_{i+1}]), RMS_h(h_i)] W_eh          (h_i as above)
    X = [x_i] x n;  one MoE layer of the block above (maps of its own,
    causal attention over x_0 .. x_i);  draft_logits_i = RMS_mtp(sum X) W_head
  with the model's own E and W_head: the distribution of token i + 2. Its
  layer's cached row at i is `latents[n_layers, i]`.

The acceptance rule (`accept`; Leviathan et al. arXiv:2211.17192): a draft d
drawn from q is accepted with probability min(1, p(d) / q(d)) and followed
by a draw from the next position's distribution; rejected, ONE token is
drawn from norm(max(p - q, 0)); without a draft, from p. `rule_distances`
replays a step of the engine from the probabilities it read and the
request's uniforms and says how far, in probability mass, each delivered
token lies from the interval the rule gives it (0 inside; float32 sums on
the device move an interval's ends by ~1e-7 of the mass, and at a
temperature that spreads the mass over 131,072 tokens an interval is
7.6e-6 wide, so equality of tokens is no test; distance is).

Limits. How a run's numbers are held against this reference is in
`families/xing4.py`. Each limit lies between two readings on the chip (v5e,
xing4_29b_pp8 at its published widths, my chip runs, PR 53; PERF.md section
4 has the table): the engine against this reference (`engine`: the cell's
own runs and `benchmark/readings_xing4.py`: sixteen runs of as many seeds,
3000053001, -011, -013, -101 to -106, -200 to -206; three check prompts of
200, 700 and 1,500 tokens, two greedy and one sampled at 2.8, ~114 steps a
run, 61 sampled requests decoding beside them), and a control or planted fault judged by the same `judge`
(`readings_xing4`, seeds -011 and -013): every weight matrix through float8
e4m3 (`weights`, the nearest precision below bfloat16); the module fed the
hidden state one position off (`hidden_off`); a rejected draft's latent row
left in place and attended by the next step (`stale_row`, planted in the
ENGINE); a rejection that redraws from p (`redraw_p`). Each comes out as
not correct by at least one limit.

What decides the form of the limits: with ALL 64 experts held, 243-250 of a
run's ~258 compared positions (231-252 over the sixteen runs) route by less than ROUTE_EPS in one of the
five routed layers, a bfloat16 engine keeps another expert at some of them,
and such a logits row differs from the reference's by up to 3.5 of its root
mean square where the class's median row differs by 0.15 (two unrelated
rows: ~6). So a CLASS of rows is held by its median, which flips do not
move and every fault above does, and a single row only to being the same
row at all.
  MEDIAN_LOGIT_ERR 0.8  largest |engine - reference| over a logits row as a
              share of the row's root mean square, the MEDIAN over a class
              of rows: the first verified position of every step, the second
              (an accepted one against the sequence's own row, a rejected
              one against the reference fed the draft), the module's row at
              the newest accepted position. Engine: first 0.12-0.20, second
              0.08-0.15, module 0.24-0.40. `weights` 1.7-2.2 (all three);
              `stale_row` 2.4-3.1; `hidden_off` 5.0-5.1 (the module's).
              2.0 times over the engine's worst, 2.1 under the lowest
              control's.
  ROW_LOGIT_ERR 5.0  the same of a SINGLE row: each prefill's row and the
              largest over the step rows (and the module's) at positions the
              reference routes decidedly (~10-25 a run). Engine: prefill
              0.04-2.08 (a prompt that flips an expert at its last
              position), decided rows 0.16-1.28, the module's 0.18-1.60. `hidden_off` 5.4-5.5; a row of another
              position or request reads ~6. The undecided rows' largest
              (engine 2.2-3.5, `weights` 3.5-3.9) is logged and held by
              no limit.
  LATENT_ERR 0.14  the latent rows a request's pages hold when it retires
              against [c, k_r] of the reference over the delivered
              sequence, |difference| / |row| at the median row, the worst
              held layer and the module's layer. Engine: held 0.012-0.043,
              module 0.011-0.064 (flips upstream of most rows; the 200-token
              prompt's highest). `weights` 0.29-0.40; `hidden_off` 0.97-0.99
              (the module's). 2.2 times over the engine's worst, 2.1 under
              the lowest control's.
  LATENT_ERR_UNROUTED 0.012  the same of the held layers no routed layer
              lies before (the dense layer's and the first routed layer's
              rows): no expert can flip under them. Engine 0.0030-0.0041;
              `weights` 0.065-0.070. 2.9 times over, 5.4 under.
  RULE_DISTANCE 1e-4  `rule_distances`' largest, in probability mass.
              Engine 0 to 9.3e-8 (a float32 sum at an interval's end);
              `redraw_p` 0.0017 (both seeds: at temperature 2.8 p and
              norm(max(p - q, 0)) are both nearly flat, and still 17 times
              the limit apart).
  Counts held to 0: `positions_off` (`stale_row`: 106 of 113 steps),
              `uniforms_off`; `q_carry_err` 1e-5 (engine 1.4e-9).
  `maps` (the residual maps computed in bfloat16 where float32 is stated) is
              read and CANNOT be failed at these widths: it reads as the
              engine does (unrouted rows 0.0033-0.0036 beside the engine's
              0.0030-0.0033, held 0.023-0.034 beside 0.021-0.029, medians
              inside the engine's spread over seeds): the maps' rounding is
              a third of what bfloat16 weights already cost every row. At
              float32 (the CPU tests' toy) it fails LATENT_ERR by 30 times.
              Each limit was first set from three runs (0.7, 0.1) and moved
              once, to where sixteen put the middle; no run of the sixteen
              read over the first values.
  `limits("float32")`: the toy states float32 and is held to float32's
              readings.
"""

from __future__ import annotations

from benchmark.reference_afmoe import (decided_prefix,  # noqa: F401
                                       logit_error, padded)
from benchmark.reference_kimi_k2 import (_f32, rms_norm, rope, route, routed,
                                         softmax_scale, swiglu,
                                         yarn_inv_freq)
from benchmark.reference_motif3 import latent_error  # noqa: F401

ROW_LOGIT_ERR = 5.0
MEDIAN_LOGIT_ERR = 0.8
ROUTE_EPS = 0.012
LATENT_ERR = 0.14
LATENT_ERR_UNROUTED = 0.012
RULE_DISTANCE = 1e-4
# float32 engine against this reference (CPU, tests/benchmark_suite/
# test_xing4_check.py: under 1e-4 of a logits row, under 1e-5 of a latent
# row) and the bfloat16 control there (weights: 0.01-0.04 of a row)
FLOAT32 = {"ROW_LOGIT_ERR": 2e-3, "MEDIAN_LOGIT_ERR": 2e-3,
           "LATENT_ERR": 3e-4, "LATENT_ERR_UNROUTED": 3e-4}

CONTROLS = ("weights", "maps")
FAULTS = ("hidden_off",)


def limits(dtype: str) -> dict:
    """The limits a configuration of that dtype is held to."""
    out = {"ROW_LOGIT_ERR": ROW_LOGIT_ERR,
           "MEDIAN_LOGIT_ERR": MEDIAN_LOGIT_ERR, "LATENT_ERR": LATENT_ERR,
           "LATENT_ERR_UNROUTED": LATENT_ERR_UNROUTED,
           "RULE_DISTANCE": RULE_DISTANCE}
    if dtype == "float32":
        out.update(FLOAT32)
    return out


def mhc_maps(params, p, xs, cfg, via=None, maps_via=None):
    """xs [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the
    sublayer whose maps' parameters start with `p`. `maps_via` computes the
    maps in that dtype where float32 is stated (a control)."""
    import jax
    import jax.numpy as jnp

    n, eps = cfg["n_streams"], cfg["hc_eps"]
    t = xs.shape[0]
    xt = rms_norm(xs.reshape(t, -1), params[p + "norm"], eps)
    logits = xt @ _f32(params[p + "phi"], via)              # [T, 2n + n^2]
    a, b = _f32(params[p + "scale"]), _f32(params[p + "bias"])
    dt = jnp.float32
    if maps_via is not None:
        dt = jnp.dtype(maps_via)
        logits, a, b = logits.astype(dt), a.astype(dt), b.astype(dt)
    h_pre = jax.nn.sigmoid(a[0] * logits[:, :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(a[1] * logits[:, n:2 * n] + b[n:2 * n])
    lo, hi = cfg["hc_res_clamp"]
    m = jnp.exp(jnp.clip(a[2] * logits[:, 2 * n:] + b[2 * n:], lo, hi)
                ).reshape(t, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=-1, keepdims=True) + jnp.asarray(eps, dt))
        m = m / (m.sum(axis=-2, keepdims=True) + jnp.asarray(eps, dt))
    return tuple(v.astype(jnp.float32) for v in (h_pre, h_post, m))


def attention(params, p, x, cfg, block: int = 256, via=None):
    """-> (the attention sublayer's output of the layer under `p` for x
    [T, hidden], the latent rows [T, rank + rope] a cache would hold):
    reference_kimi_k2.attention's equations, with the rows given out."""
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
    kv = (c @ _f32(params[p + "kv_b_w"], via)).reshape(t, n, -1)
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
    return o.reshape(t, -1) @ _f32(params[p + "o_w"], via), \
        jnp.concatenate([c, k_r], axis=1)


def layer(params, p, xs, cfg, moe: bool, block, via, maps_via):
    """One decoder layer over the streams xs [T, n, C] -> (xs', its latent
    rows, its routing gap [T])."""
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    out = {"gap": jnp.full((xs.shape[0],), jnp.inf, jnp.float32)}

    def around(xs, sub, norm, fn):
        h_pre, h_post, h_res = mhc_maps(params, p + sub, xs, cfg, via,
                                        maps_via)
        u = jnp.einsum("ti,tic->tc", h_pre, xs)
        y = fn(rms_norm(u, params[p + norm], eps))
        return jnp.einsum("tij,tjc->tic", h_res, xs) \
            + h_post[:, :, None] * y[:, None, :]

    def attn(x):
        a, out["latent"] = attention(params, p, x, cfg, block, via)
        return a

    def mlp(x):
        if not moe:
            return swiglu(x, params[p + "w1"], params[p + "w3"],
                          params[p + "w2"], via)
        weights, out["gap"] = route(params, p, x, cfg, via)
        return swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                      params[p + "sh_w2"], via) \
            + routed(params, p, x, weights, cfg, via)

    xs = around(xs, "mhc_a_", "norm_in", attn)
    xs = around(xs, "mhc_m_", "norm_mlp", mlp)
    return xs, out["latent"], out["gap"]


def head_logits(x, w, via=None, chunk: int = 16384):
    """x [T, C] @ w [C, V] in float32, `chunk` columns at a time: the head
    upcast whole is 1.9 GB beside a serving engine."""
    import jax
    import jax.numpy as jnp

    v = w.shape[1]
    if v % chunk:
        return x @ _f32(w, via)

    def some(c0):
        return x @ _f32(jax.lax.dynamic_slice_in_dim(w, c0, chunk, axis=1),
                        via)

    out = jax.lax.map(some, jnp.arange(0, v, chunk, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 256, via=None, only: str = "weights",
            fault=None):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; the module's logits of the same
    positions (position i's: the distribution of token i + 2, from h_i and
    token i + 1; the last position's next token is read as token 0 and its
    row means nothing); route_gap [T] of the held layers; draft_gap [T] of
    the module's layer; latents [n_layers + 1, T, rank + rope], the
    module's last). Causal, so a padded tail is harmless. `via` with
    `only`: every weight matrix through that dtype (`weights`), or the maps
    computed in it (`maps`). `fault` "hidden_off": the module reads the
    hidden state of the position BEFORE its own."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault={fault!r}: one of {FAULTS}")
    maps_via = via if only == "maps" else None
    via = via if only == "weights" else None
    n, eps = cfg["n_streams"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        def emb(ids):       # the rows first: the table whole is 1.9 GB
            return _f32(params["x4_tok_emb"][ids], via)

        xs = jnp.repeat(emb(tokens)[:, None, :], n, axis=1)     # [T, n, C]
        gap = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        latents = []
        for i in range(cfg["n_layers"]):
            xs, lat, g = layer(params, f"x4_l{i}_", xs, cfg,
                               i >= cfg["first_k_dense"], block, via,
                               maps_via)
            latents.append(lat)
            gap = jnp.minimum(gap, g)
        h = xs.sum(axis=1)
        # the module: position i reads h_i and the embedding of token i + 1
        nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
        h_in = h if fault != "hidden_off" else jnp.concatenate(
            [jnp.zeros_like(h[:1]), h[:-1]])
        x = jnp.concatenate(
            [rms_norm(emb(nxt), params["x4_mtp_enorm"], eps),
             rms_norm(h_in, params["x4_mtp_hnorm"], eps)], axis=1) \
            @ _f32(params["x4_mtp_eh_proj"], via)
        ms, lat, draft_gap = layer(
            params, "x4_mtp_", jnp.repeat(x[:, None, :], n, axis=1), cfg,
            True, block, via, maps_via)
        latents.append(lat)
        hm = ms.sum(axis=1)
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
            hm = jax.lax.dynamic_slice_in_dim(hm, first, rows)
        return (head_logits(rms_norm(h, params["x4_norm_f"], eps),
                            params["x4_head_w"], via),
                head_logits(rms_norm(hm, params["x4_mtp_norm"], eps),
                            params["x4_head_w"], via),
                gap, draft_gap, jnp.stack(latents))


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n)` ->
    (logits [n, vocab], draft_logits [n, vocab], route_gap [len(seq)],
    draft_gap [len(seq)], latents [layers + 1, len(seq), rank + rope]). One
    compile a `pad_to` and `n`. `via` and `only` make it a lower-precision
    control, `fault` a planted fault (`forward`)."""

    def __init__(self, params, cfg: dict, via=None, only: str = "weights",
                 fault=None):
        import jax

        self.params, self.cfg = params, dict(cfg)

        def fn(params, tokens, first, rows):
            return forward(params, tokens, self.cfg, first, rows, via=via,
                           only=only, fault=fault)

        self._fn = jax.jit(fn, static_argnums=(3,))

    def rows(self, seq, pad_to: int, first: int, n: int):
        import jax.numpy as jnp
        import numpy as np

        seq = np.asarray(seq, np.int32).reshape(-1)
        logits, draft_logits, gap, draft_gap, latents = self._fn(
            self.params, jnp.asarray(padded(seq, pad_to)), first, n)
        return (np.asarray(logits), np.asarray(draft_logits),
                np.asarray(gap)[:seq.size], np.asarray(draft_gap)[:seq.size],
                np.asarray(latents)[:, :seq.size])


# ---------------------------------------------------------------------------
# the acceptance rule, plain numpy in float64

def probabilities(logits, temperature: float):
    """softmax(logits / temperature), float64 (a greedy row's: at 1)."""
    import numpy as np

    z = np.asarray(logits, np.float64) / (temperature if temperature > 0
                                          else 1.0)
    e = np.exp(z - z.max())
    return e / e.sum()


def inverse_cdf(mass, uniform: float) -> int:
    """The count of CDF entries of `mass` (unnormalised) below
    `uniform x total`, clamped to the last index."""
    import numpy as np

    c = np.cumsum(np.asarray(mass, np.float64))
    return int(min(np.searchsorted(c, uniform * c[-1]), len(c) - 1))


def accept(p, p_after, q, draft, uniforms, redraw_from_p: bool = False):
    """One row's step by the exact rule, from probabilities: `p` the
    model's at the position the draft stands for, `p_after` its
    distribution after the draft, `q` the draft's (None: no draft),
    `uniforms` (accept, redraw, second position). -> the one or two tokens.
    `redraw_from_p` is the planted fault: a rejection that draws from p
    and not from norm(max(p - q, 0))."""
    import numpy as np

    p = np.asarray(p, np.float64)
    if q is not None and uniforms[0] * q[draft] < p[draft]:
        return [int(draft), inverse_cdf(p_after, uniforms[2])]
    rest = p if q is None or redraw_from_p else np.maximum(p - q, 0.0)
    return [inverse_cdf(rest, uniforms[1])]


def cdf_distance(mass, uniform: float, token: int) -> float:
    """How far `uniform x total` lies outside the CDF interval of `token`,
    as a share of the total mass: 0 where the inverse CDF gives `token`."""
    import numpy as np

    c = np.cumsum(np.asarray(mass, np.float64))
    total = c[-1]
    if total <= 0:
        return 1.0
    lo = c[token - 1] if token else 0.0
    target = uniform * total
    return float(max(lo - target, target - c[token], 0.0) / total)


def rule_distances(p, p_after, q, draft, uniforms, tokens,
                   redraw_from_p: bool = False):
    """A sampled row's step as the engine gave it (`tokens`, one or two)
    held against the rule: for each decision and token the distance from
    where the rule puts it (module docstring), 0 for a step the rule
    gives exactly. An acceptance is measured at its boundary: |u q(d) -
    p(d)| / p(d) where engine and rule disagree."""
    import numpy as np

    p = np.asarray(p, np.float64)
    accepted = len(tokens) == 2
    out = []
    if q is None:
        if accepted:
            return [1.0]
    else:
        q = np.asarray(q, np.float64)
        if (uniforms[0] * q[draft] < p[draft]) != accepted:
            out.append(abs(uniforms[0] * q[draft] - p[draft])
                       / max(p[draft], 1e-300))
    if accepted:
        out.append(0.0 if tokens[0] == draft else 1.0)
        out.append(cdf_distance(p_after, uniforms[2], tokens[1]))
    else:
        rest = p if q is None or redraw_from_p else np.maximum(p - q, 0.0)
        out.append(cdf_distance(rest, uniforms[1], tokens[0]))
    return out
