"""Plain reference of the Motif-3 block (Motif-Technologies, `model_type:
Motif`; catalog row Motif-3-Beta), independent of the code under test:
straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, no cache, no pages, no rings, no
kernels, no batching, no absorbed products, no grouped products. It imports
nothing of the program; it reads the same parameter dict by the same names
(models/motif3.py `param_specs`) and upcasts whatever dtype it finds.

The block, from the published `config.json` and, where the config is
silent, as the configuration file lists under `assumed` (`n` streams of
width `C`, eps = rms_norm_eps):

  X_0 = [E[id]] x n                      the embedding row in every stream
  for each layer, for each sublayer F in (Attn, MLP), its own parameters:
    xt = RMS_gamma(vec(X))               over all nC values
    [l_pre (n), l_post (n), l_res (n x n)] = xt Phi
    H_pre = sigmoid(a_pre l_pre + b_pre);  H_post = 2 sigmoid(a_post l_post
    + b_post);  H_res = SK(exp(a_res l_res + B_res)): rows, then columns,
    normalised to sum 1, `mhc_sinkhorn_iters` times
    u = sum_i H_pre[i] X[i];  y = F(RMS(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, clamped to +-hidden_clamp
  h = sum_i X[i];  logits = RMS(h) W_head                          (untied)

  Attn(x), grouped differential latent attention, EXPANDED form:
    c_q = RMS(x W_qa);  [q_n (nope), q_r (rope)]_h = c_q W_qb   per q head
    [c (rank), k_r (rope)] = x W_kva;  c = RMS(c);  q_r, k_r rotated by
    position (plain rotary, theta, interleaved pairs); k_r ONE key for all
    [k_n (nope), v]_g = c W_kvb                            per K/V head g
    query head 5g + j reads K/V head g (here 5 = heads / kv heads); j < 4
    are signal heads, the group's last its noise head
    o_h = softmax((q_n . k_n + q_r . k_r) (nope + rope)^-0.5) v over keys
      t' <= t, and in a window layer t - t' < window
    lambda = sigmoid(x W_lam)                  a token and signal head
    d_{g,j} = o_{5g+j} - lambda_{4g+j} o_{5g+4}
    a = (concat(d) * sigmoid(x W_g)) W_o
  MLP, dense: (PN(x W1) * (x W3)) W2.  MoE: s = sigmoid(x Wr) over all
    experts, top-k by s, w_k = s_k / sum of the kept s x route_scale
    (applied AFTER the experts), m = Shared(x) + sum_k w_k Expert_k(x),
    every expert and the shared one the dense form at its own width.
  PN(z) = scale (w1 z / r(z) + w2 z^2 / r(z^2) + w3 z^3 / r(z^3)
    + clip(b, +-clamp)), r(a) = sqrt(mean(a^2) + eps) over the WHOLE
    intermediate width; (w, b) of its own for each MLP and each expert.

The share, as reference_kimi_k2's: `experts_held` says which routed experts
the parameters hold, the rows of `m3_tok_emb` which of the vocabulary; every
head is held. Experts that are not held add nothing, and that partial
result goes on. With every expert held this is the uncut model
(tests/test_motif3_share.py adds the shares up to it).

Departures, each also in the configuration file: no multi-token prediction
head (`num_nextn_predict_layers` 1 is carried, not computed); the mHC maps
are the published ones (arXiv:2512.24880), the catalog's "modified" not
being specified; seeded weights.

Attention runs in blocks of queries (`block`), a window layer's block over
the keys its band reaches only, so that 12,000 tokens fit beside a serving
engine on one chip.

Limits. How a run's numbers are held against this reference is in
`families/motif3.py`. Each limit lies between two readings on the chip (v5e,
the configuration motif3_beta_dp_ep8 at its real widths, my chip runs, PR
49; PERF.md section 4 has the table). The one reading is the engine against
this reference: five prompt lengths (703 to 12,000 tokens, one in each
prefill bucket) with 59 sampled requests decoding beside them, in the runs
of `benchmark/readings_motif3.py` (seeds 3000049011 and -015) and of the
cell itself. The other is a control (`readings_motif3`): the SAME engine
outputs judged by the same `families/motif3.judge` against this reference
with a part of it rounded to 8 bits (float8 e4m3, the nearest precision
below bfloat16): the latent rows as a ring or a page holds them (`latent`),
every weight matrix (`weights`); or a planted fault at the timed size
(`--plant ring_table`: later live rows fed the first row's ring table;
`no_noise` and `sinkhorn_1`: the sound engine against this reference without
the noise head's subtraction, or with one Sinkhorn iteration). Each control
and each fault has to come out as not correct by one of the limits.
  LOGIT_ERR 0.06  largest |engine - reference| over a prefill's logits row,
              as a share of that row's root mean square. Engine: a run's
              worst 0.013-0.024 in ten runs (a prompt's 0.010-0.024). `weights`
              0.31-0.67; `no_noise` 0.29-0.46; `sinkhorn_1` 0.075-0.109
              (every prompt over the limit); `latent` 0.020-0.044 (under
              it: that control is LATENT_ERR's). 2.5 times over the
              engine's worst, 5 times under the control's lowest. W_kvb
              alone through float8 reads 0.020-0.050 and changes no token:
              it is no control of this cell (it cannot be told from the
              engine's own rounding with room on both sides).
  MARGIN 0.06   a greedy token's reference logit may lie this far under the
              reference's maximum, at positions whose routing is decided.
              Engine: 0 to 0.0001 (at ROUTE_EPS 0.012). `weights` 0.32-0.35, `ring_table` 1.29,
              `sinkhorn_1` 0.064, `no_noise` 0.44.
  ROUTE_EPS 0.012  in units of the selection score, as reference_afmoe
              and reference_kimi_k2. Tried at 0.006 (seven runs of the
              cell, seeds 3000049101-107): of ~110 positions a run that
              route by more than 0.006 one chose a token 0.0588 under the
              reference's maximum (seed -103), against MARGIN 0.06: an
              engine that keeps another expert at a gap of 0.006-0.012 is
              rounding, not a fault, so those positions belong to the
              wider margin. At 0.012 (three runs) the decided positions
              read 0 to 0.0001.
  UNDECIDED_MARGIN 1.5, UNDECIDED_SHARE 0.85  the positions ROUTE_EPS
              takes from MARGIN are held to 1.5. Engine: 0 to 0.27 in ten
              runs. The `ring_table` fault reads 0.92 there and fails
              MARGIN first (1.29), so the margin has no control of its own
              in this cell; it stands where cell 4's readings put it (its
              engine read up to 0.80 where a kept expert differed). The
              SHARE is this cell's: 48 of 384 experts are held in four MoE
              layers, so an edge of the top-8 touches a held expert far
              more often than in cells 3 and 4, and 122 of a run's 200
              decoded positions route by less than 0.012 in some layer
              (seed 3000049001; binomial spread ~7): cell 4's 0.7 (140)
              lies 2.6 spreads off, too near for seeds the driver draws;
              0.85 (170) lies 7 off and still holds 30 positions a run to
              MARGIN.
  LATENT_ERR 0.012  a latent row as the request's ring or pages hold it
              when it retires against the reference's [c, k_r] of that
              position and layer, |difference| / |row|, the median over
              the rows compared (a ring: the last `sliding_window`
              positions fed, at index position mod the ring's rows; pages:
              every position fed), the worst layer of each class. Engine:
              rings 0.0031-0.0045, pages 0.0028-0.0033 (ten runs). `latent`
              0.0270-0.0280 both; `weights` 0.065-0.109; `sinkhorn_1`
              0.021-0.035; `no_noise` 0.066-0.092. 2.7 times over the
              engine's worst, 2.2 times under the control's lowest.
  `limits("float32")`: a configuration that states float32 (the CPU tests'
              toy) is held to float32's readings, not to these.
"""

from __future__ import annotations

from benchmark.reference_afmoe import (decided_prefix,  # noqa: F401
                                       greedy_gaps, logit_error, padded)

LOGIT_ERR = 0.06
MARGIN = 0.06
ROUTE_EPS = 0.012
UNDECIDED_MARGIN = 1.5
UNDECIDED_SHARE = 0.85
LATENT_ERR = 0.012
# A configuration that states float32 is held to float32: between the
# float32 engine's readings (CPU, tests/benchmark_suite/test_motif3_check.py:
# under 1e-4 of a logits row, under 1e-5 of a latent row) and the bfloat16
# control's there (every weight matrix through bfloat16: 0.01-0.03 of a
# row; the latent rows through bfloat16: ~0.002 of a row).
FLOAT32 = {"LOGIT_ERR": 2e-3, "MARGIN": 2e-3, "LATENT_ERR": 3e-4}


def limits(dtype: str) -> dict:
    """The limits a configuration of that dtype is held to."""
    out = {"LOGIT_ERR": LOGIT_ERR, "MARGIN": MARGIN,
           "UNDECIDED_MARGIN": UNDECIDED_MARGIN, "LATENT_ERR": LATENT_ERR}
    if dtype == "float32":
        out.update(FLOAT32)
    return out

CONTROLS = ("latent", "weights")


def _f32(a, via=None):
    """`a` in float32; with `via`, rounded to that dtype on the way (the
    lower-precision control). The barrier keeps the rounding
    (reference_kimi_k2._f32)."""
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


def poly_norm(z, pn, cfg):
    """PN(z) over z's last axis; pn [..., 4] = (w1, w2, w3, b), broadcast
    against z's leading axes."""
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    pn = _f32(pn)

    def unit(a):
        return a / jnp.sqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                            + eps)

    clamp = cfg["polynorm_bias_clamp"]
    return cfg["polynorm_output_scale"] * (
        pn[..., 0:1] * unit(z) + pn[..., 1:2] * unit(z * z)
        + pn[..., 2:3] * unit(z * z * z)
        + jnp.clip(pn[..., 3:4], -clamp, clamp))


def polyglu(x, w1, w3, w2, pn, cfg, via=None):
    return (poly_norm(x @ _f32(w1, via), pn, cfg) * (x @ _f32(w3, via))) \
        @ _f32(w2, via)


def sinkhorn(m, iters: int):
    """m [..., n, n] positive: rows, then columns, to sum 1, `iters`
    times."""
    for _ in range(iters):
        m = m / m.sum(axis=-1, keepdims=True)
        m = m / m.sum(axis=-2, keepdims=True)
    return m


def mhc_maps(params, p, xs, cfg, via=None, iters=None):
    """xs [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the
    sublayer whose parameters start with `p` (`..._mhc_a_` or
    `..._mhc_m_`)."""
    import jax
    import jax.numpy as jnp

    n = cfg["n_streams"]
    t = xs.shape[0]
    xt = rms_norm(xs.reshape(t, -1), params[p + "norm"], cfg["rms_norm_eps"])
    logits = xt @ _f32(params[p + "phi"], via)              # [T, 2n + n^2]
    a = _f32(params[p + "scale"])                   # a_pre, a_post, a_res
    b = _f32(params[p + "bias"])                    # b_pre, b_post, B_res
    h_pre = jax.nn.sigmoid(a[0] * logits[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * logits[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * logits[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    return h_pre, h_post, sinkhorn(
        res, cfg["mhc_sinkhorn_iters"] if iters is None else iters)


def route(params, p, x, cfg, via=None):
    """-> (weights [T, experts], zero off the kept experts; gap [T]: how
    far the k-th score lies above the (k+1)-th, infinite where neither of
    the two experts is held)."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    lo, count = cfg["experts_held"]
    s = jax.nn.sigmoid(x @ _f32(params[p + "router_w"], via))
    top, idx = jax.lax.top_k(s, k + 1)
    keep = idx[:, :k]
    kept = top[:, :k]
    w = kept
    if cfg["route_norm"]:
        w = kept / (jnp.sum(kept, axis=1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    weights = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], keep].set(w)
    edge = idx[:, k - 1:k + 1]
    held = jnp.any((edge >= lo) & (edge < lo + count), axis=1)
    return weights, jnp.where(held, top[:, k - 1] - top[:, k], jnp.inf)


def routed(params, p, x, weights, cfg, via=None):
    """sum over the HELD experts of weight x Expert(x): every held expert
    computes every token, the weight decides what is kept."""
    import jax
    import jax.numpy as jnp

    lo, count = cfg["experts_held"]
    w_held = weights[:, lo:lo + count].T                    # [E_held, T]

    def one(acc, ex):
        w1, w3, w2, pn, w = ex
        return acc + w[:, None] * polyglu(x, w1, w3, w2, pn, cfg, via), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (params[p + "ex_w1"], params[p + "ex_w3"], params[p + "ex_w2"],
         params[p + "ex_pn"], w_held))
    return acc


def rope(x, positions, theta):
    """x [T, ..., rope]; the pairs (2i, 2i+1) rotated by
    pos x theta^(-2i/rope)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      odd * jnp.cos(ang) + even * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def attention(params, p, x, cfg, window: int, block: int = 256, via=None,
              latent_via=None, noise: bool = True):
    """-> (the attention sublayer's output of layer prefix `p` for x
    [T, hidden], the latent rows [T, rank + rope] a cache would hold).
    `window` 0: every earlier key. `latent_via` rounds the latent rows as
    a page would hold them; `noise` False leaves the noise head's
    subtraction out (a planted fault)."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    n, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    g = n // nkv                            # heads a group: signal + noise
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(t, dtype=jnp.int32)
    c_q = rms_norm(x @ _f32(params[p + "q_a_w"], via),
                   params[p + "q_a_norm"], eps)
    q = (c_q @ _f32(params[p + "q_b_w"], via)).reshape(t, nkv, g, nope + rd)
    q_n = q[..., :nope]
    q_r = rope(q[..., nope:], pos, cfg["rope_theta"])
    kva = x @ _f32(params[p + "kv_a_w"], via)
    c = rms_norm(kva[:, :rank], params[p + "kv_a_norm"], eps)
    k_r = rope(kva[:, rank:], pos, cfg["rope_theta"])
    c, k_r = _f32(c, latent_via), _f32(k_r, latent_via)
    kv = (c @ _f32(params[p + "kv_b_w"], via)).reshape(t, nkv, -1)
    k_n, v = kv[..., :nope], kv[..., nope:]
    # a block's scores are heads x block x keys floats: 1.3 GB at 80 x 256
    # x 16,384, so a long sequence goes in smaller blocks
    while block > 64 and block * t > 1 << 21:
        block //= 2
    bq = block if t % block == 0 else t
    # keys a block of queries reaches: all before its end, or its band
    kw = t if not window else min(t, bq + -(-window // bq) * bq)
    scale = (nope + rd) ** -0.5

    def one_block(q0):
        k0 = jnp.clip(q0 + bq - kw, 0, t - kw)
        qn = jax.lax.dynamic_slice_in_dim(q_n, q0, bq, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, q0, bq, axis=0)
        kn = jax.lax.dynamic_slice_in_dim(k_n, k0, kw, axis=0)
        kr = jax.lax.dynamic_slice_in_dim(k_r, k0, kw, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, kw, axis=0)
        sc = (jnp.einsum("qkgd,skd->kgqs", qn, kn)
              + jnp.einsum("qkgr,sr->kgqs", qr, kr)) * scale
        tq = q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        ts = k0 + jnp.arange(kw, dtype=jnp.int32)[None, :]
        ok = ts <= tq
        if window:
            ok &= tq - ts < window
        prob = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skv->qkgv", prob, vb)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    o = o.reshape(t, nkv, g, -1)
    lam = jax.nn.sigmoid(x @ _f32(params[p + "lam_w"], via)).reshape(
        t, nkv, g - 1)
    d = o[:, :, :g - 1]
    if noise:
        d = d - lam[..., None] * o[:, :, g - 1:]
    gate = jax.nn.sigmoid(x @ _f32(params[p + "g_w"], via))
    return (d.reshape(t, -1) * gate) @ _f32(params[p + "o_w"], via), \
        jnp.concatenate([c, k_r], axis=1)


def window_of(cfg, layer: int) -> int:
    """The window of held layer `layer` (0: a full layer), by its
    PUBLISHED index: every `sliding_window_period`-th layer is full."""
    full = (cfg["layer_ids"][layer] + 1) % cfg["sliding_window_period"] == 0
    return 0 if full else cfg["sliding_window"]


def is_moe(cfg, layer: int) -> bool:
    return cfg["layer_ids"][layer] >= cfg["n_dense_first_layers"]


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 256, via=None, only: str = "weights",
            sinkhorn_iters=None, noise: bool = True):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; route_gap [T]; latents [layers, T,
    rank + rope], every layer's cached rows). Causal, so a padded tail is
    harmless. `via` is the lower-precision control: by `only` it rounds
    every weight matrix (`weights`; gains, the maps' scalars and PolyNorm's
    stay) or the latent rows alone (`latent`).
    `sinkhorn_iters` and `noise` plant a fault (another iteration count;
    no subtraction of the noise head)."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    latent_via = via if only == "latent" else None
    via = via if only == "weights" else None
    n, eps = cfg["n_streams"], cfg["rms_norm_eps"]
    clamp = cfg["hidden_clamp"]
    with jax.default_matmul_precision("highest"):
        emb = _f32(params["m3_tok_emb"][tokens], via)
        xs = jnp.repeat(emb[:, None, :], n, axis=1)             # [T, n, C]
        gap = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        latents = []

        def around(p, norm, fn):
            h_pre, h_post, h_res = mhc_maps(params, p, xs, cfg, via,
                                            sinkhorn_iters)
            u = jnp.einsum("ti,tic->tc", h_pre, xs)
            y = fn(rms_norm(u, params[norm], eps))
            return jnp.clip(jnp.einsum("tij,tjc->tic", h_res, xs)
                            + h_post[:, :, None] * y[:, None, :],
                            -clamp, clamp)

        for i in range(cfg["n_layers"]):
            p = f"m3_l{i}_"

            def attn(x):
                a, lat = attention(params, p, x, cfg, window_of(cfg, i),
                                   block, via, latent_via, noise)
                latents.append(lat)
                return a

            def mlp(x):
                nonlocal gap
                if not is_moe(cfg, i):
                    return polyglu(x, params[p + "w1"], params[p + "w3"],
                                   params[p + "w2"], params[p + "pn"], cfg,
                                   via)
                weights, g = route(params, p, x, cfg, via)
                gap = jnp.minimum(gap, g)
                return polyglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                               params[p + "sh_w2"], params[p + "sh_pn"],
                               cfg, via) \
                    + routed(params, p, x, weights, cfg, via)

            xs = around(p + "mhc_a_", p + "norm_in", attn)
            xs = around(p + "mhc_m_", p + "norm_mlp", mlp)
        h = xs.sum(axis=1)
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = rms_norm(h, params["m3_norm_f"], eps) \
            @ _f32(params["m3_head_w"], via)
        return logits, gap, jnp.stack(latents)


def latent_error(got, want) -> float:
    """Rows [T, w] the cache held against the reference's: the median
    over rows of |got - want| / |want|."""
    import numpy as np

    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)[:, :want.shape[1]]    # lane padding
    return float(np.median(np.linalg.norm(got - want, axis=1)
                           / (np.linalg.norm(want, axis=1) + 1e-30)))


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n)` ->
    (logits [n, vocab], route_gap [len(seq)], latents [layers, len(seq),
    rank + rope]). One compile a `pad_to`. `via` (a dtype) and `only` make
    it a lower-precision control, `sinkhorn_iters` and `noise` a planted
    fault (`forward`)."""

    def __init__(self, params, cfg: dict, via=None, only: str = "weights",
                 sinkhorn_iters=None, noise: bool = True):
        import jax

        self.params, self.cfg = params, dict(cfg)

        def fn(params, tokens, first, rows):
            return forward(params, tokens, self.cfg, first, rows, via=via,
                           only=only, sinkhorn_iters=sinkhorn_iters,
                           noise=noise)

        self._fn = jax.jit(fn, static_argnums=(3,))

    def rows(self, seq, pad_to: int, first: int, n: int):
        import jax.numpy as jnp
        import numpy as np

        seq = np.asarray(seq, np.int32).reshape(-1)
        logits, gap, latents = self._fn(
            self.params, jnp.asarray(padded(seq, pad_to)), first, n)
        return np.asarray(logits), np.asarray(gap)[:seq.size], \
            np.asarray(latents)[:, :seq.size]
