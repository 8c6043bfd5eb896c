"""Plain reference of the Qwen3-Next block (Qwen, `model_type: qwen3_next`),
independent of the code under test: straightforward `jax.numpy` in float32
under `jax.default_matmul_precision("highest")`, no cache, no pages, no
slots, no kernels, no batching, no grouped products, and the gated delta
rule as a SEQUENTIAL `lax.scan` over tokens (the program's prefill is the
chunked form, its decode one step a call: neither is what runs here). It
imports nothing of the program; it reads the same parameter dict by the same
names (models/qwen3_next.py `param_specs`) and upcasts whatever dtype it
finds. It follows the public `qwen3_next` model code; each departure is
noted below.

The block, from the published `config.json` (catalog row
Qwen3-Next-80B-A3B-Instruct) and, where the config is silent, as the
configuration file lists under `assumed`:

  norm(x; w) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)     (gain around zero)
  h0 = E[ids];  h = h + Mixer_l(norm(h));  h = h + MoE(norm(h))
  logits = norm(h) @ W_head                                    (untied)
  layer l is full attention where (l + 1) % 4 == 0, else Gated DeltaNet

  Attn(x): q_proj gives each query head 2 x head_dim values: its q, then a
    gate g; k, v from the K/V heads; q and k normed per head by `norm` over
    head_dim; rotary positions (theta, rotate-half) on the FIRST rotary_dim
    (head_dim x partial_rotary_factor) dimensions of q and k only; causal
    softmax(q k^T / sqrt(head_dim)) v, query head j on K/V head
    j // (heads / kv_heads);  a = (o * sigmoid(g)) W_o.  No bias.
  DeltaNet(x): in_proj_qkvz and in_proj_ba laid out BY KEY HEAD: each key
    head holds its q (dk), k (dk), its r value heads' v (r x dv) and z
    (r x dv); b (r) and a (r).  q | k | v (all heads' q, then k, then v)
    through a causal depthwise convolution of `linear_conv_kernel_dim`
    taps, no bias, then SiLU.  beta = sigmoid(b);
    g = -exp(A_log) * softplus(a + dt_bias) per value head.  q and k
    l2-normed over dk (x * rsqrt(sum x^2 + 1e-6)) and repeated to the value
    heads (value heads r i .. r i + r - 1 read key head i);  q *= dk^-0.5.
    Per value head, S [dk, dv] float32 from 0, token by token:
      S = exp(g_t) S;  u = k_t^T S;  S = S + k_t (beta_t (v_t - u))^T
      o_t = q_t^T S
    y = w * (o / sqrt(mean(o^2) + 1e-6)) * silu(z) per value head over dv
    (a plain gain, not 1 + w);  out = y W_out.
  MoE(x): p = softmax(x W_r) over all experts, float32; the top k; weights
    p_i / sum of the kept (`norm_topk_prob`); experts (silu(x W1) * (x W3))
    W2;  plus sigmoid(x w_sg) * SwiGLU_shared(x)  (w_sg one column).

The share. `cfg` says which heads, experts and vocabulary rows the
parameters hold (`num_heads`, `num_kv_heads`, `linear_key_heads`,
`linear_value_heads`, `experts_held`, the rows of `qn_tok_emb`). The router
always scores all `num_experts`; heads and experts that are not held add
nothing, and that partial result goes on to the next layer, here as in the
program; router, shared expert and norms are whole. With every head and
every expert held this is the uncut model (tests/test_qwen3_next_share.py
adds the shares up to it).

Departures from the public model code, each on purpose: no multi-token-
prediction module (the published config has no key for it and the public
code drops those weights); the rule runs token by token over the WHOLE
sequence (the public code's chunked form is the same mathematics); the
convolution sees float32 inputs throughout (the program's conv tail holds a
slot's last inputs in bfloat16), K and V stay float32 (the program's pages
are bfloat16); attention runs in blocks of queries and the routed layer
expert by expert over all tokens, so that a sequence of 17 thousand tokens
fits beside a serving engine on one chip.

Routing decides discretely: the engine multiplies bfloat16 activations, so
where the reference's k-th and (k+1)-th scores lie closer than the engine's
error the engine may keep the other expert, a change of a whole expert's
output and not of a rounding. With ten of 512 kept in each of eight layers
and a quarter of them held, the two scores lie within the engine's error of
each other in some layer at about every second position (the k-th and the
(k+1)-th of 512 unit-normal logits lie 0.04 apart on average): there is no
"decided" position to cut a prompt to, as cells 3 and 4 do with four or
eight kept of fewer held. What a turned choice moves is the smallest of ten
renormed weights (0.06-0.08) times one expert's output, about as much as
bfloat16's rounding does, and it is part of every reading below: the limits
are set over it and the controls still come out over them.

How a run's numbers are held against this reference is in
`families/qwen3_next.py`.

Limits. Each lies between two readings on the chip (v5e, the configuration
qwen3_next_80b_tp4ep4 at its published widths, my chip runs, PR 45; PERF.md
section 4 has the table): the engine against this reference, and a control
(`benchmark/readings_qwen3_next.py`): the SAME engine outputs judged, by the
same `families/qwen3_next.judge`, against this reference with a part of it
in the nearest precision below the configuration's: every weight matrix
through float8 e4m3 (`weights`), K and V through float8 as pages would hold
them (`kv`), the matrix state rounded to bfloat16 after every token
(`state`: the nearest below its float32). Each control has to come out as
not correct, by one of the limits.
"""

from __future__ import annotations

# The readings behind each limit (my chip runs, PR 45; a run's reading is
# its worst prompt's; PERF.md section 4 has the table)
LOGIT_ERR = 0.6       # largest |engine - reference| of a prefill's logits
#                       row, as a share of that row's root mean square
MARGIN = 0.65         # a greedy token's reference logit may lie this far
#                       under the reference's maximum (logits: unit scale)
STATE_ERR = 0.15      # ||engine - reference|| / ||reference|| of a layer's
#                       matrix state after a check request's decode, the
#                       worst layer's (the last: it reads seven layers'
#                       rounding and routing)
STATE_ERR_FIRST = 0.006   # the same of the FIRST layer's, whose mixer reads
#                           the embedding itself: the state's own arithmetic
KV_ERR = 0.018        # ||page - reference|| / ||reference|| of a cached
#                       position's K and V in the FIRST attention layer,
#                       the median over the request's positions
CONTROLS = ("weights", "kv", "state")
L2_EPS = 1e-6


def _f32(a, via=None):
    """`a` in float32; with `via`, rounded to that dtype on the way (the
    lower-precision control). The barrier keeps the rounding: the chip's
    compiler allows itself excess precision and drops a narrowing
    conversion that is widened again at once (PERF.md, PR 33)."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if via is not None:
        a = jax.lax.optimization_barrier(a.astype(via))
    return a.astype(jnp.float32)


def norm(x, w, eps):
    """RMS norm with the gain stored around zero."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * (1.0 + _f32(w))


def rope(x, positions, theta, rotary_dim):
    """x [T, heads, hd]: the first `rotary_dim` dimensions of each head
    rotated, pairs (i, i + rotary_dim / 2) by pos * theta^(-2i/rotary_dim);
    the rest untouched."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rotary_dim:]], axis=-1)


def swiglu(x, w1, w3, w2, via=None):
    import jax

    return (jax.nn.silu(x @ _f32(w1, via)) * (x @ _f32(w3, via))) \
        @ _f32(w2, via)


def attention(params, p, x, cfg, block: int = 256, via=None, kv_via=None):
    """-> (the gated attention sublayer's output of layer prefix `p` for x
    [T, hidden], over the heads the parameters hold, in blocks of `block`
    queries; K and V as a page would hold them, [T, 2, kv heads x hd]:
    normed and rotated keys, then values). `kv_via` rounds K and V to that
    dtype on their way into both."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    hd, nq, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    rd, eps = int(hd * cfg["partial_rotary_factor"]), cfg["rms_norm_eps"]
    pos = jnp.arange(t, dtype=jnp.int32)
    qg = (x @ _f32(params[p + "q_w"], via)).reshape(t, nq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(t, nq * hd)
    q = rope(norm(q, params[p + "q_norm"], eps), pos, cfg["rope_theta"], rd)
    k = (x @ _f32(params[p + "k_w"], via)).reshape(t, nkv, hd)
    k = rope(norm(k, params[p + "k_norm"], eps), pos, cfg["rope_theta"], rd)
    v = (x @ _f32(params[p + "v_w"], via)).reshape(t, nkv, hd)
    k, v = _f32(k, kv_via), _f32(v, kv_via)
    qh = q.reshape(t, nkv, nq // nkv, hd)
    bq = block if t % block == 0 else t

    def one_block(q0):
        qb = jax.lax.dynamic_slice_in_dim(qh, q0, bq)
        s = jnp.einsum("qkgh,skh->kgqs", qb, k) * hd ** -0.5
        ok = pos[None, :] <= (q0 + jnp.arange(bq))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", pr, v)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    o = o.reshape(t, nq * hd) * jax.nn.sigmoid(gate)
    return o @ _f32(params[p + "o_w"], via), \
        jnp.stack([k.reshape(t, nkv * hd), v.reshape(t, nkv * hd)], axis=1)


def l2norm(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def delta_net(params, p, x, cfg, via=None, state_via=None, state_at=None):
    """-> (out [T, hidden], the matrix state after position `state_at` as
    [value heads, dk, dv], or None)."""
    import jax
    import jax.numpy as jnp

    nk, nv = cfg["linear_key_heads"], cfg["linear_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kw, r = cfg["linear_conv_kernel_dim"], nv // nk
    t = x.shape[0]
    # by key head: q, k, the head's r value heads' v and z; b and a
    qkvz = (x @ _f32(params[p + "qkvz_w"], via)).reshape(
        t, nk, 2 * dk + 2 * r * dv)
    ba = (x @ _f32(params[p + "ba_w"], via)).reshape(t, nk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, nv, dv)
    b, a = ba[..., :r].reshape(t, nv), ba[..., r:].reshape(t, nv)
    mixed = jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),
                             v.reshape(t, -1)], axis=-1)
    w = _f32(params[p + "conv_w"])                          # [taps, C]
    xp = jnp.pad(mixed, ((kw - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(w[j] * xp[j:j + t] for j in range(kw)))
    q = mixed[:, :nk * dk].reshape(t, nk, dk)
    k = mixed[:, nk * dk:2 * nk * dk].reshape(t, nk, dk)
    v = mixed[:, 2 * nk * dk:].reshape(t, nv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(params[p + "a_log"])) \
        * jax.nn.softplus(a + _f32(params[p + "dt_bias"]))
    q = jnp.repeat(l2norm(q), r, axis=1) * dk ** -0.5
    k = jnp.repeat(l2norm(k), r, axis=1)
    want = -1 if state_at is None else state_at

    def token(carry, inp):
        state, kept = carry
        i, q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        u = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - u)
                                           )[:, None, :]
        state = _f32(state, state_via)
        o_t = jnp.einsum("hk,hkv->hv", q_t, state)
        return (state, jnp.where(i == want, state, kept)), o_t

    zero = jnp.zeros((nv, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(
        token, (zero, zero),
        (jnp.arange(t, dtype=jnp.int32), q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"])
    y = _f32(params[p + "gn_w"]) * o * jax.nn.silu(z)
    return y.reshape(t, nv * dv) @ _f32(params[p + "out_w"], via), \
        (None if state_at is None else kept)


def route(params, p, x, cfg, via=None):
    """-> weights [T, num_experts], zero off the kept experts."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    s = jax.nn.softmax(x @ _f32(params[p + "router_w"], via), axis=-1)
    kept, idx = jax.lax.top_k(s, k)
    w = kept / jnp.sum(kept, axis=1, keepdims=True) \
        if cfg["norm_topk_prob"] else kept
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(w)


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


def shared(params, p, x, via=None):
    """The shared expert, scaled by a sigmoid of a one-column projection:
    whole on every chip of a deployment."""
    import jax

    return jax.nn.sigmoid(x @ _f32(params[p + "sh_gate_w"], via)) \
        * swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                 params[p + "sh_w2"], via)


def is_attention(cfg, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 256, via=None, only: str = "weights",
            state_at=None):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; with `state_at`, every DeltaNet
    layer's matrix state after that position [state layers, value heads,
    dk, dv] and every attention layer's K and V [attention layers, T, 2,
    kv heads x hd], else None twice). Causal, so a padded tail is harmless. `via` is the
    lower-precision control: it rounds to that dtype, by `only`, every
    weight matrix (`weights`; gains, the convolution and the per-head
    scalars stay as they are), K and V as pages hold them (`kv`), or the
    matrix state after every token (`state`)."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    kv_via = via if only == "kv" else None
    state_via = via if only == "state" else None
    via = via if only == "weights" else None
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _f32(params["qn_tok_emb"][tokens], via)
        states, pages = [], []
        for i in range(cfg["n_layers"]):
            p = f"qn_l{i}_"
            x = norm(h, params[p + "norm_in"], eps)
            if is_attention(cfg, i):
                a, kv = attention(params, p, x, cfg, block, via, kv_via)
                pages.append(kv)
                h = h + a
            else:
                m, kept = delta_net(params, p, x, cfg, via, state_via,
                                    state_at)
                states.append(kept)
                h = h + m
            x = norm(h, params[p + "norm_post"], eps)
            h = h + shared(params, p, x, via) + routed(
                params, p, x, route(params, p, x, cfg, via), cfg, via)
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = norm(h, params["qn_norm_f"], eps) \
            @ _f32(params["qn_head_w"], via)
        if state_at is None:
            return logits, None, None
        return logits, jnp.stack(states), jnp.stack(pages)


def padded(seq, pad_to: int):
    import numpy as np

    seq = np.asarray(seq, np.int32).reshape(-1)
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to "
                         f"{pad_to}")
    out = np.zeros(pad_to, np.int32)
    out[:seq.size] = seq
    return out


def logit_error(got, want) -> float:
    """Largest |got - want| as a share of want's root mean square."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(np.sqrt(np.mean(np.square(want))), 1e-12))


def greedy_gaps(rows, chosen):
    """How far each chosen token's reference logit lies under the
    reference's maximum at its position (0: the reference's own choice)."""
    import numpy as np

    rows = np.asarray(rows, np.float64)
    idx = np.arange(len(chosen))
    return rows.max(axis=-1)[idx] - rows[idx, np.asarray(chosen)]


def kv_error(got, want) -> float:
    """The median over positions of ||got - want|| / ||want|| over a
    position's K and V, [T, 2, width] each: what bfloat16's rounding and
    the layers below leave at most positions, whatever a routing choice
    that turned did to a few."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.sqrt(np.sum(np.square(got - want), axis=(1, 2)))
    return float(np.median(err / np.maximum(
        np.sqrt(np.sum(np.square(want), axis=(1, 2))), 1e-30)))


def state_errors(got, want):
    """||got - want|| / ||want|| of each layer over [layers, ...]."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    return (np.sqrt(np.sum(np.square(got - want), axis=axes))
            / np.maximum(np.sqrt(np.sum(np.square(want), axis=axes)),
                         1e-30)).tolist()


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n,
    state_at)` -> (logits [n, vocab], states or None, K/V or None). One compile a `pad_to` (and one more with states). `via` (a
    dtype) and `only` make it a lower-precision control (`forward`)."""

    def __init__(self, params, cfg: dict, via=None, only: str = "weights"):
        import jax

        self.params, self.cfg = params, dict(cfg)

        def fn(params, tokens, first, state_at, rows, with_state):
            return forward(params, tokens, self.cfg, first, rows, via=via,
                           only=only,
                           state_at=state_at if with_state else None)

        self._fn = jax.jit(fn, static_argnums=(4, 5))

    def rows(self, seq, pad_to: int, first: int, n: int, state_at=None):
        import jax.numpy as jnp
        import numpy as np

        seq = np.asarray(seq, np.int32).reshape(-1)
        logits, states, pages = self._fn(
            self.params, jnp.asarray(padded(seq, pad_to)), first,
            0 if state_at is None else int(state_at), n,
            state_at is not None)
        if states is None:
            return np.asarray(logits), None, None
        return np.asarray(logits), np.asarray(states), \
            np.asarray(pages)[:, :seq.size]
