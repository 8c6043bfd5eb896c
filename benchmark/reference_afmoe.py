"""Plain reference of the AFMoE block (arcee-ai Trinity family,
`model_type: afmoe`), independent of the code under test: straightforward
`jax.numpy` in float32 under `jax.default_matmul_precision("highest")`, no
cache, no pages, no kernels, no batching, no grouped products. It imports
nothing of the program; it reads the same parameter dict by the same names
(models/afmoe.py `param_specs`) and upcasts whatever dtype it finds.

The block, from the published `config.json` (catalog row
Trinity-Large-Preview) and, where the config is silent, from the family's
model code as the configuration file lists under `assumed`:

  h0 = E[ids] * sqrt(hidden)                                   (muP)
  a  = Attn(RMS_in(h));   h = h + RMS_post_attn(a)
  m  = MLP(RMS_pre_mlp(h)); h = h + RMS_post_mlp(m)
  logits = RMS_f(h) @ W_head                                   (untied)

  Attn(x): q = x Wq, k = x Wk, v = x Wv, g = x Wg; q and k RMS-normed per
    head over head_dim with a learned gain; on SLIDING layers only, rotary
    positions (theta, the whole head, half-split pairs) on q and k; causal
    softmax(q k^T / sqrt(head_dim)) v with query head j on K/V head
    j // group, on sliding layers over keys t - window < s <= t only;
    a = (o * sigmoid(g)) Wo.
  Dense MLP (leading layers): (silu(x W1) * (x W3)) W2.
  MoE: s = sigmoid(x Wr) over all experts; the top-k of s + b (b: the
    selection bias); w_i = s_i / (sum of the kept s + 1e-20) * route_scale;
    m = Shared(x) + sum_i w_i Expert_i(x), every expert a SwiGLU. Dropless.

The share. `cfg` says which heads, experts and vocabulary rows the
parameters hold (`num_heads`, `num_kv_heads`, `experts_held`, the rows of
`af_tok_emb`). The router always scores all `num_experts`; experts that
are not held add nothing, and that partial result goes on to the next
layer, here as in the program. With every head and every expert held this
is the uncut model (tests/test_afmoe_share.py adds the shares up to it).

Attention runs in blocks of queries (`block`) so that a prompt of several
thousand tokens fits beside a serving engine on one chip.

How a run's numbers are held against this reference is in `families/afmoe.py`;
the limits and the readings behind them are below.

Routing decides discretely. The engine multiplies bfloat16 activations, so
the hidden state its router sees differs from the reference's in the third
digit, and where the reference's k-th and (k+1)-th selection scores lie
closer than that the engine may keep the other expert: a change of a whole
expert's output, not of a rounding. Like an argmax over random logits (the
reason greedy tokens are held by a margin, not by equality), a top-k that
the reference itself decides by less than ROUTE_EPS is not a place to
compare: `forward` returns for each row the smallest such gap over its MoE
layers, counting only ties in which one of the two experts is held (a tie
between two absent experts changes nothing here), and the check compares
rows whose routing is decided. A prompt is cut to its longest decided
prefix BEFORE it is sent, so every prompt is compared.

Limits, each between its two readings on the chip (v5e, the configuration
trinity_large_tp8ep8 at its real widths, its seeded post-norm gains
depth-scaled, PR 28; PERF.md has the table). The one reading is the engine
against this reference: five prompt lengths (294 to 6,100 tokens) in each
of 45 runs, the last 15 with 59 sampled requests decoding beside the
check prompts. The other is the control (`benchmark/readings_afmoe.py`,
seed 3000000611): the same engine outputs judged, by the same
`families/afmoe.judge`, against this reference with a part of it rounded
to 8 bits (float8 e4m3, the nearest precision below bfloat16): every
weight matrix, the routed experts' matrices alone, the keys and values
alone. Each control has to come out as not correct, by one of the limits;
one prompt over a limit makes a run not correct, so a run's reading is
its worst prompt's.
  LOGIT_ERR 0.012  largest |engine - reference| over a prefill's logits
              row, as a share of that row's root mean square. Engine:
              225 readings, mean 0.0043; a run's worst 0.0041-0.0063 in
              44 runs and 0.0083 in one (a 299-token prompt, seed
              3000000634: routing that turns on rounding INSIDE a prompt
              moves a short prompt's last row most; the prompt's end is
              cut to a decided position, its inside cannot be). Controls,
              five prompts each: the routed experts alone 0.0114-0.0192,
              the keys and values alone 0.0114-0.0167, every weight
              matrix 0.152-0.190. The limit is 1.45 times over the
              engine's worst run, 1.4 and 1.6 times under the two
              partial controls' runs (4 of their 5 prompts each lie over
              it) and 13 times under the third. (0.03 until the review
              round, set from the every-weight control alone: 8-bit
              experts or K/V would have passed.)
  MARGIN 0.04   a greedy token's reference logit may lie this far under
              the reference's maximum (the rule of reference.py; logits
              are unit scale, the largest about 4.1), at positions whose
              routing is decided. Engine: 0 at most positions, worst
              0.0042; the every-weight control 0.070 (0.114 and 0.115 in
              two earlier seeds); the two partial controls 0.003-0.005:
              they fail LOGIT_ERR, not this.
  ROUTE_EPS 0.012  in units of the selection score (a sigmoid's output,
              kept ones about 0.9). At 0.004, 7 to 16 of 40 decoded
              positions a prompt fell under it, and of the ~2,000 decided
              positions of 14 runs (post-norm gains of 1 then) ONE still
              routed otherwise in the engine: 0.251 under the maximum
              between positions at 0.0. So the engine's selection scores
              differ from these by up to ~0.004; 0.012 is three times
              that. A prompt is cut by a few tokens to end on a decided
              position.
  UNDECIDED_MARGIN 0.4, UNDECIDED_SHARE 0.7  the positions ROUTE_EPS takes
              from MARGIN are not left out: their worst gap is held to
              0.4 (engine: 0.135 once and under 0.04 otherwise, over
              ~4,500 such positions of 45 runs; a token drawn from
              another row's logits lies 3 to 5 under the maximum of
              unit-scale logits), and they may be at most 0.7 of the
              decoded positions (88-121 of 200 in 45 runs), so at least
              60 are held to MARGIN.
"""

from __future__ import annotations

LOGIT_ERR = 0.012
MARGIN = 0.04
ROUTE_EPS = 0.012
UNDECIDED_MARGIN = 0.4
UNDECIDED_SHARE = 0.7


def _f32(a, via=None):
    """`a` in float32; with `via`, rounded to that dtype on the way (the
    lower-precision control: "float8_e4m3fn" makes 8-bit weights of it)."""
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if via is not None:
        a = a.astype(via)
    return a.astype(jnp.float32)


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(gain)


def rope(x, positions, theta):
    """x [T, heads, hd]; pairs (i, i + hd/2) rotated by pos * theta^(-2i/hd)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def swiglu(x, w1, w3, w2, via=None):
    import jax

    return (jax.nn.silu(x @ _f32(w1, via)) * (x @ _f32(w3, via))) \
        @ _f32(w2, via)


def attention(params, p, x, cfg, sliding: bool, block: int = 512,
              via=None, kv_via=None):
    """The attention sublayer's output (before its post-norm) of layer
    prefix `p` for x [T, hidden], over the heads the parameters hold.
    `kv_via` rounds the keys and values as a page would hold them (after
    the norm and the rotation) to that dtype: the K/V control."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    hd, nq, nkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    group = nq // nkv
    pos = jnp.arange(t, dtype=jnp.int32)
    q = rms_norm((x @ _f32(params[p + "q_w"], via)).reshape(t, nq, hd),
                 params[p + "q_norm"], cfg["rms_norm_eps"])
    k = rms_norm((x @ _f32(params[p + "k_w"], via)).reshape(t, nkv, hd),
                 params[p + "k_norm"], cfg["rms_norm_eps"])
    v = (x @ _f32(params[p + "v_w"], via)).reshape(t, nkv, hd)
    gate = x @ _f32(params[p + "g_w"], via)
    if sliding:
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
    k, v = _f32(k, kv_via), _f32(v, kv_via)
    q = q.reshape(t, nkv, group, hd)
    bq = block if t % block == 0 else t

    def one_block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, bq, axis=0)
        sc = jnp.einsum("qkgh,skh->kgqs", qb, k) * hd ** -0.5
        tq = q0 + jnp.arange(bq, dtype=jnp.int32)[:, None]
        ok = pos[None, :] <= tq
        if sliding:
            ok &= pos[None, :] > tq - cfg["sliding_window"]
        prob = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", prob, v)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    o = o.reshape(t, nq * hd)
    return (o * jax.nn.sigmoid(gate)) @ _f32(params[p + "o_w"], via)


def route(params, p, x, cfg, via=None):
    """-> (weights [T, num_experts], zero off the kept experts; gap [T]:
    how far the k-th selection score lies above the (k+1)-th, infinite
    where neither of the two experts is held)."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    lo, count = cfg["experts_held"]
    s = jax.nn.sigmoid(x @ _f32(params[p + "router_w"], via))
    sel = s + _f32(params[p + "select_bias"])
    top, idx = jax.lax.top_k(sel, k + 1)
    kept = jnp.take_along_axis(s, idx[:, :k], axis=1)
    w = kept
    if cfg["route_norm"]:
        w = kept / (jnp.sum(kept, axis=1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    weights = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(w)
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


CONTROLS = ("weights", "experts", "kv")


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 512, via=None, only: str = "weights"):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; route_gap [T], the smallest routing
    gap of each position over the MoE layers). Causal: a position's values
    do not depend on what follows it, so a padded tail is harmless. `via`
    is the lower-precision control: it rounds to that dtype, by `only`,
    every weight matrix (`weights`; norm gains and the selection bias stay
    as they are), the routed experts' matrices alone (`experts`), or the
    keys and values alone, as pages hold them (`kv`)."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    ex_via = via if only in ("weights", "experts") else None
    kv_via = via if only == "kv" else None
    via = via if only == "weights" else None
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _f32(params["af_tok_emb"][tokens], via) \
            * cfg["hidden_size"] ** 0.5
        gap = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"af_l{i}_"
            a = attention(params, p, rms_norm(h, params[p + "norm_in"], eps),
                          cfg, kind == "sliding_attention", block, via,
                          kv_via)
            h = h + rms_norm(a, params[p + "norm_post_attn"], eps)
            x = rms_norm(h, params[p + "norm_pre_mlp"], eps)
            if i < cfg["num_dense_layers"]:
                m = swiglu(x, params[p + "w1"], params[p + "w3"],
                           params[p + "w2"], via)
            else:
                weights, g = route(params, p, x, cfg, via)
                gap = jnp.minimum(gap, g)
                m = swiglu(x, params[p + "sh_w1"], params[p + "sh_w3"],
                           params[p + "sh_w2"], via) \
                    + routed(params, p, x, weights, cfg, ex_via)
            h = h + rms_norm(m, params[p + "norm_post_mlp"], eps)
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = rms_norm(h, params["af_norm_f"], eps) \
            @ _f32(params["af_head_w"], via)
        return logits, gap


def padded(seq, pad_to: int):
    import numpy as np

    seq = np.asarray(seq, np.int32).reshape(-1)
    if seq.size > pad_to:
        raise ValueError(f"sequence of {seq.size} tokens over pad_to {pad_to}")
    out = np.zeros(pad_to, np.int32)
    out[:seq.size] = seq
    return out


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


def decided_prefix(gap, eps: float = ROUTE_EPS) -> int:
    """The longest prefix length L' such that position L' - 1 routes by
    more than `eps` in every MoE layer (0 when none does)."""
    import numpy as np

    ok = np.nonzero(np.asarray(gap) > eps)[0]
    return int(ok[-1]) + 1 if ok.size else 0


def logit_error(engine_row, reference_row) -> float:
    """Largest absolute difference as a share of the reference row's RMS."""
    import numpy as np

    ref = np.asarray(reference_row, np.float64)
    return float(np.max(np.abs(np.asarray(engine_row, np.float64) - ref))
                 / np.sqrt(np.mean(np.square(ref))))


def greedy_gaps(logit_rows, chosen):
    """At each generated position, how far the reference's logit of the
    chosen token lies under the reference's maximum (0 = same argmax)."""
    import numpy as np

    rows = np.asarray(logit_rows)
    chosen = np.asarray(chosen, np.int64).reshape(-1)
    return rows.max(axis=1) - rows[np.arange(chosen.size), chosen]
