"""Plain reference of the Falcon-H1 block (tiiuae, `model_type: falcon_h1`),
independent of the code under test: straightforward `jax.numpy` in float32
under `jax.default_matmul_precision("highest")`, no cache, no pages, no
slots, no kernels, no batching, and the recurrence as a SEQUENTIAL
`lax.scan` over tokens (the program's prefill is the chunked form, its
decode one step a call: neither is what runs here). It imports nothing of
the program; it reads the same parameter dict by the same names
(models/falcon_h1.py `param_specs`) and upcasts whatever dtype it finds,
all but the muP column scales: those it lays out itself from
`ssm_multipliers` and the configuration's widths (`mixer`), so where the
program puts each multiplier is held against this file and not against
the program's own table.

The block, from the published `config.json` (catalog row
Falcon-H1-34B-Instruct) and, where the config is silent, as the
configuration file lists under `assumed`:

  h0 = E[ids] * embedding_multiplier
  x = RMS_in(h);  h = h + Mixer(x) * ssm_out_multiplier
                        + Attn(x * attention_in_multiplier)
                          * attention_out_multiplier
  h = h + MLP(RMS_ff(h));   logits = (RMS_f(h) @ W_head) * lm_head_multiplier

  MLP(x)  = ((x W_up) * silu((x W_gate) * mlp_multipliers[0])) W_down
            * mlp_multipliers[1]
  Attn(x): q = x W_q, k = (x W_k) * key_multiplier, v = x W_v; rotary
    positions over the whole head (rotate-half, theta) on q and k; causal
    softmax(q k^T / sqrt(head_dim)) v, query head j on K/V head
    j // (heads / kv_heads); W_o.
  Mixer(x): u = ((x * ssm_in_multiplier) W_in) * mup_vector, which holds
    ssm_multipliers[0..4] over the columns of z (d_ssm), x (d_ssm),
    B (groups x d_state), C (the same), dt (heads);
    z | xBC | dt = u;  xBC = silu(causal depthwise conv1d(xBC) + bias);
    x | B | C = xBC (x: heads x head_dim; B, C: groups x d_state, head h
    reads group h // (heads / groups));
    dt = softplus(dt + dt_bias), A = -exp(A_log), a head, token by token:
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
    y = RMS_grouped(y * silu(z)) * gain (each group normed alone);  W_out.

Departures from the program, each on purpose: the recurrence runs token by
token from a zero state over the WHOLE sequence (prompt and decoded tokens
alike), the convolution sees float32 inputs throughout (the program's conv
tail holds a slot's last inputs in bfloat16), K and V stay float32 (the
program's pages are bfloat16), attention runs in blocks of queries.

How a run's numbers are held against this reference is in
`families/falcon_h1.py`. There is no routing, so every position is held to
one margin.

Limits. Each lies between two readings on the chip (v5e, the configuration
falcon_h1_34b_pp12 at its published widths, my chip runs, PR 35; PERF.md
section 4 has the table): the engine against this reference, and a control
(`benchmark/readings_falcon_h1.py`): the SAME engine outputs judged, by the
same `families/falcon_h1.judge`, against this reference with a part of it
in the nearest precision below the configuration's: every weight matrix
through float8 e4m3 (`weights`), K and V through float8 as pages would hold
them (`kv`), the recurrent state rounded to bfloat16 after every token
(`state`: the nearest below its float32). Each control has to come out as
not correct, by one of the limits.
"""

from __future__ import annotations

# The readings behind each limit (my chip runs, PR 35; a run's reading is
# its worst prompt's; PERF.md section 4 has the table):
LOGIT_ERR = 0.2       # largest |engine - reference| of a prefill's logits
#                       row, as a share of that row's root mean square.
#                       Engine 0.067-0.092 a run's worst; controls: kv
#                       0.39-0.53, weights 0.99-1.48 (state 0.07-0.15)
MARGIN = 0.13         # a greedy token's reference logit may lie this far
#                       under the reference's maximum (logits: unit scale).
#                       Engine 0.008-0.054; kv 0.29-0.35, weights 0.8-1.4
STATE_ERR = 0.06      # ||engine - reference|| / ||reference|| of a layer's
#                       recurrent state after a check request's decode, the
#                       worst layer's (the last: it reads five layers'
#                       rounding). Engine 0.020-0.032; kv 0.135-0.187,
#                       weights 0.32-0.43
STATE_ERR_FIRST = 0.008  # the same of the FIRST layer's, whose mixer reads
#                       the embedding itself: the state's own arithmetic.
#                       Engine 0.0033-0.0041; the state control (bfloat16
#                       after every token) 0.0054-0.0194 by prompt, a run's
#                       worst 0.0159-0.0194 (three seeds); weights
#                       0.056-0.067
CONTROLS = ("weights", "kv", "state")


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


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(gain)


def rope(x, positions, head_dim, theta):
    """[T, n*hd] rotated over the whole head, rotate-half."""
    import jax.numpy as jnp

    half = head_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    xh = x.reshape(x.shape[0], -1, head_dim)
    x1, x2 = xh[..., :half], xh[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                           axis=-1).reshape(x.shape)


def attention(params, p, x, cfg, block: int = 256, via=None, kv_via=None):
    """Causal grouped attention of [T, hidden] over itself, in blocks of
    `block` queries."""
    import jax
    import jax.numpy as jnp

    n, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    q = rope(x @ _f32(params[p + "q_w"], via), pos, hd, cfg["rope_theta"])
    k = rope((x @ _f32(params[p + "k_w"], via)) * cfg["key_multiplier"],
             pos, hd, cfg["rope_theta"])
    v = x @ _f32(params[p + "v_w"], via)
    k, v = _f32(k, kv_via), _f32(v, kv_via)
    qh = q.reshape(t, nkv, n // nkv, hd)
    kh, vh = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
    bq = min(block, t)

    def one_block(q0):
        qb = jax.lax.dynamic_slice_in_dim(qh, q0, bq)
        s = jnp.einsum("qkgh,skh->kgqs", qb, kh) * hd ** -0.5
        ok = pos[None, :] <= (q0 + jnp.arange(bq))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", pr, vh)

    o = jax.lax.map(one_block, jnp.arange(0, t, bq, dtype=jnp.int32))
    return o.reshape(t, n * hd) @ _f32(params[p + "o_w"], via)


def mixer(params, p, x, cfg, via=None, state_via=None, state_at=None):
    """-> (out [T, hidden], the recurrent state after position `state_at`
    as [heads, head_dim, d_state], or None)."""
    import jax
    import jax.numpy as jnp

    h, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, kw = cfg["mamba_n_groups"], cfg["mamba_d_state"], \
        cfg["mamba_d_conv"]
    d_ssm, gn = h * hp, g * n
    t = x.shape[0]
    # the five ssm_multipliers over the in-projection's columns, laid out
    # HERE from the configuration's widths: z, x, B, C, dt (the program's
    # own table, params["fh_mup_vector"], is not read)
    mup = jnp.concatenate([
        jnp.full((w,), m, jnp.float32) for w, m in zip(
            (d_ssm, d_ssm, gn, gn, h), cfg["ssm_multipliers"], strict=True)])
    u = ((x * cfg["ssm_in_multiplier"]) @ _f32(params[p + "in_w"], via)) \
        * mup
    z, xbc, dt = u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * gn], \
        u[:, 2 * d_ssm + 2 * gn:]
    w = _f32(params[p + "conv_w"])                          # [K, C]
    xp = jnp.pad(xbc, ((kw - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(w[j] * xp[j:j + t] for j in range(kw))
                      + _f32(params[p + "conv_b"]))
    xs = xbc[:, :d_ssm].reshape(t, h, hp)
    # a head reads its group's B and C
    bm = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(t, g, n), h // g, 1)
    cm = jnp.repeat(xbc[:, d_ssm + gn:].reshape(t, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + _f32(params[p + "dt_bias"]))  # [T, H]
    a = -jnp.exp(_f32(params[p + "a_log"]))
    want = -1 if state_at is None else state_at

    def token(carry, inp):
        state, kept = carry
        i, x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        state = _f32(state, state_via)
        y_t = jnp.einsum("hpn,hn->hp", state, c_t)
        return (state, jnp.where(i == want, state, kept)), y_t

    zero = jnp.zeros((h, hp, n), jnp.float32)
    (_, kept), y = jax.lax.scan(
        token, (zero, zero),
        (jnp.arange(t, dtype=jnp.int32), xs, bm, cm, dt))
    y = y + _f32(params[p + "d_skip"])[None, :, None] * xs
    y = y.reshape(t, d_ssm) * jax.nn.silu(z)
    parts = y.reshape(t, g, d_ssm // g)
    parts = parts / jnp.sqrt(jnp.mean(jnp.square(parts), axis=-1,
                                      keepdims=True) + cfg["rms_norm_eps"])
    y = parts.reshape(t, d_ssm) * _f32(params[p + "mixer_norm"])
    return y @ _f32(params[p + "out_w"], via), \
        (None if state_at is None else kept)


def forward(params, tokens, cfg, first: int = 0, rows: int = 0,
            block: int = 256, via=None, only: str = "weights",
            state_at=None):
    """[T] token ids -> (float32 logits of the `rows` positions from
    `first` on, or of every position; with `state_at`, every layer's
    recurrent state after that position [layers, heads, head_dim,
    d_state], else None). Causal, so a padded tail is harmless. `via` is
    the lower-precision control: it rounds to that dtype, by `only`, every
    weight matrix (`weights`), K and V as pages hold them (`kv`), or the
    recurrent state after every token (`state`)."""
    import jax
    import jax.numpy as jnp

    if only not in CONTROLS:
        raise ValueError(f"only={only!r}: one of {CONTROLS}")
    kv_via = via if only == "kv" else None
    state_via = via if only == "state" else None
    via = via if only == "weights" else None
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _f32(params["fh_tok_emb"][tokens], via) \
            * cfg["embedding_multiplier"]
        states = []
        for i in range(cfg["n_layers"]):
            p = f"fh_l{i}_"
            x = rms_norm(h, params[p + "norm_in"], eps)
            m, kept = mixer(params, p, x, cfg, via, state_via, state_at)
            states.append(kept)
            h = h + m * cfg["ssm_out_multiplier"] + attention(
                params, p, x * cfg["attention_in_multiplier"], cfg, block,
                via, kv_via) * cfg["attention_out_multiplier"]
            x = rms_norm(h, params[p + "norm_ff"], eps)
            gate = (x @ _f32(params[p + "gate_w"], via)) \
                * cfg["mlp_multipliers"][0]
            mid = (x @ _f32(params[p + "up_w"], via)) * jax.nn.silu(gate)
            h = h + (mid @ _f32(params[p + "down_w"], via)) \
                * cfg["mlp_multipliers"][1]
        if rows:
            h = jax.lax.dynamic_slice_in_dim(h, first, rows)
        logits = (rms_norm(h, params["fh_norm_f"], eps)
                  @ _f32(params["fh_head_w"], via)) \
            * cfg["lm_head_multiplier"]
        return logits, (None if state_at is None else jnp.stack(states))


def padded(seq, pad_to: int):
    import numpy as np

    out = np.zeros(max(pad_to, len(seq)), np.int32)
    out[:len(seq)] = seq
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


def state_errors(got, want):
    """||got - want|| / ||want|| of each layer over [layers, ...]."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    return (np.sqrt(np.sum(np.square(got - want), axis=axes))
            / np.maximum(np.sqrt(np.sum(np.square(want), axis=axes)),
                         1e-30)).tolist()


def state_error(got, want) -> float:
    """The worst layer's `state_errors`."""
    return max(state_errors(got, want))


class Reference:
    """The jitted forward for one model: `rows(seq, pad_to, first, n,
    state_at)` -> (logits [n, vocab], states or None). One compile a
    `pad_to` (and one more with states). `via` (a dtype) and `only` make
    it a lower-precision control (`forward`)."""

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
        logits, states = self._fn(
            self.params, jnp.asarray(padded(seq, pad_to)), first,
            0 if state_at is None else int(state_at), n,
            state_at is not None)
        return np.asarray(logits), \
            (None if states is None else np.asarray(states))
