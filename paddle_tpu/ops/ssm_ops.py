"""Ops of a Mamba-2 mixer behind the decode engine (models/falcon_h1.py):
the causal depthwise convolution and the selective state-space recurrence,
each in two forms over the same per-SLOT state (serving/kv_cache.py: a
recurrent state and a conv tail a slot, not a token), the split of the
input projection, the gated grouped RMS norm and rotary positions without a
QK-norm.

The recurrence, a head, in float32 (x [P], B and C [N] of the head's
group, dt and A scalars, S [N, P]):

    dt_t = softplus(dt_raw_t + dt_bias)        A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + B_t (x) (dt_t x_t)
    y_t  = C_t . S_t + D x_t

* decode step: `ssm_conv_update` shifts a row's conv tail by one token and
  `ssm_state_update` advances a row's state by one token, both IN PLACE at
  the row's slot (``Slots``; a padding row names the scratch slot, the
  arrays' last). The state update is ops/pallas/ssm_state_update.py on the
  chip; its stock lowering here is the kernel's oracle and counted
  fallback.
* whole prompt: `ssm_conv_prefill` and `ssm_chunk_scan` (the chunked form:
  inside a chunk the decay-masked ``C B^T`` product, a chunk's state handed
  to the next) WRITE the slot's tail and state, overwriting what its last
  owner left. Past the prompt's length ``dt = 0``: a padded bucket's tail
  neither decays nor feeds the state, and the conv tail is that of the last
  REAL tokens.

State arrays: ``ssm_state_<l>`` [slots + 1, heads, d_state, head_dim]
float32 (d_state on sublanes, head_dim on lanes: a head's x and y are lane
rows as the projections leave them) and ``conv_tail_<l>`` [slots + 1,
d_conv - 1, conv_dim] in the model's dtype (time-major, channels on lanes).

Which ops own which convolution: `ssm_conv_update` / `ssm_conv_prefill`
here are the convolution INSIDE a recurrent mixer (a bias or none, SiLU
after it, its channels split in three by the attrs: Mamba-2's x | B | C,
a Gated-DeltaNet layer's q | k | v); `gated_short_conv_update` /
`gated_short_conv_prefill` (ops/short_conv_ops.py) are a layer whose whole
mixer is the convolution (no activation, a gate before and a gate after,
no split, and the tail the layer's only state). What a tail is, is one
thing and lives here: `conv_window` (a row's tail at its slot joined to its
new input), `conv_prompt` / `conv_prompt_tail` (a padded prompt, and the
inputs of its last REAL tokens) and `conv_tail_write` (the slot's tail
written in place) serve both pairs.
"""

from __future__ import annotations

from ..core.registry import register_op


def _bias(ins):
    """The convolution's bias, or 0 for a layer without one."""
    import jax.numpy as jnp

    return ins["Bias"][0].astype(jnp.float32) if ins.get("Bias") else 0.0


def _split_xbc(y, attrs):
    """The convolution's channels: x, then B and C of every group."""
    d_ssm = int(attrs["n_heads"]) * int(attrs["head_dim"])
    gn = int(attrs["n_groups"]) * int(attrs["d_state"])
    return {"X": y[..., :d_ssm], "B": y[..., d_ssm:d_ssm + gn],
            "C": y[..., d_ssm + gn:]}


@register_op("ssm_split", required_attrs=("d_ssm", "conv_dim"))
def ssm_split_op(ins, attrs):
    """The mixer's input projection, scaled column by column by ``Mup``
    (the muP vector) and split ``Z | XBC | Dt``."""
    u = ins["U"][0] * ins["Mup"][0]
    d, c = int(attrs["d_ssm"]), int(attrs["conv_dim"])
    return {"Z": u[..., :d], "XBC": u[..., d:d + c], "Dt": u[..., d + c:]}


_SSM_ATTRS = ("n_heads", "head_dim", "n_groups", "d_state")


def row_slots(ins):
    """Slots [B] int32: the slot of each row's state."""
    import jax.numpy as jnp

    return ins["Slots"][0].reshape(-1).astype(jnp.int32)


def conv_window(pool, slots, x):
    """A decode step's convolution window, a row: the row's tail (the last
    ``K - 1`` inputs, at its slot of `pool` [slots + 1, K - 1, C]) and its
    new input x [B, C] -> [B, K, C] float32, oldest first."""
    import jax.numpy as jnp

    return jnp.concatenate([pool[slots].astype(jnp.float32),
                            x[:, None, :]], axis=1)


def conv_prompt(x, w):
    """A whole padded prompt x [B, S, C] through the causal depthwise
    convolution W [K, C], before any bias or activation -> (the padded
    inputs [B, S + K - 1, C], where token t sits at index t + K - 1; the
    sums [B, S, C])."""
    import jax.numpy as jnp

    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return xp, sum(w[j] * xp[:, j:j + s] for j in range(k))


def conv_prompt_tail(xp, lengths, k):
    """Of `conv_prompt`'s padded inputs, those of the last ``K - 1`` REAL
    tokens of each row (zeros before a prompt shorter than that), not the
    padded bucket's end -> [B, K - 1, C]."""
    import jax.numpy as jnp

    # token t sits at xp[t + K - 1]: the last K - 1 real ones start at L
    idx = lengths[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def conv_tail_write(pool, slots, tail):
    """`pool` with the rows' slots holding `tail` [B, K - 1, C], in the
    pool's dtype, in place (a padding row names the scratch slot)."""
    return pool.at[slots].set(tail.astype(pool.dtype))


@register_op("ssm_conv_update", required_attrs=_SSM_ATTRS)
def ssm_conv_update_op(ins, attrs):
    """One token a row through the causal depthwise convolution: the row's
    tail (the last ``K - 1`` inputs, at its slot) and this input give
    ``silu(sum_k W[k] window[k] + Bias)``, split X | B | C; the tail shifts
    by one, in place. XBC [B, conv_dim] float32, ConvTail [slots + 1,
    K - 1, conv_dim], Slots [B] int32, W [K, conv_dim], Bias [conv_dim]
    (optional: a convolution without one). The three parts are whatever
    the attrs' widths cut the channels into (a Gated-DeltaNet layer's
    q | k | v: `n_heads` x `head_dim` of q, `n_groups` x `d_state` of k)."""
    import jax
    import jax.numpy as jnp

    xbc, pool = ins["XBC"][0].astype(jnp.float32), ins["ConvTail"][0]
    slots = row_slots(ins)
    w = ins["W"][0].astype(jnp.float32)
    win = conv_window(pool, slots, xbc)                       # [B, K, C]
    y = jax.nn.silu(jnp.sum(win * w[None], axis=1) + _bias(ins))
    out = _split_xbc(y, attrs)
    out["ConvTailOut"] = conv_tail_write(pool, slots, win[:, 1:])
    return out


@register_op("ssm_conv_prefill", required_attrs=_SSM_ATTRS)
def ssm_conv_prefill_op(ins, attrs):
    """A whole (padded) prompt through the convolution, and the slot's tail
    written: the inputs of the last ``K - 1`` REAL tokens (zeros before a
    prompt shorter than that), not the padded bucket's end. XBC [B, S,
    conv_dim], Lengths [B], Slots [B]."""
    import jax
    import jax.numpy as jnp

    xbc, pool = ins["XBC"][0].astype(jnp.float32), ins["ConvTail"][0]
    slots = row_slots(ins)
    lengths = ins["Lengths"][0].reshape(-1).astype(jnp.int32)
    w = ins["W"][0].astype(jnp.float32)
    xp, y = conv_prompt(xbc, w)
    y = jax.nn.silu(y + _bias(ins))
    tail = conv_prompt_tail(xp, lengths, w.shape[0])
    out = _split_xbc(y, attrs)
    out["ConvTailOut"] = conv_tail_write(pool, slots, tail)
    return out


def ssm_step_terms(x, dt_raw, a_log, dt_bias, n_heads, head_dim):
    """What one step of the recurrence multiplies, a row and head:
    -> (dt * x [B, H, P], exp(dt * A) [B, H])."""
    import jax
    import jax.numpy as jnp

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))           # [B, H]
    decay = jnp.exp(-dt * jnp.exp(a_log.astype(jnp.float32)))
    xh = x.astype(jnp.float32).reshape(-1, n_heads, head_dim)
    return dt[..., None] * xh, decay


@register_op("ssm_state_update", required_attrs=_SSM_ATTRS)
def ssm_state_update_op(ins, attrs):
    """One step of the recurrence a row, the row's state read and written
    once, in place, at its slot. X [B, H*P], B and C [B, G*N], Dt [B, H]
    (raw), ALog, D, DtBias [H], State [slots + 1, H, N, P] float32, Slots
    [B] int32 -> Y [B, H*P], StateOut. The kernel under the PT_PALLAS
    dispatch (ops/pallas/ssm_state_update.py); 'off' and untileable shapes
    take the counted stock lowering."""
    import jax.numpy as jnp

    from .pallas.ssm_state_update import ssm_state_update

    h, p = int(attrs["n_heads"]), int(attrs["head_dim"])
    g, n = int(attrs["n_groups"]), int(attrs["d_state"])
    x = ins["X"][0]
    xdt, decay = ssm_step_terms(x, ins["Dt"][0], ins["ALog"][0],
                                ins["DtBias"][0], h, p)
    rows = x.shape[0]
    y, state = ssm_state_update(
        ins["State"][0], ins["Slots"][0].reshape(-1).astype(jnp.int32),
        xdt, decay, ins["B"][0].astype(jnp.float32).reshape(rows, g, n),
        ins["C"][0].astype(jnp.float32).reshape(rows, g, n))
    skip = ins["D"][0].astype(jnp.float32)[None, :, None] \
        * x.astype(jnp.float32).reshape(rows, h, p)
    return {"Y": (y + skip).reshape(rows, h * p), "StateOut": state}


def chunk_scan(x, bm, cm, dt, a, chunk):
    """The chunked form of the recurrence from a zero state. x [B, S, H, P],
    bm and cm [B, S, G, N], dt [B, S, H] (0 where a position is padding),
    a [H] (negative) -> (y [B, S, H, P] without the D term, the state after
    the last position [B, H, N, P]). Float32 at 'highest'."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    hg, ln = h // g, min(int(chunk), s)
    if s % ln:
        raise ValueError(f"prompt length {s} is no multiple of the chunk "
                         f"{ln}")
    nc = s // ln
    xs = x.reshape(b, nc, ln, g, hg, p)
    bs, cs = bm.reshape(b, nc, ln, g, n), cm.reshape(b, nc, ln, g, n)
    dts = dt.reshape(b, nc, ln, g, hg)
    acs = jnp.cumsum(dts * a.reshape(g, hg), axis=2)     # log decay, <= 0
    # inside a chunk: y_l += sum_{s <= l} (C_l . B_s) decay(s -> l) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cs, bs, precision=hi)
    seg = acs[:, :, :, None] - acs[:, :, None, :]        # [b, c, l, s, g, hg]
    causal = jnp.tril(jnp.ones((ln, ln), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    w = jnp.moveaxis(cb, 2, 4)[..., None] * decay * dts[:, :, None]
    y = jnp.einsum("bclsgh,bcsghp->bclghp", w, xs, precision=hi)
    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(acs[:, :, -1:] - acs) * dts         # [b, c, s, g, hg]
    own = jnp.einsum("bcsgh,bcsghp,bcsgn->bcghnp", to_end, xs, bs,
                     precision=hi)
    whole = jnp.exp(acs[:, :, -1])                       # [b, c, g, hg]

    def carry(state, c):
        own_c, whole_c = c
        return whole_c[..., None, None] * state + own_c, state

    last, before = jax.lax.scan(
        carry, jnp.zeros((b, g, hg, n, p), jnp.float32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                  # [b, c, g, hg, n, p]
    y = y + jnp.exp(acs)[..., None] * jnp.einsum(
        "bclgn,bcghnp->bclghp", cs, before, precision=hi)
    return y.reshape(b, s, h, p), last.reshape(b, h, n, p)


@register_op("ssm_chunk_scan", required_attrs=_SSM_ATTRS + ("chunk",))
def ssm_chunk_scan_op(ins, attrs):
    """A whole (padded) prompt through the recurrence in chunks of
    `chunk`, from a zero state, and the slot's state WRITTEN with the state
    after the prompt's last real token (``dt = 0`` past ``Lengths``). X
    [B, S, H*P], B and C [B, S, G*N], Dt [B, S, H] raw, Lengths [B], Slots
    [B] -> Y [B, S, H*P], StateOut."""
    import jax
    import jax.numpy as jnp

    h, p = int(attrs["n_heads"]), int(attrs["head_dim"])
    g, n = int(attrs["n_groups"]), int(attrs["d_state"])
    x = ins["X"][0].astype(jnp.float32)
    b, s, _ = x.shape
    pool = ins["State"][0]
    slots = ins["Slots"][0].reshape(-1).astype(jnp.int32)
    lengths = ins["Lengths"][0].reshape(-1).astype(jnp.int32)
    dt = jax.nn.softplus(ins["Dt"][0].astype(jnp.float32)
                         + ins["DtBias"][0].astype(jnp.float32))
    real = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    xh = x.reshape(b, s, h, p)
    y, last = chunk_scan(
        xh, ins["B"][0].astype(jnp.float32).reshape(b, s, g, n),
        ins["C"][0].astype(jnp.float32).reshape(b, s, g, n), dt,
        -jnp.exp(ins["ALog"][0].astype(jnp.float32)), int(attrs["chunk"]))
    y = y + ins["D"][0].astype(jnp.float32)[None, None, :, None] * xh
    return {"Y": y.reshape(b, s, h * p),
            "StateOut": pool.at[slots].set(last.astype(pool.dtype))}


@register_op("gated_group_rms_norm", required_attrs=("groups",))
def gated_group_rms_norm_op(ins, attrs):
    """Y = RMSNorm_grouped(X * silu(Gate)) * Scale: the gate first, then
    each of `groups` equal parts of the last axis normed alone, float32."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0].astype(jnp.float32)
    hgt = x * jax.nn.silu(ins["Gate"][0].astype(jnp.float32))
    parts = hgt.reshape(x.shape[:-1] + (int(attrs["groups"]), -1))
    ms = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    parts = parts * jax.lax.rsqrt(ms + float(attrs.get("epsilon", 1e-5)))
    return {"Y": parts.reshape(x.shape)
            * ins["Scale"][0].astype(jnp.float32)}


@register_op("qk_rope", required_attrs=("head_dim",))
def qk_rope_op(ins, attrs):
    """Rotary positions over the whole head in the half-split convention on
    Q and on ``K * k_scale``, no norm before them: (x1, x2) -> (x1 cos -
    x2 sin, x2 cos + x1 sin) with angle pos * theta^(-2i/head_dim). Q
    [..., nq*hd], K [..., nkv*hd]; Positions int32, shaped like Q without
    its last axis."""
    import jax.numpy as jnp

    hd = int(attrs["head_dim"])
    half = hd // 2
    inv = float(attrs.get("theta", 10000.0)) ** (
        -jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = ins["Positions"][0].astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def one(x, scale):
        xh = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, hd)) * scale
        x1, x2 = xh[..., :half], xh[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1
                               ).reshape(x.shape)

    return {"QOut": one(ins["Q"][0], 1.0),
            "KOut": one(ins["K"][0], float(attrs.get("k_scale", 1.0)))}
