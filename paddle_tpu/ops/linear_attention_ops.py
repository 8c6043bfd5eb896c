"""Ops of a Gated-DeltaNet layer behind the decode engine
(models/qwen3_next.py): the gated delta rule in two forms over the same
per-SLOT matrix state (serving/kv_cache.py: a state and a conv tail a slot,
not a token), the by-key-head split of the two input projections, the
per-head gated RMS norm, and the split of a projection that gives each head
two vectors. The causal convolution before the rule is ops/ssm_ops.py's
(`ssm_conv_update` / `ssm_conv_prefill`, without a bias).

The rule, a value head, in float32 (q, k [K] l2-normed, q scaled by
K^-0.5; v [V]; S [K, V]; value heads ``r i .. r i + r - 1`` read key head
``i``):

    beta_t = sigmoid(b_t)     g_t = -exp(A_log) softplus(a_t + dt_bias)
    S = exp(g_t) S;   u = k_t^T S;   S = S + k_t (x) (beta_t (v_t - u))
    o_t = q_t^T S

* decode step: `gated_delta_state_update` advances a row's state by one
  token IN PLACE at the row's slot (``Slots``; a padding row names the
  scratch slot, the arrays' last): ops/pallas/gated_delta_state_update.py
  on the chip; its stock lowering is the kernel's oracle and counted
  fallback.
* whole prompt: `gated_delta_chunk_scan`, the chunked (WY) form: inside a
  chunk of C tokens the rule's C rank-one corrections are solved at once
  (a unit lower-triangular system, by forward substitution), a chunk's
  state handed to the next (ops/pallas/gated_delta_chunk_scan.py on the
  chip: the solve and the scan over chunks in VMEM; its stock lowering,
  two sequential XLA loops, is the oracle and counted fallback); it
  WRITES the slot's state, overwriting what its last owner left. Past the
  prompt's length ``g = 0`` and ``beta = 0``: a padded bucket's tail
  neither decays nor feeds the state.

State arrays: ``ssm_state_<l>`` [slots + 1, value heads, K, V] float32 (K
on sublanes, V on lanes: v, u and o are lane rows as the projections leave
them) and ``conv_tail_<l>`` [slots + 1, d_conv - 1, 2 x key dim + value
dim] in the model's dtype (q | k | v, each head by head).
"""

from __future__ import annotations

from ..core import telemetry
from ..core.registry import register_op

L2_EPS = 1e-6       # the family's l2norm: x * rsqrt(sum(x^2) + 1e-6)
_GDN_ATTRS = ("key_heads", "key_dim", "value_heads", "value_dim")


def _gdn_sizes(attrs):
    return tuple(int(attrs[name]) for name in _GDN_ATTRS)


@register_op("gdn_split", required_attrs=_GDN_ATTRS)
def gdn_split_op(ins, attrs):
    """The two input projections of a Gated-DeltaNet layer, laid out BY KEY
    HEAD as published: of QKVZ each key head holds its q (key_dim), its k
    (key_dim), its value heads' v (r x value_dim) and z (r x value_dim); of
    BA its value heads' b (r) and a (r); r = value_heads / key_heads.
    -> QKV [..., 2 x key_heads x key_dim + value_heads x value_dim]: q of
    every head, then k, then v (the convolution's channels); Z [...,
    value_heads x value_dim]; B and A [..., value_heads]."""
    import jax.numpy as jnp

    nk, dk, nv, dv = _gdn_sizes(attrs)
    r = nv // nk
    qkvz, ba = ins["QKVZ"][0], ins["BA"][0]
    lead = qkvz.shape[:-1]
    by_head = qkvz.reshape(lead + (nk, 2 * dk + 2 * r * dv))
    q, k = by_head[..., :dk], by_head[..., dk:2 * dk]
    v = by_head[..., 2 * dk:2 * dk + r * dv]
    z = by_head[..., 2 * dk + r * dv:]
    ba = ba.reshape(lead + (nk, 2 * r))
    flat = lead + (-1,)
    return {"QKV": jnp.concatenate([q.reshape(flat), k.reshape(flat),
                                    v.reshape(flat)], axis=-1),
            "Z": z.reshape(flat), "B": ba[..., :r].reshape(flat),
            "A": ba[..., r:].reshape(flat)}


@register_op("split_head_pairs", required_attrs=("head_dim",))
def split_head_pairs_op(ins, attrs):
    """X [..., n x 2 x head_dim], each head two vectors side by side (a
    query and its output gate) -> First, Second [..., n x head_dim]."""
    hd = int(attrs["head_dim"])
    x = ins["X"][0]
    pairs = x.reshape(x.shape[:-1] + (-1, 2 * hd))
    flat = x.shape[:-1] + (-1,)
    return {"First": pairs[..., :hd].reshape(flat),
            "Second": pairs[..., hd:].reshape(flat)}


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def delta_rule_terms(q, k, v, a, b, a_log, dt_bias, nk, dk, nv, dv):
    """What the rule multiplies, position and value head, from the
    convolution's outputs and the raw gates: q, k [..., nk x dk], v [...,
    nv x dv], a and b [..., nv] -> (q [..., nv, dk] l2-normed and scaled,
    k [..., nv, dk] l2-normed, v [..., nv, dv], g [..., nv] (log decay, <=
    0), beta [..., nv]), float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lead = q.shape[:-1]
    r = nv // nk
    qh = _l2norm(q.astype(f32).reshape(lead + (nk, dk))) * dk ** -0.5
    kh = _l2norm(k.astype(f32).reshape(lead + (nk, dk)))
    g = -jnp.exp(a_log.astype(f32)) \
        * jax.nn.softplus(a.astype(f32) + dt_bias.astype(f32))
    return (jnp.repeat(qh, r, axis=-2), jnp.repeat(kh, r, axis=-2),
            v.astype(f32).reshape(lead + (nv, dv)), g,
            jax.nn.sigmoid(b.astype(f32)))


@register_op("gated_delta_state_update", required_attrs=_GDN_ATTRS)
def gated_delta_state_update_op(ins, attrs):
    """One token of the gated delta rule a row, the row's state read and
    written once, in place, at its slot. Q, K [B, nk x dk], V [B, nv x dv]
    (the convolution's outputs), A, B [B, nv] (raw), ALog, DtBias [nv],
    State [slots + 1, nv, dk, dv] float32, Slots [B] int32 -> Y [B, nv x
    dv], StateOut. The kernel under the PT_PALLAS dispatch
    (ops/pallas/gated_delta_state_update.py); 'off' and untileable shapes
    take the counted stock lowering."""
    import jax.numpy as jnp

    from .pallas.gated_delta_state_update import gated_delta_state_update

    nk, dk, nv, dv = _gdn_sizes(attrs)
    q, k, v, g, beta = delta_rule_terms(
        ins["Q"][0], ins["K"][0], ins["V"][0], ins["A"][0], ins["B"][0],
        ins["ALog"][0], ins["DtBias"][0], nk, dk, nv, dv)
    y, state = gated_delta_state_update(
        ins["State"][0], ins["Slots"][0].reshape(-1).astype(jnp.int32),
        q, k, v, jnp.exp(g), beta, heads_per_key=nv // nk)
    return {"Y": y.reshape(y.shape[0], nv * dv), "StateOut": state}


@register_op("gated_delta_chunk_scan",
             required_attrs=_GDN_ATTRS + ("chunk",))
def gated_delta_chunk_scan_op(ins, attrs):
    """A whole (padded) prompt through the gated delta rule in chunks of
    `chunk`, from a zero state, and the slot's state WRITTEN with the state
    after the prompt's last real token (``g = 0`` and ``beta = 0`` past
    ``Lengths``). Q, K [B, S, nk x dk], V [B, S, nv x dv], A, B [B, S, nv]
    raw, Lengths [B], Slots [B] -> Y [B, S, nv x dv], StateOut. The kernel
    under the PT_PALLAS dispatch (ops/pallas/gated_delta_chunk_scan.py);
    'off' and untileable shapes take the counted stock lowering."""
    import jax.numpy as jnp

    from .pallas.gated_delta_chunk_scan import gated_delta_chunk_scan

    # trace-time, as the kernels' dispatch counters are (which beside this
    # one say whether the kernel or its stock form was traced)
    telemetry.counter_add("ops.gated_delta_chunk_scan_dispatches", 1)
    nk, dk, nv, dv = _gdn_sizes(attrs)
    q, k, v, g, beta = delta_rule_terms(
        ins["Q"][0], ins["K"][0], ins["V"][0], ins["A"][0], ins["B"][0],
        ins["ALog"][0], ins["DtBias"][0], nk, dk, nv, dv)
    b, s = g.shape[0], g.shape[1]
    pool = ins["State"][0]
    slots = ins["Slots"][0].reshape(-1).astype(jnp.int32)
    lengths = ins["Lengths"][0].reshape(-1).astype(jnp.int32)
    real = (jnp.arange(s, dtype=jnp.int32)[None, :]
            < lengths[:, None])[..., None]
    y, last = gated_delta_chunk_scan(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
        int(attrs["chunk"]), heads_per_key=nv // nk)
    return {"Y": y.reshape(b, s, nv * dv),
            "StateOut": pool.at[slots].set(last.astype(pool.dtype))}


@register_op("gated_head_rms_norm", required_attrs=("head_dim",))
def gated_head_rms_norm_op(ins, attrs):
    """Y = Scale * RMSNorm_head(X) * silu(Gate): each `head_dim` of the
    last axis normed alone (the norm first, then the gate), one gain
    [head_dim] for every head, applied as it is stored; float32."""
    import jax
    import jax.numpy as jnp

    hd = int(attrs["head_dim"])
    x = ins["X"][0].astype(jnp.float32)
    heads = x.reshape(x.shape[:-1] + (-1, hd))
    ms = jnp.mean(jnp.square(heads), axis=-1, keepdims=True)
    heads = heads * jax.lax.rsqrt(ms + float(attrs.get("epsilon", 1e-6))) \
        * ins["Scale"][0].astype(jnp.float32)
    return {"Y": heads.reshape(x.shape)
            * jax.nn.silu(ins["Gate"][0].astype(jnp.float32))}
