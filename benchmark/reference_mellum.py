"""Plain reference of one chip's share of a Mellum decoder's pretraining
step (JetBrains Mellum 2, `model_type: mellum`): forward, loss and
gradients in straightforward float32 `jax.numpy` at "highest" matmul
precision, `jax.grad` of a Python function. It imports nothing of the
program: dense masked attention a head at a time, a loop over the held
experts with a mask, no kernels, no sorting, no chunks.

The equations (held share in brackets; configs/mellum2_12b_tp4ep4.json):

* ``h0 = E[ids]``; a layer: ``h += Attn(RMS(h; g1))``,
  ``h += MoE(RMS(h; g2))``; ``logits = RMS(h; gf) @ W_head``;
  ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``.
* ``Attn``: ``q = x Wq`` [heads held x 128], ``k = x Wk``, ``v = x Wv``
  [K/V heads held x 128]; per-head RMS norm of q and k with a learned gain;
  rotary pairs in the half-split convention over the whole head: plain
  ``theta^(-2i/d)`` on sliding layers, YaRN's frequencies with cos and sin
  times ``attention_factor`` on full ones; query head j reads K/V head
  ``j // group``; ``softmax(q k^T / sqrt(d))`` over ``s <= t``, on sliding
  layers over ``t - window < s <= t``; ``a = o Wo``.
* ``MoE``: ``p = softmax(x Wr)`` over ALL experts; the top k by p;
  ``w = p_top / sum(p_top)``; ``y = sum_e w_e (silu(x W1_e) * (x W3_e))
  W2_e`` over the chosen experts that are HELD.
* loss: mean over positions of the cross-entropy of softmax(logits) over
  the held rows against the next token.

`model` (a dict) carries the sizes: head_dim, num_heads, num_kv_heads,
layer_types (of the held layers), num_experts_per_tok, experts_held
(first, count), sliding_window, rms_norm_eps, rope_theta, yarn ({} or
factor, original_max, beta_fast, beta_slow, attention_factor). Parameters
go by the program's names (``ml_tok_emb``, ``ml_l0_q_w`` ...), as float32.

The comparison (`check_train_step`), its limits, the readings they were
set from and the control are at the end.
"""

from __future__ import annotations

import math

SLIDING = "sliding_attention"


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The dim // 2 rotary frequencies under YaRN (arXiv:2309.00071): pair
    i turns by theta^(-2i/dim) a position where it makes more than
    `beta_fast` turns over the original `original_max` positions, by that
    over `factor` where it makes fewer than `beta_slow`, and by a linear
    blend between (the ramp over whole pair indices)."""
    import numpy as np

    half = dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)

    def pair_with(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_with(beta_fast)), 0)
    high = min(math.ceil(pair_with(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rms(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _rotate(x, cos, sin):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, pre, x, model, sliding):
    """x [S, D] -> [S, D] of one sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hd, n, nkv = model["head_dim"], model["num_heads"], model["num_kv_heads"]
    s, eps = x.shape[0], model["rms_norm_eps"]
    q = _rms((x @ p[pre + "q_w"]).reshape(s, n, hd), p[pre + "q_norm"], eps)
    k = _rms((x @ p[pre + "k_w"]).reshape(s, nkv, hd), p[pre + "k_norm"], eps)
    v = (x @ p[pre + "v_w"]).reshape(s, nkv, hd)
    yarn = model.get("yarn") or {}
    if sliding or not yarn:
        inv = float(model["rope_theta"]) ** (
            -np.arange(hd // 2, dtype=np.float64) * 2.0 / hd)
        factor = 1.0
    else:
        inv = yarn_inv_freq(hd, float(model["rope_theta"]), yarn["factor"],
                            yarn["original_max"], yarn["beta_fast"],
                            yarn["beta_slow"])
        factor = yarn["attention_factor"]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    t = jnp.arange(s)
    ok = t[None, :] <= t[:, None]
    if sliding:
        ok &= t[None, :] > t[:, None] - model["sliding_window"]
    group = n // nkv

    def head(j):
        sc = q[:, j] @ k[:, j // group].T / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1) \
            @ v[:, j // group]

    o = jnp.stack([head(j) for j in range(n)], axis=1)
    return o.reshape(s, n * hd) @ p[pre + "o_w"]


def route(p, pre, x, model):
    """-> (idx [S, k] the chosen experts, w [S, k] their weights)."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(x @ p[pre + "router_w"], axis=-1)
    top, idx = jax.lax.top_k(probs, model["num_experts_per_tok"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def experts(p, pre, x, model):
    import jax
    import jax.numpy as jnp

    idx, w = route(p, pre, x, model)
    lo, count = model["experts_held"]
    y = jnp.zeros_like(x)
    for e in range(count):
        mine = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
        mid = jax.nn.silu(x @ p[pre + "ex_w1"][e]) * (x @ p[pre + "ex_w3"][e])
        y = y + mine[:, None] * (mid @ p[pre + "ex_w2"][e])
    return y


def sequence_loss(p, tokens, labels, model):
    """Sum over one sequence's positions of the next-token cross-entropy
    (the caller divides by all positions of the batch)."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    h = p["ml_tok_emb"][tokens]

    def layer(h, lp, i, sliding):
        pre = f"ml_l{i}_"
        h = h + attention(lp, pre, _rms(h, lp[pre + "norm_attn"], eps),
                          model, sliding)
        return h + experts(lp, pre, _rms(h, lp[pre + "norm_moe"], eps),
                           model)

    for i, kind in enumerate(model["layer_types"]):
        # a layer's scores ([heads, S, S]) are made again in the backward
        # and not kept: what lets an 8,192-token sequence fit the chip
        h = jax.checkpoint(layer, static_argnums=(2, 3))(
            h, p, i, kind == SLIDING)
    logits = _rms(h, p["ml_norm_f"], eps) @ p["ml_head_w"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def routing(p, tokens, model):
    """[layers, S, k] experts the reference chooses for one sequence, each
    layer's from the reference's own hidden states."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        eps = model["rms_norm_eps"]
        h = p["ml_tok_emb"][tokens]
        out = []
        for i, kind in enumerate(model["layer_types"]):
            pre = f"ml_l{i}_"
            h = h + attention(p, pre, _rms(h, p[pre + "norm_attn"], eps),
                              model, kind == SLIDING)
            x = _rms(h, p[pre + "norm_moe"], eps)
            out.append(route(p, pre, x, model)[0])
            h = h + experts(p, pre, x, model)
        return jnp.stack(out)


def loss_and_grads(params, tokens, labels, model, through=None):
    """-> (mean loss, {name: gradient}, chosen [B, layers, S, k]) of a
    [B, S] batch, a sequence at a time and summed. `through` rounds every
    matrix (ndim >= 2) on its way in: the control's lower precision."""
    import jax
    import jax.numpy as jnp

    def rounded(p):
        if through is None:
            return p
        return {n: v.astype(through).astype(jnp.float32) if v.ndim >= 2
                else v for n, v in p.items()}

    def one(p, tok, lab):
        with jax.default_matmul_precision("highest"):
            return sequence_loss(rounded(p), tok, lab, model)

    p32 = {n: jnp.asarray(v, jnp.float32) for n, v in params.items()}
    step = jax.jit(jax.value_and_grad(one))
    chosen_of = jax.jit(lambda p, tok: routing(rounded(p), tok, model))
    total, grads, chosen = 0.0, None, []
    for tok, lab in zip(tokens, labels):
        tok, lab = jnp.asarray(tok, jnp.int32), jnp.asarray(lab, jnp.int32)
        loss, g = step(p32, tok, lab)
        total += float(loss)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        chosen.append(chosen_of(p32, tok))
    count = float(tokens.shape[0] * tokens.shape[1])
    return (total / count, {n: g / count for n, g in grads.items()},
            jnp.stack(chosen))


# ---------------------------------------------------------------------------
# the optimizer's step


def adamw_first_step(params, grads, lr, weight_decay, beta1=0.9,
                     beta2=0.999, epsilon=1e-8):
    """AdamW's FIRST step from zero moments (decoupled decay, Kingma &
    Ba's section 2 form with epsilon beside the uncorrected root, as
    Paddle's adam_op.h has it), in float32:

        m = (1 - b1) g;  v = (1 - b2) g^2
        p' = p - lr sqrt(1 - b2) / (1 - b1) * m / (sqrt(v) + eps)
               - lr * decay * p

    and p' stored in the dtype `params[name]` came in: a configuration
    that keeps bfloat16 parameters keeps the step's rounding too (at lr
    1e-5 most of a bfloat16 matrix does not move at all). -> {name: p'}
    for the names in `grads`, as numpy arrays of the stored dtype."""
    import numpy as np

    out = {}
    for name, g in grads.items():
        p = np.asarray(params[name])
        p32, g = p.astype(np.float32), np.asarray(g, np.float32)
        m, v = np.float32(1 - beta1) * g, np.float32(1 - beta2) * g * g
        lr_t = np.float32(lr * math.sqrt(1 - beta2) / (1 - beta1))
        new = p32 - lr_t * m / (np.sqrt(v) + np.float32(epsilon)) \
            - np.float32(lr * weight_decay) * p32
        out[name] = new.astype(p.dtype)
    return out


# ---------------------------------------------------------------------------
# the comparison
#
# A bfloat16 program against this float32 reference, one step of the timed
# program at the timed sizes (4 layers, 2 x 8,192 tokens). Routing is
# discrete: a (token, slot) whose two candidate experts lie closer than
# bfloat16's rounding of the hidden state moves them picks the other one in
# the program, so the choices are compared by the share that agree, and the
# loss and the gradients as they are (a flipped near-tie swaps two experts
# whose weights are nearly equal, at a token: it moves the gradient of
# those two experts' rows by that token's part).
#
# Limits, EACH NUMBER ITS OWN, between what the program read over seeds
# ("sound": the most over thirty-two seeds) and what the control read: the
# reference itself with every matrix rounded through float8_e4m3fn (3 bits
# of mantissa where bfloat16 keeps 7: "the nearest precision below"),
# judged as a program's step is. Readings on the chip (v5e, my chip runs,
# PR 43: `python3 -m benchmark.readings_mellum --seed <n>` and the cell's
# own runs; PERF.md section 4 has them by seed):
#
#                              sound, most   limit   control
#   loss_rel_err               1.2e-5        1e-4    5.5e-4 - 6.5e-4
#   grad ml_tok_emb            0.0101        0.022   0.0536 - 0.0549
#   grad ml_head_w             0.0058        0.015   0.0432 - 0.0455
#   grad ml_l0_q_w             0.0166        0.03    0.0617 - 0.0636
#   grad ml_l0_router_w        0.0259        0.038   0.0549 - 0.0561
#   grad ml_l1_ex_w1[0]        0.0320        0.055   0.0996 - 0.1096
#   grad ml_l1_ex_w2[15]       0.0289        0.055   0.1054 - 0.1094
#   grad ml_l3_k_w             0.0180        0.04    0.1014 - 0.1057
#   routing_agreement (floor)  0.99927       0.997   0.9933
#
# (relative L2 over the tensor; one expert's two are the widest, a
# near-tie that bfloat16 turns moves a whole token between two experts'
# rows.) The control fails EVERY one of them. A wrong product in the
# routed backward read 0.18-0.97 on six of the seven. A leaf no reading
# was taken of (a toy cell's, `readings_mellum --all`) takes
# GRAD_TOL_OTHER, the widest of these.
#
# The optimizer's step: after the step the compared parameters are read
# again, and the CHANGE of each is held against the reference's own AdamW
# step from the reference's own gradient, |dp - dp_ref| / |dp_ref| over
# the tensor (of a stacked expert matrix, the one expert). A state left
# unchanged or a skipped leaf reads 1, a step of twice the rate about 1,
# of half of it about 0.5. Adam's first step is lr * g / (|g| + eps'):
# nearly the gradient's SIGN, so what it reads is mostly the share of
# moved elements whose small gradient bfloat16 turns round (twice the
# step each: 2 sqrt(share); 0.44% of the router's moved elements, 0.009%
# of the head's), and the optimizer op alone (the program's change
# against this AdamW of the program's own fetched gradients) reads
# 0.0003-0.03. At lr 1e-5 a bfloat16 matrix moves in 8-15% of its
# elements (where half an ulp is under the step), and the embedding (std
# 1, gradients under eps') in none: a leaf the reference leaves where it
# was has to be left there (reads 0, else inf). Readings on the chip (my
# chip runs, PR 43, second session: sixteen seeds sound, one the control;
# PERF.md section 4 has the seeds):
#
#                              sound, most   limit   control
#   update ml_head_w           0.037         0.10    0.302
#   update ml_l0_q_w           0.092         0.135   0.208
#   update ml_l0_router_w      0.144         0.185   0.224
#   update ml_l1_ex_w1[0]      0.123         0.18    0.267
#   update ml_l1_ex_w2[15]     0.121         0.17    0.281
#   update ml_l3_k_w           0.107         0.18    0.342
#   update ml_tok_emb          0 (unmoved)   0.18    0 (unmoved)
#
# UPDATE_TOL_OTHER is for a leaf no reading was taken of.
LOSS_TOL = 1e-4
GRAD_TOL = {"ml_tok_emb": 0.022, "ml_head_w": 0.015, "ml_l0_q_w": 0.03,
            "ml_l0_router_w": 0.038, "ml_l1_ex_w1[0]": 0.055,
            "ml_l1_ex_w2[15]": 0.055, "ml_l3_k_w": 0.04}
GRAD_TOL_OTHER = 0.055
ROUTING_AGREE_MIN = 0.997
UPDATE_TOL = {"ml_head_w": 0.10, "ml_l0_q_w": 0.135,
              "ml_l0_router_w": 0.185, "ml_l1_ex_w1[0]": 0.18,
              "ml_l1_ex_w2[15]": 0.17, "ml_l3_k_w": 0.18}
UPDATE_TOL_OTHER = 0.18


def routing_agreement(chosen, reference_chosen) -> float:
    """Share of (token, slot) choices the two make alike: a token's k
    experts as sets."""
    import numpy as np

    a = np.sort(np.asarray(chosen), axis=-1)
    b = np.sort(np.asarray(reference_chosen), axis=-1)
    both = (a[..., :, None] == b[..., None, :]).any(axis=-1)
    return float(both.mean())


def _rel_err(got, want) -> float:
    """|got - want| / |want|; where `want` is nothing at all, 0 if `got`
    is nothing too, else inf."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    off, size = np.linalg.norm(got - want), np.linalg.norm(want)
    if size == 0:
        return 0.0 if off == 0 else math.inf
    return float(off / size)


def compare(loss, grads, chosen, reference, changes=None):
    """Holds a step's loss, the gradients named in `grads` (relative L2
    error over the whole tensor), its routed layers' choices `chosen`
    [B, layers, S, k] and, where given, the `changes` its optimizer made
    ({name: (the program's p' - p, the reference's)}) against `reference`,
    what `loss_and_grads` gave. Returns (notes, compared): notes is empty
    when all agree, compared is [name, value, limit] of each number held."""
    import jax.numpy as jnp

    ref_loss, ref_grads, ref_chosen = reference
    compared = [["loss_rel_err", abs(float(loss) / ref_loss - 1), LOSS_TOL]]
    notes = []
    if not compared[0][1] <= LOSS_TOL:
        notes.append(f"step loss {float(loss)} against the reference's "
                     f"{ref_loss}: over {LOSS_TOL} relative")
    for name, g in grads.items():
        err = _rel_err(jnp.asarray(g, jnp.float32), ref_grads[name])
        limit = GRAD_TOL.get(name, GRAD_TOL_OTHER)
        compared.append([f"grad_rel_err.{name}", err, limit])
        if not err <= limit:
            notes.append(f"gradient of {name} is {err:.4f} (relative L2) "
                         f"from the reference's: over {limit}")
    agree = routing_agreement(chosen, ref_chosen)
    compared.append(["routing_agreement", agree, ROUTING_AGREE_MIN])
    if not agree >= ROUTING_AGREE_MIN:
        notes.append(f"{agree:.4f} of the routed (token, slot) choices "
                     f"agree with the reference's: under "
                     f"{ROUTING_AGREE_MIN}")
    for name, (got, want) in (changes or {}).items():
        err = _rel_err(got, want)
        limit = UPDATE_TOL.get(name, UPDATE_TOL_OTHER)
        compared.append([f"update_rel_err.{name}", err, limit])
        if not err <= limit:
            notes.append(f"the optimizer's change of {name} is {err:.4f} "
                         f"(relative L2) from the reference's AdamW step: "
                         f"over {limit} (an unchanged state reads 1)")
    return notes, compared


def changes(before, after, reference_after):
    """{name: (the program's change, the reference's)} of the parameters
    in `after`, in float64 from whatever dtype they are stored in."""
    import numpy as np

    def f64(v):
        return np.asarray(v).astype(np.float64)

    return {n: (f64(after[n]) - f64(before[n]),
                f64(reference_after[n]) - f64(before[n])) for n in after}


def control(params, tokens, labels, model, names, optimizer=None,
            through="float8_e4m3fn"):
    """The reference's own step with every matrix rounded through
    `through`, held against the reference as a program's step is: at least
    one limit must fail, or the limits would let a cheaper number format
    pass. `optimizer` (adamw_first_step's keywords) adds the AdamW step
    each side makes from its own gradients. -> (notes, compared)."""
    import jax.numpy as jnp

    reference = loss_and_grads(params, tokens, labels, model)
    loss, grads, chosen = loss_and_grads(params, tokens, labels, model,
                                         through=jnp.dtype(through))
    moved = None
    if optimizer is not None:
        moved = changes(
            params,
            adamw_first_step(params, {n: grads[n] for n in names},
                             **optimizer),
            adamw_first_step(params, {n: reference[1][n] for n in names},
                             **optimizer))
    return compare(loss, {n: grads[n] for n in names}, chosen, reference,
                   moved)
