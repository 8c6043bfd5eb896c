"""Optimizer op lowerings — state updates ARE ops in the program.

Capability mirror of paddle/fluid/operators/optimizers/ (sgd_op.cc,
momentum_op.cc, adam_op.{cc,cu,h}, adamax, adagrad, rmsprop, lamb_op,
lars_momentum_op.cc, ftrl, adadelta, dgc_momentum). Each op consumes
Param/Grad/state and emits ParamOut/state-out; the output var NAMES equal the
input var names, so the functional executor threads the update "in place"
(the reference mutates scope vars directly).

XLA fuses an entire optimizer sweep (all params' update ops) into the same
compiled program as the backward — the role of fuse_optimizer_ops_pass
(ir/fuse_optimizer_ops_pass/) comes for free.
"""

from __future__ import annotations

import numpy as np

from ..core.registry import register_op

_OPT = dict(non_diff_inputs=("Param", "Grad", "LearningRate", "Moment", "Moment1",
                             "Moment2", "Beta1Pow", "Beta2Pow", "Velocity",
                             "MeanSquare", "MeanGrad"))


def _dense_grad(g):
    """Optimizers without a dedicated SelectedRows kernel densify the
    sparse grad (the reference's fallback for ops lacking a
    SelectedRows specialisation; sgd/momentum/adam/adamw have real
    sparse paths)."""
    from ..core.selected_rows import SelectedRows

    return g.to_dense() if isinstance(g, SelectedRows) else g


def _sparse_rows(g):
    """Duplicate-merged (rows_u, values_u, valid) for a SelectedRows grad,
    or None for dense grads. valid masks the live slots; dead slots carry
    row id == height so scatter writes drop them (mode='drop')."""
    from ..core.selected_rows import SelectedRows, merge_duplicates

    if not isinstance(g, SelectedRows):
        return None
    rows_u, values_u = merge_duplicates(g)
    return rows_u, values_u, rows_u < g.height


@register_op("sgd", **_OPT)
def sgd(ins, attrs):
    """reference sgd_op.cc — including its SelectedRows grad kernel:
    a sparse embedding gradient updates only the touched rows
    (duplicates accumulate via scatter-add, the reference merge)."""
    from ..core.selected_rows import SelectedRows

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    if isinstance(g, SelectedRows):
        step = (lr.astype(p.dtype).reshape(())
                * g.values.astype(p.dtype))
        return {"ParamOut": p.at[g.rows].add(-step)}
    return {"ParamOut": p - lr.astype(p.dtype) * g.astype(p.dtype)}


@register_op("momentum", **_OPT)
def momentum(ins, attrs):
    """reference: momentum_op.h MomentumFunctor + its SparseMomentum
    branch: a SelectedRows grad updates velocity/param only on the
    touched rows (untouched velocities do not decay — the reference's
    sparse kernel semantics)."""
    sp = _sparse_rows(ins["Grad"][0])
    mu32 = attrs.get("mu", 0.9)
    rd = attrs.get("regularization_coeff", 0.0)
    l2 = attrs.get("regularization_method", "") == "l2_decay" and rd
    if sp is not None:
        import jax.numpy as jnp

        rows, gv, valid = sp
        p, v, lr = ins["Param"][0], ins["Velocity"][0], ins["LearningRate"][0]
        mu = np.asarray(mu32, p.dtype)
        lr = lr.astype(p.dtype).reshape(())
        rows_c = jnp.where(valid, rows, 0)
        p_r = p[rows_c]
        g_r = gv.astype(p.dtype)
        if l2:
            g_r = g_r + np.asarray(rd, p.dtype) * p_r
        v_r = mu * v[rows_c] + g_r
        if attrs.get("use_nesterov", False):
            p_new = p_r - (g_r + mu * v_r) * lr
        else:
            p_new = p_r - lr * v_r
        return {"ParamOut": p.at[rows].set(p_new, mode="drop"),
                "VelocityOut": v.at[rows].set(v_r.astype(v.dtype),
                                              mode="drop")}
    p, g, v, lr = (ins["Param"][0], ins["Grad"][0], ins["Velocity"][0],
                   ins["LearningRate"][0])
    mu = np.asarray(mu32, p.dtype)
    g = g.astype(p.dtype)
    lr = lr.astype(p.dtype)
    if l2:
        g = g + np.asarray(rd, p.dtype) * p
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


def _sparse_adam(ins, attrs, sp, coeff=0.0):
    """Row-wise Adam(W) on a merged SelectedRows grad (reference
    SparseAdamFunctor lazy_mode, operators/optimizers/adam_op.h:404):
    gather the touched rows' state, update, scatter back — never
    materialising a [V, D] dense gradient or a full-table moment pass."""
    import jax.numpy as jnp

    rows, gv, valid = sp
    p, lr = ins["Param"][0], ins["LearningRate"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    b1 = np.asarray(attrs.get("beta1", 0.9), np.float32)
    b2 = np.asarray(attrs.get("beta2", 0.999), np.float32)
    eps = np.asarray(attrs.get("epsilon", 1e-8), np.float32)
    rows_c = jnp.where(valid, rows, 0)
    gf = gv.astype(m1.dtype)
    m1n = b1 * m1[rows_c] + (1 - b1) * gf
    m2n = b2 * m2[rows_c] + (1 - b2) * gf * gf
    p_r = p[rows_c].astype(jnp.float32)
    lr_t = (lr * jnp.sqrt(1 - b2p) / (1 - b1p)).reshape(())
    step = lr_t * m1n / (jnp.sqrt(m2n) + eps)
    if coeff:
        step = step + lr.reshape(()) * np.float32(coeff) * p_r
    p_new = (p_r - step).astype(p.dtype)
    return {"ParamOut": p.at[rows].set(p_new, mode="drop"),
            "Moment1Out": m1.at[rows].set(m1n.astype(m1.dtype),
                                          mode="drop"),
            "Moment2Out": m2.at[rows].set(m2n.astype(m2.dtype),
                                          mode="drop"),
            "Beta1PowOut": b1p * b1, "Beta2PowOut": b2p * b2}


@register_op("adam", **_OPT)
def adam(ins, attrs):
    """reference: operators/optimizers/adam_op.h AdamFunctor (+ the
    SparseAdamFunctor lazy_mode row-wise branch)."""
    if attrs.get("lazy_mode", False):
        sp = _sparse_rows(ins["Grad"][0])
        if sp is not None:
            return _sparse_adam(ins, attrs, sp)
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    b1 = np.asarray(attrs.get("beta1", 0.9), np.float32)
    b2 = np.asarray(attrs.get("beta2", 0.999), np.float32)
    eps = np.asarray(attrs.get("epsilon", 1e-8), np.float32)
    gf = g.astype(m1.dtype)
    m1o = b1 * m1 + (1 - b1) * gf
    m2o = b2 * m2 + (1 - b2) * gf * gf
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    step = lr_t * m1o / (jnp.sqrt(m2o) + eps)
    return {"ParamOut": (p.astype(np.float32) - step).astype(p.dtype),
            "Moment1Out": m1o, "Moment2Out": m2o,
            "Beta1PowOut": b1p * b1, "Beta2PowOut": b2p * b2}


@register_op("adamw", **_OPT)
def adamw(ins, attrs):
    if attrs.get("lazy_mode", False):
        sp = _sparse_rows(ins["Grad"][0])
        if sp is not None:
            return _sparse_adam(
                ins, attrs, sp,
                coeff=float(attrs.get("coeff", 0.01))
                if attrs.get("with_decay", True) else 0.0)
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    p, lr = ins["Param"][0], ins["LearningRate"][0]
    coeff = np.asarray(attrs.get("coeff", 0.01), np.float32)
    # plain ops on purpose: XLA fuses the per-parameter update chains, and
    # one Pallas kernel per parameter measured 18-19 ms a step slower on
    # ERNIE-large (BASELINE.md, v5e)
    outs = adam(ins, attrs)
    if attrs.get("with_decay", True):
        outs["ParamOut"] = (outs["ParamOut"].astype(np.float32)
                            - lr * coeff * p.astype(np.float32)).astype(p.dtype)
    return outs


@register_op("adagrad", **_OPT)
def adagrad(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, mom, lr = (ins["Param"][0], ins["Grad"][0], ins["Moment"][0],
                     ins["LearningRate"][0])
    eps = attrs.get("epsilon", 1e-6)
    mo = mom + g * g
    return {"ParamOut": p - lr * g / (jnp.sqrt(mo) + eps), "MomentOut": mo}


@register_op("adamax", **_OPT)
def adamax(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    m, inf = ins["Moment"][0], ins["InfNorm"][0]
    b1p = ins["Beta1Pow"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    mo = b1 * m + (1 - b1) * g
    info = jnp.maximum(b2 * inf, jnp.abs(g))
    lr_t = lr / (1 - b1p)
    return {"ParamOut": p - lr_t * mo / (info + eps),
            "MomentOut": mo, "InfNormOut": info}


@register_op("adadelta", **_OPT)
def adadelta(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq, avg_upd = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    sq = rho * avg_sq + (1 - rho) * g * g
    upd = jnp.sqrt(avg_upd + eps) / jnp.sqrt(sq + eps) * g
    upd_acc = rho * avg_upd + (1 - rho) * upd * upd
    return {"ParamOut": p - upd, "AvgSquaredGradOut": sq,
            "AvgSquaredUpdateOut": upd_acc}


@register_op("rmsprop", **_OPT)
def rmsprop(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mu = attrs.get("momentum", 0.0)
    centered = attrs.get("centered", False)
    ms_out = rho * ms + (1 - rho) * g * g
    if centered:
        mg = ins["MeanGrad"][0]
        mg_out = rho * mg + (1 - rho) * g
        denom = ms_out - mg_out * mg_out + eps
    else:
        mg_out = None
        denom = ms_out + eps
    mom_out = mu * mom + lr * g / jnp.sqrt(denom)
    outs = {"ParamOut": p - mom_out, "MeanSquareOut": ms_out, "MomentOut": mom_out}
    if mg_out is not None:
        outs["MeanGradOut"] = mg_out
    return outs


@register_op("lars_momentum", **_OPT)
def lars_momentum(ins, attrs):
    """reference: operators/optimizers/lars_momentum_op.cc — layer-wise
    adaptive rate scaling for large-batch training."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, v, lr = (ins["Param"][0], ins["Grad"][0], ins["Velocity"][0],
                   ins["LearningRate"][0])
    mu = attrs.get("mu", 0.9)
    coeff = attrs.get("lars_coeff", 0.001)
    decay = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 1e-9)
    pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(np.float32))))
    gn = jnp.sqrt(jnp.sum(jnp.square(g.astype(np.float32))))
    local_lr = lr * coeff * pn / (gn + decay * pn + eps)
    v_out = mu * v + local_lr * (g + decay * p)
    return {"ParamOut": p - v_out, "VelocityOut": v_out}


@register_op("lamb", **_OPT)
def lamb(ins, attrs):
    """reference: operators/optimizers/lamb_op.h — LAMB for large-batch BERT."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    pf = p.astype(np.float32)
    gf = g.astype(np.float32)
    m1o = b1 * m1 + (1 - b1) * gf
    m2o = b2 * m2 + (1 - b2) * gf * gf
    mhat = m1o / (1 - b1p)
    vhat = m2o / (1 - b2p)
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * pf
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    p_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
    ratio = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    return {"ParamOut": (pf - lr * ratio * r).astype(p.dtype),
            "Moment1Out": m1o, "Moment2Out": m2o,
            "Beta1PowOut": b1p * b1, "Beta2PowOut": b2p * b2}


@register_op("ftrl", **_OPT)
def ftrl(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    sq, lin = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    new_sq = sq + g * g
    sigma = (new_sq ** -power - sq ** -power) / lr
    lin_out = lin + g - sigma * p
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    denom = new_sq ** -power / lr + 2 * l2
    return {"ParamOut": pre / denom, "SquaredAccumOut": new_sq,
            "LinearAccumOut": lin_out}


@register_op("decayed_adagrad", **_OPT)
def decayed_adagrad(ins, attrs):
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, mom, lr = (ins["Param"][0], ins["Grad"][0], ins["Moment"][0],
                     ins["LearningRate"][0])
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mo = decay * mom + (1 - decay) * g * g
    return {"ParamOut": p - lr * g / (jnp.sqrt(mo) + eps), "MomentOut": mo}


@register_op("clip_by_norm")
def clip_by_norm(ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]
    max_norm = attrs.get("max_norm", 1.0)
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": jnp.where(norm > max_norm, x * (max_norm / norm), x)}


@register_op("proximal_gd", **_OPT)
def proximal_gd(ins, attrs):
    """reference: optimizers/proximal_gd_op.cc — SGD step followed by
    L1/L2 proximal shrinkage."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    lr = lr.astype(p.dtype).reshape(())
    prox = p - lr * g.astype(p.dtype)
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return {"ParamOut": prox / (1.0 + lr * l2)}


@register_op("proximal_adagrad", **_OPT)
def proximal_adagrad(ins, attrs):
    """reference: optimizers/proximal_adagrad_op.cc."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax.numpy as jnp

    p, g = ins["Param"][0], ins["Grad"][0]
    m = ins["Moment"][0]
    lr = ins["LearningRate"][0].astype(p.dtype).reshape(())
    l1 = float(attrs.get("l1", 0.0))
    l2 = float(attrs.get("l2", 0.0))
    g = g.astype(p.dtype)
    m_out = m + g * g
    eff_lr = lr / jnp.sqrt(m_out)
    prox = p - eff_lr * g
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - eff_lr * l1,
                                            0.0)
    return {"ParamOut": prox / (1.0 + eff_lr * l2), "MomentOut": m_out}


@register_op("dpsgd", **_OPT)
def dpsgd(ins, attrs):
    """Differentially-private SGD (reference: optimizers/dpsgd_op.cc):
    clip the gradient to clip-norm, add Gaussian noise sigma, step."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    import jax
    import jax.numpy as jnp

    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    from .tensor_ops import _rng_key

    clip = float(attrs.get("clip", 1.0))
    sigma = float(attrs.get("sigma", 0.0))
    g = g.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    g = g * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    # fresh noise every step (key folds in __step__) — constant noise
    # would be a bias, voiding the DP guarantee
    noise = sigma * clip * jax.random.normal(_rng_key(attrs), g.shape)
    return {"ParamOut": p - lr.astype(p.dtype).reshape(())
            * (g + noise).astype(p.dtype)}


@register_op("dgc_clip_by_norm")
def dgc_clip_by_norm(ins, attrs):
    """reference: dgc_clip_by_norm_op.cc — clip_by_norm rescaled by the
    current DGC step's k ratio."""
    import jax.numpy as jnp

    x = ins["X"][0]
    max_norm = float(attrs.get("max_norm", 1.0))
    norm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return {"Out": (x.astype(jnp.float32) * scale).astype(x.dtype)}


@register_op("dgc", non_diff_inputs=("U", "V", "Grad", "Param",
                                     "current_step", "nranks"))
def dgc(ins, attrs):
    """Deep gradient compression (reference: dgc_op.cc): momentum
    correction + top-k sparsification. The sparse exchange itself is
    pointless on ICI (VERDICT r1 note) but the COMPRESSION math is real:
    U/V accumulate, the top-k fraction of |V| is released and the rest
    carried over."""
    import jax
    import jax.numpy as jnp

    u, v = ins["U"][0], ins["V"][0]
    g = ins["Grad"][0]
    m = float(attrs.get("m", 0.9))
    ratio = float(attrs.get("ratios", attrs.get("ratio", 0.001)))
    use_nesterov = bool(attrs.get("use_nesterov", False))
    gf = g.astype(jnp.float32)
    u_out = m * u + gf if not use_nesterov else m * (u + gf)
    v_out = v + (u_out + gf if use_nesterov else u_out)
    flat = jnp.abs(v_out).reshape(-1)
    k = max(1, int(flat.shape[0] * ratio))
    thr = jax.lax.top_k(flat, k)[0][-1]
    mask = jnp.abs(v_out) >= thr
    encoded = jnp.where(mask, v_out, 0.0)
    return {"U_out": jnp.where(mask, 0.0, u_out),
            "V_out": jnp.where(mask, 0.0, v_out),
            "EncodeGrad": encoded.astype(g.dtype),
            "Grad_out": encoded.astype(g.dtype),
            "GatherBuff": encoded.astype(g.dtype),
            "k": jnp.float32(k)}


@register_op("dgc_momentum", **_OPT)
def dgc_momentum(ins, attrs):
    """reference: optimizers/dgc_momentum_op.h — momentum applied to the
    DGC-released gradient."""
    ins = dict(ins, Grad=[_dense_grad(ins["Grad"][0])])
    p, g = ins["Param"][0], ins["Grad"][0]
    v = ins["Velocity"][0]
    lr = ins["LearningRate"][0].astype(p.dtype).reshape(())
    mu = float(attrs.get("mu", 0.9))
    v_out = mu * v + g.astype(p.dtype)
    return {"ParamOut": p - lr * v_out, "VelocityOut": v_out}
