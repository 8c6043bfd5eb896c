"""NN op lowerings: conv, pooling, normalisation, dropout.

Capability mirror of paddle/fluid/operators/ conv_op.cc (+conv_cudnn),
pool_op.cc, batch_norm_op.cc, layer_norm_op.{cc,cu}, dropout_op.cc,
conv_transpose_op.cc, group_norm_op.cc. Convs lower to
lax.conv_general_dilated (NCHW, fluid's default layout — XLA changes
layout for the MXU internally); norms are jnp compositions XLA fuses into one kernel.
"""

from __future__ import annotations

import numpy as np

from ..core.registry import register_op
from ..core.types import convert_dtype


def _conv_padding(attrs, spatial_rank=2):
    p = attrs.get("paddings", [0] * spatial_rank)
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    if algo == "SAME":
        return "SAME"
    if algo == "VALID":
        return "VALID"
    if len(p) == spatial_rank:
        return [(int(pi), int(pi)) for pi in p]
    if len(p) == 2 * spatial_rank:
        return [(int(p[2 * i]), int(p[2 * i + 1])) for i in range(spatial_rank)]
    return [(0, 0)] * spatial_rank


@register_op("conv2d")
def conv2d(ins, attrs):
    """reference: operators/conv_op.cc (NCHW). Filter is OIHW."""
    import jax.lax as lax

    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1]))
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=_conv_padding(attrs),
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=None)
    return {"Output": out}


@register_op("depthwise_conv2d")
def depthwise_conv2d(ins, attrs):
    x = ins["Input"][0]
    attrs = dict(attrs)
    attrs["groups"] = x.shape[1]
    return {"Output": conv2d({"Input": ins["Input"], "Filter": ins["Filter"]},
                             attrs)["Output"]}


@register_op("conv2d_transpose")
def conv2d_transpose(ins, attrs):
    """reference: operators/conv_transpose_op.cc. Filter is IOHW (paddle keeps
    [in_c, out_c/groups, kh, kw])."""
    import jax.lax as lax

    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1]))
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    pad = _conv_padding(attrs)
    if isinstance(pad, str):
        padding = pad
    else:
        # conv_transpose output padding math: lax.conv_transpose with
        # transpose_kernel handles the fluid semantics for symmetric pads
        padding = [(p0, p1) for (p0, p1) in pad]
        kh, kw = w.shape[2], w.shape[3]
        padding = [(kh - 1 - padding[0][0], kh - 1 - padding[0][1]),
                   (kw - 1 - padding[1][0], kw - 1 - padding[1][1])]
    in_c, out_pg, kh_, kw_ = w.shape
    # paddle stores [in_c, out_c/groups, kh, kw]; the equivalent forward
    # conv needs [out_c, in_c/groups, kh, kw] with in/out swapped WITHIN
    # each group (plain transpose(1,0) only handles groups == 1)
    w_g = w.reshape(groups, in_c // groups, out_pg, kh_, kw_)
    w_t = w_g.transpose(0, 2, 1, 3, 4).reshape(
        groups * out_pg, in_c // groups, kh_, kw_)[:, :, ::-1, ::-1]
    out = lax.conv_general_dilated(
        x, w_t, window_strides=(1, 1), padding=padding,
        lhs_dilation=strides, rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": out}


@register_op("pool2d")
def pool2d(ins, attrs):
    """reference: operators/pool_op.cc — max/avg, NCHW."""
    import jax.lax as lax
    import jax.numpy as jnp

    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and \
            tuple(attrs.get("ksize", ())) == (1, 1):
        axis = (2, 3)
        out = (jnp.max(x, axis=axis, keepdims=True) if ptype == "max"
               else jnp.mean(x, axis=axis, keepdims=True))
        return {"Out": out}
    if attrs.get("adaptive", False):
        # adaptive semantics: ksize IS the OUTPUT size; cell (i, j)
        # reduces x[floor(i*H/oh):ceil((i+1)*H/oh), ...] (reference
        # pool_op.cc AdaptStartIndex/AdaptEndIndex) — NOT a fixed
        # window, and well-defined even when output > input
        oh, ow = tuple(attrs["ksize"])
        H, W = int(x.shape[2]), int(x.shape[3])
        red_axes = (lambda w, ax: jnp.max(w, axis=ax)) if ptype == "max" \
            else (lambda w, ax: jnp.mean(w, axis=ax))
        if H % oh == 0 and W % ow == 0:
            # divisible: one reshape + one fused reduction (same trick
            # as the spp op) instead of oh*ow slices
            n, c = x.shape[0], x.shape[1]
            w = x.reshape(n, c, oh, H // oh, ow, W // ow)
            return {"Out": red_axes(w, (3, 5))}
        rows = []
        for i in range(oh):
            h0, h1 = (i * H) // oh, -(-((i + 1) * H) // oh)
            cols = [red_axes(
                x[:, :, h0:h1, (j * W) // ow:-(-((j + 1) * W) // ow)],
                (2, 3)) for j in range(ow)]
            rows.append(jnp.stack(cols, axis=-1))
        return {"Out": jnp.stack(rows, axis=-2)}
    ksize = tuple(attrs.get("ksize", [2, 2]))
    strides = tuple(attrs.get("strides", ksize))
    pad = _conv_padding(attrs)
    if isinstance(pad, str):
        padding = pad
    else:
        padding = [(0, 0), (0, 0)] + list(pad)
    window = (1, 1) + ksize
    strides4 = (1, 1) + strides
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, np.asarray(init, x.dtype), lax.max, window,
                                strides4, padding)
    else:
        summed = lax.reduce_window(x, np.asarray(0.0, x.dtype), lax.add, window,
                                   strides4, padding)
        if attrs.get("exclusive", True) and padding != "VALID" and not isinstance(padding, str):
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, np.asarray(0.0, x.dtype), lax.add,
                                       window, strides4, padding)
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    return {"Out": out}


@register_op("pool3d")
def pool3d(ins, attrs):
    """reference: operators/pool_op.cc Pool3D variant — max/avg, NCDHW."""
    import jax.lax as lax
    import jax.numpy as jnp

    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False) or (
            attrs.get("adaptive", False)
            and tuple(attrs.get("ksize", ())) == (1, 1, 1)):
        axis = (2, 3, 4)
        out = (jnp.max(x, axis=axis, keepdims=True) if ptype == "max"
               else jnp.mean(x, axis=axis, keepdims=True))
        return {"Out": out}
    if attrs.get("adaptive", False):
        # see pool2d: ksize is the OUTPUT size (adaptive cell bounds)
        od, oh, ow = tuple(attrs["ksize"])
        D, H, W = (int(s) for s in x.shape[2:])
        red_axes = (lambda w, ax: jnp.max(w, axis=ax)) if ptype == "max" \
            else (lambda w, ax: jnp.mean(w, axis=ax))
        if D % od == 0 and H % oh == 0 and W % ow == 0:
            n, c = x.shape[0], x.shape[1]
            w = x.reshape(n, c, od, D // od, oh, H // oh, ow, W // ow)
            return {"Out": red_axes(w, (3, 5, 7))}
        planes = []
        for d in range(od):
            d0, d1 = (d * D) // od, -(-((d + 1) * D) // od)
            rows = []
            for i in range(oh):
                h0, h1 = (i * H) // oh, -(-((i + 1) * H) // oh)
                cols = [red_axes(
                    x[:, :, d0:d1, h0:h1,
                      (j * W) // ow:-(-((j + 1) * W) // ow)],
                    (2, 3, 4)) for j in range(ow)]
                rows.append(jnp.stack(cols, axis=-1))
            planes.append(jnp.stack(rows, axis=-2))
        return {"Out": jnp.stack(planes, axis=-3)}
    ksize = tuple(attrs.get("ksize", [2, 2, 2]))
    strides = tuple(attrs.get("strides", ksize))
    pad = _conv_padding(attrs, spatial_rank=3)
    padding = pad if isinstance(pad, str) else [(0, 0), (0, 0)] + list(pad)
    window = (1, 1) + ksize
    strides5 = (1, 1) + strides
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, np.asarray(init, x.dtype), lax.max,
                                window, strides5, padding)
    else:
        summed = lax.reduce_window(x, np.asarray(0.0, x.dtype), lax.add,
                                   window, strides5, padding)
        if attrs.get("exclusive", True) and padding != "VALID":
            counts = lax.reduce_window(
                jnp.ones_like(x), np.asarray(0.0, x.dtype), lax.add,
                window, strides5, padding)
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    return {"Out": out}


@register_op("spectral_norm", non_diff_inputs=("U", "V"))
def spectral_norm(ins, attrs):
    """reference: operators/spectral_norm_op.cc — weight / sigma, with
    sigma from `power_iters` rounds of power iteration on the weight
    matricised over `dim`. Matches the reference state + grad
    conventions (ADVICE r3): UOut/VOut carry the advanced iteration
    vectors (the reference mutates U/V in place — the executor threads
    the outputs back through the same persistable vars), and u/v are
    held CONSTANT for autodiff (spectral_norm_grad treats them as data,
    so the power iteration sits under stop_gradient)."""
    import jax
    import jax.numpy as jnp

    w = ins["Weight"][0]
    u = ins["U"][0].reshape(-1)
    v = ins["V"][0].reshape(-1)
    dim = int(attrs.get("dim", 0))
    iters = int(attrs.get("power_iters", 1))
    eps = float(attrs.get("eps", 1e-12))
    perm = (dim,) + tuple(i for i in range(w.ndim) if i != dim)
    wm = jnp.transpose(w, perm).reshape(w.shape[dim], -1)  # [H, W]

    def norm(x):
        return x / (jnp.linalg.norm(x) + eps)

    wm_c = jax.lax.stop_gradient(wm)
    for _ in range(max(iters, 0)):
        v = norm(wm_c.T @ u)
        u = norm(wm_c @ v)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ wm @ v        # grads flow through wm only (u,v constant)
    return {"Out": w / sigma,
            "UOut": u.astype(ins["U"][0].dtype).reshape(ins["U"][0].shape),
            "VOut": v.astype(ins["V"][0].dtype).reshape(ins["V"][0].shape)}


@register_op("affine_grid", non_diff_inputs=("OutputShape",))
def affine_grid(ins, attrs):
    """reference: operators/affine_grid_op.cc — 2-D affine sampling grid
    from Theta [N, 2, 3]; Out [N, H, W, 2] in [-1, 1] coords."""
    import jax.numpy as jnp

    theta = ins["Theta"][0]
    shape = attrs.get("output_shape")
    if not shape and ins.get("OutputShape"):
        os_t = ins["OutputShape"][0]
        if hasattr(os_t, "aval") and not hasattr(os_t, "__array__"):
            raise NotImplementedError(
                "affine_grid: a traced OutputShape tensor is not "
                "XLA-compatible — pass the static output_shape attr "
                "(same constraint as ShapeTensor, tensor_ops.py)")
        shape = [int(d) for d in np.asarray(os_t)]
    n, _, h, w = [int(d) for d in shape]
    align = bool(attrs.get("align_corners", True))
    if align:
        xs = jnp.linspace(-1.0, 1.0, w)
        ys = jnp.linspace(-1.0, 1.0, h)
    else:
        xs = (jnp.arange(w) * 2 + 1) / w - 1.0
        ys = (jnp.arange(h) * 2 + 1) / h - 1.0
    gx, gy = jnp.meshgrid(xs, ys)                     # [H, W]
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [H, W, 3]
    out = jnp.einsum("hwk,nck->nhwc", base.astype(theta.dtype), theta)
    return {"Output": out}


@register_op("hierarchical_sigmoid", non_diff_inputs=("Label", "PathTable",
                                                      "PathCode"))
def hierarchical_sigmoid(ins, attrs):
    """reference: operators/hierarchical_sigmoid_op.cc — O(log C) softmax
    over the default complete binary tree (SimpleCode: node index
    ((c + C) >> (i+1)) - 1, bit (c + C) >> i & 1), or a custom tree via
    PathTable/PathCode. Cost[b] = sum_i softplus(pre_i) - bit_i * pre_i."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]                                  # [B, D]
    w = ins["W"][0]                                  # [C-1, D]
    label = ins["Label"][0].reshape(-1).astype(jnp.int32)  # [B]
    bias = ins.get("Bias", [None])[0]
    path = ins.get("PathTable", [None])[0]
    code = ins.get("PathCode", [None])[0]
    if path is None:
        c = int(attrs["num_classes"])
        max_len = int(np.floor(np.log2(max(c - 1, 1)))) + 1
        lc = label + c
        i = jnp.arange(max_len)
        idx = (lc[:, None] >> (i[None, :] + 1)) - 1   # [B, L] W row ids
        bit = (lc[:, None] >> i[None, :]) & 1
        valid = idx >= 0                              # stop above the root
    else:
        idx = path.astype(jnp.int32)
        bit = code.astype(jnp.int32)
        valid = idx >= 0
    idx_c = jnp.where(valid, idx, 0)
    pre = jnp.einsum("bd,bld->bl", x, w[idx_c])
    if bias is not None:
        pre = pre + bias.reshape(-1)[idx_c]
    cost = jax.nn.softplus(pre) - bit.astype(pre.dtype) * pre
    cost = jnp.where(valid, cost, 0.0)
    # reference output slot is "Out" (hierarchical_sigmoid_op.cc)
    return {"Out": jnp.sum(cost, axis=1, keepdims=True),
            "PreOut": jnp.where(valid, pre, 0.0)}


def _batch_norm_impl(ins, attrs, cross_rank=False):
    """Shared batch_norm body. cross_rank=True allreduces the batch
    sum/sumsq/count over the mesh axis before normalising
    (sync_batch_norm)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = bool(attrs.get("is_test", False)) or bool(attrs.get("use_global_stats", False))
    layout = attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        xf = x.astype(jnp.float32)
        s = jnp.sum(xf, axis=axes)
        ss = jnp.sum(jnp.square(xf), axis=axes)
        cnt = jnp.asarray(float(np.prod([x.shape[a] for a in axes])),
                          jnp.float32)
        if cross_rank:
            import jax

            from .collective_ops import _axis_name, _bound_axes

            bound = _bound_axes(_axis_name(attrs))
            if bound:
                ax = bound if len(bound) > 1 else bound[0]
                s = jax.lax.psum(s, ax)
                ss = jax.lax.psum(ss, ax)
                cnt = jax.lax.psum(cnt, ax)
        use_mean = s / cnt
        use_var = ss / cnt - jnp.square(use_mean)
        mean_out = mean * momentum + use_mean * (1.0 - momentum)
        var_out = var * momentum + use_var * (1.0 - momentum)
        saved_mean = use_mean
        saved_var = 1.0 / jnp.sqrt(use_var + eps)
    inv = 1.0 / jnp.sqrt(use_var.astype(jnp.float32) + eps)
    y = (x - use_mean.reshape(bshape).astype(x.dtype)) * \
        (inv * scale.astype(jnp.float32)).reshape(bshape).astype(x.dtype) + \
        bias.reshape(bshape).astype(x.dtype)
    return {"Y": y, "MeanOut": mean_out, "VarianceOut": var_out,
            "SavedMean": saved_mean, "SavedVariance": saved_var}


@register_op("batch_norm")
def batch_norm(ins, attrs):
    """reference: operators/batch_norm_op.cc. Outputs Y plus updated running
    stats (MeanOut/VarianceOut alias the input stat vars — in-place through
    scope threading) and SavedMean/SavedVariance for the backward."""
    return _batch_norm_impl(ins, attrs, cross_rank=False)


@register_op("sync_batch_norm", is_collective=True)
def sync_batch_norm(ins, attrs):
    """reference: operators/sync_batch_norm_op.cu:21 (SyncBatchNormKernel) —
    batch_norm whose batch statistics are allreduced across data-parallel
    ranks before normalisation. The reference does an explicit NCCL
    allreduce of per-rank sum/sumsq; here the op emits lax.psum over the
    mesh axis (attrs axis_name, default "dp"), which XLA lowers to an ICI
    allreduce. Outside an SPMD region (world size 1) it degenerates to
    batch_norm exactly — matching the reference where a ring of size 1 is
    a no-op. The backward needs no special handling: JAX transposes the
    psum in the re-traced forward, reproducing the reference grad kernel's
    cross-rank dy/dy·x̂ reductions."""
    return _batch_norm_impl(ins, attrs, cross_rank=True)


@register_op("layer_norm")
def layer_norm(ins, attrs):
    """reference: operators/layer_norm_op.cc — normalise trailing dims from
    begin_norm_axis; compute in fp32 for bf16 inputs (TPU practice)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    scale = ins["Scale"][0] if ins.get("Scale") and ins["Scale"][0] is not None else None
    bias = ins["Bias"][0] if ins.get("Bias") and ins["Bias"][0] is not None else None
    eps = attrs.get("epsilon", 1e-5)
    axis = int(attrs.get("begin_norm_axis", 1))
    axes = tuple(range(axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    inv = 1.0 / jnp.sqrt(var + eps)
    y = (xf - mean) * inv
    norm_shape = x.shape[axis:]
    if scale is not None:
        y = y * scale.reshape(norm_shape).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(norm_shape).astype(jnp.float32)
    red = int(np.prod([x.shape[a] for a in axes]))
    lead = x.shape[:axis]
    return {"Y": y.astype(x.dtype),
            "Mean": mean.reshape(lead),
            "Variance": var.reshape(lead)}


@register_op("group_norm")
def group_norm(ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]
    scale = ins["Scale"][0] if ins.get("Scale") else None
    bias = ins["Bias"][0] if ins.get("Bias") else None
    g = int(attrs.get("groups", 1))
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": y, "Mean": mean.reshape((n, g)), "Variance": var.reshape((n, g))}


@register_op("dropout")
def dropout(ins, attrs):
    """reference: operators/dropout_op.cc. Seed assigned at build; runtime
    folds the global step so masks differ per run but stay reproducible.

    Mask generation is a splitmix32 hash over the element lattice keyed
    by the derived seed — measured ~30 ms/step cheaper than threefry
    bernoulli on the ERNIE-large bench (49 dropouts over [32,512,1024]);
    same iid Bernoulli(1-p) distribution. Tensors >= 2^32 elements fall
    back to threefry (the uint32 lattice would alias)."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    p = float(attrs.get("dropout_prob", 0.5))
    is_test = bool(attrs.get("is_test", False))
    impl = attrs.get("dropout_implementation", "upscale_in_train")
    if is_test or p == 0.0:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones(x.shape, np.uint8)}
    from .tensor_ops import _rng_key

    key = _rng_key(attrs)
    n = int(np.prod(x.shape)) if x.shape else 1
    if n < (1 << 32):
        from .pallas.flash_attention import _splitmix

        kd = jnp.asarray(jax.random.key_data(key)).reshape(-1) \
            .astype(jnp.uint32)
        seed = kd[0] ^ kd[-1]
        U = jnp.uint32
        lin = jax.lax.iota(U, n).reshape(x.shape)
        h = _splitmix(lin ^ (seed * U(0x9E3779B9)))
        keep = h >= U(min(int(p * 4294967296.0), 4294967295))
    else:
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    return {"Out": out, "Mask": keep.astype(np.uint8)}


@register_op("interpolate")
@register_op("nearest_interp")
@register_op("bilinear_interp")
def interpolate(ins, attrs):
    import jax

    x = ins["X"][0]
    out_h = int(attrs.get("out_h", 0))
    out_w = int(attrs.get("out_w", 0))
    scale = attrs.get("scale", 0)
    scale_h = attrs.get("scale_h", scale)
    scale_w = attrs.get("scale_w", scale)
    if not out_h and scale_h:
        out_h = int(x.shape[2] * scale_h)
    if not out_w and scale_w:
        out_w = int(x.shape[3] * scale_w)
    method = "nearest" if "nearest" in attrs.get("interp_method", "nearest") else "linear"
    out = jax.image.resize(x, (x.shape[0], x.shape[1], out_h, out_w), method)
    return {"Out": out.astype(x.dtype)}


@register_op("pad2d")
def pad2d(ins, attrs):
    import jax.numpy as jnp

    x = ins["X"][0]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    pairs = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        return {"Out": jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))}
    jmode = {"reflect": "reflect", "edge": "edge", "replicate": "edge",
             "circular": "wrap"}[mode]
    return {"Out": jnp.pad(x, pairs, mode=jmode)}


@register_op("prelu")
def prelu(ins, attrs):
    import jax.numpy as jnp

    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": jnp.where(x > 0, x, x * alpha)}


@register_op("label_smooth", non_diff_inputs=("PriorDist",))
def label_smooth(ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.1)
    k = x.shape[-1]
    return {"Out": x * (1.0 - eps) + eps / k}


@register_op("depthwise_conv2d_transpose")
def depthwise_conv2d_transpose(ins, attrs):
    """Depthwise transposed conv (reference: conv_transpose_op.cc:581
    REGISTER_OPERATOR(depthwise_conv2d_transpose, ...) — same kernel as
    conv2d_transpose with groups == input channels)."""
    x = ins["Input"][0]
    attrs = dict(attrs)
    attrs["groups"] = x.shape[1]
    return {"Output": conv2d_transpose(
        {"Input": ins["Input"], "Filter": ins["Filter"]}, attrs)["Output"]}
