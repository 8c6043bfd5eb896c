"""Fused inference/training ops.

Capability mirror of operators/fused/ (multihead_matmul_op.cu,
fused_embedding_eltwise_layernorm_op.cu, fusion_repeated_fc_relu_op.cc,
fusion_squared_mat_sub_op.cc, fusion_seqpool_concat_op.cc,
fused_elemwise_activation_op.cc, fusion_gru_op.cc, fusion_lstm_op.cc).
On TPU these are thin compositions: XLA fuses the elementwise epilogues
into the matmuls, and the attention form dispatches into the fused
attention path (ops/pallas/flash_attention.py) — the hand-written CUDA
kernels' role, played by the compiler plus the Pallas/XLA custom paths.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op


@register_op("multihead_matmul", non_diff_inputs=("BiasQK",))
def multihead_matmul(ins, attrs):
    """Fused QKV-projected attention for inference (reference:
    fused/multihead_matmul_op.cu). Input [B, S, 3*H] already holds the
    stacked QKV projections (the fuse pass feeds it); BiasQK is the
    additive attention bias."""
    import jax.numpy as jnp

    from .pallas.flash_attention import flash_attention

    x = ins["Input"][0]
    bias_qk = ins.get("BiasQK", [None])[0]
    n_head = int(attrs["head_number"])
    scale = float(attrs.get("alpha", 1.0))
    b, s, h3 = x.shape
    h = h3 // 3
    hd = h // n_head
    qkv = x.reshape(b, s, 3, n_head, hd).transpose(2, 0, 3, 1, 4)
    out = flash_attention(qkv[0], qkv[1], qkv[2], bias=bias_qk,
                          scale=scale)
    return {"Out": out.transpose(0, 2, 1, 3).reshape(b, s, h)}


@register_op("fused_embedding_eltwise_layernorm", non_diff_inputs=("Ids",))
def fused_embedding_eltwise_layernorm(ins, attrs):
    """sum of N embedding lookups + layer_norm (reference:
    fused/fused_embedding_eltwise_layernorm_op.cu — the BERT embedding
    stack)."""
    import jax.numpy as jnp

    import jax.lax as lax

    ids = ins["Ids"]                  # N x [B, S] int
    embs = ins["Embs"]                # N x [V_i, H]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    eps = float(attrs.get("epsilon", 1e-5))
    acc = None
    for i, e in zip(ids, embs):
        v = e[i.astype(jnp.int32)]
        acc = v if acc is None else acc + v
    mean = jnp.mean(acc, axis=-1, keepdims=True)
    var = jnp.var(acc, axis=-1, keepdims=True)
    y = (acc - mean) * lax.rsqrt(var + eps) * scale + bias
    return {"Out": y}


@register_op("fusion_repeated_fc_relu")
def fusion_repeated_fc_relu(ins, attrs):
    """Chain of fc+relu blocks (reference:
    fused/fusion_repeated_fc_relu_op.cc)."""
    import jax.numpy as jnp

    x = ins["X"][0]
    ws, bs = ins["W"], ins["Bias"]
    for w, b in zip(ws, bs):
        x = jnp.maximum(x @ w + b, 0.0)
    return {"Out": x}


@register_op("fusion_squared_mat_sub")
def fusion_squared_mat_sub(ins, attrs):
    """(X@Y)^2 - (X^2)@(Y^2), scaled (reference:
    fused/fusion_squared_mat_sub_op.cc — the FM interaction term)."""
    import jax.numpy as jnp

    x, y = ins["X"][0], ins["Y"][0]
    scalar = float(attrs.get("scalar", 1.0))
    ab = x @ y
    return {"Out": scalar * (jnp.square(ab) - jnp.square(x) @ jnp.square(y)),
            "SquaredXY": jnp.square(ab)}


@register_op("fusion_seqpool_concat", non_diff_inputs=("Lod",))
def fusion_seqpool_concat(ins, attrs):
    """Per-input sequence pool then feature concat (reference:
    fused/fusion_seqpool_concat_op.cc). Padded form with shared
    lengths Lod [N, B] or full-length pooling."""
    import jax.numpy as jnp

    xs = ins["X"]                        # N x [B, S, D]
    ptype = str(attrs.get("pooltype", "SUM")).upper()
    lens = ins.get("Lod", [None])[0]
    pooled = []
    for i, x in enumerate(xs):
        if lens is not None:
            ln = lens[i].reshape(-1, 1)
            mask = (jnp.arange(x.shape[1])[None, :]
                    < ln).astype(x.dtype)[..., None]
            x = x * mask
            denom = jnp.maximum(ln.astype(x.dtype), 1.0)
        else:
            denom = float(x.shape[1])
        s = jnp.sum(x, axis=1)
        if ptype == "AVERAGE":
            s = s / denom
        elif ptype == "SQRT":
            s = s / jnp.sqrt(denom)
        pooled.append(s)
    return {"Out": jnp.concatenate(pooled, axis=-1)}


@register_op("fused_elemwise_activation", required_attrs=("functor_list",))
def fused_elemwise_activation(ins, attrs):
    """Compose a binary elementwise op with a unary activation
    (reference: fused/fused_elemwise_activation_op.cc,
    functor_list attr like ["elementwise_add", "relu"])."""
    import jax
    import jax.numpy as jnp

    x, y = ins["X"][0], ins["Y"][0]
    functors = list(attrs.get("functor_list", []))
    unary = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
             "tanh": jnp.tanh, "scale": lambda v: v * float(
                 attrs.get("scale", 1.0)),
             # match the standalone gelu op's default (erf form)
             "gelu": lambda v: jax.nn.gelu(
                 v, approximate=bool(attrs.get("approximate", False)))}
    binary = {"elementwise_add": jnp.add, "elementwise_sub": jnp.subtract,
              "elementwise_mul": jnp.multiply}

    def apply(fn_name, *args):
        if fn_name in binary:
            return binary[fn_name](*args)
        return unary[fn_name](args[0])

    f0, f1 = functors
    if f0 in binary:
        out = apply(f1, apply(f0, x, y))       # unary(binary(x, y))
        inter = apply(f0, x, y)
    else:
        out = apply(f1, apply(f0, y), x) if f1 in binary else None
        inter = apply(f0, y)
        if out is None:
            raise ValueError(f"unsupported functor_list {functors}")
    return {"Out": out, "IntermediateOut": inter}


@register_op("fusion_seqpool_cvm_concat", non_diff_inputs=("CVM", "Lod"))
def fusion_seqpool_cvm_concat(ins, attrs):
    """reference: fused/fusion_seqpool_cvm_concat_op.cc — per-input
    sequence pool, CVM transform of each pooled tensor, feature concat.
    Composes the fusion_seqpool_concat and cvm lowerings (XLA fuses the
    chain; the reference hand-fused it for CPU)."""
    from .metrics_ops import cvm as cvm_op

    import jax.numpy as jnp

    pooled = fusion_seqpool_concat(
        {"X": ins["X"], "Lod": ins.get("Lod", [None])}, attrs)["Out"]
    n = len(ins["X"])
    use_cvm = bool(attrs.get("use_cvm", True))
    parts = jnp.split(pooled, n, axis=1)
    outs = [cvm_op({"X": [p], "CVM": ins.get("CVM", [None])},
                   {"use_cvm": use_cvm})["Y"] for p in parts]
    return {"Out": jnp.concatenate(outs, axis=1)}


@register_op("fusion_group", skip_infer_shape=True,
             required_attrs=("sub_ops", "ext_in_names", "ext_out_names"))
def fusion_group(ins, attrs):
    """Composite elementwise-chain op (reference: ir/fusion_group/ +
    fusion_group_op — runtime CUDA codegen for elementwise subgraphs).
    TPU redesign: the pass packs the chain's OpDescs into `sub_ops` and
    this lowering replays them through their registered forwards — ONE
    dispatch (and one jit-cache entry) on the interpreting executor,
    where per-op dispatch is the analog of the reference's per-kernel
    launch overhead. Under the compiling executor
    the trace is identical to the unfused chain, so XLA's fusion
    decisions are unchanged. Runtime attrs (__step__/__axis_coords__)
    are threaded into every sub-op so stochastic members (dropout) keep
    per-step/per-rank mask semantics."""
    from ..core import registry as _registry

    env = dict(zip(list(attrs["ext_in_names"]), list(ins["X"])))
    for sub in attrs["sub_ops"]:
        sub_attrs = dict(sub["attrs"])
        for k in ("__step__", "__axis_coords__"):
            if k in attrs:
                sub_attrs[k] = attrs[k]
        sub_ins = {slot: [env[n] for n in names]
                   for slot, names in sub["inputs"].items()}
        outs = _registry.normalize_outputs(
            _registry.get(sub["type"]).forward(sub_ins, sub_attrs))
        for slot, names in sub["outputs"].items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                env[n] = v
    return {"Out": [env[n] for n in attrs["ext_out_names"]]}


@register_op("fusion_squared_mat_sub")
def fusion_squared_mat_sub(ins, attrs):
    """reference: fused/fusion_squared_mat_sub_op.cc —
    ((X@Y)^2 - (X^2)@(Y^2)) * scalar, with the squared intermediates
    exposed (AsIntermediate outputs)."""
    x, y = ins["X"][0], ins["Y"][0]
    scalar = float(attrs.get("scalar", 1.0))
    sx = jnp.square(x)
    sy = jnp.square(y)
    sxy = jnp.square(jnp.matmul(x, y))
    return {"SquaredX": sx, "SquaredY": sy, "SquaredXY": sxy,
            "Out": (sxy - jnp.matmul(sx, sy)) * scalar}


@register_op("fusion_repeated_fc_relu")
def fusion_repeated_fc_relu(ins, attrs):
    """reference: fused/fusion_repeated_fc_relu_op.cc — a chain of
    relu(x @ W_i + b_i); every per-stage relu output is exposed
    (ReluOut, AsIntermediate)."""
    import jax

    x = ins["X"][0]
    relu_outs = []
    for w, b in zip(ins["W"], ins["Bias"]):
        x = jax.nn.relu(jnp.matmul(x, w) + b)
        relu_outs.append(x)
    return {"ReluOut": relu_outs[:-1], "Out": relu_outs[-1]}
