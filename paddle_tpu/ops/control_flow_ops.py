"""Control-flow ops holding sub-blocks — lowered to lax.cond/while/checkpoint.

Capability mirror of paddle/fluid/operators/controlflow/
(conditional_block_op.cc, while_op.cc) and the recompute machinery
(backward.py:689 _append_backward_ops_with_checkpoints_). The reference
interprets sub-blocks with nested executors; here a sub-block is traced into
the surrounding XLA computation via lax.cond / lax.while_loop /
jax.checkpoint — compiler-friendly control flow with static shapes.

`block_call` is the workhorse: it inlines a sub-block as one IR node. With
attrs["remat"]=True the segment is wrapped in jax.checkpoint, giving
segment-level activation recomputation (RecomputeOptimizer). Gradients flow
through via the generic __vjp_grad__ (jax.vjp traces through run_block).
"""

from __future__ import annotations

from typing import Any, Dict

from ..core.registry import register_op


def _run_sub_block(blk, env: Dict[str, Any], step=None, axis_coords=None):
    from ..core.executor import run_block

    run_block(blk, env, step=step, axis_coords=axis_coords)
    return env


@register_op("block_call", skip_infer_shape=True,
             required_attrs=("sub_block", "input_names", "output_names"))
def block_call(ins, attrs):
    """Run a sub-block as a function of its inputs; optionally rematerialised.

    inputs:  X: values of attrs["input_names"] (ordered)
    outputs: Out: values of attrs["output_names"] (ordered)
    """
    import jax

    blk = attrs["sub_block"]
    in_names = list(attrs["input_names"])
    out_names = list(attrs["output_names"])
    step = attrs.get("__step__")

    def body(*vals):
        env = dict(zip(in_names, vals))
        _run_sub_block(blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        return tuple(env[n] for n in out_names)

    if attrs.get("remat", False):
        body = jax.checkpoint(body)
    outs = body(*ins["X"])
    return {"Out": list(outs)}


@register_op("conditional_block", skip_infer_shape=True,
             non_diff_inputs=("Cond",),
             required_attrs=("sub_block", "input_names", "output_names"))
def conditional_block(ins, attrs):
    """lax.cond over a sub-block (reference: conditional_block_op.cc).
    The false branch passes through the current values of the output vars,
    so every output name must also appear in input_names."""
    import jax

    blk = attrs["sub_block"]
    in_names = list(attrs["input_names"])
    out_names = list(attrs["output_names"])
    step = attrs.get("__step__")
    cond = ins["Cond"][0]
    if cond.ndim > 0:
        cond = cond.reshape(())

    def true_fn(vals):
        env = dict(zip(in_names, vals))
        _run_sub_block(blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        return tuple(env[n] for n in out_names)

    def false_fn(vals):
        env = dict(zip(in_names, vals))
        return tuple(env[n] for n in out_names)

    outs = jax.lax.cond(cond, true_fn, false_fn, tuple(ins["X"]))
    return {"Out": list(outs)}


@register_op("while", skip_infer_shape=True, non_diff_inputs=("Condition",),
             required_attrs=("sub_block", "carry_names", "cond_name"))
def while_op(ins, attrs):
    """lax.while_loop over a sub-block (reference: while_op.cc). The
    sub-block must rewrite the condition var each iteration; carried shapes
    are fixed (XLA requirement — the reference's growing TensorArrays need
    pre-sized buffers here)."""
    import jax

    blk = attrs["sub_block"]
    carry_names = list(attrs["carry_names"])  # includes the condition var
    cond_name = attrs["cond_name"]
    step = attrs.get("__step__")

    def cond_fn(vals):
        env = dict(zip(carry_names, vals))
        c = env[cond_name]
        return c.reshape(()) if getattr(c, "ndim", 0) else c

    def body_fn(vals):
        env = dict(zip(carry_names, vals))
        _run_sub_block(blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        return tuple(env[n] for n in carry_names)

    outs = jax.lax.while_loop(cond_fn, body_fn, tuple(ins["X"]))
    return {"Out": list(outs)}


@register_op("print", skip_infer_shape=True)
def print_op(ins, attrs):
    """Debug print (reference: controlflow/print_op). Uses jax.debug.print
    so it also fires inside jitted programs."""
    import jax

    x = ins["X"][0]
    jax.debug.print(attrs.get("message", "print_op") + ": {x}", x=x)
    return {"Out": x}


@register_op("cond", skip_infer_shape=True, non_diff_inputs=("Cond",),
             required_attrs=("true_block", "input_names",
                             "true_out_names", "false_out_names"))
def cond_two_branch(ins, attrs):
    """Two-sub-block lax.cond (layers/control_flow.py cond): both branches
    trace; reverse-differentiable via the generic vjp grad maker."""
    import jax

    tb, fb = attrs["true_block"], attrs.get("false_block")
    in_names = list(attrs["input_names"])
    t_out = list(attrs["true_out_names"])
    f_out = list(attrs["false_out_names"])
    step = attrs.get("__step__")
    pred = ins["Cond"][0]
    if getattr(pred, "ndim", 0):
        pred = pred.reshape(())
    vals = tuple(ins["X"])

    cond_name = attrs.get("cond_name")

    def run(blk, out_names):
        def fn(vs):
            env = dict(zip(in_names, vs))
            if cond_name:
                env[cond_name] = ins["Cond"][0]  # branches may read the pred
            if blk is not None:
                _run_sub_block(blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
            return tuple(env[n] for n in out_names)

        return fn

    if not t_out:                       # side-effect-free branch selection
        return {"Out": []}
    outs = jax.lax.cond(pred, run(tb, t_out), run(fb, f_out), vals)
    return {"Out": list(outs)}


@register_op("while_loop", skip_infer_shape=True,
             required_attrs=("cond_block", "body_block", "carry_names",
                             "body_out_names", "ext_names", "cond_out_name"))
def while_loop_op(ins, attrs):
    """Separate cond/body sub-blocks (layers/control_flow.py while_loop).

    Two lowerings (reference while_op.cc differentiates via a sub-block
    grad program; XLA's while primitive is forward-only, so):
      * default — lax.while_loop, dynamic trip count, NOT
        reverse-differentiable;
      * grad_max_iters=N attr — a bounded lax.scan of N steps whose
        carry only advances while the condition holds (masked
        pass-through after convergence). scan has a transpose, so the
        generic vjp grad maker differentiates it — grads flow through
        exactly the active iterations. This is the documented
        bounded-iteration lowering for grad-of-while.
    """
    import jax
    import jax.numpy as jnp

    cond_blk, body_blk = attrs["cond_block"], attrs["body_block"]
    carry_names = list(attrs["carry_names"])
    body_out_names = list(attrs["body_out_names"])
    ext_names = list(attrs["ext_names"])
    cond_out = attrs["cond_out_name"]
    step = attrs.get("__step__")
    ext_env = dict(zip(ext_names, ins.get("Ext", [])))

    def cond_fn(carry):
        env = dict(ext_env)
        env.update(zip(carry_names, carry))
        _run_sub_block(cond_blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        c = env[cond_out]
        return c.reshape(()) if getattr(c, "ndim", 0) else c

    def body_fn(carry):
        env = dict(ext_env)
        env.update(zip(carry_names, carry))
        _run_sub_block(body_blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        return tuple(env[n] for n in body_out_names)

    max_iters = int(attrs.get("grad_max_iters", 0) or 0)
    if max_iters > 0:
        def scan_body(carry, _):
            active = cond_fn(carry)
            new = body_fn(carry)
            out = tuple(jnp.where(active, n, c)
                        for n, c in zip(new, carry))
            return out, None

        outs, _ = jax.lax.scan(scan_body, tuple(ins["X"]), None,
                               length=max_iters)
        # runtime truncation guard (ADVICE r3): if the condition still
        # holds after max_iters steps the result is silently wrong for
        # THIS input (the trace-time warning only saw the example input).
        # Interpreting path (concrete values): raise. Compiled path
        # (tracers): loud host-side warning via debug callback — raising
        # inside an XLA callback does not propagate reliably.
        nc = cond_fn(outs)
        trunc_msg = (
            f"while_loop: bounded scan truncated at {max_iters} "
            f"iterations — the runtime trip count exceeds grad_max_iters "
            f"(set from the traced example input); results are WRONG for "
            f"this input. Pass to_static(fn, loop_max_iters=N) / "
            f"while_loop(grad_max_iters=N) with a larger bound.")
        concrete = True
        try:
            truncated = bool(nc)
        except Exception:
            concrete = False
        if concrete:
            if truncated:
                raise RuntimeError(trunc_msg)
        else:
            # compiled-path guard via debug callback (host callbacks
            # compile under jit on the CPU and the TPU runtime alike)
            def _host_guard(t):
                if t:
                    import warnings

                    warnings.warn(trunc_msg, stacklevel=2)

            jax.debug.callback(_host_guard, nc)
        return {"Out": list(outs)}

    outs = jax.lax.while_loop(cond_fn, body_fn, tuple(ins["X"]))
    return {"Out": list(outs)}


from ..core.registry import default_grad_maker, register_grad_maker  # noqa: E402


@register_grad_maker("while_loop")
def _while_loop_grad_maker(op, out_grads, in_grads):
    """Grads of an UNBOUNDED while would crash deep inside jax ('reverse
    -mode differentiation does not work for lax.while_loop'); surface the
    fix at program-build time instead. With grad_max_iters the bounded
    scan lowering transposes fine -> generic vjp."""
    if not int(op.attrs.get("grad_max_iters", 0) or 0):
        wanted = any(g is not None
                     for gs in in_grads.values() for g in (gs or []))
        if wanted:
            raise ValueError(
                "while_loop is not reverse-differentiable with a dynamic "
                "trip count (XLA while has no transpose); pass "
                "grad_max_iters=N to while_loop for the bounded-scan "
                "lowering, or use static_loop")
        return []
    return default_grad_maker(op, out_grads, in_grads)


@register_op("static_loop", skip_infer_shape=True,
             required_attrs=("body_block", "carry_names", "body_out_names",
                             "ext_names", "i_name", "num_steps"))
def static_loop_op(ins, attrs):
    """Fixed-trip lax.scan loop (layers/control_flow.py static_loop) —
    reverse-differentiable; the StaticRNN role with static shapes."""
    import jax
    import jax.numpy as jnp

    blk = attrs["body_block"]
    carry_names = list(attrs["carry_names"])
    body_out_names = list(attrs["body_out_names"])
    ext_names = list(attrs["ext_names"])
    i_name = attrs["i_name"]
    n = int(attrs["num_steps"])
    step = attrs.get("__step__")
    ext_env = dict(zip(ext_names, ins.get("Ext", [])))

    def body(carry, i):
        env = dict(ext_env)
        env.update(zip(carry_names, carry))
        env[i_name] = i
        _run_sub_block(blk, env, step=step, axis_coords=attrs.get('__axis_coords__'))
        return tuple(env[nm] for nm in body_out_names), None

    (outs), _ = jax.lax.scan(body, tuple(ins["X"]), jnp.arange(n))
    return {"Out": list(outs)}


@register_op("array_read", non_diff_inputs=("I",))
def array_read(ins, attrs):
    """Read slot I of a step-stacked tensor array (reference:
    controlflow/tensor_array_read_write.cc ReadFromArray — LoDTensorArray
    becomes a [S, ...] stacked tensor under static shapes; dynamic index
    lowers to lax.dynamic_index inside scans)."""
    import jax.numpy as jnp

    x, i = ins["X"][0], ins["I"][0]
    return {"Out": jnp.take(x, jnp.asarray(i, jnp.int32).reshape(()),
                            axis=0)}


@register_op("array_write", non_diff_inputs=("I",))
def array_write(ins, attrs):
    """Write V into slot I of the stacked array (reference WriteToArray);
    functional: returns the updated buffer (the executor threads it
    in-place through the var name)."""
    import jax.numpy as jnp

    x, i, v = ins["X"][0], ins["I"][0], ins["V"][0]
    return {"Out": x.at[jnp.asarray(i, jnp.int32).reshape(())].set(
        v.astype(x.dtype))}


@register_op("lod_rank_table", non_diff_inputs=("X",))
def lod_rank_table(ins, attrs):
    """Length-descending rank table (reference:
    lod_rank_table_op.cc — items sorted by sequence length desc, used to
    schedule shrinking-batch RNN decoding). Padded form: X carries the
    per-row Length [B]; outputs Items (sorted lengths) and Index (the
    original row of each sorted position)."""
    import jax.numpy as jnp

    ln = ins["X"][0].reshape(-1).astype(jnp.int32)
    order = jnp.argsort(-ln, stable=True)
    return {"Items": ln[order], "Index": order.astype(jnp.int32)}


@register_op("split_lod_tensor", non_diff_inputs=("Mask",))
def split_lod_tensor(ins, attrs):
    """Route rows of X by a boolean Mask (reference:
    split_lod_tensor_op.cc — the IfElse building block that compacts
    true/false rows into two LoD tensors). Static-shape re-design: both
    outputs keep X's full shape with the non-selected rows ZEROED
    instead of compacted — the merge_lod_tensor recombination (and thus
    IfElse semantics) is exactly preserved, while XLA keeps static
    shapes. Branch bodies that mix rows (e.g. batch reductions) see the
    zero rows; layers/control_flow.py IfElse documents this contract.
    Mask [B,1] (or [B]) bool/float over the leading axis."""
    import jax.numpy as jnp

    x = ins["X"][0]
    mask = ins["Mask"][0].reshape(-1).astype(bool)
    m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
    zero = jnp.zeros((), x.dtype)
    return {"OutTrue": jnp.where(m, x, zero),
            "OutFalse": jnp.where(m, zero, x)}


@register_op("merge_lod_tensor", non_diff_inputs=("Mask",))
def merge_lod_tensor(ins, attrs):
    """Merge per-branch rows back by Mask (reference:
    merge_lod_tensor_op.cc): Out[i] = InTrue[i] if Mask[i] else
    InFalse[i]. With the zero-padded split above this is the exact
    inverse of split_lod_tensor, and composing split -> branch ->
    merge reproduces the reference IfElse row-for-row."""
    import jax.numpy as jnp

    t, f = ins["InTrue"][0], ins["InFalse"][0]
    mask = ins["Mask"][0].reshape(-1).astype(bool)
    m = mask.reshape((-1,) + (1,) * (t.ndim - 1))
    return {"Out": jnp.where(m, t, f.astype(t.dtype))}


@register_op("run_program", skip_infer_shape=True,
             required_attrs=("program",))
def run_program(ins, attrs):
    """Execute a captured sub-Program as ONE op (reference:
    operators/run_program_op.cc — the dygraph<->static bridge backing
    partial_program.py PartialProgramLayer).

    Inputs: X = the sub-program's feed tensors (attr feed_names order),
    Params = its parameters (attr param_names order). Outputs: Out =
    attr fetch_names. The attrs carry the Program object itself (the
    same block-carrying convention as the cond/while ops), so the op is
    a real program-as-an-op re-entry point: the generic vjp grad op
    re-traces the block, which IS the sub-program's backward — grads
    flow to Params and X exactly like the reference's grad block.

    The block execution is jitted once per Program (cached on the
    Program object) so eager dygraph pays one dispatch per call, not
    one per contained op — the to_static speedup the reference gets
    from executor caching."""
    import jax

    from .. import core as _core  # noqa: F401  (executor import cycle)
    from ..core.executor import run_block

    prog = attrs["program"]
    feed_names = list(attrs.get("feed_names", ()))
    param_names = list(attrs.get("param_names", ()))
    fetch_names = list(attrs.get("fetch_names", ()))
    env = {}
    for n, v in zip(param_names, ins.get("Params", []) or []):
        env[n] = v
    for n, v in zip(feed_names, ins.get("X", []) or []):
        env[n] = v
    step = attrs.get("__step__")

    import jax.core as jcore

    tracing = any(isinstance(v, jcore.Tracer) for v in env.values())
    if tracing:
        # already under an outer jit/vjp trace: run inline
        run_block(prog.global_block(), env, step=step)
        return {"Out": [env[n] for n in fetch_names]}
    fn = getattr(prog, "_run_program_jit", None)
    if fn is None:
        block = prog.global_block()

        def call(e, step_arr):
            ee = dict(e)
            run_block(block, ee, step=step_arr)
            return [ee[n] for n in fetch_names]

        fn = jax.jit(call)
        prog._run_program_jit = fn
    import jax.numpy as jnp

    outs = fn(env, jnp.asarray(0 if step is None else step, jnp.int32))
    return {"Out": list(outs)}
