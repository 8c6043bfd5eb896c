"""pipeline_forward op — the GPipe microbatch schedule as one XLA program.

Capability mirror of the reference's pipeline stack (PipelineOptimizer
optimizer.py:3695, PipelineTrainer pipeline_trainer.cc:24, SectionWorker
section_worker.cc:82) re-designed for TPU: instead of one thread + queue per
stage, the whole schedule lives inside one jitted computation over the 'pp'
mesh axis — `lax.switch` on the rank id picks the stage body, activations
rotate stage→stage via `lax.ppermute` each tick, and the backward schedule
falls out of jax.vjp through the forward (ppermute transposes to the
reverse ring).

The op consumes every external var of all stages (feeds + params), emits a
per-rank partial loss sum over microbatches (nonzero only on the last
stage's rank); the PipelineOptimizer follows it with
c_allreduce_sum('pp') + scale(1/M) to form the global loss.
"""

from __future__ import annotations

from typing import Any, Dict, List

from ..core.registry import register_op


def _pipeline_env(ins, attrs):
    """Shared setup for both schedule ops: flat env of op inputs keyed by
    var name, and the data feeds reshaped [B, ...] -> [M, B/M, ...]."""
    env: Dict[str, Any] = {}
    for slot, vals in ins.items():
        names = attrs["input_names"][slot]
        for name, val in zip(names, vals):
            env[name] = val
    m = int(attrs["num_microbatches"])
    mb_feeds = {}
    for name in attrs["mb_feed_names"]:
        v = env.pop(name)
        if v.shape[0] % m:
            raise ValueError(
                f"pipeline feed '{name}' batch {v.shape[0]} not divisible "
                f"by num_microbatches={m}")
        mb_feeds[name] = v.reshape((m, v.shape[0] // m) + v.shape[1:])
    return env, mb_feeds


def _check_ring(axis, n):
    from jax import lax

    nranks = lax.axis_size(axis)
    if nranks != n:
        raise ValueError(
            f"pipeline: '{axis}' mesh axis has {nranks} ranks but the "
            f"program has {n} stages — they must match")


@register_op("pipeline_forward", is_collective=True, skip_infer_shape=True)
def pipeline_forward(ins, attrs):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..core.executor import run_op
    from .collective_ops import _in_spmd

    stages: List[List] = attrs["stages"]                # list of op lists
    boundaries: List[List[str]] = attrs["boundaries"]   # iface names per cut
    # scalars produced by the last stage and summed over microbatches; the
    # classic form is a single loss, the composed (SP x PP) form is e.g.
    # [num, denom] with normalisation + collectives as post-ops OUTSIDE
    # this op (keeps every branch of the lax.switch collective-uniform)
    acc_names: List[str] = list(attrs.get("acc_names")
                                or [attrs["loss_name"]])
    m = int(attrs["num_microbatches"])
    axis = attrs.get("axis_name", "pp")
    n = len(stages)
    na = len(acc_names)

    env, mb_feeds = _pipeline_env(ins, attrs)
    step = attrs.get("__step__")

    def bind_mb(e, mb):
        for name, v in mb_feeds.items():
            e[name] = lax.dynamic_index_in_dim(v, mb, keepdims=False)

    def run_stage(k, e):
        for op in stages[k]:
            run_op(op, e, step=step, axis_coords=attrs.get('__axis_coords__'))

    def stage_body(k, buf, mb):
        """Run stage k for microbatch index mb; buf = incoming interface."""
        e = dict(env)
        bind_mb(e, mb)           # stage 0 consumes data; later stages may
        if k > 0:                # read labels/masks from the feed too
            for name, val in zip(boundaries[k - 1], buf):
                e[name] = val
        run_stage(k, e)
        return e

    def accs_of(e):
        return tuple(e[nm].astype(jnp.float32).reshape(()) for nm in acc_names)

    def pack(accs):
        if len(accs) == 1:
            return {"AccPartials": [accs[0]], "LossPartial": accs[0]}
        return {"AccPartials": list(accs), "LossPartial": accs[0]}

    # -- single-rank / no-'pp'-axis mode: sequential microbatch loop ---------
    if n == 1 or not _in_spmd(axis):
        total = (jnp.float32(0.0),) * na
        for mb in range(m):
            buf = ()
            for k in range(n):
                e = stage_body(k, buf, mb)
                if k < n - 1:
                    buf = tuple(e[nm] for nm in boundaries[k])
            total = tuple(t + a for t, a in zip(total, accs_of(e)))
        return pack(total)

    # -- SPMD GPipe schedule over the 'pp' ring ------------------------------
    def branch(k):
        def fn(buf, mb):
            e = stage_body(k, buf, mb)
            if k < n - 1:
                return (tuple(e[nm] for nm in boundaries[k]),
                        (jnp.float32(0.0),) * na)
            zero_out = tuple(jnp.zeros_like(b) for b in buf)
            return zero_out, accs_of(e)

        return fn

    _check_ring(axis, n)
    branches = [branch(k) for k in range(n)]
    r = lax.axis_index(axis)

    # uniform interface structure, derived abstractly from stage 0
    iface_struct, _ = jax.eval_shape(
        lambda mb: branches[0]((), mb), jnp.int32(0))
    buf0 = tuple(jnp.zeros(s.shape, s.dtype) for s in iface_struct)
    perm = [(i, (i + 1) % n) for i in range(n)]
    ticks = m + n - 1

    # scan over ticks: each stage body is traced ONCE (inside switch), not
    # per tick — keeps HLO size O(n) instead of O(n * (m+n))
    def tick(carry, t):
        buf, acc = carry
        mb_idx = jnp.clip(t - r, 0, m - 1).astype(jnp.int32)
        valid = jnp.logical_and(t - r >= 0, t - r < m)
        out, ls = lax.switch(r, branches, buf, mb_idx)
        acc = tuple(a + jnp.where(valid, l, 0.0) for a, l in zip(acc, ls))
        buf = tuple(lax.ppermute(o, axis, perm) for o in out)
        return (buf, acc), None

    (_, acc), _ = lax.scan(tick, (buf0, (jnp.float32(0.0),) * na),
                           jnp.arange(ticks))
    return pack(acc)


@register_op("pipeline_1f1b", is_collective=True, skip_infer_shape=True)
def pipeline_1f1b(ins, attrs):
    """Steady-state 1F1B microbatch schedule (reference:
    section_worker.cc:82 steady-state loop, optimizer.py:3695), as ONE
    XLA computation that produces the loss AND the parameter gradients.

    Where `pipeline_forward` (GPipe) gets its backward from jax.vjp of
    the whole forward scan — storing scan residuals for all M microbatches
    — this op hand-schedules the reference's 1F1B pattern: each scan step
    is a (forward microbatch, backward microbatch) pair per rank, stage
    backward runs via per-stage jax.vjp with the stage forward RECOMPUTED
    from a saved-input ring buffer of depth 2*n. Activation memory is
    O(num_stages), independent of num_microbatches — the same memory
    property that makes the reference's 1F1B viable at scale.

    Schedule (pair index i, rank r, n stages, m microbatches):
      forward  of microbatch f on rank r at i = r + f
      backward of microbatch b on rank r at i = (2n - 2 - r) + b
    Total pairs = m + 2n - 2 (the extra n-1 warmup pairs vs the
    theoretical 1F1B bound keep every collective unconditionally executed
    on every rank — a requirement for SPMD ppermute correctness).
    Activations rotate +1 over the 'pp' ring, cotangents rotate -1.

    Outputs: LossPartial (sum of per-microbatch losses, last rank only;
    divide by M outside) and one gradient per trainable param
    (grads of params of OTHER ranks' stages are zero — the
    PipelineOptimizer allreduce-sums them over the ring).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..core.executor import run_op
    from .collective_ops import _in_spmd

    stages: List[List] = attrs["stages"]
    boundaries: List[List[str]] = attrs["boundaries"]
    mb_feed_names: List[str] = list(attrs["mb_feed_names"])
    param_names: List[str] = list(attrs["param_names"])
    loss_name: str = attrs["loss_name"]
    m = int(attrs["num_microbatches"])
    axis = attrs.get("axis_name", "pp")
    n = len(stages)

    env, mb_feeds = _pipeline_env(ins, attrs)
    step = attrs.get("__step__")
    params = {nm: env.pop(nm) for nm in param_names}

    def stage_fn(k, p, x_iface, mb):
        """Stage k as a pure function of (params, incoming iface, mb idx).
        Returns the outgoing iface tuple, or the loss scalar for the last
        stage."""
        e = dict(env)
        e.update(p)
        for name, v in mb_feeds.items():
            e[name] = lax.dynamic_index_in_dim(v, mb, keepdims=False)
        if k > 0:
            for name, val in zip(boundaries[k - 1], x_iface):
                e[name] = val
        for op in stages[k]:
            run_op(op, e, step=step, axis_coords=attrs.get('__axis_coords__'))
        if k == n - 1:
            return e[loss_name].astype(jnp.float32).reshape(())
        return tuple(e[nm] for nm in boundaries[k])

    # loss = (sum over microbatches) / m outside -> per-microbatch seed 1/m
    seed = jnp.float32(1.0 / m)

    # -- single-rank / no-'pp'-axis mode: sequential, same math -------------
    if n == 1 or not _in_spmd(axis):
        total = jnp.float32(0.0)
        grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), params)
        for mb in range(m):

            def full_fwd(p, mb=mb):
                buf = ()
                for k in range(n):
                    buf = stage_fn(k, p, buf, jnp.int32(mb))
                return buf
            loss_mb, pull = jax.vjp(full_fwd, params)
            (dp,) = pull(seed)
            grads = jax.tree_util.tree_map(lax.add, grads, dp)
            total = total + loss_mb
        out = {"LossPartial": total}
        out["ParamGrads"] = [grads[nm] for nm in param_names]
        return out

    # -- SPMD 1F1B over the 'pp' ring ---------------------------------------
    _check_ring(axis, n)
    r = lax.axis_index(axis)

    def fwd_branch(k):
        def fn(x_iface, mb):
            out = stage_fn(k, params, x_iface, mb)
            if k == n - 1:
                zero_ifc = tuple(jnp.zeros_like(b) for b in x_iface)
                return zero_ifc, out
            return out, jnp.float32(0.0)
        return fn

    def bwd_branch(k):
        def fn(x_iface, mb, dout):
            f = lambda p, x: stage_fn(k, p, x, mb)
            _, pull = jax.vjp(f, params, x_iface)
            ct = seed if k == n - 1 else dout
            dp, dx = pull(ct)
            return dx, dp
        return fn

    fwd_branches = [fwd_branch(k) for k in range(n)]
    bwd_branches = [bwd_branch(k) for k in range(n)]

    iface_struct, _ = jax.eval_shape(
        lambda mb: fwd_branches[0]((), mb), jnp.int32(0))
    zeros_iface = tuple(jnp.zeros(s.shape, s.dtype) for s in iface_struct)
    W = 2 * n                                  # saved-input ring depth
    saved0 = tuple(jnp.zeros((W,) + s.shape, s.dtype) for s in iface_struct)
    grads0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, p.dtype), params)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    pairs = m + 2 * n - 2

    def pair(carry, i):
        fbuf, gbuf, saved, grads, loss_acc = carry

        # ---- forward half: microbatch f = i - r ----
        f_idx = i - r
        valid_f = jnp.logical_and(f_idx >= 0, f_idx < m)
        f_mb = jnp.clip(f_idx, 0, m - 1).astype(jnp.int32)
        out_ifc, loss_mb = lax.switch(r, fwd_branches, fbuf, f_mb)
        loss_acc = loss_acc + jnp.where(valid_f, loss_mb, 0.0)
        slot_f = (f_mb % W).astype(jnp.int32)
        saved = tuple(
            lax.dynamic_update_index_in_dim(
                buf,
                jnp.where(valid_f, x,
                          lax.dynamic_index_in_dim(buf, slot_f,
                                                   keepdims=False)),
                slot_f, 0)
            for buf, x in zip(saved, fbuf))
        fbuf = tuple(lax.ppermute(o, axis, perm_fwd) for o in out_ifc)

        # ---- backward half: microbatch b = i - (2n - 2 - r) ----
        b_idx = i - (2 * n - 2 - r)
        valid_b = jnp.logical_and(b_idx >= 0, b_idx < m)
        b_mb = jnp.clip(b_idx, 0, m - 1).astype(jnp.int32)
        slot_b = (b_mb % W).astype(jnp.int32)
        x_saved = tuple(
            lax.dynamic_index_in_dim(buf, slot_b, keepdims=False)
            for buf in saved)
        dx, dp = lax.switch(r, bwd_branches, x_saved, b_mb, gbuf)
        grads = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(valid_b, d.astype(g.dtype),
                                       jnp.zeros_like(g)),
            grads, dp)
        gbuf = tuple(lax.ppermute(d, axis, perm_bwd) for d in dx)

        return (fbuf, gbuf, saved, grads, loss_acc), None

    gbuf0 = zeros_iface
    (_, _, _, grads, loss_acc), _ = lax.scan(
        pair, (zeros_iface, gbuf0, saved0, grads0, jnp.float32(0.0)),
        jnp.arange(pairs))
    out = {"LossPartial": loss_acc}
    out["ParamGrads"] = [grads[nm] for nm in param_names]
    return out
