"""Collective op lowerings — XLA cross-replica collectives over ICI.

Capability mirror of paddle/fluid/operators/collective/ (c_allreduce_op.h:124
ncclAllReduce, c_broadcast_op, c_allgather_op, c_reducescatter_op,
c_reduce_op, barrier_op, c_comm_init_op.cc, c_gen_nccl_id_op.cc,
c_sync_calc_stream_op.cc, c_sync_comm_stream_op.cc).

Design: each collective carries a mesh axis name (the reference's ring_id →
axis name mapping lives in the op attrs). When the op executes inside a
`shard_map` SPMD region (collective executor mode, executor.py) the lowering
emits `lax.psum`-family primitives that compile to ICI collectives. Outside
an SPMD region (single-rank semantics) they are identities — matching the
reference where a ring of size 1 is a no-op.

Stream-ordering ops (c_sync_*) are identities: XLA's dataflow order subsumes
the reference's manual compute/comm stream synchronisation.
"""

from __future__ import annotations

import numpy as np

from ..core.registry import register_grad_maker, register_op


def _axis_name(attrs):
    # ring_id kept for API parity; axis_name wins if present. May be a
    # tuple/list of axes (e.g. ("dp", "sp") grad allreduce for
    # sequence-parallel training) — lax.psum-family accept multi-axis.
    ax = attrs.get("axis_name")
    if ax:
        return tuple(ax) if isinstance(ax, (list, tuple)) else ax
    ring = int(attrs.get("ring_id", 0))
    return {0: "dp", 1: "mp", 2: "pp", 3: "sp"}.get(ring, "dp")


def _bound_axes(axis) -> tuple:
    """Subset of `axis` (name or tuple of names) bound as SPMD axes in the
    current trace — a program asking for ("dp","sp") still reduces over the
    axes the active mesh actually has."""
    import jax

    axes = axis if isinstance(axis, tuple) else (axis,)
    bound = []
    for a in axes:
        try:
            jax.lax.axis_index(a)
            bound.append(a)
        except Exception:
            pass
    return tuple(bound)


def _in_spmd(axis) -> bool:
    return bool(_bound_axes(axis))


def _allreduce(reduce_fn):
    def lowering(ins, attrs):
        import jax

        x = ins["X"][0]
        bound = _bound_axes(_axis_name(attrs))
        if bound:
            x = reduce_fn(x, bound if len(bound) > 1 else bound[0])
        return {"Out": x}

    return lowering


def _register_allreduce():
    import jax.lax as lax

    for name, fn in [("c_allreduce_sum", lax.psum),
                     ("c_allreduce_max", lax.pmax),
                     ("c_allreduce_min", lax.pmin),
                     ("c_allreduce_prod",
                      lambda x, ax: lax.all_gather(x, ax).prod(axis=0)),
                     ("allreduce", lax.psum)]:
        register_op(name, is_collective=True)(_allreduce(fn))


_register_allreduce()


@register_op("c_broadcast", is_collective=True)
def c_broadcast(ins, attrs):
    """Root's value to all ranks (reference: c_broadcast_op)."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    ax = _axis_name(attrs)
    root = int(attrs.get("root", 0))
    if _in_spmd(ax):
        full = jax.lax.all_gather(x, ax)
        x = full[root]
    return {"Out": x}


@register_op("c_allgather", is_collective=True)
def c_allgather(ins, attrs):
    """Concatenate shards along dim 0 (reference: c_allgather_op)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        x = jax.lax.all_gather(x, ax, tiled=True)
    return {"Out": x}


@register_op("c_reducescatter", is_collective=True)
def c_reducescatter(ins, attrs):
    """Reduce-sum then scatter along dim 0 (reference: c_reducescatter_op)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        x = jax.lax.psum_scatter(x, ax, tiled=True)
    return {"Out": x}


@register_op("c_reduce_sum", is_collective=True)
def c_reduce_sum(ins, attrs):
    """Reduce to root; non-roots keep the reduced value too (XLA has no
    cheaper rooted reduce on ICI; semantics superset of the reference)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        x = jax.lax.psum(x, ax)
    return {"Out": x}


@register_op("c_concat", is_collective=True)
def c_concat(ins, attrs):
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        x = jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)
    return {"Out": x}


@register_op("c_split", is_collective=True)
def c_split(ins, attrs):
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        idx = jax.lax.axis_index(ax)
        n = jax.lax.axis_size(ax)
        per = x.shape[-1] // n
        x = jax.lax.dynamic_slice_in_dim(x, idx * per, per, axis=x.ndim - 1)
    return {"Out": x}


@register_op("c_ppermute", is_collective=True)
def c_ppermute(ins, attrs):
    """Ring permute — the sequence-parallel / pipeline building block
    (no reference equivalent; the reference's peer-to-peer is PS RPC)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    shift = int(attrs.get("shift", 1))
    if _in_spmd(ax):
        n = jax.lax.axis_size(ax)
        perm = [(i, (i + shift) % n) for i in range(n)]
        x = jax.lax.ppermute(x, ax, perm)
    return {"Out": x}


@register_op("c_identity", is_collective=True)
def c_identity(ins, attrs):
    return {"Out": ins["X"][0]}


@register_op("barrier", is_collective=True)
def barrier(ins, attrs):
    """XLA programs are globally scheduled; barrier is an identity on the
    optional token input (reference: collective/barrier_op.cc)."""
    x = ins.get("X", [None])[0]
    return {"Out": x if x is not None else np.zeros((1,), np.float32)}


# -- comm bootstrap (API parity; mesh construction replaces ncclUniqueId) -----

@register_op("c_comm_init", is_collective=True)
def c_comm_init(ins, attrs):
    """Reference boots NCCL comms (c_comm_init_op.cc); here the Mesh already
    defines the comm domain — no-op kept for program compatibility."""
    return {}


@register_op("c_gen_unique_id", is_collective=True)
def c_gen_unique_id(ins, attrs):
    """Reference exchanges ncclUniqueId over TCP (c_gen_nccl_id_op.cc);
    jax.distributed's coordination service replaces it."""
    return {}


@register_op("c_sync_calc_stream", is_collective=True)
def c_sync_calc_stream(ins, attrs):
    return {"Out": ins["X"][0]}


@register_op("c_sync_comm_stream", is_collective=True)
def c_sync_comm_stream(ins, attrs):
    return {"Out": ins["X"][0]}


# -- gradients ---------------------------------------------------------------
# y = psum(x) over an axis: each local x contributes once to the global sum,
# so with a replicated upstream cotangent dL/dy, dL/dx_local = dL/dy —
# identity. (The default vjp-based grad maker would emit jax.vjp(psum),
# whose in-region transpose psums the replicated cotangent — an n× grad.)

def _identity_grad(op, out_grads, in_grads):
    from ..core.ir import OpDesc

    og = (out_grads.get("Out") or [None])[0]
    ig = (in_grads.get("X") or [None])[0]
    if og is None or ig is None:
        return []
    return [OpDesc("assign", {"X": [og]}, {"Out": [ig]}, {})]


for _t in ("c_allreduce_sum", "allreduce", "c_reduce_sum", "c_identity",
           "c_sync_calc_stream", "c_sync_comm_stream"):
    register_grad_maker(_t)(_identity_grad)


@register_op("c_reduce_max", is_collective=True)
def c_reduce_max(ins, attrs):
    """reference: collective/c_reduce_op.h (max variant)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    return {"Out": jax.lax.pmax(x, ax) if _in_spmd(ax) else x}


@register_op("c_reduce_min", is_collective=True)
def c_reduce_min(ins, attrs):
    """reference: collective/c_reduce_op.h (min variant)."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    return {"Out": jax.lax.pmin(x, ax) if _in_spmd(ax) else x}


@register_op("c_reduce_prod", is_collective=True)
def c_reduce_prod(ins, attrs):
    """reference: collective/c_reduce_op.h (prod variant)."""
    import jax
    import jax.numpy as jnp

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        x = jnp.exp(jax.lax.psum(jnp.log(jnp.maximum(jnp.abs(x), 1e-30)),
                                 ax)) * jnp.prod(
            jnp.sign(jax.lax.all_gather(x, ax)), axis=0)
    return {"Out": x}


@register_op("c_scatter", is_collective=True)
def c_scatter(ins, attrs):
    """Root's tensor split across ranks (reference:
    collective/c_scatter_op.cc). SPMD form: every rank holds the full
    input replicated; each keeps its own slice."""
    import jax

    x = ins["X"][0]
    ax = _axis_name(attrs)
    if _in_spmd(ax):
        n = jax.lax.axis_size(ax)
        idx = jax.lax.axis_index(ax)
        chunk = x.shape[0] // n
        x = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=0)
    return {"Out": x}


@register_op("broadcast", is_collective=True)
def broadcast(ins, attrs):
    """Legacy broadcast op (reference: distributed_ops/broadcast_op.cc);
    same lowering as c_broadcast."""
    return c_broadcast(ins, attrs)


@register_op("c_comm_init_all", is_collective=True)
def c_comm_init_all(ins, attrs):
    """reference: collective/c_comm_init_all_op.cc — comm setup is mesh
    construction on TPU; no-op marker like c_comm_init."""
    return {}


@register_op("c_gen_nccl_id", is_collective=True)
def c_gen_nccl_id(ins, attrs):
    """reference: collective/c_gen_nccl_id_op.cc (TCP bootstrap of the
    NCCL unique id) — jax.distributed's coordinator plays this role; the
    op is a no-op marker kept for program parity."""
    return {}


@register_op("local_sgd_sync", is_collective=True)
def local_sgd_sync(ins, attrs):
    """Every k steps, replace the local param with its cross-rank mean
    (reference: fleet/meta_optimizers/localsgd_optimizer.py — k local
    steps then averaged sync; transpiler/collective.py:270 LocalSGD).
    The pmean runs UNCONDITIONALLY every step (collectives must execute
    on every rank every step for SPMD uniformity); a where() keeps the
    local value between sync points."""
    import jax
    import jax.numpy as jnp

    p = ins["X"][0]
    ax = _axis_name(attrs)
    k = int(attrs.get("k_steps", 1))
    step = attrs.get("__step__")
    if not _in_spmd(ax):
        return {"Out": p}
    mean = jax.lax.pmean(p, ax)
    if k <= 1 or step is None:
        return {"Out": mean}
    do_sync = ((jnp.asarray(step) + 1) % k) == 0
    return {"Out": jnp.where(do_sync, mean, p)}
