"""Parameter-server IR ops: send / recv / barriers.

Capability mirror of the reference's distributed_ops
(operators/distributed_ops/send_op.cc, recv_op.cc, send_barrier_op.cc,
fetch_barrier_op.cc, listen_and_serv_op.cc): side-effecting host ops
carrying tensors between trainer and pserver over the ps.rpc transport.

These ops do HOST network IO, so they run on the interpreting executor
(op-by-op, the reference's executor.cc model — the natural home for PS
workloads, whose reference workers are CPU Hogwild threads). The
compiling executor refuses programs containing them; Executor.run
auto-routes such programs to the interpreting path.

Sync protocol (transpiler sync_mode=True): send carries trainer_id; the
pserver applies a param's update once all trainers' grads arrived and
bumps the param version; recv blocks for version >= step+1 — per-param
versioned barriers, no global lockstep needed (the reference's
send_barrier/fetch_barrier exist as explicit no-op markers).
"""

from __future__ import annotations

from ..core.registry import register_op


@register_op("send", skip_infer_shape=True)
def send_op(ins, attrs):
    import numpy as np

    from ..distributed.ps.rpc import RPCClient

    cli = RPCClient.get(attrs["endpoint"])
    # values arrive positionally; var NAMES travel in the var_names attr
    # (set by the transpiler) since lowerings never see names
    for name, val in zip(attrs["var_names"], ins.get("X", [])):
        cli.call("send_grad", name, np.asarray(val),
                 aux=int(attrs.get("trainer_id", 0)))
    return {}


# client-side per-(endpoint, param, trainer) last-seen version: sync recv
# waits for last+1 (one update per training step); after a trainer restart
# the dict resets to 0 and the wait degrades to "current version" — safe
# resume. Keyed by trainer_id so multiple in-process trainers (threads in
# tests, chaos harnesses) track versions independently.
_recv_versions = {}


def reset_recv_versions():
    _recv_versions.clear()


@register_op("recv", skip_infer_shape=True)
def recv_op(ins, attrs):
    from ..distributed.ps.rpc import RPCClient

    cli = RPCClient.get(attrs["endpoint"])
    sync = bool(attrs.get("sync_mode", True))
    outs = []
    for name in attrs["var_names"]:
        key = (attrs["endpoint"], name, int(attrs.get("trainer_id", 0)))
        want = _recv_versions.get(key, 0) + 1 if sync else 0
        val, ver = cli.call("recv_param", name, aux=want)
        _recv_versions[key] = ver
        outs.append(val)
    return {"Out": outs}


@register_op("send_barrier", skip_infer_shape=True)
def send_barrier_op(ins, attrs):
    from ..distributed.ps.rpc import RPCClient

    for ep in attrs.get("endpoints", []):
        RPCClient.get(ep).call("barrier")
    return {}


@register_op("fetch_barrier", skip_infer_shape=True)
def fetch_barrier_op(ins, attrs):
    from ..distributed.ps.rpc import RPCClient

    for ep in attrs.get("endpoints", []):
        RPCClient.get(ep).call("barrier")
    return {}


def _kv_client(attrs):
    from ..distributed.ps.kv_service import get_kv_client

    return get_kv_client(str(attrs["endpoints"]), str(attrs["table_name"]),
                         int(attrs["dim"]), int(attrs.get("seed", 0)))


def _kv_ids(ids_np):
    """JAX runs x64-disabled, so int64 id feeds reach the graph as int32
    (ids >= 2^32 alias — documented limit of the in-graph op; use
    DistributedKV directly for full 64-bit id spaces). Reinterpret the
    wrapped int32 as unsigned so ids in [2^31, 2^32) keep distinct,
    non-negative table keys."""
    import numpy as np

    arr = np.asarray(ids_np)
    if arr.dtype == np.int32:
        arr = arr.astype(np.int64) & 0xFFFFFFFF
    return arr


@register_op("distributed_lookup_table", non_diff_inputs=("Ids",))
def distributed_lookup_table(ins, attrs):
    """Pull embedding rows for Ids from the remote sharded KV service
    (reference: operators/distributed_ops/distributed_lookup_table_op.cc;
    servers: distributed/ps/kv_service.py). Ids [...]; W is the [1, dim]
    proxy parameter that threads the op into the grad graph (the
    reference op's W input plays the same meta role — the real table
    lives server-side); Out [..., dim] f32. jax.io_callback keeps the
    pull composable with jit: the dense compute stays compiled while the
    lookup round-trips to the pserver hosts.

    Attrs: endpoints (comma list), table_name, dim, seed, lr (server-side
    SGD rate applied by the backward push op)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    ids = ins["Ids"][0]
    dim = int(attrs["dim"])
    cfg = {k: attrs[k] for k in ("endpoints", "table_name", "dim")}
    cfg["seed"] = attrs.get("seed", 0)

    def pull_host(ids_np):
        arr = _kv_ids(ids_np)
        rows = _kv_client(cfg).pull(arr.reshape(-1))
        return rows.reshape(arr.shape + (dim,))

    shape = tuple(int(d) for d in ids.shape) + (dim,)
    out = io_callback(pull_host, jax.ShapeDtypeStruct(shape, jnp.float32),
                      ids, ordered=True)
    return {"Out": out}


@register_op("distributed_lookup_table_grad", skip_infer_shape=True,
             non_diff_inputs=("Ids", "W", "OutGrad"))
def distributed_lookup_table_grad(ins, attrs):
    """Backward push: send the row cotangents to the owning pservers
    (server-side SGD apply — reference fleet_wrapper.h
    PushSparseVarsWithLabelAsync). WGrad is zeros for the proxy param;
    the io_callback's IO effect keeps the push alive under jit even
    though only those zeros flow onward."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    ids, w, og = ins["Ids"][0], ins["W"][0], ins["OutGrad"][0]
    dim = int(attrs["dim"])
    lr = float(attrs.get("lr", 0.01))
    cfg = {k: attrs[k] for k in ("endpoints", "table_name", "dim")}
    cfg["seed"] = attrs.get("seed", 0)

    def push_host(ids_np, grads_np):
        import numpy as np

        arr = _kv_ids(ids_np)
        _kv_client(cfg).push(arr.reshape(-1),
                             np.asarray(grads_np).reshape(arr.size, dim),
                             lr=lr)
        return np.zeros((), np.int32)

    io_callback(push_host, jax.ShapeDtypeStruct((), jnp.int32), ids,
                og.astype(jnp.float32), ordered=True)
    return {"WGrad": jnp.zeros_like(w)}


from ..core.ir import OpDesc  # noqa: E402
from ..core.registry import register_grad_maker  # noqa: E402


@register_grad_maker("distributed_lookup_table")
def _distributed_lookup_table_grad_maker(op, out_grads, in_grads):
    og = (out_grads.get("Out") or [None])[0]
    wg = (in_grads.get("W") or [None])[0]
    if og is None or wg is None:
        return []
    return [OpDesc("distributed_lookup_table_grad",
                   {"Ids": list(op.inputs["Ids"]),
                    "W": list(op.inputs["W"]), "OutGrad": [og]},
                   {"WGrad": [wg]}, dict(op.attrs))]


@register_op("listen_and_serv", skip_infer_shape=True)
def listen_and_serv_op(ins, attrs):
    """Marker op (reference listen_and_serv_op.cc) — the actual serving
    loop is distributed.ps.pserver.PServer.run(); fleet/launch start it
    directly. Executing the op raises to catch misuse."""
    raise RuntimeError(
        "listen_and_serv is a pserver-role marker; start the server via "
        "paddle_tpu.distributed.ps.PServer(...).run()")
