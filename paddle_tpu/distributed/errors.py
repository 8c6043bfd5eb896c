"""Typed errors for the distributed/PS transport.

The reference distinguishes transport failures (gRPC status codes, the
retry env knobs GRPC_* consumed by grpc_client.cc) from application
errors surfaced by the remote handler. The seed collapsed everything
into RuntimeError, which forced ElasticRunner's RECOVERABLE tuple to
include plain RuntimeError — swallowing programming errors. This module
gives the transport its own hierarchy so recovery policy can be precise:

* RpcError            — transport-level failure after retries were
                        exhausted (reconnects kept failing). Recoverable.
* RpcDeadlineError    — the per-call deadline (FLAGS_ps_rpc_timeout)
                        elapsed before a reply arrived; also a
                        TimeoutError so pre-existing timeout handling
                        still matches. Recoverable.
* RpcRemoteError      — the remote handler raised and the error was
                        passed back over the wire (the '__err__' status).
                        Kept under RpcError because the dominant causes
                        (sync-barrier stalls, checkpoint races) are
                        transient cluster conditions, not local bugs.
* BarrierTimeoutError — raised pserver-side when a sync barrier stalls
                        past FLAGS_ps_sync_barrier_timeout; trainers see
                        it as an RpcRemoteError naming this type.
"""

from __future__ import annotations


class RpcError(RuntimeError):
    """PS transport failure (connect/send/recv kept failing)."""


class RpcDeadlineError(RpcError, TimeoutError):
    """Per-call deadline exceeded before a reply arrived."""


class RpcRemoteError(RpcError):
    """The remote handler raised; the error text travelled back as an
    '__err__' status frame. `.remote_type` holds the peer-side exception
    class name when it could be parsed."""

    def __init__(self, message: str, remote_type: str = ""):
        super().__init__(message)
        self.remote_type = remote_type


class BarrierTimeoutError(RuntimeError):
    """Sync barrier stalled past its timeout (pserver-side)."""
