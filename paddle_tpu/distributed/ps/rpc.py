"""Fault-tolerant RPC transport for the parameter-server runtime.

Capability mirror of the reference's PS transport
(operators/distributed/rpc_client.h, rpc_server.h, grpc/ + brpc/
implementations, send_recv.proto.in): a length-prefixed binary protocol
over TCP sockets carrying numpy tensors. The reference serialises
through protobuf + zero-copy bytebuffers over gRPC/BRPC; here the framing
is a 32-byte header (method id, dtype, ndim, aux, client id, sequence
number) + shape + raw array bytes — no pickle of untrusted data,
payloads are raw tensor buffers.

Failure is a first-class condition (the reference leans on gRPC's retry
env knobs + heart_beat_monitor.h; Li et al. OSDI'14 build retry into the
PS transport itself):

* every call carries a (client id, per-client monotonic seq) pair; the
  server remembers the last (seq, reply) per client, so a retried frame
  — e.g. a send_grad whose reply was lost — is answered from the cache
  instead of re-applied: exactly-once application under retries;
* RPCClient.call reconnects on ConnectionError/OSError and retries with
  exponential backoff + jitter under a per-call deadline
  (FLAGS_ps_rpc_timeout / FLAGS_ps_rpc_max_retries /
  FLAGS_ps_rpc_backoff), raising errors.RpcDeadlineError /
  errors.RpcError when the budget is gone, and evicting itself from the
  shared pool so the next get() starts from a fresh connection. The
  schedule itself (backoff curve, jitter, deadline-first decision) is
  the shared core/retry.py RetryPolicy — this transport contributes the
  sockets, the typed errors and the ps.rpc_* counter names;
* named fault-injection sites (core/faults.py): `ps.rpc.send` before a
  request frame leaves, `ps.rpc.recv` before the reply is read,
  `ps.handler` around server-side dispatch — a seeded PT_FAULT_SPEC
  drives deterministic chaos through the exact production code paths;
* telemetry: ps.rpc_retries / ps.rpc_reconnects /
  ps.rpc_deadline_exceeded / ps.rpc_dedup_hits alongside the existing
  call/bytes/latency accounting.

Server: a thread-per-connection loop dispatching to a handler object
(finished threads are reaped; shutdown closes live connections and joins
with a bounded wait). Client: one pooled connection per endpoint,
thread-safe via a lock, reconnecting under the hood.
"""

from __future__ import annotations

import itertools
import os
import socket
import struct
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ...core import faults, telemetry, trace
from ...core import flags as _flags
from ...core import retry as _retry
from ...core.analysis import lockdep
from ..errors import RpcDeadlineError, RpcError, RpcRemoteError

# trace-context separator on the wire: when a sampled trace is active the
# client appends "\x1f<trace>-<span>" to the frame's method string, so the
# context survives retries byte-identically (same frame, same seq) and the
# server's dedup replay path never re-dispatches — one logical client span,
# at most one handler span per applied request
_TRACE_SEP = "\x1f"

# method_len, name_len, dtype_code, ndim, aux, client_id, seq
_HDR = struct.Struct("<IIHHIQQ")
_DTYPES = ["float32", "float64", "int32", "int64", "uint8", "bool",
           "float16", "bfloat16"]
_MAX_FRAME = 1 << 33  # 8 GiB: generous tensor cap, rejects garbage lengths
_MAX_NDIM = 32


def _send_msg(sock, method: str, name: str, arr: Optional[np.ndarray],
              aux: int = 0, client: int = 0, seq: int = 0):
    mb = method.encode()
    nb = name.encode()
    if arr is None:
        head = _HDR.pack(len(mb), len(nb), 0xFFFF, 0, aux, client, seq)
        body = b""
        shape = b""
    else:
        arr = np.ascontiguousarray(arr)
        code = _DTYPES.index(str(arr.dtype))
        head = _HDR.pack(len(mb), len(nb), code, arr.ndim, aux, client, seq)
        shape = struct.pack(f"<{arr.ndim}q", *arr.shape)
        body = arr.tobytes()
    payload = head + mb + nb + shape + body
    sock.sendall(struct.pack("<Q", len(payload)) + payload)


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock) -> Tuple[str, str, Optional[np.ndarray], int, int, int]:
    """Decode one frame. Every header field is validated against the
    payload before any allocation/frombuffer — a malformed or truncated
    frame raises ConnectionError (connection-fatal, never mis-frames the
    next message) instead of IndexError deep in numpy."""
    (total,) = struct.unpack("<Q", _recv_exact(sock, 8))
    if total < _HDR.size or total > _MAX_FRAME:
        raise ConnectionError(f"malformed RPC frame: length {total}")
    payload = _recv_exact(sock, total)
    mlen, nlen, code, ndim, aux, client, seq = _HDR.unpack_from(payload, 0)
    off = _HDR.size
    if off + mlen + nlen > total or ndim > _MAX_NDIM:
        raise ConnectionError(
            f"malformed RPC frame: header (mlen={mlen} nlen={nlen} "
            f"ndim={ndim}) exceeds payload of {total}")
    method = payload[off:off + mlen].decode(); off += mlen
    name = payload[off:off + nlen].decode(); off += nlen
    if code == 0xFFFF:
        if off != total:
            raise ConnectionError("malformed RPC frame: trailing bytes "
                                  "on tensor-less message")
        return method, name, None, aux, client, seq
    if code >= len(_DTYPES) or off + 8 * ndim > total:
        raise ConnectionError(
            f"malformed RPC frame: dtype code {code} / shape overrun")
    shape = struct.unpack_from(f"<{ndim}q", payload, off)
    off += 8 * ndim
    if any(d < 0 for d in shape):
        raise ConnectionError(f"malformed RPC frame: negative dim {shape}")
    dt = np.dtype(_DTYPES[code])
    count = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if off + count * dt.itemsize != total:
        raise ConnectionError(
            f"malformed RPC frame: {total - off} body bytes for shape "
            f"{shape} {dt}")
    arr = np.frombuffer(payload, dtype=dt, offset=off, count=count)
    return method, name, arr.reshape(shape).copy(), aux, client, seq


class RPCServer:
    """reference: operators/distributed/rpc_server.h RPCServer +
    request_handler_impl.cc — handler(method, name, array, aux) ->
    (array|None, aux)."""

    def __init__(self, endpoint: str, handler: Callable):
        host, port = endpoint.rsplit(":", 1)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, int(port)))
        self._srv.listen(64)
        self.endpoint = f"{host}:{self._srv.getsockname()[1]}"
        self._handler = handler
        self._stop = threading.Event()
        self._threads = []
        self._conns = set()
        self._conns_lock = lockdep.lock("rpc.server.conns")
        # retry dedup: client_id -> (last seq, reply | None=in-flight).
        # The client serialises its calls, so one entry per client makes
        # a resent frame (reply lost in transit) answerable without
        # re-dispatching — exactly-once application for send_grad/kv_push.
        # A retry that lands while the original is STILL dispatching (the
        # client gave up on the reply early) waits on the condition for
        # the in-flight reply instead of racing a second apply.
        self._dedup: Dict[int, Tuple[int, Optional[tuple]]] = {}
        self._dedup_cv = lockdep.condition("rpc.server.dedup")
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               name="pt-ps-rpc-accept",
                                               daemon=True)
        self._accept_thread.start()

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            with self._conns_lock:
                self._conns.add(conn)
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name="pt-ps-rpc-conn", daemon=True)
            t.start()
            # reap finished connection threads so a long-lived server
            # with churning clients doesn't grow the list without bound;
            # the list is rebound here AND in shutdown() (another
            # thread), so both writers take the conns lock
            with self._conns_lock:
                self._threads.append(t)
                if len(self._threads) > 32:
                    self._threads = [th for th in self._threads
                                     if th.is_alive()]

    def _dedup_claim(self, client: int, seq: int) -> Optional[tuple]:
        """Returns the cached reply to replay for a duplicate frame, or
        None after claiming (seq, in-flight) — the caller must then
        dispatch and publish the reply. A duplicate of an in-flight
        original blocks here until the original publishes (or its
        connection thread dies and releases the claim)."""
        with self._dedup_cv:
            while True:
                entry = self._dedup.get(client)
                if entry is None or entry[0] != seq:
                    self._dedup[client] = (seq, None)   # claim
                    return None
                if entry[1] is not None:
                    return entry[1]
                # original still dispatching — wait for its reply
                if not self._dedup_cv.wait(timeout=30.0):
                    # wedged original: reclaim rather than hang the retry
                    self._dedup[client] = (seq, None)
                    return None

    def _dispatch(self, method, name, arr, aux) -> tuple:
        """Run the handler behind the `ps.handler` fault site. An
        injected ConnectionError/OSError drops the connection (the
        client retries); any other exception — injected or real — is
        returned to the caller as an '__err__' status."""
        try:
            faults.maybe_fail("ps.handler", method=method)
        except (ConnectionError, OSError):
            raise
        except Exception as e:
            return ("__err__", f"{type(e).__name__}: {e}", None, 0)
        try:
            out, oaux = self._handler(method, name, arr, aux)
        except Exception as e:  # surface to the caller, keep serving
            return ("__err__", f"{type(e).__name__}: {e}", None, 0)
        return ("ok", name, out, oaux)

    def _serve_conn(self, conn):
        try:
            while not self._stop.is_set():
                method, name, arr, aux, client, seq = _recv_msg(conn)
                # strip the propagated trace context (if any) BEFORE any
                # method comparison/dispatch — the wire method is
                # "<method>[\x1f<trace>-<span>]"
                method, _, tparent = method.partition(_TRACE_SEP)
                if method == "__stop__":
                    _send_msg(conn, "ok", "", None, client=client, seq=seq)
                    self._stop.set()
                    try:
                        self._srv.close()
                    except OSError:
                        pass
                    return
                if client and seq:
                    replay = self._dedup_claim(client, seq)
                    if replay is not None:
                        # a retry of the last frame: the original was
                        # applied but its reply was lost — answer from
                        # the cache, do NOT re-dispatch
                        telemetry.counter_add("ps.rpc_dedup_hits", 1,
                                              method=method)
                        _send_msg(conn, *replay, client=client, seq=seq)
                        continue
                try:
                    if tparent:
                        # continue the client's trace: one handler span per
                        # actually-dispatched request (replays above never
                        # reach here)
                        with trace.span_from(tparent, "ps.rpc.handler",
                                             method=method):
                            reply = self._dispatch(method, name, arr, aux)
                    else:
                        reply = self._dispatch(method, name, arr, aux)
                except BaseException:
                    # dispatch died without a reply (injected connection
                    # fault): release the in-flight claim so the retry
                    # re-dispatches instead of waiting forever
                    if client and seq:
                        with self._dedup_cv:
                            if self._dedup.get(client) == (seq, None):
                                del self._dedup[client]
                            self._dedup_cv.notify_all()
                    raise
                if client and seq:
                    # publish before the send: a reply lost on the wire
                    # must still be replayable to the retry
                    with self._dedup_cv:
                        self._dedup[client] = (seq, reply)
                        self._dedup_cv.notify_all()
                _send_msg(conn, *reply, client=client, seq=seq)
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()
            with self._conns_lock:
                self._conns.discard(conn)

    def wait(self):
        while not self._stop.is_set():
            self._stop.wait(0.2)

    def shutdown(self):
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        # unblock connection threads stuck in recv, then join (bounded:
        # daemon threads may not exit if a handler is wedged)
        with self._conns_lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        deadline = time.monotonic() + 2.0
        with self._conns_lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        with self._conns_lock:
            self._threads = [t for t in self._threads if t.is_alive()]


class RPCClient:
    """reference: operators/distributed/rpc_client.h (AsyncSendVar /
    AsyncGetVar surface, synchronous under the hood here) + the gRPC
    client's retry knobs, made explicit: call() reconnects and retries
    under a deadline instead of dying with its socket."""

    _pool: Dict[str, "RPCClient"] = {}
    _pool_lock = lockdep.lock("rpc.client.pool")
    _ids = itertools.count(1)

    def __init__(self, endpoint: str, timeout: Optional[float] = None):
        """timeout: socket/connect timeout when no per-call deadline is
        active (FLAGS_ps_rpc_timeout <= 0); None uses blocking sockets.
        Connection is LAZY — a client constructed while its server is
        down connects on the first call."""
        self.endpoint = endpoint
        self._timeout = timeout
        self._sock: Optional[socket.socket] = None
        # held for the WHOLE retry schedule of one call: this client's
        # calls are serialised by design (one socket, one in-flight seq)
        self._lock = lockdep.lock("rpc.client.call")
        self._was_connected = False
        # (client id, per-call seq) ride the frame header for server-side
        # retry dedup; pid + process counter keeps ids unique across the
        # trainer fleet without coordination
        self._client_id = ((os.getpid() & 0xFFFFFFFF) << 32) | \
            (next(RPCClient._ids) & 0xFFFFFFFF)
        self._seq = 0

    @classmethod
    def get(cls, endpoint: str) -> "RPCClient":
        with cls._pool_lock:
            cli = cls._pool.get(endpoint)
            if cli is None:
                cli = cls(endpoint)
                cls._pool[endpoint] = cli
            return cli

    @classmethod
    def reset_pool(cls):
        with cls._pool_lock:
            for cli in cls._pool.values():
                cli._close()
            cls._pool.clear()

    def evict(self):
        """Drop this client's socket and remove it from the shared pool
        so the next get() builds a fresh client instead of a corpse."""
        self._close()
        with RPCClient._pool_lock:
            if RPCClient._pool.get(self.endpoint) is self:
                del RPCClient._pool[self.endpoint]

    # -- connection plumbing -------------------------------------------------
    def _close(self):
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _connect(self, sched: "_retry.RetrySchedule"):
        host, port = self.endpoint.rsplit(":", 1)
        self._sock = socket.create_connection(
            (host, int(port)),
            timeout=sched.remaining(default=self._timeout))
        if self._was_connected:
            telemetry.counter_add("ps.rpc_reconnects", 1,
                                  endpoint=self.endpoint)
        self._was_connected = True

    # -- the call ------------------------------------------------------------
    def call(self, method: str, name: str = "", arr=None, aux: int = 0,
             deadline: Optional[float] = None,
             max_retries: Optional[int] = None):
        """One request/reply exchange with retry/backoff/deadline.

        deadline: seconds of total budget for this call (default
        FLAGS_ps_rpc_timeout; <= 0 means unbounded). max_retries:
        reconnect-and-resend attempts (default FLAGS_ps_rpc_max_retries).
        Retries resend the SAME sequence number, so a request that was
        applied before its reply was lost is answered from the server's
        dedup cache instead of being re-applied."""
        a = None if arr is None else np.asarray(arr)
        budget = _flags.flag("ps_rpc_timeout") if deadline is None \
            else float(deadline)
        retries = _flags.flag("ps_rpc_max_retries") if max_retries is None \
            else int(max_retries)
        backoff = _flags.flag("ps_rpc_backoff")
        t0 = time.perf_counter()
        policy = _retry.RetryPolicy(
            max_retries=retries, backoff=backoff,
            deadline=budget if budget and budget > 0 else None)
        # the span covers the WHOLE retry schedule — retries resend the
        # same frame (same seq, same propagated context), so client call
        # and server handler stay one logical parent/child pair no matter
        # how many wire attempts it took
        with trace.span("ps.rpc.call", method=method,
                        endpoint=self.endpoint) as tctx:
            wire_method = method if tctx is None \
                else method + _TRACE_SEP + tctx.header()
            with self._lock:
                self._seq += 1
                seq = self._seq
                sched = policy.start()
                while True:
                    try:
                        faults.maybe_fail("ps.rpc.send", method=method,
                                          endpoint=self.endpoint)
                        if self._sock is None:
                            # pt-lint: disable=blocking-call-under-lock(one socket per client: calls serialise on the lock by design, bounded by the retry schedule's deadline)
                            self._connect(sched)
                        self._sock.settimeout(
                            sched.remaining(default=self._timeout))
                        # pt-lint: disable=blocking-call-under-lock(serialised per-client protocol; the socket timeout bounds the send)
                        _send_msg(self._sock, wire_method, name, a, aux,
                                  self._client_id, seq)
                        faults.maybe_fail("ps.rpc.recv", method=method,
                                          endpoint=self.endpoint)
                        status, err, out, oaux, _, rseq = \
                            _recv_msg(self._sock)  # pt-lint: disable=blocking-call-under-lock(reply read is the call; settimeout() above bounds it to the deadline)
                        if rseq and rseq != seq:
                            raise ConnectionError(
                                f"out-of-sequence reply: got {rseq}, "
                                f"expected {seq}")
                        break
                    except (ConnectionError, OSError) as e:
                        self._close()
                        outcome, delay = sched.note_failure()
                        if outcome == _retry.DEADLINE:
                            telemetry.counter_add(
                                "ps.rpc_deadline_exceeded", 1,
                                method=method)
                            self.evict()
                            raise RpcDeadlineError(
                                f"PS RPC '{method}' to {self.endpoint} "
                                f"exceeded its {budget:.3f}s deadline "
                                f"(attempt {sched.attempt}: "
                                f"{type(e).__name__}: {e})") from e
                        if outcome == _retry.EXHAUSTED:
                            self.evict()
                            raise RpcError(
                                f"PS RPC '{method}' to {self.endpoint} "
                                f"failed after {sched.attempt} attempts: "
                                f"{type(e).__name__}: {e}") from e
                        telemetry.counter_add("ps.rpc_retries", 1,
                                              method=method)
                        time.sleep(delay)  # pt-lint: disable=blocking-call-under-lock(retry backoff: concurrent callers of this client must wait out the schedule anyway; delay is deadline-clipped)
            # transport accounting (reference analog: the gRPC/BRPC client
            # metrics) — call count, payload bytes each way, latency
            # histogram
            telemetry.counter_add("ps.rpc_calls", 1, method=method)
            if a is not None:
                telemetry.counter_add("ps.rpc_send_bytes", int(a.nbytes))
            if out is not None:
                telemetry.counter_add("ps.rpc_recv_bytes", int(out.nbytes))
            telemetry.observe("ps.rpc_ms", (time.perf_counter() - t0) * 1e3,
                              kind="timer", method=method)
            if status == "__err__":
                telemetry.counter_add("ps.rpc_errors", 1, method=method)
                rtype = err.split(":", 1)[0] if ":" in err else ""
                raise RpcRemoteError(
                    f"PS RPC '{method}' failed on {self.endpoint}: {err}",
                    remote_type=rtype)
            return out, oaux

    def stop_server(self):
        try:
            # a short, retry-free budget: stopping an already-dead server
            # must not burn the full retry/deadline schedule
            self.call("__stop__", deadline=5.0, max_retries=0)
        except (RpcError, ConnectionError, OSError):
            pass


def start_heartbeat(endpoints, trainer_id: int, interval: float = 10.0,
                    metrics_url: str = ""):
    """Trainer-side liveness pings (reference: the trainer's periodic
    beat consumed by heart_beat_monitor.h). A daemon thread pings every
    pserver on its own connection so a trainer blocked in a sync recv
    still reads as alive. Returns a stop() callable; stop also closes
    the private sockets (under the same lock the beat thread holds while
    using them, so a close can't race a call in flight).

    ``metrics_url`` (the trainer's telemetry.start_metrics_server URL,
    when it runs one) rides the beat's spare ``name`` field: the pserver
    lands it in core/fleetobs.announce, so a fleet aggregator colocated
    with the PS tier scrapes trainers with zero extra RPCs."""
    if isinstance(endpoints, str):
        endpoints = [e.strip() for e in endpoints.split(",") if e.strip()]
    stop = threading.Event()
    clients: Dict[str, Optional[RPCClient]] = {ep: None for ep in endpoints}
    clients_lock = lockdep.lock("rpc.heartbeat.clients")

    def beat():
        # connect lazily + reconnect after any failure: a pserver that is
        # not up yet (launch race) or restarts mid-run must not silence
        # heartbeats forever. One attempt per tick — the beat itself is
        # the retry loop (call-level retries would pile up behind a dead
        # server and skew the beat period).
        while not stop.wait(interval):
            for ep in endpoints:
                with clients_lock:
                    if stop.is_set():
                        return
                    try:
                        if clients[ep] is None:
                            clients[ep] = RPCClient(ep, timeout=interval)
                        clients[ep].call("heartbeat", name=metrics_url,
                                         aux=int(trainer_id),
                                         deadline=interval, max_retries=0)
                    except (RpcError, ConnectionError, OSError):
                        cli, clients[ep] = clients[ep], None
                        if cli is not None:
                            cli._close()

    threading.Thread(target=beat, name="pt-ps-heartbeat",
                     daemon=True).start()

    def stop_heartbeat():
        stop.set()
        with clients_lock:
            for ep, cli in clients.items():
                if cli is not None:
                    cli._close()
                clients[ep] = None

    return stop_heartbeat
