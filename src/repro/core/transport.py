"""Pluggable RPC transports.

Components (dispatcher, workers) expose ``handle(method, payload) -> payload``
and are reachable through an address:

* ``inproc://<name>``   — direct function call via a process-local registry
  (default for single-process deployments and tests; zero-copy).
* ``tcp://host:port``   — length-prefixed pickle over a socket; stands in for
  the paper's gRPC channel and makes the deployment genuinely multi-process.
* ``grpc://host:port``  — the paper's actual wire protocol (§3.1: "all
  communication ... is done via gRPC, which uses HTTP/2, and multiplexes
  multiple calls on a single TCP connection").  A single generic unary RPC
  carries (method, pickled payload); uses grpcio's generic handler API so
  no .proto codegen is required.
* ``shm://<segment>``   — data-plane-only ring descriptor (``core.shm_ring``):
  names a shared-memory frame ring negotiated over an existing control
  channel (the ``shm_attach`` RPC).  It carries no request/response channel,
  so ``Stub`` refuses it with a ``TransportError`` explaining the contract.

Client code uses ``Stub(address)`` and never sees the difference.  Schemes
are pluggable: :func:`register_scheme` maps a scheme name to a connection
factory, so deployments can add transports without patching ``Stub``.

Per-scheme error contract (what ``Stub.call`` raises)
-----------------------------------------------------
Uniform rule: **connection-level failures always surface as**
``TransportError`` — never a raw ``OSError``/``socket.error``/``RpcError``
— so every ``Backoff`` retry loop in the codebase triggers on exactly one
exception type, for every scheme:

==========  ===============================  ==============================
scheme      connection loss / connect fail   remote handler exception
==========  ===============================  ==============================
inproc      ``TransportError`` (not bound)   propagates NATIVELY (same
                                             process, same traceback)
tcp         ``TransportError`` (wraps
            ``OSError``, connect+send+recv,  ``TransportError`` carrying
            malformed address, truncated     the remote ``repr``
            stream)
grpc        ``TransportError`` (wraps        ``TransportError`` carrying
            ``RpcError``, missing grpcio,    the remote ``repr``
            undecodable response)
shm         ``TransportError`` always (data plane only — no call channel)
==========  ===============================  ==============================

A failed call drops the cached connection; the next call reconnects
(simple failover).  Callers implement retry on ``TransportError``: clients
ride through dispatcher downtime and mark worker tasks failed (§3.4).
"""
from __future__ import annotations

import pickle
import random
import socket
import socketserver
import struct
import threading
from typing import Any, Callable, Dict, List, Optional, Protocol

# Re-exported for backwards compatibility: payload compression used to live
# here; it is now a pluggable registry (see codecs.py for negotiation rules).
from .codecs import compress, decompress  # noqa: F401
from ..obs.tracing import annotate

# Default per-call deadline when a Stub is built without an explicit
# timeout.  Paths whose liveness budget is tighter than this (standby
# journal tail, heartbeats) MUST pass their own — the D003 static pass
# flags retry-critical call sites that rely on this default.
DEFAULT_RPC_TIMEOUT_S = 30.0


class TransportError(Exception):
    """Raised for any transport-level failure (connect, send, remote error).

    Callers implement retry / failover on this: clients ride through
    dispatcher downtime and mark worker tasks failed (paper §3.4).  Remote
    exceptions raised by a handler are shipped back and re-raised as
    ``TransportError`` with the remote ``repr`` in the message.
    """


class Handler(Protocol):
    def handle(self, method: str, payload: Dict[str, Any]) -> Dict[str, Any]: ...


class Backoff:
    """Bounded exponential backoff with equal jitter for reconnect loops.

    Delay for attempt ``n`` is drawn from ``[d/2, d]`` where
    ``d = min(cap, base * multiplier**n)`` — the jitter spreads a fleet of
    workers reconnecting to a freshly promoted standby across half a period
    instead of landing them in one thundering herd; the cap bounds how long
    any single retry sleeps once the outage is long.

    ``rng`` is injectable for deterministic tests (defaults to the module
    ``random``; only ``.uniform`` is used).
    """

    def __init__(
        self,
        base: float = 0.05,
        cap: float = 2.0,
        multiplier: float = 2.0,
        rng: Optional[Any] = None,
    ):
        self.base = base
        self.cap = cap
        self.multiplier = multiplier
        self._rng = rng if rng is not None else random
        self._attempt = 0

    @property
    def attempt(self) -> int:
        return self._attempt

    def next_delay(self) -> float:
        d = min(self.cap, self.base * self.multiplier**self._attempt)
        if d < self.cap:
            # stop growing the exponent once capped (a long outage must not
            # overflow float pow after thousands of attempts)
            self._attempt += 1
        return d / 2 + self._rng.uniform(0.0, d / 2)

    def reset(self) -> None:
        self._attempt = 0


# ---------------------------------------------------------------------------
# Scheme registry: pluggable connection factories
# ---------------------------------------------------------------------------
# Maps scheme name -> factory(address, timeout) -> connection.  A connection
# exposes ``call(method, payload) -> payload`` and ``close()``.  Factories
# may raise anything; Stub wraps non-TransportError construction failures.
# A connection with ``native_errors = True`` (inproc) opts out of Stub's
# error wrapping: exceptions from the handler propagate to the caller with
# their original type and traceback.
_SCHEMES: Dict[str, Callable[[str, float], Any]] = {}


def register_scheme(name: str, factory: Callable[[str, float], Any]) -> None:
    """Register (or replace) a transport scheme's connection factory.

    ``factory(address, timeout)`` receives the FULL address (including the
    ``scheme://`` prefix) and the stub's per-call deadline, and returns a
    connection object (``call``/``close``).  Registered names appear in
    ``Stub``'s dispatch; replacing a built-in is allowed (tests inject
    fault-y transports this way).
    """
    _SCHEMES[name] = factory


# ---------------------------------------------------------------------------
# In-process registry transport
# ---------------------------------------------------------------------------
class _InprocRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._handlers: Dict[str, Handler] = {}

    def bind(self, name: str, handler: Handler) -> str:
        with self._lock:
            self._handlers[name] = handler
        return f"inproc://{name}"

    def unbind(self, name: str) -> None:
        with self._lock:
            self._handlers.pop(name, None)

    def get(self, name: str) -> Handler:
        with self._lock:
            h = self._handlers.get(name)
        if h is None:
            raise TransportError(f"inproc endpoint not bound: {name}")
        return h


INPROC = _InprocRegistry()


class _InprocConnection:
    """Stateless 'connection' that dispatches into the inproc registry.

    The handler lookup happens per call (not at construction) so a stub
    built before its endpoint binds — or after a rebind — still resolves.
    Handler exceptions propagate natively (``native_errors``): an inproc
    call IS a function call, and masking e.g. a ``ValueError`` from the
    dispatcher behind ``TransportError`` would break same-process callers
    that branch on the real type.
    """

    native_errors = True

    def __init__(self, address: str, timeout: float):
        self._name = address[len("inproc://") :]

    def call(self, method: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        return INPROC.get(self._name).handle(method, payload)

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# TCP transport (length-prefixed pickle; request/response per connection pool)
# ---------------------------------------------------------------------------
def _send_msg(sock: socket.socket, obj: Any, method: str = "") -> None:
    """One length-prefixed pickle frame.  ``method`` names the RPC on the
    spans (the response to a ``get_elements`` carries the batch)."""
    with annotate("transport.encode", method=method):
        data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    with annotate("transport.send", method=method):
        _send_all(sock, [struct.pack("<I", len(data)), data])


def _send_all(sock: socket.socket, parts: List[bytes]) -> None:
    """``sendall`` of ``parts`` as gathered writes: the header goes out with
    the payload without copying the payload after it, which for a batch of
    large elements is a copy of hundreds of MB under the interpreter lock."""
    views = [memoryview(p) for p in parts]
    while views:
        sent = sock.sendmsg(views)
        while views and sent >= len(views[0]):
            sent -= len(views.pop(0))
        if views:
            views[0] = views[0][sent:]


def _recv_msg(sock: socket.socket, method: str = "") -> Any:
    hdr = _recv_exact(sock, 4)  # the wait for a frame: no span
    (n,) = struct.unpack("<I", hdr)
    with annotate("transport.recv", method=method):
        data = _recv_exact(sock, n)
    with annotate("transport.decode", method=method):
        return pickle.loads(data)


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    """``n`` bytes, received into one buffer: appending chunk by chunk
    recopies the prefix at every chunk, quadratic in a large frame."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:])
        if not k:
            raise TransportError("connection closed mid-message")
        got += k
    return buf


class TCPServer:
    """Threaded TCP server fronting a Handler."""

    def __init__(self, handler: Handler, host: str = "127.0.0.1", port: int = 0):
        self._handler = handler
        outer = self

        class _ReqHandler(socketserver.BaseRequestHandler):
            def handle(self) -> None:  # one connection, many requests
                while True:
                    try:
                        method, payload = _recv_msg(self.request)
                    except (TransportError, EOFError, ConnectionError, OSError):
                        return
                    try:
                        result = outer._handler.handle(method, payload)
                        _send_msg(self.request, ("ok", result), method)
                    except Exception as e:  # ship the error to the caller
                        _send_msg(self.request, ("err", repr(e)), method)

        class _Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = _Server((host, port), _ReqHandler)
        self.address = f"tcp://{self._server.server_address[0]}:{self._server.server_address[1]}"
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)

    def start(self) -> "TCPServer":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()


class _TCPConnection:
    def __init__(self, host: str, port: int, timeout: float = DEFAULT_RPC_TIMEOUT_S):
        # the socket timeout bounds connect AND every recv: a peer that
        # accepts but never answers surfaces as TransportError after
        # `timeout`, not a silent hang
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._lock = threading.Lock()

    def call(self, method: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            _send_msg(self._sock, (method, payload), method)
            status, result = _recv_msg(self._sock, method)
        if status != "ok":
            raise TransportError(f"remote error from {method}: {result}")
        return result

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# gRPC transport (optional; the paper's production wire protocol)
# ---------------------------------------------------------------------------
_GRPC_METHOD = "/repro.DataService/Call"


class GrpcServer:
    """gRPC server fronting a Handler via one generic unary method.

    Uses grpcio's generic_rpc_handlers so the repo carries no generated
    proto code; the request/response bodies are (method, payload) pickles —
    the same message schema as the TCP transport, over HTTP/2 multiplexing.
    """

    def __init__(self, handler: Handler, host: str = "127.0.0.1", port: int = 0):
        import grpc  # deferred: optional dependency
        from concurrent import futures

        outer_handler = handler

        class _Generic(grpc.GenericRpcHandler):
            def service(self, handler_call_details):
                if handler_call_details.method != _GRPC_METHOD:
                    return None

                def unary(request: bytes, context) -> bytes:
                    method, payload = pickle.loads(request)
                    try:
                        return pickle.dumps(
                            ("ok", outer_handler.handle(method, payload)),
                            protocol=pickle.HIGHEST_PROTOCOL,
                        )
                    except Exception as e:
                        return pickle.dumps(("err", repr(e)))

                return grpc.unary_unary_rpc_method_handler(
                    unary,
                    request_deserializer=lambda b: b,
                    response_serializer=lambda b: b,
                )

        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=16),
            options=[("grpc.max_receive_message_length", 128 * 1024 * 1024),
                     ("grpc.max_send_message_length", 128 * 1024 * 1024)],
        )
        self._server.add_generic_rpc_handlers((_Generic(),))
        bound = self._server.add_insecure_port(f"{host}:{port}")
        self.address = f"grpc://{host}:{bound}"

    def start(self) -> "GrpcServer":
        self._server.start()
        return self

    def stop(self) -> None:
        self._server.stop(grace=0.2)


class _GrpcConnection:
    def __init__(self, target: str, timeout: float = DEFAULT_RPC_TIMEOUT_S):
        import grpc

        self._timeout = timeout

        self._grpc = grpc
        self._channel = grpc.insecure_channel(
            target,
            options=[("grpc.max_receive_message_length", 128 * 1024 * 1024),
                     ("grpc.max_send_message_length", 128 * 1024 * 1024)],
        )
        self._call = self._channel.unary_unary(
            _GRPC_METHOD,
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )

    def call(self, method: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        try:
            resp = self._call(
                pickle.dumps((method, payload), protocol=pickle.HIGHEST_PROTOCOL),
                timeout=self._timeout,
            )
        except self._grpc.RpcError as e:
            raise TransportError(f"grpc call {method} failed: {e.code()}")
        try:
            status, result = pickle.loads(resp)
        except Exception as e:  # truncated/garbage body: connection-level
            raise TransportError(
                f"grpc call {method}: undecodable response: {e!r}"
            ) from e
        if status != "ok":
            raise TransportError(f"remote error from {method}: {result}")
        return result

    def close(self) -> None:
        self._channel.close()


# ---------------------------------------------------------------------------
# Built-in scheme registrations
# ---------------------------------------------------------------------------
def _tcp_factory(address: str, timeout: float) -> _TCPConnection:
    hostport = address[len("tcp://") :]
    try:
        host, port_s = hostport.rsplit(":", 1)
        port = int(port_s)
    except ValueError as e:  # no colon / non-numeric port
        raise TransportError(f"malformed tcp address {address!r}: {e}") from e
    return _TCPConnection(host, port, timeout=timeout)


def _grpc_factory(address: str, timeout: float) -> _GrpcConnection:
    # _GrpcConnection's deferred ``import grpc`` (optional dep) and channel
    # construction errors are wrapped by Stub's factory guard.
    return _GrpcConnection(address[len("grpc://") :], timeout=timeout)


def _shm_factory(address: str, timeout: float) -> Any:
    raise TransportError(
        f"shm:// is a data-plane descriptor, not a call channel: {address!r} "
        "names a shared-memory frame ring (core.shm_ring) negotiated via the "
        "shm_attach RPC on an existing tcp/grpc control connection"
    )


register_scheme("inproc", _InprocConnection)
register_scheme("tcp", _tcp_factory)
register_scheme("grpc", _grpc_factory)
register_scheme("shm", _shm_factory)


# ---------------------------------------------------------------------------
# Stub: uniform client handle over any transport
# ---------------------------------------------------------------------------
class Stub:
    """Uniform client handle over any transport scheme.

    One ``Stub`` owns at most one underlying connection and serializes calls
    on it — a single stub gives strictly request/response semantics.  To
    overlap multiple outstanding requests against the same endpoint (the
    client's pipelined prefetch window), open one ``Stub`` per in-flight
    request: each TCP/gRPC stub gets its own connection/channel, and inproc
    stubs are free.
    """

    def __init__(self, address: str, timeout: Optional[float] = None):
        self.address = address
        # per-stub RPC deadline; retry-critical loops (standby journal tail,
        # heartbeats) pass one derived from their own lease so a hung peer
        # can't stall them for the transport default
        self.timeout = DEFAULT_RPC_TIMEOUT_S if timeout is None else timeout
        self._conn: Optional[Any] = None
        self._lock = threading.Lock()

    def call(self, method: str, **payload: Any) -> Dict[str, Any]:
        """Invoke ``method`` on the remote handler and return its response.

        Connections are opened lazily (via the scheme's registered factory)
        and dropped on error so the next call reconnects (simple failover).
        Per the module's error contract: every connection-level failure —
        connect refused, malformed address, mid-call socket death, missing
        optional transport package, undecodable response — surfaces as
        ``TransportError``, never a raw ``OSError``; remote handler
        exceptions also arrive as ``TransportError`` (carrying the remote
        ``repr``) — EXCEPT over ``inproc://``, where handler exceptions
        propagate natively (same-process call).
        """
        scheme = self.address.split("://", 1)[0] if "://" in self.address else ""
        factory = _SCHEMES.get(scheme)
        if factory is None:
            raise TransportError(f"unsupported address scheme: {self.address}")
        with self._lock:
            if self._conn is None:
                try:
                    self._conn = factory(self.address, self.timeout)
                except TransportError:
                    raise
                except Exception as e:  # OSError, ImportError, bad address...
                    raise TransportError(
                        f"cannot connect to {self.address}: {e}"
                    ) from e
            conn = self._conn
        if getattr(conn, "native_errors", False):
            return conn.call(method, payload)
        try:
            return conn.call(method, payload)
        except TransportError:
            self._drop(conn)
            raise
        except (OSError, EOFError, pickle.UnpicklingError) as e:
            self._drop(conn)
            raise TransportError(str(e)) from e

    def _drop(self, conn: Any) -> None:
        """Discard a failed connection so the next call reconnects."""
        with self._lock:
            if self._conn is conn:
                try:
                    conn.close()
                except Exception:
                    pass
                self._conn = None

    def close(self) -> None:
        """Drop the cached connection (if any); the stub stays usable."""
        with self._lock:
            if self._conn is not None:
                self._conn.close()
                self._conn = None
