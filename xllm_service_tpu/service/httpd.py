"""Minimal threaded HTTP substrate: routed server + JSON/SSE client helpers.

The reference runs two brpc servers (HTTP front door + worker RPC) and brpc
channels between processes (master.cpp:60-140, instance_mgr.cpp:523-551).
This module is the rebuild's equivalent transport: a stdlib-only threaded
HTTP/1.1 server with a route table and chunked/SSE streaming responses, and
client helpers for JSON calls and progressive SSE reads (the reference's
``ProgressiveReader``, http_service/service.cpp:113-143). All of this is
host-side CPU code on the TPU-VM — the data plane (tokens) is tiny compared
to the compute, so HTTP/JSON over DCN matches the reference's control-plane
role without vendoring an RPC stack.
"""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
from http.client import HTTPConnection
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Tuple)
from urllib.parse import parse_qs, urlparse

from xllm_service_tpu.obs import profiler
from xllm_service_tpu.utils.locks import make_lock
from xllm_service_tpu.utils.threads import spawn


class Request:
    def __init__(self, method: str, path: str, query: Dict[str, List[str]],
                 headers: Dict[str, str], body: bytes) -> None:
        self.method = method
        self.path = path
        self.query = query
        # HTTP header names are case-insensitive; normalize to lowercase
        # so lookups like headers.get("x-request-id") always hit.
        self.headers = {k.lower(): v for k, v in headers.items()}
        self.body = body

    def param(self, name: str, default: str = "") -> str:
        vals = self.query.get(name)
        return vals[0] if vals else default

    def json(self) -> Any:
        if not self.body:
            return {}
        return json.loads(self.body.decode("utf-8"))


class Response:
    """``body`` for buffered responses; ``stream`` (an iterator of byte
    chunks) for progressive/SSE responses — chunks are flushed as produced
    by the thread that runs the handler. ``push``: a progressive body its
    PRODUCER writes, for a server whose chunk call cannot block
    (``chunks_block`` False: the native one). Once the headers are queued
    the server calls ``push.serve(write)`` on the handler's thread;
    ``write(chunk) -> int`` may then be called from any thread, queues
    the chunk and returns nonzero once the client is gone. ``serve``
    returns when the body is over: True ends it cleanly, False breaks
    the connection without the terminating chunk. A handler returns one
    only to a server that says it can take it."""

    def __init__(self, status: int = 200, body: Optional[bytes] = None,
                 content_type: str = "application/json",
                 headers: Optional[Dict[str, str]] = None,
                 stream: Optional[Iterable[bytes]] = None,
                 on_close: Optional[Callable[[], None]] = None,
                 push: Any = None) -> None:
        self.status = status
        self.body = body if body is not None else b""
        self.content_type = content_type
        self.headers = headers or {}
        self.stream = stream
        self.push = push
        # Invoked by the server EXACTLY when it is done with this
        # response — including when a stream body is never iterated
        # (failed header write): a never-STARTED generator's finally
        # does not run on close (PEP 342), so cleanup that must always
        # happen belongs here, not in the generator.
        self.on_close = on_close

    @classmethod
    def json(cls, obj: Any, status: int = 200) -> "Response":
        return cls(status=status,
                   body=json.dumps(obj).encode("utf-8"))

    @classmethod
    def error(cls, status: int, message: str,
              err_type: str = "invalid_request_error") -> "Response":
        """OpenAI-style error envelope."""
        return cls.json(
            {"error": {"message": message, "type": err_type, "code": status}},
            status=status)

    @classmethod
    def sse(cls, chunks: Optional[Iterable[bytes]] = None,
            push: Any = None) -> "Response":
        return cls(content_type="text/event-stream",
                   headers={"Cache-Control": "no-cache"}, stream=chunks,
                   push=push)


Handler = Callable[[Request], Response]


class Admission:
    """Server-wide concurrent-request limit — the rebuild of brpc's
    ``max_concurrency`` backpressure (reference global_gflags.cpp:33-48,
    applied to both servers in master.cpp:60-140). Past the limit a new
    request gets an immediate 503 + Retry-After instead of an unbounded
    thread pile-up; a 503 is exactly the refusal class the service's
    re-dispatch path already handles, so worker-side overload shifts
    load instead of failing requests.

    ``limit`` may be an int, None (unlimited), or a zero-arg callable
    returning either — the callable form reads a live options object so
    ``/admin/flags`` hot-reload applies without a restart. A slot is
    held for the FULL handler lifetime including streaming, so long SSE
    responses count toward the limit (they hold a server thread)."""

    def __init__(self, limit=None) -> None:
        self._limit = limit
        self._active = 0
        self._lock = threading.Lock()
        self.rejected_total = 0

    def _current_limit(self) -> Optional[int]:
        lim = self._limit() if callable(self._limit) else self._limit
        return None if not lim or lim <= 0 else lim

    @property
    def active(self) -> int:
        return self._active

    def try_enter(self) -> bool:
        with self._lock:
            lim = self._current_limit()
            if lim is not None and self._active >= lim:
                self.rejected_total += 1
                return False
            self._active += 1
            return True

    def probe(self) -> bool:
        """Advisory admission check WITHOUT claiming a slot (counts a
        rejection). Used by the native front door to shed large-body
        uploads at header-complete time, before buffering the body; the
        authoritative ``try_enter`` still runs at dispatch."""
        with self._lock:
            lim = self._current_limit()
            if lim is not None and self._active >= lim:
                self.rejected_total += 1
                return False
            return True

    def leave(self) -> None:
        with self._lock:
            self._active -= 1


# Admission bites at REQUEST ENTRY (client-facing /v1/*), never on
# control-plane or continuation traffic:
# - liveness (heartbeats), observability, and the knobs to RAISE the
#   limit must not be starved by the congestion they diagnose;
# - /rpc/* carries workers' pushes for ALREADY-admitted requests
#   (generations fan-in) — shedding those doesn't reduce load, it
#   corrupts in-flight streams (tokens silently dropped).
# Servers with other continuation/control verbs extend this list
# (worker.py: /sleep, /kv/import, /encode, ...).
_ADMISSION_EXEMPT = ("/metrics", "/hello", "/admin/", "/rpc/")


class Router:
    """Exact-path and prefix routes per method."""

    def __init__(self) -> None:
        self._exact: Dict[Tuple[str, str], Handler] = {}
        self._prefix: List[Tuple[str, str, Handler]] = []

    def route(self, method: str, path: str, handler: Handler) -> None:
        self._exact[(method.upper(), path)] = handler

    def route_prefix(self, method: str, prefix: str,
                     handler: Handler) -> None:
        self._prefix.append((method.upper(), prefix, handler))

    def dispatch(self, req: Request) -> Response:
        h = self._exact.get((req.method, req.path))
        if h is None:
            for method, prefix, ph in self._prefix:
                if req.method == method and req.path.startswith(prefix):
                    h = ph
                    break
        if h is None:
            return Response.error(404, f"no route for {req.method} {req.path}")
        try:
            return h(req)
        except Exception as e:  # noqa: BLE001 — route errors become 500s
            import traceback
            traceback.print_exc()
            return Response.error(500, f"internal error: {e}",
                                  "internal_error")


class _RequestHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # Keep-alive + Nagle is poison: a small response segment can sit for
    # the ~40 ms delayed-ACK window before the next one flushes.
    disable_nagle_algorithm = True
    # Idle keep-alive connections must not pin their server thread
    # forever (ThreadingHTTPServer is thread-per-connection): close them
    # after this long with no next request. Clients evict pooled
    # connections well before this (see _ConnPool._MAX_IDLE_S), so a
    # reused client socket is never one the server already killed.
    timeout = 60.0
    router: Router       # set by server factory
    admission: Optional[Admission] = None      # set by server factory
    admission_exempt: Tuple[str, ...] = _ADMISSION_EXEMPT

    def log_message(self, fmt: str, *args: Any) -> None:  # quiet
        pass

    # ThreadingHTTPServer runs setup/handle/finish once a CONNECTION, on
    # the connection's own bare thread: the place to give it a root, so
    # that a scrape can say what the handlers cost beside the engine's
    # loop (obs/profiler.py), and to book its seconds before it exits.
    def setup(self) -> None:
        profiler.register_thread_root(profiler.HANDLER_ROOT)
        super().setup()

    def finish(self) -> None:
        try:
            super().finish()
        finally:
            profiler.retire_thread_root()

    def _handle(self) -> None:
        parsed = urlparse(self.path)
        # Admission runs BEFORE the body read: a shed request must not
        # pay an unbounded (or slow-loris) upload on a server thread —
        # the reject path closes the connection instead of draining.
        admitted = (self.admission is None
                    or parsed.path.startswith(self.admission_exempt)
                    or self.admission.try_enter())
        if not admitted:
            self.close_connection = True
            try:
                self._write(Response(
                    status=503,
                    body=json.dumps({"error": {
                        "message": "server at max_concurrency",
                        "type": "overloaded_error",
                        "code": 503}}).encode("utf-8"),
                    headers={"Retry-After": "1", "Connection": "close"}))
            except (BrokenPipeError, ConnectionResetError):
                pass
            return
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length) if length else b""
        req = Request(self.command, parsed.path, parse_qs(parsed.query),
                      dict(self.headers.items()), body)
        try:
            resp = self.router.dispatch(req)
        except BaseException:
            if self.admission is not None \
                    and not parsed.path.startswith(self.admission_exempt):
                self.admission.leave()
            raise
        try:
            self._write(resp)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away mid-stream
        finally:
            if self.admission is not None \
                    and not parsed.path.startswith(self.admission_exempt):
                self.admission.leave()
            # Run a STARTED stream generator's finally first, then the
            # response-level cleanup (covers the never-started case).
            if resp.stream is not None and hasattr(resp.stream, "close"):
                try:
                    resp.stream.close()
                except Exception:  # noqa: BLE001 — best-effort cleanup;
                    pass            # the response is already resolved
            if resp.on_close is not None:
                try:
                    resp.on_close()
                except Exception:  # noqa: BLE001 — a failing finish hook
                    pass            # must not poison this server thread

    def _write(self, resp: Response) -> None:
        self.send_response(resp.status)
        self.send_header("Content-Type", resp.content_type)
        for k, v in resp.headers.items():
            self.send_header(k, v)
        if resp.stream is not None:
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            for chunk in resp.stream:
                if not chunk:
                    continue
                # One write (one TCP segment under NODELAY) per frame —
                # size line + payload + CRLF as three writes tripled the
                # syscall count of every streamed token.
                self.wfile.write(b"".join(
                    (f"{len(chunk):X}\r\n".encode(), chunk, b"\r\n")))
                self.wfile.flush()
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        else:
            self.send_header("Content-Length", str(len(resp.body)))
            self.end_headers()
            if resp.body:
                self.wfile.write(resp.body)
                self.wfile.flush()

    do_GET = _handle
    do_POST = _handle
    do_PUT = _handle
    do_DELETE = _handle


class PyHttpServer:
    """Threaded HTTP server bound to (host, port); port 0 picks a free one.

    ``max_concurrency``: int / None / zero-arg callable — see
    ``Admission``. Control-plane paths (``_ADMISSION_EXEMPT``) bypass it."""

    # A chunk goes to a blocking ``wfile``: one thread writing every
    # stream would put them all behind the slowest client, so a
    # streamed body is pulled on its connection's own thread.
    chunks_block = True

    def __init__(self, host: str, port: int, router: Router,
                 max_concurrency=None,
                 admission_exempt: Tuple[str, ...] = _ADMISSION_EXEMPT
                 ) -> None:
        self.admission = (Admission(max_concurrency)
                          if max_concurrency is not None else None)
        handler = type("BoundHandler", (_RequestHandler,),
                       {"router": router, "admission": self.admission,
                        "admission_exempt": tuple(admission_exempt)})
        self._srv = ThreadingHTTPServer((host, port), handler)
        self._srv.daemon_threads = True
        self.host = host
        self.port = self._srv.server_address[1]
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def start(self) -> "HttpServer":
        self._thread = spawn(
            "httpd.serve", self._srv.serve_forever,
            thread_name=f"httpd-{self.port}")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()
        if self._thread:
            self._thread.join(timeout=5)


def HttpServer(host: str, port: int, router: Router,  # noqa: N802
               max_concurrency=None,
               admission_exempt: Tuple[str, ...] = _ADMISSION_EXEMPT):
    """Server factory: the native epoll front door (csrc/xllm_httpd.cpp,
    the brpc-shaped event loop) when the library builds, else the
    pure-Python threaded server. ``XLLM_NATIVE_HTTPD=0`` forces Python.
    Both expose the same surface: ``start/stop/address/port/admission``."""
    try:
        from xllm_service_tpu.service.native_httpd import NativeHttpServer
        return NativeHttpServer(host, port, router,
                                max_concurrency=max_concurrency,
                                admission_exempt=admission_exempt)
    except (OSError, ImportError):
        # Library unavailable, module missing from a partial deployment,
        # or port-bind raced: the Python server's bind surfaces a genuine
        # port conflict identically.
        return PyHttpServer(host, port, router,
                            max_concurrency=max_concurrency,
                            admission_exempt=admission_exempt)


# ---------------------------------------------------------------------------
# Client helpers
# ---------------------------------------------------------------------------

class _NoDelayHTTPConnection(HTTPConnection):
    """TCP_NODELAY client connection — on a reused keep-alive socket the
    header and body writes are separate small segments, and with Nagle on
    the second waits out the peer's delayed-ACK timer (~40 ms p50 measured
    on the service bench)."""

    def connect(self) -> None:
        super().connect()
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


class _ConnPool:
    """Keep-alive HTTPConnection pool per address — the rebuild of the
    reference's per-instance brpc channel cache (instance_mgr.cpp:
    523-551). A fresh TCP connect per service→worker call costs a
    round-trip and a server thread spawn on every request; checked-out
    connections return here after a clean exchange instead.

    Staleness is handled by AVOIDANCE, not by blind retry (re-sending a
    non-idempotent POST could run an inference twice or repeat a CAS):
    pooled connections are discarded once idle longer than
    ``_MAX_IDLE_S``, well under the server's 60 s keep-alive timeout, so
    a reused socket is never one the peer already closed. Dead
    instances' sockets age out of the pool the same way (a periodic
    sweep piggybacks on ``put``)."""

    _MAX_IDLE_PER_ADDR = 8
    _MAX_IDLE_S = 20.0
    _SWEEP_INTERVAL_S = 5.0

    def __init__(self) -> None:
        # address -> [(conn, last_used_monotonic)]
        self._idle: Dict[str, List[Tuple[HTTPConnection, float]]] = {}
        self._lock = make_lock("httpd.connpool", 92)
        self._last_sweep = 0.0
        # Reuse counters (served at /metrics): a transport regression —
        # peers closing keep-alives early, the idle window mistuned, the
        # per-address cap too small under fan-out — shows up here as a
        # falling hit:miss ratio or climbing overflow before it shows up
        # as p50 latency in service_bench. Mutated under _lock.
        self.hits_total = 0        # get() satisfied from the pool
        self.misses_total = 0      # get() had to open a fresh TCP conn
        self.overflow_total = 0    # put() dropped a conn (addr cap full)
        self.expired_total = 0     # idle conns aged out (sweep or get)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits_total": self.hits_total,
                    "misses_total": self.misses_total,
                    "overflow_total": self.overflow_total,
                    "expired_total": self.expired_total,
                    "idle": sum(len(v) for v in self._idle.values())}

    def get(self, address: str, timeout: float
            ) -> Tuple[HTTPConnection, bool]:
        """→ (connection, reused)."""
        now = time.monotonic()
        stale: List[HTTPConnection] = []
        conn = None
        with self._lock:
            conns = self._idle.get(address)
            while conns:
                cand, last = conns.pop()
                if now - last <= self._MAX_IDLE_S:
                    conn = cand
                    break
                stale.append(cand)
            stale.extend(self._sweep_locked(now))
            self.expired_total += len(stale)
            if conn is not None:
                self.hits_total += 1
            else:
                self.misses_total += 1
        for c in stale:
            c.close()
        if conn is not None:
            conn.timeout = timeout
            if conn.sock is not None:
                conn.sock.settimeout(timeout)
            return conn, True
        return _NoDelayHTTPConnection(address, timeout=timeout), False

    def put(self, address: str, conn: HTTPConnection) -> None:
        now = time.monotonic()
        evicted: List[HTTPConnection] = []
        with self._lock:
            conns = self._idle.setdefault(address, [])
            if len(conns) < self._MAX_IDLE_PER_ADDR:
                conns.append((conn, now))
                conn = None
            else:
                self.overflow_total += 1
            swept = self._sweep_locked(now)
            self.expired_total += len(swept)
            evicted.extend(swept)
        if conn is not None:
            evicted.append(conn)
        for c in evicted:
            c.close()

    def _sweep_locked(self, now: float) -> List[HTTPConnection]:
        """Age out every address's idle conns (deregistered workers are
        never requested again — without this their sockets would sit in
        CLOSE_WAIT until process exit). Time-gated so any pool traffic,
        however light, triggers it; called with the lock held."""
        if now - self._last_sweep < self._SWEEP_INTERVAL_S:
            return []
        self._last_sweep = now
        evicted: List[HTTPConnection] = []
        for addr in list(self._idle):
            kept = [(c, t) for (c, t) in self._idle[addr]
                    if now - t <= self._MAX_IDLE_S]
            evicted.extend(c for (c, t) in self._idle[addr]
                           if now - t > self._MAX_IDLE_S)
            if kept:
                self._idle[addr] = kept
            else:
                del self._idle[addr]
        return evicted


_POOL = _ConnPool()


def conn_pool_stats() -> Dict[str, int]:
    """Process-wide keep-alive pool counters for /metrics exporters."""
    return _POOL.stats()


def flush_conn_pool_metrics(registry, plane: str) -> None:
    """Mirror the pool counters into an obs registry under the exporting
    plane's label (the pool is process-global; co-located planes export
    the same series under distinct labels instead of colliding). Shared
    by both planes' /metrics handlers so the series shapes can't drift."""
    for k, v in conn_pool_stats().items():
        name = f"xllm_http_conn_pool_{k}"
        if k.endswith("_total"):
            registry.counter(name, labelnames=("plane",)).set_total(
                v, plane=plane)
        else:
            registry.gauge(name, labelnames=("plane",)).set(
                v, plane=plane)

# Failures while SENDING on a reused socket — the request never reached
# the peer whole, so one fresh-connection retry cannot double-execute it.
_SEND_ERRORS = (http.client.CannotSendRequest, ConnectionResetError,
                BrokenPipeError, ConnectionAbortedError)


def http_json(method: str, address: str, path: str, obj: Any = None,
              timeout: float = 30.0,
              headers: Optional[Dict[str, str]] = None
              ) -> Tuple[int, Any]:
    """One JSON request to ``address`` ("host:port") over a pooled
    keep-alive connection. Returns (status, parsed-json-or-None)."""
    body = None if obj is None else json.dumps(obj).encode("utf-8")
    hdrs = {"Content-Type": "application/json"}
    if headers:
        hdrs.update(headers)
    while True:
        conn, reused = _POOL.get(address, timeout)
        try:
            conn.request(method, path, body=body, headers=hdrs)
        except _SEND_ERRORS:
            conn.close()
            if reused:
                continue      # request never delivered — safe to retry
            raise
        except Exception:
            conn.close()
            raise
        try:
            resp = conn.getresponse()
            data = resp.read()
            parsed = json.loads(data.decode("utf-8")) if data else None
        except http.client.RemoteDisconnected:
            # Peer closed without ANY response. On a reused socket this
            # almost always means the peer restarted and the kernel RST'd
            # a dead connection the idle-age eviction missed — the new
            # process never saw the request, so retry once on a fresh
            # connection (urllib3's default for exactly this case). The
            # residual received-then-crashed-before-responding window is
            # the same one a fresh connection has.
            conn.close()
            if reused:
                continue
            raise
        except Exception:
            # Other response-phase failure: the peer may have executed
            # the request — no retry, surface it to the caller.
            conn.close()
            raise
        if resp.will_close:
            conn.close()
        else:
            _POOL.put(address, conn)
        return resp.status, parsed


def http_stream_status(method: str, address: str, path: str,
                       obj: Any = None, timeout: float = 600.0,
                       headers: Optional[Dict[str, str]] = None,
                       raw: Optional[bytes] = None
                       ) -> Tuple[int, Iterator[bytes]]:
    """Like ``http_stream`` but connects EAGERLY and returns
    (status, body-iterator) so callers can act on the status (e.g.
    re-dispatch a 503) before relaying any bytes. The caller must
    exhaust or close the iterator once it has been started; a non-200
    body should simply be drained (it is small)."""
    conn = _NoDelayHTTPConnection(address, timeout=timeout)
    try:
        if raw is not None:
            body = raw
            hdrs = {"Content-Type": "application/octet-stream"}
        else:
            body = None if obj is None else json.dumps(obj).encode("utf-8")
            hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        conn.request(method, path, body=body, headers=hdrs)
        resp = conn.getresponse()
    except Exception:
        conn.close()
        raise

    return resp.status, _StreamBody(resp, conn)


class _StreamBody:
    """Iterable response body that is ALSO closeable without having been
    iterated — closing a never-started generator cannot run its finally
    (PEP 342), but dropping the connection must always be possible."""

    def __init__(self, resp, conn) -> None:
        self._resp = resp
        self._conn = conn

    def __iter__(self) -> Iterator[bytes]:
        try:
            while True:
                chunk = self._resp.read1(65536)
                if not chunk:
                    return
                yield chunk
        finally:
            self._conn.close()

    def close(self) -> None:
        self._conn.close()


def http_stream(method: str, address: str, path: str, obj: Any = None,
                timeout: float = 600.0,
                headers: Optional[Dict[str, str]] = None,
                raw: Optional[bytes] = None
                ) -> Iterator[bytes]:
    """Progressive byte-chunk reader (reference CustomProgressiveReader,
    service.cpp:113-143): yields raw chunks as they arrive. ``raw`` sends
    an octet-stream body instead of JSON (KV migration payloads)."""
    _, body = http_stream_status(method, address, path, obj=obj,
                                 timeout=timeout, headers=headers, raw=raw)
    yield from body


def iter_sse_events(chunks: Iterable[bytes]) -> Iterator[str]:
    """Reassemble SSE ``data:`` payloads from a progressive byte stream."""
    buf = b""
    for chunk in chunks:
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            for line in event.decode("utf-8").splitlines():
                if line.startswith("data: "):
                    yield line[len("data: "):]
                elif line.startswith("data:"):
                    yield line[len("data:"):]
