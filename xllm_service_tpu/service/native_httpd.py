"""ctypes bridge to the native epoll front door (csrc/xllm_httpd.cpp).

The reference's servers are brpc: a C++ event loop owning every socket
with a bounded worker pool behind it (reference master.cpp:60-140). This
module gives the rebuild the same split: ``csrc/xllm_httpd.cpp`` handles
accept/parse/keep-alive/chunked-writes in one epoll thread, and complete
requests surface here through a ctypes callback. Routing, admission
control (the live ``max_concurrency`` semantics tests pin), and handler
execution stay in Python — identical semantics to the pure-Python
``HttpServer``, which remains as the fallback when the native library
cannot build (``XLLM_NATIVE_HTTPD=0`` forces the fallback).

What moves off Python threads: idle keep-alive connections (the Python
server pins one thread per connection for up to 60 s), socket parsing,
slow-client writes (buffered in C++ so a stalled reader cannot block the
token producer), and shed requests (a 503 costs no thread spawn).
"""

from __future__ import annotations

import ctypes
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple
from urllib.parse import parse_qs

from xllm_service_tpu.obs import profiler
from xllm_service_tpu.utils.locks import make_lock
from xllm_service_tpu.utils import threads
from xllm_service_tpu.utils.native_build import build_artifact
from xllm_service_tpu.utils.threads import spawn

# The headers blob is "key\0value\0...": it MUST cross as pointer+length
# (c_void_p + c_int64) — a c_char_p conversion would truncate it at the
# first embedded NUL.
_CB_TYPE = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
    ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
    ctypes.c_void_p, ctypes.c_int64)
# Advisory early-shed check (epoll thread, header-complete, large bodies
# only): 1 = proceed, 0 = send the canned 503 without reading the body.
_ADMIT_TYPE = ctypes.CFUNCTYPE(ctypes.c_int32, ctypes.c_void_p,
                               ctypes.c_char_p, ctypes.c_char_p)

_native_lock = make_lock("native_httpd.lib", 96)
_native_lib: Optional[ctypes.CDLL] = None
_native_tried = False


_FLAGS = ("-O2", "-std=c++17", "-shared", "-fPIC", "-pthread")


def _build() -> Optional[str]:
    """The front-door library for the current csrc/xllm_httpd.cpp; None
    (no toolchain) falls back to the stdlib ThreadingHTTPServer path."""
    return build_artifact("xllm_httpd.cpp", "libxllm_httpd", ".so",
                          _FLAGS, timeout_s=120)


def _load() -> Optional[ctypes.CDLL]:
    global _native_lib, _native_tried
    with _native_lock:
        if _native_tried:
            return _native_lib
        _native_tried = True
        if os.environ.get("XLLM_NATIVE_HTTPD", "1") == "0" \
                or os.environ.get("XLLM_DISABLE_NATIVE"):
            return None
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.xllm_httpd_start.argtypes = [
                ctypes.c_char_p, ctypes.c_int32, _CB_TYPE, _ADMIT_TYPE,
                ctypes.c_void_p]
            lib.xllm_httpd_start.restype = ctypes.c_int64
            lib.xllm_httpd_port.argtypes = [ctypes.c_int64]
            lib.xllm_httpd_port.restype = ctypes.c_int32
            lib.xllm_httpd_run.argtypes = [ctypes.c_int64]
            lib.xllm_httpd_run.restype = ctypes.c_int32
            lib.xllm_httpd_set_shed_response.argtypes = [
                ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
            lib.xllm_httpd_set_shed_response.restype = ctypes.c_int32
            lib.xllm_httpd_stop.argtypes = [ctypes.c_int64]
            lib.xllm_httpd_respond.argtypes = [
                ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32,
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
                ctypes.c_int64]
            lib.xllm_httpd_respond.restype = ctypes.c_int32
            lib.xllm_httpd_stream_begin.argtypes = [
                ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32,
                ctypes.c_char_p, ctypes.c_int64]
            lib.xllm_httpd_stream_begin.restype = ctypes.c_int32
            lib.xllm_httpd_stream_chunk.argtypes = [
                ctypes.c_int64, ctypes.c_uint64, ctypes.c_char_p,
                ctypes.c_int64]
            lib.xllm_httpd_stream_chunk.restype = ctypes.c_int32
            lib.xllm_httpd_stream_end.argtypes = [
                ctypes.c_int64, ctypes.c_uint64]
            lib.xllm_httpd_stream_end.restype = ctypes.c_int32
            lib.xllm_httpd_stream_abort.argtypes = [
                ctypes.c_int64, ctypes.c_uint64]
            lib.xllm_httpd_stream_abort.restype = ctypes.c_int32
            _native_lib = lib
        except Exception:  # noqa: BLE001 — a stale .so missing a newer
            _native_lib = None  # export raises AttributeError, not OSError;
        return _native_lib      # any load failure means "use the fallback"


def native_httpd_available() -> bool:
    return _load() is not None


def _parse_headers_blob(blob: bytes) -> Dict[str, str]:
    # "key\0value\0...\0\0" with keys already lowercased by the parser.
    out: Dict[str, str] = {}
    parts = blob.split(b"\0")
    for i in range(0, len(parts) - 1, 2):
        if parts[i]:
            out[parts[i].decode("latin-1")] = parts[i + 1].decode("latin-1")
    return out


def _log_handler_crash(fut) -> None:
    if fut.cancelled():
        return
    exc = fut.exception()
    if exc is not None:
        import traceback
        traceback.print_exception(type(exc), exc, exc.__traceback__)


def _headers_blob(headers: Dict[str, str]) -> bytes:
    out = bytearray()
    for k, v in headers.items():
        out += k.encode("latin-1") + b"\0" + str(v).encode("latin-1") + b"\0"
    return bytes(out)


class NativeHttpServer:
    """Drop-in for ``httpd.HttpServer`` riding the epoll library.

    Construction raises ``OSError`` if the native library is unavailable;
    the ``HttpServer`` factory in ``httpd`` catches that and falls back
    to the pure-Python server, so callers never see the difference."""

    # ``xllm_httpd_stream_chunk`` only queues an op for the event loop,
    # from any thread and for any request's id: it never waits for a
    # client's socket, so a producer may write every stream from one
    # thread (``Response.push``).
    chunks_block = False

    def __init__(self, host: str, port: int, router,
                 max_concurrency=None,
                 admission_exempt: Optional[Tuple[str, ...]] = None
                 ) -> None:
        from xllm_service_tpu.service.httpd import (_ADMISSION_EXEMPT,
                                                    Admission, Request)
        lib = _load()
        if lib is None:
            raise OSError("native httpd unavailable")
        self._lib = lib
        self._Request = Request
        self.router = router
        self.admission = (Admission(max_concurrency)
                          if max_concurrency is not None else None)
        # Stored VERBATIM like PyHttpServer: an explicitly empty tuple
        # means "no exemptions", not "use the defaults".
        self._exempt = (_ADMISSION_EXEMPT if admission_exempt is None
                        else tuple(admission_exempt))
        self._stopped = False
        self._stop_lock = threading.Lock()
        # The callback objects must outlive the server: C++ calls through
        # them until xllm_httpd_stop joins its threads.
        self._cb = _CB_TYPE(self._on_request)
        self._admit_cb = _ADMIT_TYPE(self._on_admit_early)
        self._h = lib.xllm_httpd_start(host.encode(), port, self._cb,
                                       self._admit_cb, None)
        if self._h <= 0:
            raise OSError(f"cannot bind {host}:{port}")
        self.host = host
        self.port = int(lib.xllm_httpd_port(self._h))
        shed = self._render_shed_response()
        lib.xllm_httpd_set_shed_response(self._h, shed, len(shed))
        # Handler pool: REUSED threads instead of one fresh Thread per
        # request (measured: ~1 thread start per request in the service
        # bench profile — spawn cost + GIL churn on the hot path; the
        # reference fronts a bounded brpc worker pool, master.cpp:60-140).
        # Streaming responses PIN their pool thread for the stream's
        # lifetime and the admission limit is LIVE (hot-reloadable
        # callable), so the pool is sized from the limit AT BOOT as a
        # reuse breadth only — _on_request overflows to a fresh Thread
        # whenever every pool thread is busy, preserving the old
        # unbounded-spawn liveness for long-poll handlers (StoreServer
        # /watch) and post-reload limit raises. Created after
        # xllm_httpd_start so thread names carry the RESOLVED port.
        limit = (self.admission._current_limit()
                 if self.admission is not None else None)
        self._pool_cap = max((limit or 0) + 32, 64)
        self._pool_busy = 0
        self._pool_lock = threading.Lock()
        # A pool thread is a bare thread: it takes the handlers' root
        # once, when it starts (obs/profiler.py, as the Python server's
        # thread a connection does).
        self._pool = ThreadPoolExecutor(
            max_workers=self._pool_cap,
            thread_name_prefix=f"httpd-native-{self.port}",
            initializer=profiler.register_thread_root,
            initargs=(profiler.HANDLER_ROOT,))
        self._dispatch_rooted = False

    @staticmethod
    def _render_shed_response() -> bytes:
        from xllm_service_tpu.service.httpd import Response
        resp = Response.error(503, "server at max_concurrency",
                              "overloaded_error")
        return (b"HTTP/1.1 503 Service Unavailable\r\n"
                b"Content-Type: application/json\r\n"
                b"Retry-After: 1\r\nConnection: close\r\n"
                b"Content-Length: " + str(len(resp.body)).encode() +
                b"\r\n\r\n" + resp.body)

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def start(self) -> "NativeHttpServer":
        # Bound since construction (port known, connections queue in the
        # TCP backlog); accepting begins here — same lifecycle as the
        # Python server, whose handlers must not run before the rest of
        # the owning object (worker engine loop, scheduler) is wired up.
        self._lib.xllm_httpd_run(self._h)
        return self

    def stop(self) -> None:
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        # ctypes releases the GIL around the call, so the dispatch
        # thread can finish an in-flight callback while we join it.
        self._lib.xllm_httpd_stop(self._h)
        # After the C++ side is down no new submits can arrive; don't
        # wait — in-flight streams notice the dead connection via the
        # nonzero stream_chunk rc and unwind on their own. Queued
        # never-started tasks (shouldn't exist: overflow spawns instead
        # of queuing) are cancelled so nothing dispatches into the
        # torn-down owner after stop() returns.
        self._pool.shutdown(wait=False, cancel_futures=True)

    # --- request path (dispatch thread → handler threads) -------------

    def _on_admit_early(self, _user, method, path) -> int:
        """Advisory shed for large-body uploads, from the epoll thread at
        header-complete: returning 0 makes C++ answer with the canned 503
        before the body is buffered (the Python server's
        admission-before-body-read invariant). The authoritative
        try_enter still happens at dispatch."""
        try:
            if self.admission is None:
                return 1
            path_s = path.decode("latin-1")
            if path_s.startswith(self._exempt):
                return 1
            return 1 if self.admission.probe() else 0
        except Exception:  # noqa: BLE001 — never wedge the epoll thread
            return 1

    def _send_overloaded(self, rid: int) -> None:
        from xllm_service_tpu.service.httpd import Response
        resp = Response.error(503, "server at max_concurrency",
                              "overloaded_error")
        self._respond(rid, 503,
                      {"Content-Type": resp.content_type,
                       "Retry-After": "1", "Connection": "close"},
                      resp.body)

    def _on_request(self, _user, rid, method, path, query, headers_ptr,
                    headers_len, body_ptr, body_len) -> None:
        if not self._dispatch_rooted:
            # The library's ONE dispatch thread, which runs this much
            # Python a request under the interpreter's lock.
            self._dispatch_rooted = True
            profiler.register_thread_root("httpd.dispatch")
        try:
            method_s = method.decode("latin-1")
            path_s = path.decode("latin-1")
            query_d = parse_qs(query.decode("latin-1")) if query else {}
            headers = _parse_headers_blob(
                ctypes.string_at(headers_ptr, headers_len)
                if headers_ptr and headers_len else b"")
            body = (ctypes.string_at(body_ptr, body_len)
                    if body_ptr and body_len else b"")
            req = self._Request(method_s, path_s, query_d, headers, body)
            counted = (self.admission is not None
                       and not path_s.startswith(self._exempt))
            if counted and not self.admission.try_enter():
                # Shed WITHOUT spawning a thread — the whole point of
                # admission control is that overload costs O(1).
                self._send_overloaded(rid)
                return
            try:
                with self._pool_lock:
                    overflow = self._pool_busy >= self._pool_cap
                    if not overflow:
                        self._pool_busy += 1
                if overflow:
                    # Every pool thread busy (pinned streams, long
                    # polls, or a live limit raise): fall back to the
                    # old per-request Thread so nothing queues behind a
                    # 30 s watcher or an SSE stream.
                    spawn("native_httpd.overflow", self._run_overflow,
                          args=(rid, req, counted),
                          thread_name=(f"httpd-native-{self.port}-ovf")
                          ).start()
                else:
                    try:
                        fut = self._pool.submit(self._run_pooled,
                                                rid, req, counted)
                    except BaseException:
                        # submit() raising (a late dispatch racing
                        # stop()'s pool shutdown) means _run_pooled's
                        # finally never runs: give the busy count back
                        # here or it stays inflated forever and every
                        # future request takes the per-request-Thread
                        # overflow path.
                        with self._pool_lock:
                            self._pool_busy -= 1
                        raise
                    # A fresh Thread's crash used to print via the
                    # default excepthook; an unread Future swallows it
                    # — re-surface.
                    fut.add_done_callback(_log_handler_crash)
            except BaseException:
                # Spawn/submit rejection after try_enter: the admission
                # slot MUST be returned or it leaks until restart.
                if counted:
                    self.admission.leave()
                raise
        except Exception:  # noqa: BLE001 — a broken request must not
            import traceback    # take down the dispatch thread
            traceback.print_exc()
            self._respond(rid, 500, {"Content-Type": "application/json"},
                          b'{"error":{"message":"dispatch error"}}')

    def _run_overflow(self, rid: int, req, counted: bool) -> None:
        # A thread a request: a handler's like the pool's (the
        # supervised wrapper books its seconds when it exits).
        profiler.register_thread_root(profiler.HANDLER_ROOT)
        self._run(rid, req, counted)

    def _run_pooled(self, rid: int, req, counted: bool) -> None:
        try:
            self._run(rid, req, counted)
        except Exception as e:
            # _run answers its own 500s; anything still escaping here
            # would vanish into the executor's never-result()ed Future
            # — the silent-death class xlint rule 14 forbids. Logged +
            # counted; the pool thread survives for the next request.
            threads.record_callback_error("native_httpd.pool", e)
        finally:
            with self._pool_lock:
                self._pool_busy -= 1

    def _run(self, rid: int, req, counted: bool) -> None:
        try:
            resp = self.router.dispatch(req)
        except BaseException:
            if counted:
                self.admission.leave()
            raise
        try:
            self._write(rid, resp)
        finally:
            if counted:
                self.admission.leave()
            if resp.stream is not None and hasattr(resp.stream, "close"):
                try:
                    resp.stream.close()
                except Exception:  # noqa: BLE001 — best-effort cleanup;
                    pass            # the C++ side already resolved rid
            if resp.on_close is not None:
                try:
                    resp.on_close()
                except Exception:  # noqa: BLE001 — a failing finish hook
                    pass            # must not poison the pool thread

    def _respond(self, rid: int, status: int, headers: Dict[str, str],
                 body: bytes) -> None:
        blob = _headers_blob(headers)
        self._lib.xllm_httpd_respond(self._h, rid, status, blob, len(blob),
                                     body, len(body))

    def _write(self, rid: int, resp) -> None:
        headers = {"Content-Type": resp.content_type}
        headers.update(resp.headers)
        if resp.push is not None or resp.stream is not None:
            blob = _headers_blob(headers)
            self._lib.xllm_httpd_stream_begin(self._h, rid, resp.status,
                                              blob, len(blob))
            chunk_call, h = self._lib.xllm_httpd_stream_chunk, self._h

            def write(chunk: bytes) -> int:
                return chunk_call(h, rid, chunk, len(chunk))
            clean = True
            try:
                if resp.push is not None:
                    # The producer writes; this thread parks in there
                    # for the body's lifetime and holds no interpreter.
                    clean = resp.push.serve(write)
                else:
                    for chunk in resp.stream:
                        if chunk and write(chunk) != 0:
                            break   # client went away — stop producing
            except BaseException:
                clean = False
                raise
            finally:
                # Producer failure mid-stream (or a producer that says
                # so): ABORT (close without the chunked terminator) so
                # the client's decoder sees a truncated response — a
                # clean 0-chunk would make a partial answer look
                # complete. The connection must always be resolved one
                # way or the other: a busy+streaming conn is skipped by
                # the idle sweep.
                if clean:
                    self._lib.xllm_httpd_stream_end(self._h, rid)
                else:
                    self._lib.xllm_httpd_stream_abort(self._h, rid)
        else:
            self._respond(rid, resp.status, headers, resp.body)
