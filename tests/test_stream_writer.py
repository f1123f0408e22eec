"""One writer for the worker's streams (PR 56): where the front door's
chunk call cannot block (the native server), an iteration's outputs go
as ONE queue item to ONE thread, root ``worker.stream_writer``, which
runs each through ``Worker._stream_output`` into its stream's sink; a
request's handler thread parks on one event a request. Under the Python
server (a blocking ``wfile``) the connection's own thread pulls from
``live.q`` as before. What is pinned here: both paths deliver the same
bytes, an iteration makes one ``put``, every end of a stream keeps its
meaning, and a writer crash breaks its streams and is counted."""

import http.client
import json
import queue
import re
import threading
import time
from http.client import HTTPConnection

import pytest

from xllm_service_tpu.runtime import worker as worker_mod
from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import (
    HttpServer, PyHttpServer, Response, Router, http_stream)
from xllm_service_tpu.service.native_httpd import native_httpd_available
from xllm_service_tpu.utils import threads
from xllm_service_tpu.utils.types import RequestOutput, SequenceOutput

from tests.test_e2e import small_engine_cfg, wait_until

pytestmark = pytest.mark.skipif(
    not native_httpd_available(),
    reason="csrc/xllm_httpd.cpp does not build here (no toolchain): the "
           "writer owns a stream only under the native front door")

ROOT = "worker.stream_writer"


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------
def _worker(server=None, monkeypatch=None, start=True, **opts):
    if server is not None:
        monkeypatch.setattr(worker_mod, "HttpServer", server)
    w = Worker(WorkerOptions(model="tiny", **opts), InMemoryStore(),
               engine_cfg=small_engine_cfg())
    return w.start() if start else w


@pytest.fixture(scope="module")
def native():
    w = _worker()
    assert not w._srv.chunks_block
    _read_all(_open(w, "warm", "compile here", 4))
    try:
        yield w
    finally:
        w.stop()


def _body(srid, prompt, max_tokens, **more):
    return {"model": "tiny", "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "stream": True, "ignore_eos": True,
            "service_request_id": srid, **more}


def _open(w, srid, prompt, max_tokens, **more):
    """The response of a streamed completion, its body not yet read."""
    host, port = w.name.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=60)
    conn.request("POST", "/v1/completions",
                 body=json.dumps(_body(srid, prompt, max_tokens, **more)),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    resp.conn = conn
    return resp


def _read_all(resp):
    """(the body's bytes, whether it ended as a chunked body must)."""
    got, clean = b"", True
    try:
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            got += chunk
    except (http.client.IncompleteRead, ConnectionError, OSError):
        clean = False
    finally:
        resp.conn.close()
    return got, clean


def _read_until(resp, pattern, timeout=30.0):
    got, deadline = b"", time.monotonic() + timeout
    while not re.search(pattern, got):
        assert time.monotonic() < deadline, got
        chunk = resp.read1(65536)
        assert chunk, got
        got += chunk
    return got


def _events(raw):
    return [json.loads(p) if p != "[DONE]" else p for p in
            re.findall(r"data: (.*)\n\n", raw.decode())]


def _normal(raw):
    """The frames without the one field that is the wall clock's."""
    return re.sub(rb'"created": ?\d+', b'"created":0', raw)


def _stream_many(w, n_streams, n_tokens, tag):
    """``n_streams`` responses open at once (opened in turn: the Python
    server's listen queue is 5 deep), read side by side."""
    resps = [_open(w, f"{tag}-{i}", f"stream number {i} says " * (1 + i % 3),
                   n_tokens) for i in range(n_streams)]
    out = {}

    def one(i):
        out[i] = _read_all(resps[i])
    ts = [threading.Thread(target=one, args=(i,)) for i in range(n_streams)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert sorted(out) == list(range(n_streams))
    return out


def _outputs_by_path(w):
    host, port = w.name.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    return {p: float(v) for p, v in re.findall(
        r'xllm_worker_stream_outputs_total\{model="tiny",path="(\w+)"\} '
        r'(\S+)', text)}


def _slow_emit(w, monkeypatch, seconds=0.01):
    """A tiny model's stream is over in milliseconds: give a test that
    acts mid-stream iterations it can see."""
    real = w._dispatch_outputs

    def slow(rt, outs, ms):
        time.sleep(seconds)
        return real(rt, outs, ms)
    monkeypatch.setattr(w, "_dispatch_outputs", slow)


def _idle(w):
    with w._live_lock:
        live = bool(w._live) or bool(w._live_srid)
    return not live and not w._writer_owned and not any(
        rt.engine.has_work() for rt in w.runtimes.values())


# ---------------------------------------------------------------------------
# (i) the same bytes on both paths, and who ran them
# ---------------------------------------------------------------------------
def test_sixteen_streams_deliver_the_pull_paths_bytes(native, monkeypatch):
    pulled = _worker(PyHttpServer, monkeypatch)
    try:
        assert pulled._srv.chunks_block
        _read_all(_open(pulled, "warm", "compile here", 4))
        want = _stream_many(pulled, 16, 12, "same")
        assert _outputs_by_path(pulled).keys() == {"handler"}
    finally:
        pulled.stop()
    before = _outputs_by_path(native)
    got = _stream_many(native, 16, 12, "same")
    for i in range(16):
        assert got[i][1] and want[i][1]
        assert _normal(got[i][0]) == _normal(want[i][0]), i
        assert _events(got[i][0])[-1] == "[DONE]"
    after = _outputs_by_path(native)
    assert after.keys() == {"writer"}
    assert after["writer"] - before.get("writer", 0) == 16 * 12
    assert wait_until(lambda: _idle(native))


def test_choices_of_one_request_share_its_stream(native, monkeypatch):
    """n > 1: the choices of one ``live`` are one stream's, serialised
    by the one thread; each choice's text is what it is pulled."""
    pulled = _worker(PyHttpServer, monkeypatch)
    try:
        want, _ = _read_all(_open(pulled, "n3", "three ways on", 6, n=3,
                                  seed=7, temperature=0.8))
    finally:
        pulled.stop()
    got, clean = _read_all(_open(native, "n3", "three ways on", 6, n=3,
                                 seed=7, temperature=0.8))
    assert clean

    def texts(raw):
        by = {}
        for ev in _events(raw)[:-1]:
            for c in ev["choices"]:
                by[c["index"]] = by.get(c["index"], "") + c["text"]
        return by
    assert sorted(texts(got)) == [0, 1, 2] and texts(got) == texts(want)


# ---------------------------------------------------------------------------
# (ii) one put an iteration
# ---------------------------------------------------------------------------
class _CountingQueue(queue.SimpleQueue):
    def __init__(self):
        super().__init__()
        self.items = []

    def put(self, item, *a, **kw):
        self.items.append(item)
        super().put(item, *a, **kw)


@pytest.fixture(scope="module")
def counted():
    """A worker whose writer queue records every item, driven by eight
    concurrent streams; what each ``_dispatch_outputs`` call put."""
    w = _worker(start=False)
    w._writer_q = q = _CountingQueue()
    calls, real = [], w._dispatch_outputs

    def emit(rt, outs, ms):
        n0 = len(q.items)
        real(rt, outs, ms)
        calls.append((list(outs), q.items[n0:]))
    w._dispatch_outputs = emit
    w.start()
    try:
        _read_all(_open(w, "warm", "compile here", 4))
        assert wait_until(lambda: _idle(w))
        del calls[:]
        base = (w._writer_batches, w._writer_batch_outs)
        _stream_many(w, 8, 16, "put")
        assert wait_until(lambda: _idle(w))
        yield w, list(calls), base
    finally:
        w.stop()


def test_an_iteration_of_n_streams_makes_one_put(counted):
    w, calls, base = counted
    widest = 0
    for outs, puts in calls:
        # (a handler's _ATTACH may land inside a call: a tuple, not the
        # engine's list)
        batches = [p for p in puts if isinstance(p, list)]
        assert len(batches) == (1 if outs else 0), (len(outs), puts)
        if outs:
            assert sorted(id(o) for _, o in batches[0]) \
                == sorted(id(o) for o in outs)
            widest = max(widest, len(outs))
    assert widest >= 2, "no iteration carried two streams"
    # what a scrape says of it: a sum and a count
    handed = [outs for outs, _ in calls if outs]
    assert w._writer_batches - base[0] == len(handed)
    assert w._writer_batch_outs - base[1] == sum(map(len, handed)) == 8 * 16


def test_outputs_with_a_first_token_lead_their_batch(counted):
    _, calls, _ = counted
    mixed = 0
    for outs, puts in calls:
        for batch in (p for p in puts if isinstance(p, list)):
            first = [o.first_token_stamps is not None for _, o in batch]
            assert first == sorted(first, reverse=True), first
            mixed += 0 < sum(first) < len(first)
            # nothing else moves: each half keeps the emit's order
            order = [outs.index(o) for _, o in batch]
            k = sum(first)
            assert order[:k] == sorted(order[:k])
            assert order[k:] == sorted(order[k:])
    assert mixed, "no iteration carried a first token beside others"


# ---------------------------------------------------------------------------
# (iii) every end of a stream
# ---------------------------------------------------------------------------
def test_done_at_worker_stop(monkeypatch):
    """``stop()`` releases every parked handler: the writer hands each
    open stream ``[DONE]`` and exits before the server goes down.
    (Whether the server's event loop still gets the frame onto the wire
    is the library's shutdown, not the worker's: the sink is what is
    held to it here, and the client's read ending at once.)"""
    w = _worker()
    stopped = False
    try:
        _slow_emit(w, monkeypatch)
        sunk, real = [], w._serve_pushed

        def recording(st, write):
            return real(st, lambda c: (sunk.append(c), write(c))[1])
        monkeypatch.setattr(w, "_serve_pushed", recording)
        resp = _open(w, "stop-1", "until the worker stops", 200)
        head = _read_until(resp, rb"data: \{")
        t0 = time.monotonic()
        w.stop()
        stopped = True
        tail, _ = _read_all(resp)
        assert time.monotonic() - t0 < 10.0       # released, not timed out
        assert sunk[-1] == b"data: [DONE]\n\n"
        assert len(sunk) < 200                    # cut, not served out
        assert b"".join(sunk).startswith(head + tail)
        assert not w._writer_thread.is_alive() and not w._writer_owned
    finally:
        if not stopped:
            w.stop()


def test_abort_breaks_the_socket_without_done(monkeypatch):
    w = _worker()
    try:
        _slow_emit(w, monkeypatch)
        w.failpoints.arm("worker.die_after_n_tokens", mode="after", n=30)
        raw, clean = _read_all(_open(w, "die-1", "dies mid stream", 100))
        assert not clean and b"[DONE]" not in raw
        # (a frame a token at most: the decoder holds half a character)
        assert 1 <= len(_events(raw)) <= 30
        assert wait_until(lambda: not w._writer_owned)
    finally:
        w.stop()


def test_engine_fault_is_a_typed_frame(native, monkeypatch):
    _slow_emit(native, monkeypatch)
    resp = _open(native, "fault-1", "blamed by the boundary", 200)
    head = _read_until(resp, rb"data: \{")
    native._fail_lives_engine_fault(["fault-1"], "blamed: test")
    tail, clean = _read_all(resp)
    last = _events(head + tail)[-1]
    assert clean and last["error"]["type"] == "engine_fault"
    assert last["error"]["code"] == 500
    assert "blamed: test" in last["error"]["message"]
    assert wait_until(lambda: _idle(native))


def test_engine_silence_is_a_typed_timeout_frame(native, monkeypatch):
    """No output for ``request_timeout_s``: the parked handler's timed
    wait posts it, the writer writes it, the engine work is cancelled."""
    monkeypatch.setattr(native.opts, "request_timeout_s", 1.0)
    real = native._dispatch_outputs
    seen = []

    def forty_then_silence(rt, outs, ms):
        if len(seen) < 40:
            seen.append(outs)
            real(rt, outs, ms)
    monkeypatch.setattr(native, "_dispatch_outputs", forty_then_silence)
    t0 = time.monotonic()
    raw, clean = _read_all(_open(native, "silent-1", "then nothing", 100))
    took = time.monotonic() - t0
    evs = _events(raw)
    assert clean and evs[-1]["error"]["type"] == "timeout"
    assert evs[-1]["error"]["code"] == 504
    # (counted from the last output written, so later than 1 s in all)
    assert 1.0 <= took < 20.0 and 1 <= len(seen) <= 40
    assert all("choices" in ev for ev in evs[:-1])
    monkeypatch.setattr(native, "_dispatch_outputs", real)
    assert wait_until(lambda: _idle(native))


def test_a_client_that_leaves_is_cancelled_and_the_others_go_on(
        native, monkeypatch):
    _slow_emit(native, monkeypatch)
    stays = _open(native, "stays-1", "reads to the end", 60)
    leaves = _open(native, "leaves-1", "hangs up early", 200)
    _read_until(leaves, rb"data: \{")
    leaves.conn.sock.close()
    leaves.conn.close()
    raw, clean = _read_all(stays)
    assert clean and _events(raw)[-1] == "[DONE]"
    assert len(_events(raw)) >= 2
    # the one that left: its live dropped, its engine work cancelled
    assert wait_until(lambda: _idle(native))
    with native._live_lock:
        assert "leaves-1" not in native._live_srid


def _late_handler(w, monkeypatch, initial=None):
    """Hold a request's handler back until the engine has emitted for it
    and the writer holds those outputs."""
    real, held = w._sse_response, []

    def late(live, _initial=None):
        assert wait_until(lambda: len(live.push.held) >= 2)
        held.append(len(live.push.held))
        return real(live, initial)
    monkeypatch.setattr(w, "_sse_response", late)
    return held


def test_an_output_emitted_before_the_sink_is_written_first(
        native, monkeypatch):
    want, _ = _read_all(_open(native, "early-1", "the engine is faster", 9))
    held = _late_handler(native, monkeypatch)
    got, clean = _read_all(_open(native, "early-1", "the engine is faster",
                                 9))
    assert clean and held and held[0] >= 2
    assert _normal(got) == _normal(want)
    assert len(_events(got)) >= 3


def test_initial_frames_lead(native, monkeypatch):
    """The PD and import paths' ``initial`` outputs go out before any
    output the writer held."""
    lead = RequestOutput(
        request_id="lead-1", service_request_id="lead-1",
        outputs=[SequenceOutput(index=0, text="<migrated>",
                                token_ids=[5])])
    want, _ = _read_all(_open(native, "lead-1", "after the first", 6))
    _late_handler(native, monkeypatch, initial=[lead])
    got, clean = _read_all(_open(native, "lead-1", "after the first", 6))
    evs = _events(got)
    assert clean and evs[-1] == "[DONE]"
    assert evs[0]["choices"][0]["text"] == "<migrated>"
    # and behind it, the stream as it is without one
    assert [e["choices"][0]["text"] for e in evs[1:-1]] == \
        [e["choices"][0]["text"] for e in _events(want)[:-1]]


# ---------------------------------------------------------------------------
# (iv) a writer crash
# ---------------------------------------------------------------------------
def test_a_writer_crash_breaks_its_streams_and_is_counted(monkeypatch):
    w = _worker()
    try:
        _read_all(_open(w, "warm", "compile here", 4))
        _slow_emit(w, monkeypatch)
        real = w._serve_pushed

        def raising_sink(st, write):
            n = [0]

            def sink(chunk):
                n[0] += 1
                if st.live.service_request_id == "bad-1" and n[0] == 4:
                    raise OSError("the sink broke")
                return write(chunk)
            return real(st, sink)
        monkeypatch.setattr(w, "_serve_pushed", raising_sink)
        before = threads.crash_counts().get(ROOT, 0)
        other = _open(w, "beside-1", "owned by the same writer", 200)
        _read_until(other, rb"data: \{")
        bad, bad_clean = _read_all(_open(w, "bad-1", "its sink raises", 200))
        beside, beside_clean = _read_all(other)
        # both broken (no [DONE], no clean end), neither left hanging
        assert not bad_clean and b"[DONE]" not in bad
        assert not beside_clean and b"[DONE]" not in beside
        # (the streams are broken first, the crash counted as it leaves)
        assert wait_until(
            lambda: threads.crash_counts().get(ROOT, 0) == before + 1)
        assert wait_until(lambda: _idle(w))
        # the supervised thread came back: the next stream is served
        assert wait_until(w._writer_thread.is_alive)
        raw, clean = _read_all(_open(w, "after-1", "a writer again", 5))
        assert clean and _events(raw)[-1] == "[DONE]"
        host, port = w.name.rsplit(":", 1)
        conn = HTTPConnection(host, int(port), timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        m = re.search(r'xllm_thread_crashes_total\{root="%s"\} (\S+)'
                      % re.escape(ROOT), text)
        assert m and float(m.group(1)) >= 1
        assert f'xllm_thread_cpu_seconds_total{{root="{ROOT}"}}' in text
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# The front door's hand-off, without a worker
# ---------------------------------------------------------------------------
class _Pushed:
    def __init__(self, chunks, clean):
        self.chunks, self.clean = chunks, clean
        self.thread = self.rcs = None

    def serve(self, write):
        self.thread = threading.current_thread().name
        rcs, done = [], threading.Event()

        def produce():          # any thread may write: not the handler's
            rcs.extend(write(c) for c in self.chunks)
            done.set()
        threading.Thread(target=produce, daemon=True).start()
        assert done.wait(timeout=10)
        self.rcs = rcs
        return self.clean


@pytest.mark.parametrize("clean", [True, False], ids=["end", "abort"])
def test_the_native_server_serves_a_pushed_body(clean):
    body = _Pushed([b"data: one\n\n", b"data: two\n\n"], clean)
    closed = []
    router = Router()

    def handler(req):
        resp = Response.sse(push=body)
        resp.on_close = lambda: closed.append(True)
        return resp
    router.route("GET", "/pushed", handler)
    srv = HttpServer("127.0.0.1", 0, router).start()
    try:
        assert not srv.chunks_block
        chunks = []
        try:
            for c in http_stream("GET", srv.address, "/pushed", timeout=10):
                chunks.append(c)
            ended = True
        except (http.client.IncompleteRead, ConnectionError):
            ended = False
        assert b"".join(chunks) == b"data: one\n\ndata: two\n\n"
        assert ended is clean
        assert body.rcs == [0, 0] and "httpd-native" in body.thread
        assert wait_until(lambda: closed == [True])
    finally:
        srv.stop()


def test_who_owns_a_stream_is_read_off_the_server_and_the_request(native):
    """With no option: under a server whose chunk call can block,
    without ``stream``, or to the master's fan-in, ``live.push`` stays
    None and the outputs go to ``live.q``."""
    from types import SimpleNamespace
    blocking = SimpleNamespace(_srv=SimpleNamespace(chunks_block=True))
    assert PyHttpServer.chunks_block and not native._srv.chunks_block

    def adopt(worker, stream=True, to_service=False):
        live = worker_mod._LiveRequest(
            None, native.tokenizer, "sr", "tiny", False, stream, False,
            to_service)
        Worker._writer_adopt(worker, live)
        return live.push
    assert adopt(blocking) is None
    assert adopt(native).path == "writer"
    assert adopt(native, stream=False) is None
    assert adopt(native, to_service=True) is None
