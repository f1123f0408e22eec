"""The generator times every request from when it was due, against a
stand-in server that streams SSE frames (chunked) at known delays."""

import asyncio
import json
import threading
import time

import pytest

from chipbench import loadgen, stats


def test_token_ids_of():
    assert loadgen.token_ids_of(" t12 t7") == [12, 7]
    assert loadgen.token_ids_of("t5") == [5]
    assert loadgen.token_ids_of("") == []


class _Server:
    """Minimal HTTP/1.1 server: first frame after ``first_s``, then one
    frame every ``gap_s``, ``max_tokens`` in all, then [DONE]."""

    def __init__(self, first_s=0.05, gap_s=0.02, chunked=True):
        self.first_s, self.gap_s, self.chunked = first_s, gap_s, chunked
        self.bodies = []
        self.loop = asyncio.new_event_loop()
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        self.ready.wait(5)

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.server = self.loop.run_until_complete(
            asyncio.start_server(self._serve, "127.0.0.1", 0))
        self.port = self.server.sockets[0].getsockname()[1]
        self.ready.set()
        self.loop.run_forever()

    async def _serve(self, reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        n = int([ln for ln in head.split(b"\r\n")
                 if ln.lower().startswith(b"content-length")][0]
                .split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        self.bodies.append(body)
        writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream"
                     b"\r\n" + (b"Transfer-Encoding: chunked\r\n"
                                if self.chunked else b"") + b"\r\n")

        def send(payload: bytes):
            data = b"data: " + payload + b"\n\n"
            if self.chunked:
                data = b"%x\r\n" % len(data) + data + b"\r\n"
            writer.write(data)

        for i in range(body["max_tokens"]):
            await asyncio.sleep(self.first_s if i == 0 else self.gap_s)
            last = i == body["max_tokens"] - 1
            send(json.dumps({"id": "x", "choices": [{
                "text": ("" if i == 0 else " ") + f"t{100 + i}",
                "finish_reason": "length" if last else None}]}).encode())
            await writer.drain()
        send(b"[DONE]")
        if self.chunked:
            writer.write(b"0\r\n\r\n")
        await writer.drain()
        writer.close()

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)


@pytest.mark.parametrize("chunked", [True, False])
def test_open_loop_times_from_due(chunked):
    srv = _Server(chunked=chunked)
    try:
        sched = {"loop": "open", "docs": [[9, 9, 9]], "requests": [
            {"id": f"r{i}", "due": 0.1 * i, "doc": 0 if i % 2 else None,
             "tokens": [3, 4, 5], "max_tokens": 5} for i in range(6)]}
        t0 = time.monotonic() + 0.05
        recs = asyncio.run(loadgen.drive(
            sched, f"127.0.0.1:{srv.port}", "m", {"temperature": 0.0}, t0))
    finally:
        srv.close()
    assert len(recs) == 6 and all(r["ok"] for r in recs), recs
    for i, r in enumerate(sorted(recs, key=lambda r: r["id"])):
        assert r["due"] == pytest.approx(t0 + 0.1 * i)
        assert 0 <= r["sent"] - r["due"] < 0.05       # lateness
        ttft = r["frames"][0][0] - r["due"]
        assert 0.05 <= ttft < 0.15
        assert r["token_ids"] == [100, 101, 102, 103, 104]
        assert r["done"] is not None
    assert [b["token_ids"] for b in srv.bodies].count([9, 9, 9, 3, 4, 5]) == 3
    assert all(b["stream"] and b["temperature"] == 0.0 for b in srv.bodies)
    late = stats.lateness(recs)
    assert late["worst_ms"] < 50


def test_closed_loop_sends_next_when_last_ends():
    srv = _Server(first_s=0.03, gap_s=0.01)
    try:
        sched = {"loop": "closed", "clients": 2, "stagger_s": 0.05,
                 "end_t": 0.6, "docs": [], "requests": [
                     {"id": f"c{c}k{k}", "client": c, "order": k,
                      "due": None, "doc": None, "tokens": [3],
                      "max_tokens": 3} for c in range(2) for k in range(40)]}
        t0 = time.monotonic() + 0.05
        recs = asyncio.run(loadgen.drive(
            sched, f"127.0.0.1:{srv.port}", "m", {}, t0))
    finally:
        srv.close()
    assert all(r["ok"] for r in recs)
    for c in range(2):
        mine = sorted((r for r in recs if r["client"] == c),
                      key=lambda r: r["due"])
        assert len(mine) >= 3
        assert mine[0]["due"] >= t0 + c * 0.05 - 1e-3
        for a, b in zip(mine, mine[1:]):
            assert b["due"] >= a["done"]              # one at a time
            assert b["due"] - a["done"] < 0.05
        assert mine[-1]["due"] < t0 + 0.6             # none started late
