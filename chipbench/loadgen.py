"""The load generator: a child process that never imports JAX.

Reads a schedule (``chipbench/traffic.py``), sends every request to the
master's OpenAI endpoint (``POST /v1/completions``, SSE streaming) from
one asyncio thread, and times every request from when it was DUE, not
from when it got round to sending it. Writes one record per request.

The SSE framing follows ``benchmarks/loadgen.py`` (``data: <json>`` /
``data: [DONE]``); the schedule is new: absolute due times on the
monotonic clock, never a sleep between sends.

Run: python -m chipbench.loadgen <schedule.json> <host:port> <model> <out.json>
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Any, Dict, List, Optional

REQUEST_TIMEOUT_S = 180.0


def token_ids_of(text: str) -> List[int]:
    """The served tokenizer spells token i as the word ``t<i>``."""
    return [int(w[1:]) for w in text.split() if w[:1] == "t"
            and w[1:].isdigit()]


def request_bytes(host: str, body: Dict[str, Any], rid: str) -> bytes:
    raw = json.dumps(body, separators=(",", ":")).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\nx-request-id: {rid}\r\n"
            f"Content-Length: {len(raw)}\r\nConnection: close\r\n\r\n")
    return head.encode() + raw


async def _read_body(reader: asyncio.StreamReader, chunked: bool):
    """Yields the response body's bytes as they arrive, de-chunked."""
    if not chunked:
        while True:
            data = await reader.read(65536)
            if not data:
                return
            yield data
    while True:
        line = await reader.readline()
        if not line:
            return
        size = int(line.split(b";")[0].strip() or b"0", 16)
        if size == 0:
            return
        yield await reader.readexactly(size)
        await reader.readexactly(2)


async def one_request(addr: str, model: str, rid: str, prompt: List[int],
                      max_tokens: int, sampling: Dict[str, Any],
                      rec: Dict[str, Any]) -> None:
    """Send one request and record when each streamed frame arrived."""
    host, port = addr.rsplit(":", 1)
    body = {"model": model, "token_ids": prompt, "max_tokens": max_tokens,
            "stream": True, **sampling}
    rec["sent"] = time.monotonic()
    try:
        reader, writer = await asyncio.open_connection(host, int(port))
    except OSError as e:
        rec["error"] = f"connect: {e}"
        return
    try:
        writer.write(request_bytes(addr, body, rid))
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1]) if status else 0
        chunked = False
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"transfer-encoding") \
                    and b"chunked" in line.lower():
                chunked = True
        buf = b""
        async for data in _read_body(reader, chunked):
            now = time.monotonic()
            buf += data
            if rec["status"] != 200:
                continue
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                for ln in frame.split(b"\n"):
                    if not ln.startswith(b"data:"):
                        continue
                    payload = ln[5:].strip()
                    if payload == b"[DONE]":
                        rec["done"] = now
                        continue
                    obj = json.loads(payload)
                    if obj.get("error"):
                        rec["error"] = str(obj["error"])[:300]
                        continue
                    for ch in obj.get("choices") or []:
                        ids = token_ids_of(ch.get("text") or "")
                        if ids:
                            rec["frames"].append([now, len(ids)])
                            rec["token_ids"].extend(ids)
                        if ch.get("finish_reason"):
                            rec["finish"] = ch["finish_reason"]
        if rec["status"] != 200:
            rec["error"] = f"HTTP {rec['status']}: " \
                + buf[:200].decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001 — recorded, counted as failed
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        writer.close()
    rec["ok"] = (not rec.get("error") and rec.get("done") is not None
                 and len(rec["token_ids"]) == max_tokens)
    if not rec["ok"] and not rec.get("error"):
        rec["error"] = (f"stream ended with {len(rec['token_ids'])} of "
                        f"{max_tokens} tokens")


def _new_record(req: Dict[str, Any]) -> Dict[str, Any]:
    return {"id": req["id"], "due": None, "sent": None, "status": 0,
            "frames": [], "token_ids": [], "ok": False, "done": None,
            "error": None, "max_tokens": req["max_tokens"],
            "phase": req.get("phase"), "client": req.get("client")}


async def drive(schedule: Dict[str, Any], addr: str, model: str,
                sampling: Dict[str, Any], t0: float
                ) -> List[Dict[str, Any]]:
    docs = schedule["docs"]
    records: List[Dict[str, Any]] = []

    def prompt(req):
        doc = docs[req["doc"]] if req.get("doc") is not None else []
        return doc + req["tokens"]

    async def run(req, due: Optional[float]):
        rec = _new_record(req)
        records.append(rec)
        if due is not None:
            await asyncio.sleep(max(0.0, t0 + due - time.monotonic()))
            rec["due"] = t0 + due
        else:
            rec["due"] = time.monotonic()
        try:
            await asyncio.wait_for(
                one_request(addr, model, req["id"], prompt(req),
                            req["max_tokens"], sampling, rec),
                REQUEST_TIMEOUT_S)
        except asyncio.TimeoutError:
            rec["ok"] = False
            rec["error"] = f"no end of stream within {REQUEST_TIMEOUT_S} s"

    if schedule["loop"] == "open":
        await asyncio.gather(*[run(r, r["due"])
                               for r in schedule["requests"]],
                             return_exceptions=True)
    else:
        end = t0 + schedule["end_t"]

        async def client(c: int):
            await asyncio.sleep(max(
                0.0, t0 + c * schedule["stagger_s"] - time.monotonic()))
            mine = sorted((r for r in schedule["requests"]
                           if r["client"] == c), key=lambda r: r["order"])
            for req in mine:
                if time.monotonic() >= end:
                    return
                await run(req, None)
            if end < t0 + 1e8:
                raise RuntimeError(
                    f"client {c} ran out of requests before the run's "
                    f"end: raise max_rounds_per_s")

        res = await asyncio.gather(*[client(c) for c in
                                     range(schedule["clients"])],
                                   return_exceptions=True)
        for r in res:
            if isinstance(r, Exception):
                records.append({"id": "client", "ok": False, "frames": [],
                                "token_ids": [], "due": None, "sent": None,
                                "error": f"{type(r).__name__}: {r}"})
    return records


def main(argv: List[str]) -> int:
    schedule_path, addr, model, out_path = argv[:4]
    with open(schedule_path, "r", encoding="utf-8") as f:
        schedule = json.load(f)
    sampling = schedule.get("sampling") or {}
    if schedule.get("only") == "setup":
        # Fill the cache: the shared documents, one after another.
        reqs = [dict(r, due=None, client=0, order=i)
                for i, r in enumerate(schedule["setup_requests"])]
        schedule = dict(schedule, loop="closed", clients=1, stagger_s=0.0,
                        end_t=1e9, requests=reqs)
    t0 = time.monotonic() + 0.25
    print(f"T0 {t0!r}", flush=True)
    try:
        records = asyncio.run(drive(schedule, addr, model, sampling, t0))
    except RuntimeError as e:
        records = [{"id": "loadgen", "ok": False, "frames": [],
                    "token_ids": [], "due": None, "sent": None,
                    "error": str(e)}]
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"t0": t0, "records": records}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
