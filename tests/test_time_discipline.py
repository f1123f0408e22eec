"""Time-discipline regression pins (xlint rules 20–22, PR 17).

Each test pins one runtime fix the rules forced in-tree, so the fix
cannot regress even if the rule (or its allowlist) drifts:

1. the worker's fan-out queue waits are bounded by
   ``request_timeout_s`` and surface a TYPED 504 — never a silent
   stall — on engine silence (the pulled stream, the collect path, AND
   a stream the worker's writer owns, where the bounded wait is the
   parked handler's on the stream's event);
2. the etcd watch stream socket carries the config-time
   ``XLLM_ETCD_WATCH_TIMEOUT_S`` bound, and both watch planes pace
   reconnects through ``utils/retry.RetryPolicy`` (capped, jittered,
   stop-aware) instead of fixed-interval sleeps;
3. the chaos e2e: a loadgen ``--chaos`` stage arming ``store.hang`` +
   ``worker.hang_rpc`` mid-run — every request must RESOLVE (success
   or typed error) within the harness budget, and the cluster must
   serve again after the stage with no thread wedged past its
   deadline.
"""

import json
import queue
import threading
import time
from types import SimpleNamespace

import pytest

from xllm_service_tpu.utils.retry import RetryPolicy


def _fake_worker(timeout_s: float):
    """The minimal surface ``_stream_sse``/``_collect_full`` touch:
    a request-timeout knob, the finalizer, and the step fan-out."""
    w = SimpleNamespace()
    w.opts = SimpleNamespace(request_timeout_s=timeout_s)
    w.finalized = []
    w._finalize_live = w.finalized.append
    w._process_step_output = lambda live, out: []
    w._fold_token_out = lambda live: None    # nothing was handed out
    return w


def _fake_live():
    return SimpleNamespace(q=queue.Queue(), is_chat=False,
                           service_request_id="sr-1", model="tiny",
                           include_usage=False, emit_token_ids=False,
                           target_n=1)


class TestBoundedEngineWait:
    """Worker fan-out: engine silence is a typed 504, not a stall."""

    def test_stream_engine_silence_yields_typed_504(self):
        from xllm_service_tpu.runtime.worker import Worker
        w, live = _fake_worker(0.05), _fake_live()
        t0 = time.monotonic()
        frames = list(Worker._stream_sse(w, live))
        assert time.monotonic() - t0 < 5.0, "stream wait not bounded"
        assert len(frames) == 1
        payload = json.loads(frames[0].decode()[len("data: "):])
        assert payload["error"]["type"] == "timeout"
        assert payload["error"]["code"] == 504
        # The finalizer ran: unfinished engine work gets cancelled.
        assert w.finalized == [live]

    def test_writer_owned_stream_engine_silence_yields_typed_504(self):
        """Where the stream writer owns the stream, the thread that
        WRITES the typed frame is the writer's; the handler's thread
        only parks, with a timed wait, and posts ``_TIMEOUT``."""
        import functools
        from xllm_service_tpu.runtime import worker as wm
        w, live = _fake_worker(0.05), _fake_live()
        w._live_lock = threading.Lock()
        w._writer_closed = False
        w._writer_q = queue.SimpleQueue()
        w._writer_owned, w._writer_batch = set(), ()
        for name in ("_writer_run", "_writer_take", "_writer_end"):
            setattr(w, name,
                    functools.partial(getattr(wm.Worker, name), w))
        st = live.push = wm._Stream(w, live, "writer")
        wrote = []

        def writer():
            for batch in iter(w._writer_q.get, None):
                w._writer_run(batch)
        t = threading.Thread(target=writer, daemon=True)
        t.start()

        def sink(chunk):
            wrote.append((chunk, threading.current_thread()))
            return 0
        t0 = time.monotonic()
        clean = wm.Worker._serve_pushed(w, st, sink)
        assert 0.05 <= time.monotonic() - t0 < 5.0, "park not bounded"
        w._writer_q.put(None)
        t.join(timeout=5)
        assert clean and st.done and st.over.is_set()
        assert len(wrote) == 1 and wrote[0][1] is t
        payload = json.loads(wrote[0][0].decode()[len("data: "):])
        assert payload["error"]["type"] == "timeout"
        assert payload["error"]["code"] == 504

    def test_collect_engine_silence_returns_typed_504(self):
        from xllm_service_tpu.runtime.worker import Worker
        w, live = _fake_worker(0.05), _fake_live()
        t0 = time.monotonic()
        resp = Worker._collect_full(w, live)
        assert time.monotonic() - t0 < 5.0, "collect wait not bounded"
        assert resp.status == 504
        body = json.loads(resp.body.decode())
        assert body["error"]["type"] == "timeout"
        assert w.finalized == [live]


class TestWatchPlaneBounds:
    """Watch streams: bounded sockets, policy-paced reconnects."""

    def test_etcd_watch_socket_carries_config_timeout(self, monkeypatch):
        from xllm_service_tpu.service.etcd_store import (
            EtcdStore, MockEtcdServer)
        from tests.test_e2e import wait_until
        monkeypatch.setenv("XLLM_ETCD_WATCH_TIMEOUT_S", "7.5")
        server = MockEtcdServer().start()
        try:
            client = EtcdStore(server.address)
            try:
                assert client._watch_timeout_s == 7.5
                seen = []
                wid = client.add_watch("XLLMTEST:",
                                       lambda ev: seen.append(ev))
                # The live stream connection registered for this watch
                # carries the knob (HTTPConnection.timeout feeds
                # sock.settimeout on connect).
                assert wait_until(
                    lambda: client._watches.get(wid, (None, None))[1]
                    is not None, timeout=10.0)
                conn = client._watches[wid][1]
                assert conn.timeout == 7.5
                # The conn registers BEFORE the stream is established,
                # and a "from now" watch only sees events after the
                # server opens it — so nudge with warm-up puts until
                # one lands (then the stream carries a resume revision
                # and cannot miss anything).
                deadline = time.monotonic() + 10.0
                while not any(e[1] == "XLLMTEST:warm" for e in seen) \
                        and time.monotonic() < deadline:
                    client.put("XLLMTEST:warm", "x")
                    time.sleep(0.05)
                assert any(e[1] == "XLLMTEST:warm" for e in seen)
                # And the bounded stream still delivers events.
                client.put("XLLMTEST:k", "v")
                assert wait_until(lambda: ("PUT", "XLLMTEST:k", "v")
                                  in seen, timeout=10.0)
                client.cancel_watch(wid)
            finally:
                client.close()
        finally:
            server.stop()

    def test_etcd_watch_reconnect_routes_through_policy(self):
        from xllm_service_tpu.service.etcd_store import (
            EtcdStore, MockEtcdServer)
        server = MockEtcdServer().start()
        try:
            client = EtcdStore(server.address)
            try:
                assert isinstance(client._watch_retry, RetryPolicy)
                # Capped: a long outage cannot grow an unclamped
                # exponential (the float-overflow class PR 6 fixed).
                assert client._watch_retry.max_delay_s <= 10.0
                # Stop-aware: shutdown interrupts the backoff at once
                # instead of waiting the interval out.
                stop = threading.Event()
                stop.set()
                t0 = time.monotonic()
                assert client._watch_retry.sleep(9, stop_event=stop) \
                    is False
                assert time.monotonic() - t0 < 1.0
            finally:
                client.close()
        finally:
            server.stop()

    def test_remote_store_watch_backoff_is_policy_paced(self):
        from xllm_service_tpu.service.coordination_net import RemoteStore
        store = RemoteStore("127.0.0.1:1")   # never dialed
        assert isinstance(store._watch_retry, RetryPolicy)
        assert store._watch_retry.max_delay_s <= 10.0
        stop = threading.Event()
        stop.set()
        t0 = time.monotonic()
        assert store._watch_retry.sleep(9, stop_event=stop) is False
        assert time.monotonic() - t0 < 1.0


@pytest.mark.slow
class TestChaosHangStage:
    """Satellite e2e: the loadgen --chaos machinery arms the two hang
    classes mid-run; the time-discipline contract says NOTHING may
    stall unboundedly — every request resolves, the cluster recovers."""

    def test_hang_stage_every_request_resolves_within_budget(self):
        from benchmarks.loadgen import parse_chaos, run_load
        from tests.test_e2e import make_cluster, wait_until
        from xllm_service_tpu.service.coordination import InMemoryStore
        from xllm_service_tpu.service.httpd import http_json

        store = InMemoryStore(sweep_interval_s=0.02)
        master, workers = make_cluster(store)

        def transient_threads():
            # httpd-native-* are ThreadPoolExecutor pool threads: they
            # grow under load and idle until server shutdown by design
            # (Dummy-* are native-lib callback registrations). The
            # threads a server-side stall WOULD wedge are the loadgen
            # workers and the chaos scheduler — count only those.
            return [t for t in threading.enumerate()
                    if not t.name.startswith(("httpd-native-", "Dummy-"))]

        try:
            baseline_threads = len(transient_threads())
            # store.hang: every store call sleeps then fails like a
            # timeout (capped by the guard deadline). worker.hang_rpc:
            # generate handlers block 2 s then refuse typed — well
            # under the client budget, far over a healthy latency.
            chaos = parse_chaos(
                "store.hang=always:2@0+4,"
                "worker.hang_rpc=always:2@0+4")
            t0 = time.monotonic()
            summary = run_load(
                master.http_address, "tiny", num_requests=6,
                request_rate=0.0, max_tokens=4, mean_prompt_len=16,
                timeout=90.0, chaos=chaos)
            wall = time.monotonic() - t0
            # Budget: the whole run — hang window, redispatch retries,
            # recovery — must finish in bounded time, nowhere near the
            # 90 s client timeout that would mark a silent stall.
            assert wall < 80.0, f"chaos run took {wall:.1f}s"
            # EVERY request resolved: completed or typed error, none
            # missing (a None result = a loadgen thread still blocked
            # at join timeout = an unbounded server-side stall).
            assert summary["num_ok"] + summary["num_errors"] == 6, \
                summary
            assert summary["chaos"]["schedule"], summary["chaos"]
            # The stage is over: a fresh request must succeed promptly
            # (no serving thread still wedged on the released hang).
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "after the storm",
                 "max_tokens": 4, "temperature": 0.0,
                 "ignore_eos": True}, timeout=60.0)
            assert status == 200, resp
            assert resp["choices"][0]["text"]
            # No serving thread blocked past its deadline: the
            # transient load-generator / hang threads drain back to
            # (about) the pre-run population.
            assert wait_until(
                lambda: len(transient_threads())
                <= baseline_threads + 3, timeout=30.0), \
                f"threads wedged: {[t.name for t in transient_threads()]}"
        finally:
            for w in workers:
                w.stop()
            master.stop()
            store.close()
