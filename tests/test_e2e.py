"""End-to-end in-process cluster: Master (service) + Worker (TPU engine on
CPU devices) + InMemoryStore — OpenAI requests in, tokens out.

This is the multi-"instance" integration harness the reference never built
(SURVEY.md §4): real HTTP between service and worker, real registration via
store lease + heartbeat, both response topologies.
"""

import json
import re
import time
from typing import Optional

import pytest

from xllm_service_tpu.config import (
    EngineConfig, InstanceType, LoadBalancePolicyType, ServiceOptions)
from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import (
    http_json, http_stream, iter_sse_events)
from xllm_service_tpu.service.master import Master


def wait_until(cond, timeout=10.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


def small_engine_cfg() -> EngineConfig:
    return EngineConfig(page_size=16, num_pages=64, max_model_len=256,
                        max_batch_size=4, max_prefill_tokens=256,
                        prefill_buckets=(32, 64, 128))


def make_cluster(store, decode_to_service: bool = False,
                 n_workers: int = 1, engine_cfg: Optional[EngineConfig] = None):
    opts = ServiceOptions(
        http_port=0, rpc_port=0, num_output_pools=4,
        load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
        block_size=16, heartbeat_interval_s=0.2,
        master_upload_interval_s=0.2,
        enable_decode_response_to_service=decode_to_service)
    master = Master(opts, store=store).start()
    workers = []
    for _ in range(n_workers):
        wopts = WorkerOptions(
            port=0, instance_type=InstanceType.DEFAULT,
            service_addr=master.rpc_address, model="tiny",
            heartbeat_interval_s=0.2, lease_ttl_s=2.0)
        workers.append(Worker(
            wopts, store,
            engine_cfg=engine_cfg or small_engine_cfg()).start())
    assert wait_until(
        lambda: len(master.scheduler.instance_mgr.prefill_instances())
        == n_workers, timeout=15.0), "workers never registered"
    return master, workers


@pytest.fixture()
def store():
    s = InMemoryStore(sweep_interval_s=0.02)
    yield s
    s.close()


class TestEndToEnd:
    def test_completion_non_stream(self, store):
        master, workers = make_cluster(store)
        try:
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "hello world",
                 "max_tokens": 4, "temperature": 0.0,
                 "ignore_eos": True},
                timeout=120.0)
            assert status == 200, resp
            assert resp["object"] == "text_completion"
            assert resp["choices"][0]["finish_reason"] == "length"
            assert resp["usage"]["completion_tokens"] == 4
            assert resp["usage"]["prompt_tokens"] == len("hello world")
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_chat_stream_sse_grammar(self, store):
        master, workers = make_cluster(store)
        try:
            payloads = list(iter_sse_events(http_stream(
                "POST", master.http_address, "/v1/chat/completions",
                {"model": "tiny",
                 "messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 3, "temperature": 0.0, "stream": True,
                 "ignore_eos": True,
                 "stream_options": {"include_usage": True}},
                timeout=120.0)))
            assert payloads[-1] == "[DONE]"
            objs = [json.loads(p) for p in payloads[:-1]]
            assert objs[0]["object"] == "chat.completion.chunk"
            assert objs[0]["choices"][0]["delta"]["role"] == "assistant"
            finish_chunks = [o for o in objs
                     if o["choices"]
                     and o["choices"][0]["finish_reason"]]
            assert finish_chunks and finish_chunks[0]["choices"][0]["finish_reason"] \
                == "length"
            usage = [o for o in objs if not o["choices"]]
            assert usage and usage[0]["usage"]["completion_tokens"] == 3
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_decode_response_to_service_topology(self, store):
        master, workers = make_cluster(store, decode_to_service=True)
        try:
            # Worker must have learned the mode from /rpc/config.
            assert wait_until(lambda: workers[0]._decode_to_service,
                              timeout=5.0)
            status, resp = http_json(
                "POST", master.http_address, "/v1/chat/completions",
                {"model": "tiny",
                 "messages": [{"role": "user", "content": "ping"}],
                 "max_tokens": 4, "temperature": 0.0,
                 "ignore_eos": True},
                timeout=120.0)
            assert status == 200, resp
            assert resp["object"] == "chat.completion"
            assert resp["usage"]["completion_tokens"] == 4
            # stream through the RPC fan-in too
            payloads = list(iter_sse_events(http_stream(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "abc", "max_tokens": 2,
                 "temperature": 0.0, "stream": True, "ignore_eos": True},
                timeout=120.0)))
            assert payloads[-1] == "[DONE]"
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_models_and_metrics_endpoints(self, store):
        master, workers = make_cluster(store)
        try:
            status, models = http_json("GET", master.http_address,
                                       "/v1/models")
            assert status == 200
            assert any(m["id"] == "tiny" for m in models["data"])

            import http.client
            conn = http.client.HTTPConnection(master.http_address,
                                              timeout=10)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            text = r.read().decode()
            conn.close()
            assert "xllm_service_instances 1" in text
            assert "xllm_service_is_master 1" in text

            # Worker-local metrics carry the per-phase step-time ledger
            # (pack/dispatch/readback per program) after serving traffic.
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "warm", "max_tokens": 2,
                 "temperature": 0.0, "ignore_eos": True}, timeout=60.0)
            assert status == 200
            conn = http.client.HTTPConnection(workers[0].name, timeout=10)
            conn.request("GET", "/metrics")
            wtext = conn.getresponse().read().decode()
            conn.close()
            assert 'xllm_worker_phase_seconds_total' in wtext
            assert 'phase="prefill.dispatch"' in wtext
            # ...and the jit compile census: warmup plus the completion
            # above must have compiled at least one prefill variant.
            assert 'xllm_worker_jit_compiles_total' in wtext
            m_compiles = re.search(
                r'xllm_worker_jit_compiles_total\{model="tiny",'
                r'program="prefill"\} (\d+)', wtext)
            assert m_compiles, wtext
            assert int(m_compiles.group(1)) >= 1

            # Keep-alive reuse pool counters (service→worker transport)
            # surface on /metrics so transport regressions are visible
            # under service_bench. Cluster traffic above (registration
            # RPCs + the completion relay) must have moved them.
            conn = http.client.HTTPConnection(master.http_address,
                                              timeout=10)
            conn.request("GET", "/metrics")
            mtext = conn.getresponse().read().decode()
            conn.close()
            for counter in ("hits_total", "misses_total",
                            "overflow_total", "expired_total", "idle"):
                assert (f'xllm_http_conn_pool_{counter}'
                        f'{{plane="service"}} ') in mtext, mtext
            misses = next(
                int(line.split()[-1]) for line in mtext.splitlines()
                if line.startswith('xllm_http_conn_pool_misses_total'
                                   '{plane="service"}'))
            assert misses >= 1     # at least one fresh TCP connect

            # Exposition-format gate on BOTH planes: every line parses
            # and every histogram is internally consistent (_bucket
            # cumulative-monotone, _count == +Inf bucket, _sum present).
            from xllm_service_tpu.obs import validate_exposition
            for plane, text in (("service", mtext), ("worker", wtext)):
                errs = validate_exposition(text)
                assert errs == [], f"{plane} /metrics invalid: {errs}"
            # The request latency histograms recorded the completion
            # (non-stream: TTFT is worker-side only, but queue-wait and
            # end-to-end are always observable at the front door).
            assert "xllm_service_queue_wait_ms_bucket" in mtext
            assert "xllm_service_e2e_ms_count" in mtext
            # Engine step-loop flush split occupancy prefill vs decode.
            assert ('xllm_worker_step_tokens_total'
                    '{model="tiny",phase="prefill"}') in wtext
            assert ('xllm_worker_step_tokens_total'
                    '{model="tiny",phase="decode"}') in wtext
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_streamed_chat_decode_pipeline_overlap(self, store):
        """The decode pipeline end to end: a streamed chat completes
        with the usual SSE grammar, and the worker /metrics plane proves
        the overlap engaged — decode steps were on the device before
        their iteration began (launched ahead and taken), the overlap
        counters and the hit-ratio gauge say so, and the split readback
        attribution reaches the phase ledger."""
        import http.client
        opts = ServiceOptions(
            http_port=0, rpc_port=0, num_output_pools=4,
            load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
            block_size=16, heartbeat_interval_s=0.2,
            master_upload_interval_s=0.2)
        master = Master(opts, store=store).start()
        # Large pages so the write of a step launched ahead stays
        # covered by the already-grown table on most steps (a launch
        # ahead never allocates: at a page boundary it is the tail
        # dispatch's, the rest hit).
        ecfg = EngineConfig(page_size=64, num_pages=32, max_model_len=256,
                            max_batch_size=4, max_prefill_tokens=256,
                            prefill_buckets=(32, 64, 128))
        wopts = WorkerOptions(
            port=0, instance_type=InstanceType.DEFAULT,
            service_addr=master.rpc_address, model="tiny",
            heartbeat_interval_s=0.2, lease_ttl_s=2.0)
        worker = Worker(wopts, store, engine_cfg=ecfg).start()
        try:
            assert wait_until(
                lambda: len(master.scheduler.instance_mgr
                            .prefill_instances()) == 1, timeout=15.0)
            payloads = list(iter_sse_events(http_stream(
                "POST", master.http_address, "/v1/chat/completions",
                {"model": "tiny",
                 "messages": [{"role": "user", "content": "overlap"}],
                 "max_tokens": 24, "temperature": 0.0, "stream": True,
                 "ignore_eos": True}, timeout=120.0)))
            assert payloads[-1] == "[DONE]"
            objs = [json.loads(p) for p in payloads[:-1]]
            assert objs[0]["object"] == "chat.completion.chunk"
            assert any(o["choices"] and o["choices"][0]["finish_reason"]
                       == "length" for o in objs)

            eng = worker.primary_runtime().engine
            assert eng.phase_counts["decode.ahead_hit"] > 0
            assert eng.phase_counts["decode.ahead_dispatch"] > 0
            conn = http.client.HTTPConnection(worker.name, timeout=10)
            conn.request("GET", "/metrics")
            wtext = conn.getresponse().read().decode()
            conn.close()
            ahead = next(
                float(line.split()[-1]) for line in wtext.splitlines()
                if line.startswith('xllm_worker_decode_ahead_total'
                                   '{model="tiny",result="hit"}'))
            assert ahead > 0
            launched = next(
                float(line.split()[-1]) for line in wtext.splitlines()
                if line.startswith('xllm_worker_decode_ahead_total'
                                   '{model="tiny",result="launched"}'))
            assert launched >= ahead
            # the kept series alone says it: the pair that repeated it
            # under the deleted burst's names is gone (PR 55)
            assert "xllm_worker_decode_overlap" not in wtext
            om = eng.overlap_metrics()
            assert om["ahead_hits"] >= ahead > 0
            assert om["ahead_dispatches"] >= launched
            # The split readback attribution reaches the phase ledger.
            assert 'phase="decode.device_wait"' in wtext
            assert 'phase="decode.host_copy"' in wtext
            from xllm_service_tpu.obs import validate_exposition
            assert validate_exposition(wtext) == []
        finally:
            worker.stop()
            master.stop()

    def test_request_span_timeline_cross_plane(self, store):
        """Stream a chat completion, then pull its merged span timeline
        from /admin/trace/<id>: the full service-plane stage sequence
        plus the worker-side stages (shipped on the heartbeat path)
        under the SAME correlation id the service stamped on the
        forwarded request (x-xllm-request-id)."""
        import http.client
        master, workers = make_cluster(store)
        try:
            payloads = list(iter_sse_events(http_stream(
                "POST", master.http_address, "/v1/chat/completions",
                {"model": "tiny",
                 "messages": [{"role": "user", "content": "trace me"}],
                 "max_tokens": 3, "temperature": 0.0, "stream": True,
                 "ignore_eos": True}, timeout=120.0)))
            assert payloads[-1] == "[DONE]"
            srid = json.loads(payloads[0])["id"]

            def fetch_span():
                conn = http.client.HTTPConnection(master.http_address,
                                                  timeout=10)
                conn.request("GET", f"/admin/trace/{srid}")
                r = conn.getresponse()
                body = r.read().decode()
                conn.close()
                return (json.loads(body) if r.status == 200 else None)

            # Worker stages arrive on the next heartbeat (0.2s cadence).
            def worker_merged():
                span = fetch_span()
                return span is not None and any(
                    e["plane"] == "worker" for e in span["events"])
            assert wait_until(worker_merged, timeout=15.0), \
                "worker span stages never merged into the service trace"

            span = fetch_span()
            assert span["request_id"] == srid
            stages = {(e["plane"], e["stage"]) for e in span["events"]}
            for st in ("received", "admitted", "scheduled", "dispatched",
                       "first_token", "finished"):
                assert ("service", st) in stages, (st, sorted(stages))
            for st in ("received", "scheduled", "first_token",
                       "finished"):
                assert ("worker", st) in stages, (st, sorted(stages))
            # The worker read the service's correlation header and
            # logged its span under that exact id.
            assert span["attrs"]["worker"]["correlation_header"] == srid
            # Events are wall-clock ordered; per-plane monotonic stamps
            # order that plane's own stages.
            svc = [e["stage"] for e in span["events"]
                   if e["plane"] == "service"]
            assert svc.index("received") < svc.index("first_token") \
                < svc.index("finished")
            # Unknown ids 404 instead of fabricating a timeline.
            conn = http.client.HTTPConnection(master.http_address,
                                              timeout=10)
            conn.request("GET", "/admin/trace/no-such-request")
            assert conn.getresponse().status == 404
            conn.close()
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_conn_pool_counters_unit(self):
        """Pool-counter semantics pinned without a cluster: a put past
        the per-address cap counts overflow; an idle-expired get counts
        expiry + miss; a warm get counts a hit."""
        from xllm_service_tpu.service.httpd import _ConnPool

        class _FakeConn:
            sock = None

            def close(self):
                pass

        pool = _ConnPool()
        for _ in range(pool._MAX_IDLE_PER_ADDR + 1):
            pool.put("a:1", _FakeConn())
        st = pool.stats()
        assert st["overflow_total"] == 1
        assert st["idle"] == pool._MAX_IDLE_PER_ADDR
        conn, reused = pool.get("a:1", timeout=1.0)
        assert reused
        assert pool.stats()["hits_total"] == 1
        # Age the rest out: the next get must expire them and MISS.
        with pool._lock:
            pool._idle["a:1"] = [(c, t - 2 * pool._MAX_IDLE_S)
                                 for (c, t) in pool._idle["a:1"]]
        conn2, reused2 = pool.get("a:1", timeout=1.0)
        assert not reused2
        st = pool.stats()
        assert st["misses_total"] == 1
        assert st["expired_total"] == pool._MAX_IDLE_PER_ADDR - 1
        conn2.close()

    def test_admin_flags_hot_reload(self, store):
        """SLO thresholds flip at runtime through /admin/flags (the
        reference marks target_ttft/target_tpot brpc-reloadable,
        global_gflags.cpp:95-104) and the routing layer sees the new
        values because ServiceOptions is shared by reference."""
        master, workers = make_cluster(store)
        try:
            status, flags = http_json("GET", master.http_address,
                                      "/admin/flags")
            assert status == 200
            assert flags["target_tpot_ms"] == pytest.approx(
                master.opts.target_tpot_ms)

            status, resp = http_json(
                "POST", master.http_address, "/admin/flags",
                {"target_ttft_ms": 750, "target_tpot_ms": 25})
            assert status == 200, resp
            # The scheduler/InstanceMgr routing path reads the same
            # options object — no restart, next request uses these.
            assert master.scheduler.instance_mgr.opts.target_ttft_ms == 750
            assert master.scheduler.opts.target_tpot_ms == 25

            status, resp = http_json(
                "POST", master.http_address, "/admin/flags",
                {"nope": 1})
            assert status == 400
            # Atomicity: a rejected batch must leave EVERY flag untouched,
            # including the valid keys that preceded the bad one.
            status, resp = http_json(
                "POST", master.http_address, "/admin/flags",
                {"target_ttft_ms": 111, "target_tpot_ms": -5})
            assert status == 400
            assert master.opts.target_ttft_ms == 750
            status, resp = http_json(
                "POST", master.http_address, "/admin/flags",
                {"target_tpot_ms": "nan"})
            assert status == 400
            assert master.opts.target_tpot_ms == 25
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_graceful_drain_completes_inflight_stream(self, store):
        """drain_and_stop: the in-flight stream finishes cleanly while
        new requests are refused, then the worker deregisters. (The
        reference has no graceful shutdown at all — SURVEY.md §7.4.)"""
        import json as _json
        import threading
        master, workers = make_cluster(store)
        events = []
        done = threading.Event()
        body = {"model": "tiny", "prompt": "drain me", "max_tokens": 60,
                "temperature": 0.0, "ignore_eos": True}

        def reader():
            for e in iter_sse_events(http_stream(
                    "POST", master.http_address, "/v1/completions",
                    dict(body, stream=True))):
                events.append(e)
            done.set()

        try:
            # Greedy baseline on the same engine: what the full stream
            # must reproduce even though drain happens mid-generation.
            status, base = http_json(
                "POST", master.http_address, "/v1/completions", body,
                timeout=60.0)
            assert status == 200
            want_text = base["choices"][0]["text"]

            t = threading.Thread(target=reader, daemon=True)
            t.start()
            # Let the request reach the engine before draining.
            assert wait_until(
                lambda: any(rt.engine is not None and rt.engine.has_work()
                            for rt in workers[0].runtimes.values()),
                timeout=10.0)
            assert workers[0].drain_and_stop(timeout_s=30.0)
            assert done.wait(timeout=30.0)
            # The stream completed: [DONE]-terminated, full greedy text.
            assert events and events[-1] == "[DONE]"
            got_text = "".join(
                _json.loads(e)["choices"][0].get("text", "")
                for e in events if e != "[DONE]")
            assert got_text == want_text
            # Worker deregistered: the service clears it via lease revoke.
            assert wait_until(
                lambda: master.scheduler.instance_mgr.prefill_instances()
                == [], timeout=10.0)
            # New requests now have nowhere to go.
            status, _ = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "late", "max_tokens": 1},
                timeout=30.0)
            assert status == 503
        finally:
            for w in workers:
                try:
                    w.stop()        # idempotent after drain_and_stop
                except Exception:   # noqa: BLE001
                    pass
            master.stop()

    def test_graceful_drain_rpc_topology(self, store):
        """Drain must also see idle in decode-to-service mode, where the
        engine loop pushes outputs to the service fan-in and the worker
        cleans its registry inline rather than via a response consumer."""
        master, workers = make_cluster(store, decode_to_service=True)
        try:
            # The worker learns this mode from GET /rpc/config — the
            # request must not race it into the relay topology.
            assert wait_until(lambda: workers[0]._decode_to_service,
                              timeout=10.0)
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "rpc mode warm",
                 "max_tokens": 4, "temperature": 0.0,
                 "ignore_eos": True}, timeout=120.0)
            assert status == 200, resp
            assert workers[0].drain_and_stop(timeout_s=20.0)
            assert wait_until(
                lambda: master.scheduler.instance_mgr.prefill_instances()
                == [], timeout=10.0)
        finally:
            for w in workers:
                try:
                    w.stop()
                except Exception:  # noqa: BLE001
                    pass
            master.stop()

    def test_redispatch_on_worker_refusal(self, store):
        """A request routed to a worker that refuses it (503: draining)
        is re-dispatched to a healthy instance instead of surfacing the
        error — the rescheduling the reference README claims but never
        implements (SURVEY.md §5.3)."""
        master, workers = make_cluster(store, n_workers=2)
        try:
            # Force refusal on worker 0 WITHOUT telling the router (the
            # drain handshake normally removes it from routing first) —
            # this exercises the re-dispatch path itself.
            workers[0]._refuse_new = True
            for i in range(4):     # RR alternates; ~half hit worker 0
                status, resp = http_json(
                    "POST", master.http_address, "/v1/completions",
                    {"model": "tiny", "prompt": f"redispatch {i}",
                     "max_tokens": 2, "temperature": 0.0,
                     "ignore_eos": True}, timeout=60.0)
                assert status == 200, resp
            # Streaming takes the eager-open + re-dispatch path.
            events = list(iter_sse_events(http_stream(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "redispatch stream",
                 "max_tokens": 2, "stream": True, "temperature": 0.0,
                 "ignore_eos": True})))
            assert events and events[-1] == "[DONE]"
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_worker_failure_detected_via_lease(self, store):
        master, workers = make_cluster(store)
        try:
            workers[0].stop()   # revokes lease → DELETE → removal
            assert wait_until(
                lambda: master.scheduler.instance_mgr.prefill_instances()
                == [], timeout=8.0)
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "x", "max_tokens": 1},
                timeout=30.0)
            assert status == 503
        finally:
            master.stop()

    def test_sleep_wakeup_via_model_triggers(self, store):
        master, workers = make_cluster(store)
        try:
            status, resp = http_json(
                "POST", master.http_address, "/model/triggers",
                {"model": "tiny", "action": "sleep"}, timeout=60.0)
            assert status == 200, resp
            rt = workers[0].primary_runtime()
            assert rt.state == "asleep" and rt.engine is None
            status, resp = http_json(
                "POST", master.http_address, "/model/triggers",
                {"model": "tiny", "action": "wakeup"}, timeout=120.0)
            assert status == 200, resp
            assert rt.state == "awake" and rt.engine is not None
            # Serves again after wakeup (weights restored from host RAM).
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "back", "max_tokens": 2,
                 "temperature": 0.0, "ignore_eos": True},
                timeout=120.0)
            assert status == 200, resp
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_round_robin_across_two_workers(self, store):
        master, workers = make_cluster(store, n_workers=2)
        try:
            for i in range(2):
                status, resp = http_json(
                    "POST", master.http_address, "/v1/completions",
                    {"model": "tiny", "prompt": f"req {i}",
                     "max_tokens": 1, "temperature": 0.0,
                     "ignore_eos": True},
                    timeout=120.0)
                assert status == 200, resp
        finally:
            for w in workers:
                w.stop()
            master.stop()


def _get_text(address: str, path: str) -> str:
    import http.client
    conn = http.client.HTTPConnection(address, timeout=10)
    conn.request("GET", path)
    body = conn.getresponse().read().decode()
    conn.close()
    return body


class TestPrefixReuse:
    """Cluster-scale prefix reuse acceptance (docs/KV_CACHE.md): a
    prompt served cold on worker A, then a same-prefix prompt routed
    (round-robin) to worker B — B pulls A's cached blocks over
    /kv/blocks, reports nonzero cached tokens, and produces
    byte-identical temperature=0 output; the planner's verdict + cost
    terms sit on the request span and in
    xllm_kv_fetch_decisions_total; an armed worker.fail_kv_fetch
    degrades to recompute with output still byte-identical."""

    def test_cross_worker_fetch_and_failpoint_fallback(self, store):
        master, workers = make_cluster(store, n_workers=2)
        try:
            def completion(token_ids):
                status, resp = http_json(
                    "POST", master.http_address, "/v1/completions",
                    {"model": "tiny", "token_ids": list(token_ids),
                     "max_tokens": 6, "temperature": 0.0,
                     "ignore_eos": True}, timeout=120.0)
                assert status == 200, resp
                return resp["id"], resp["choices"][0]["text"]

            # --- warm fetch ------------------------------------------
            prompt_a = list(range(10, 74)) + [99, 98, 97]  # 4 blocks
            _, cold_text = completion(prompt_a)            # RR → w1
            assert wait_until(
                lambda: master.scheduler.kvcache_mgr.num_blocks() >= 4,
                timeout=15.0), "cluster index never learned A's blocks"
            srid1, warm_text = completion(prompt_a)        # RR → w2
            assert warm_text == cold_text                  # byte-identical
            fetcher = [w for w in workers
                       if w.primary_runtime().engine.fetched_blocks]
            assert len(fetcher) == 1, "exactly one worker fetched"
            w2 = fetcher[0]
            # B's engine reports cached tokens (fetched blocks hit).
            assert w2.primary_runtime().engine.prefix_hit_tokens > 0
            assert w2.kv_fetch_attempts == 1 \
                and w2.kv_fetch_failures == 0
            assert w2.kv_fetch_bytes > 0
            # Planner verdict counted on the service plane...
            metrics = _get_text(master.http_address, "/metrics")
            assert ('xllm_kv_fetch_decisions_total{verdict="fetch"}'
                    in metrics), metrics.splitlines()[-5:]
            # ...and the decision + both cost terms on the span.
            span = json.loads(_get_text(master.http_address,
                                        f"/admin/trace/{srid1}"))
            kvf = span["attrs"]["schedule_decision"]["kv_fetch"]
            assert kvf["verdict"] == "fetch"
            assert kvf["fetch_ms"] > 0 and kvf["recompute_ms"] > 0
            assert kvf["holder"] and kvf["holder_blocks"] >= 4
            # Worker-side span half gains cache_hit_tokens once its
            # heartbeat ships the finished span.
            def hit_tokens_on_span():
                s = json.loads(_get_text(master.http_address,
                                         f"/admin/trace/{srid1}"))
                return s["attrs"].get("worker", {}).get(
                    "cache_hit_tokens", 0) > 0
            assert wait_until(hit_tokens_on_span, timeout=15.0)
            # Fetched blocks visible on the worker plane's /metrics.
            wm = _get_text(w2.name, "/metrics")
            assert "xllm_worker_prefix_cache_fetched_blocks_total" in wm
            assert "xllm_worker_prefix_cache_hashed_tokens_total" in wm

            # --- failpoint fallback ----------------------------------
            prompt_b = list(range(200, 264)) + [1, 2, 3]
            blocks_before = master.scheduler.kvcache_mgr.num_blocks()
            _, cold_b = completion(prompt_b)               # cold, no plan
            assert wait_until(
                lambda: master.scheduler.kvcache_mgr.num_blocks()
                > blocks_before, timeout=15.0)
            for w in workers:
                w.failpoints.arm("worker.fail_kv_fetch", mode="always")
            _, warm_b = completion(prompt_b)
            assert warm_b == cold_b        # recompute fallback, correct
            assert sum(w.kv_fetch_failures for w in workers) >= 1
            tripped = [w for w in workers if w.kv_fetch_failures]
            wm = _get_text(tripped[0].name, "/metrics")
            assert ('xllm_failpoints_tripped_total{'
                    'name="worker.fail_kv_fetch"}') in wm
        finally:
            for w in workers:
                w.stop()
            master.stop()


class TestJudgmentLayer:
    """PR-4 acceptance: drive load past a deliberately tight SLO target
    and prove the whole attribution loop — burn-rate breach at
    /admin/slo, the breach event at /admin/events, the routing audit on
    the request's span, a parseable flight-recorder bundle holding all
    of it, and both planes' /metrics still passing the exposition
    validator with the new series present."""

    def test_slo_breach_audit_events_and_debug_bundle(self, store,
                                                      monkeypatch):
        # Sub-millisecond targets: every real request breaches. Fast
        # ticks so the breach opens inside the test budget; windows wide
        # enough that the bad traffic cannot age OUT of the fast window
        # (closing the breach) before the later assertions run.
        monkeypatch.setenv("XLLM_SLO_TTFT_MS", "0.01")
        monkeypatch.setenv("XLLM_SLO_E2E_MS", "0.01")
        monkeypatch.setenv("XLLM_SLO_QUEUE_WAIT_MS", "0.01")
        monkeypatch.setenv("XLLM_SLO_FAST_WINDOW_S", "30.0")
        monkeypatch.setenv("XLLM_SLO_SLOW_WINDOW_S", "120.0")
        monkeypatch.setenv("XLLM_SLO_TICK_S", "0.1")
        opts = ServiceOptions(
            http_port=0, rpc_port=0, num_output_pools=4,
            load_balance_policy=LoadBalancePolicyType.CACHE_AWARE,
            block_size=16, heartbeat_interval_s=0.2,
            master_upload_interval_s=0.2)
        master = Master(opts, store=store).start()
        workers = [Worker(WorkerOptions(
            port=0, instance_type=InstanceType.DEFAULT,
            service_addr=master.rpc_address, model="tiny",
            heartbeat_interval_s=0.2, lease_ttl_s=2.0), store,
            engine_cfg=small_engine_cfg()).start()]
        try:
            assert wait_until(
                lambda: len(master.scheduler.instance_mgr
                            .prefill_instances()) == 1, timeout=15.0)
            srid = None
            for i in range(3):
                status, resp = http_json(
                    "POST", master.http_address, "/v1/completions",
                    {"model": "tiny", "prompt": f"breach me {i}",
                     "max_tokens": 2, "temperature": 0.0,
                     "ignore_eos": True}, timeout=60.0)
                assert status == 200, resp
                srid = resp["id"]

            # 1) /admin/slo: the e2e objective breaches with a nonzero
            # fast-window burn (every request blew the 0.01ms target).
            def breached():
                status, slo = http_json("GET", master.http_address,
                                        "/admin/slo")
                if status != 200:
                    return False
                obj = slo["objectives"]["e2e"]
                return bool(obj["breach"]) \
                    and obj["windows"]["fast"]["burn_rate"] > 0
            assert wait_until(breached, timeout=15.0), \
                "SLO breach never opened"
            status, slo = http_json("GET", master.http_address,
                                    "/admin/slo")
            assert "e2e" in slo["breached"]
            assert slo["objectives"]["e2e"]["windows"]["fast"][
                "attainment"] < 1.0

            # 2) /admin/events: the breach event is in the log, next to
            # the cluster-lifecycle events that preceded it.
            status, ev = http_json("GET", master.http_address,
                                   "/admin/events?since=0")
            assert status == 200
            types = {e["type"] for e in ev["events"]}
            assert "slo_breach_open" in types, types
            assert "master_elected" in types
            assert "instance_join" in types
            assert "instance_confirm" in types
            assert ev["latest_seq"] >= len(ev["events"])
            open_ev = next(e for e in ev["events"]
                           if e["type"] == "slo_breach_open")
            assert open_ev["attrs"]["fast_burn"] > 0
            # since=<seq> pagination: nothing before the cursor.
            status, tail = http_json(
                "GET", master.http_address,
                f"/admin/events?since={open_ev['seq'] - 1}")
            assert all(e["seq"] >= open_ev["seq"]
                       for e in tail["events"])

            # 3) The routing audit rode the request's span: candidates
            # with their score terms, and the winner that served it.
            status, span = http_json("GET", master.http_address,
                                     f"/admin/trace/{srid}")
            assert status == 200, span
            audit = span["attrs"]["schedule_decision"]
            assert audit["policy"] == "cache_aware"
            cands = audit["prefill"]["candidates"]
            assert cands and all(
                k in cands[0] for k in ("instance", "score",
                                        "match_ratio", "kv_usage",
                                        "waiting_ratio"))
            assert audit["prefill"]["winner"] == workers[0].name
            # No prefix overlap on a cold cache: the fallback is named.
            assert audit["prefill"]["fallback_reason"] \
                == "no_prefix_overlap"

            # 4) /admin/debug_bundle: one parseable snapshot with all of
            # the above inside.
            status, bundle = http_json("GET", master.http_address,
                                       "/admin/debug_bundle")
            assert status == 200
            assert bundle["is_master"] is True
            assert bundle["service_id"] == master.scheduler.service_id
            inst = {i["name"]: i for i in bundle["instances"]}
            assert workers[0].name in inst
            assert "heartbeat_age_s" in inst[workers[0].name]
            assert bundle["slo"]["objectives"]["e2e"]["breach"]
            assert any(e["type"] == "slo_breach_open"
                       for e in bundle["events"])
            assert isinstance(bundle["tracked_requests"], list)
            recent = bundle["spans"]["recent_finished"]
            assert any(s["request_id"] == srid for s in recent)
            assert "schedule_decision" in next(
                s for s in recent if s["request_id"] == srid)["attrs"]
            assert bundle["flags"]["target_ttft_ms"] == \
                opts.target_ttft_ms
            # The embedded metrics text is the real exposition.
            from xllm_service_tpu.obs import validate_exposition
            assert validate_exposition(bundle["metrics"]) == []

            # 5) Both planes' live /metrics still validate, with the new
            # judgment-layer series present.
            mtext = _get_text(master.http_address, "/metrics")
            wtext = _get_text(workers[0].name, "/metrics")
            for plane, text in (("service", mtext), ("worker", wtext)):
                errs = validate_exposition(text)
                assert errs == [], f"{plane} /metrics invalid: {errs}"
            assert 'xllm_slo_breach{objective="e2e"} 1' in mtext
            assert 'xllm_slo_attainment{objective="e2e"}' in mtext
            assert 'xllm_slo_burn_rate{objective="e2e",window="fast"}' \
                in mtext
            assert 'xllm_events_total{type="slo_breach_open"} ' in mtext
            assert ('xllm_schedule_decisions_total{policy="cache_aware"'
                    ',reason="fallback"} ') in mtext
            assert "xllm_span_evictions_total 0" in mtext
            assert "xllm_span_evictions_total 0" in wtext
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_trace_tombstone_410_after_eviction(self, store, monkeypatch):
        """A span the ring HELD and evicted answers 410 {"evicted":
        true} at /admin/trace — distinguishable from a never-seen 404."""
        monkeypatch.setenv("XLLM_SPAN_RING", "4")
        master, workers = make_cluster(store)
        try:
            srids = []
            for i in range(6):      # overflow the 4-slot ring
                status, resp = http_json(
                    "POST", master.http_address, "/v1/completions",
                    {"model": "tiny", "prompt": f"evict {i}",
                     "max_tokens": 1, "temperature": 0.0,
                     "ignore_eos": True}, timeout=60.0)
                assert status == 200, resp
                srids.append(resp["id"])
            import http.client
            conn = http.client.HTTPConnection(master.http_address,
                                              timeout=10)
            conn.request("GET", f"/admin/trace/{srids[0]}")
            r = conn.getresponse()
            body = json.loads(r.read().decode())
            conn.close()
            assert r.status == 410, body
            assert body["evicted"] is True
            # Never-seen ids still 404.
            conn = http.client.HTTPConnection(master.http_address,
                                              timeout=10)
            conn.request("GET", "/admin/trace/never-seen-rid")
            assert conn.getresponse().status == 404
            conn.close()
            # The eviction is visible on /metrics.
            mtext = _get_text(master.http_address, "/metrics")
            evicted = next(
                int(line.split()[-1]) for line in mtext.splitlines()
                if line.startswith("xllm_span_evictions_total"))
            assert evicted >= 2
        finally:
            for w in workers:
                w.stop()
            master.stop()


class TestEmbeddings:
    def test_embeddings_endpoint(self, store):
        master, workers = make_cluster(store)
        try:
            status, resp = http_json(
                "POST", master.http_address, "/v1/embeddings",
                {"model": "tiny",
                 "input": ["hello world", "hello world", "different"]},
                timeout=120.0)
            assert status == 200, resp
            assert resp["object"] == "list"
            assert len(resp["data"]) == 3
            import numpy as np
            e0 = np.array(resp["data"][0]["embedding"])
            e1 = np.array(resp["data"][1]["embedding"])
            e2 = np.array(resp["data"][2]["embedding"])
            # Unit-norm, deterministic, and input-sensitive.
            assert abs(np.linalg.norm(e0) - 1.0) < 1e-3
            np.testing.assert_allclose(e0, e1, atol=1e-5)
            assert np.linalg.norm(e0 - e2) > 1e-3
            assert resp["usage"]["prompt_tokens"] > 0

            # Over-limit inputs get a 400 naming the limit and the
            # offending input — NEVER a silent truncation to the first
            # 256 tokens (a truncated embedding is a wrong answer that
            # looks right). Pins Worker.EMBED_MAX_TOKENS semantics.
            from xllm_service_tpu.runtime.worker import Worker
            limit = Worker.EMBED_MAX_TOKENS
            # ByteTokenizer (the registry-model fallback): 1 token/byte.
            status, resp = http_json(
                "POST", master.http_address, "/v1/embeddings",
                {"model": "tiny", "input": ["short", "x" * (limit + 40)]},
                timeout=120.0)
            assert status == 400, resp
            msg = resp["error"]["message"]
            assert str(limit) in msg, msg        # limit named
            assert "input 1" in msg, msg         # offender named
            # Exactly at the limit still succeeds (boundary pin).
            status, resp = http_json(
                "POST", master.http_address, "/v1/embeddings",
                {"model": "tiny", "input": ["y" * limit]}, timeout=120.0)
            assert status == 200, resp
            assert resp["usage"]["prompt_tokens"] == limit
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_embeddings_requires_input(self, store):
        master, workers = make_cluster(store)
        try:
            status, resp = http_json(
                "POST", master.http_address, "/v1/embeddings",
                {"model": "tiny"}, timeout=30.0)
            assert status == 400
        finally:
            for w in workers:
                w.stop()
            master.stop()

    def test_role_flip_revokes_old_lease(self, store):
        """A /flip_role re-registration must revoke the previous lease —
        each flip otherwise leaks a live lease in the store."""
        master, workers = make_cluster(store)
        try:
            w = workers[0]
            base = len(store._leases)
            for role in ("PREFILL", "DECODE", "PREFILL", "DEFAULT"):
                status, resp = http_json(
                    "POST", w.name, "/flip_role",
                    {"instance_type": role}, timeout=10.0)
                assert status == 200, resp
            assert len(store._leases) == base, (
                f"leaked {len(store._leases) - base} leases across flips")
        finally:
            for wk in workers:
                wk.stop()
            master.stop()


class TestRequestTrace:
    """--enable_request_trace captures BOTH halves: the inbound body and
    every outbound write (per-frame egress — reference call_data.h:151-162
    traces each payload the CallData writes)."""

    @pytest.mark.parametrize("decode_to_service", [False, True])
    def test_stream_egress_traced_per_frame(self, store, tmp_path,
                                            decode_to_service):
        trace_path = str(tmp_path / "trace.jsonl")
        opts = ServiceOptions(
            http_port=0, rpc_port=0, num_output_pools=4,
            load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
            block_size=16, heartbeat_interval_s=0.2,
            master_upload_interval_s=0.2,
            enable_request_trace=True, trace_path=trace_path,
            enable_decode_response_to_service=decode_to_service)
        master = Master(opts, store=store).start()
        workers = [Worker(WorkerOptions(
            port=0, instance_type=InstanceType.DEFAULT,
            service_addr=master.rpc_address, model="tiny",
            heartbeat_interval_s=0.2, lease_ttl_s=2.0), store,
            engine_cfg=small_engine_cfg()).start()]
        try:
            assert wait_until(
                lambda: len(master.scheduler.instance_mgr
                            .prefill_instances()) == 1, timeout=15.0)
            if decode_to_service:
                assert wait_until(lambda: workers[0]._decode_to_service,
                                  timeout=5.0)
            frames = list(http_stream(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": "trace me", "max_tokens": 3,
                 "temperature": 0.0, "stream": True, "ignore_eos": True},
                timeout=120.0))
            assert frames

            with open(trace_path, encoding="utf-8") as f:
                lines = [json.loads(l) for l in f if l.strip()]
            srids = {l["service_request_id"] for l in lines}
            assert len(srids) == 1
            stages = [l["data"].get("stage") for l in lines]
            assert "ingress" in stages
            egress = [l["data"] for l in lines
                      if l["data"].get("stage") == "egress"
                      and "frame" in l["data"]]
            # One trace line per WRITE, in write order. In the RPC fan-in
            # topology a write is exactly one assembler frame; the relay
            # topology writes transport chunks, which may coalesce
            # several frames — so the per-frame count is only asserted
            # where writes are frames.
            assert egress
            if decode_to_service:
                assert len(egress) >= 3
            assert [e["seq"] for e in egress] == list(range(len(egress)))
            joined = "".join(e["frame"] for e in egress)
            assert "[DONE]" in joined
            # The ingress half survived alongside (the round-2 state).
            ingress = [l["data"] for l in lines
                       if l["data"].get("stage") == "ingress"]
            assert ingress[0]["body"]["prompt"] == "trace me"
        finally:
            for w in workers:
                w.stop()
            master.stop()
