"""Device-plane step observatory (tier-1).

Units for the step flight recorder (bounded ring, CLOSED field schema,
seq/window tails, disabled-mode zero-build gate), the peaks table and
its env override, the master's
StepBooks (heartbeat-tail dedupe on seq), the cluster-merged
chrome-trace builder (byte-stable determinism, counter tracks, complete
s→t→f flows) and its offline validator (tools/trace_view.py); then one
e2e on two IN-PROCESS CPU workers: a named request streamed through the
front door must come back out of ``GET /admin/timeline`` as a validated
trace with service-plane stage slices, worker step slices with phase
sub-events, ≥1 counter track, and a complete flow chain for that rid.
"""

import json
import time
import tracemalloc

import pytest

from tools.trace_view import main as trace_view_main
from tools.trace_view import summarize, validate_trace
from xllm_service_tpu.config import (
    EngineConfig, InstanceType, LoadBalancePolicyType, ServiceOptions)
from xllm_service_tpu.obs import Registry, steptrace
from xllm_service_tpu.obs.timeline import (
    CHROME_PHASES, MASTER_PID, build_timeline, render)
from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import (
    http_json, http_stream, iter_sse_events)
from xllm_service_tpu.service.master import Master


def wait_until(cond, timeout=15.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


# ---------------------------------------------------------------------------
# Units: the ring
# ---------------------------------------------------------------------------
class TestStepTraceRing:
    def test_ring_is_bounded_and_seq_monotone(self):
        st = steptrace.StepTrace(enabled=True, ring=16)
        for i in range(40):
            st.record(kind="decode", step_ms=float(i), t_wall=1000.0 + i)
        assert len(st) == 16
        tail = st.tail()
        assert [r["seq"] for r in tail] == list(range(25, 41))
        assert st.last_seq() == 40

    def test_capacity_floor(self):
        assert steptrace.StepTrace(enabled=True, ring=1).capacity == 16

    def test_unknown_field_rejected_schema_is_closed(self):
        st = steptrace.StepTrace(enabled=True, ring=16)
        with pytest.raises(ValueError, match="STEP_FIELDS"):
            st.record(kind="decode", stepms=1.0)
        # Every schema field round-trips.
        st.record(**{f: 0 for f in steptrace.STEP_FIELDS
                     if f != "seq"})
        assert len(st) == 1

    def test_tail_since_seq_and_window(self):
        st = steptrace.StepTrace(enabled=True, ring=64)
        for i in range(10):
            st.record(kind="decode", t_wall=1000.0 + i)
        since = st.tail(since_seq=7)
        assert [r["seq"] for r in since] == [8, 9, 10]
        # Window clips against the NEWEST record's wall clock.
        win = st.tail(window_s=2.5)
        assert [r["t_wall"] for r in win] == [1007.0, 1008.0, 1009.0]
        assert st.tail(n=2)[-1]["seq"] == 10 and len(st.tail(n=2)) == 2

    def test_readers_get_copies(self):
        st = steptrace.StepTrace(enabled=True, ring=16)
        st.record(kind="decode", phases={"decode.dispatch": 1.0})
        st.tail()[0]["kind"] = "mutated"
        assert st.tail()[0]["kind"] == "decode"

    def test_disabled_gate_builds_nothing(self):
        """XLLM_STEPTRACE=0 collapses the recording path to ONE branch:
        the gated loop must not retain a single byte per iteration."""
        st = steptrace.StepTrace(enabled=False, ring=16)

        def hot(n):
            for _ in range(n):
                if st.enabled:
                    st.record(kind="decode")

        hot(10)  # warm any lazy allocations out of the measurement
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        hot(10_000)
        grown = tracemalloc.get_traced_memory()[0] - base
        tracemalloc.stop()
        assert grown < 512, f"disabled gate retained {grown} bytes"
        assert len(st) == 0 and st.last_seq() == 0


class TestStepBooks:
    def test_ingest_dedupes_on_seq_and_sorts(self):
        books = steptrace.StepBooks(per_instance=8)
        a = [{"seq": 1, "kind": "prefill"}, {"seq": 2, "kind": "decode"}]
        # Re-shipped overlap (an undelivered heartbeat's tail): 2 again.
        b = [{"seq": 2, "kind": "decode"}, {"seq": 3, "kind": "decode"}]
        books.ingest("w0", a)
        books.ingest("w0", b)
        books.ingest("w1", [{"seq": 7}])
        assert [r["seq"] for r in books.tail("w0")] == [1, 2, 3]
        assert books.instances() == ["w0", "w1"]
        assert books.tail("nope") == []

    def test_per_instance_bound(self):
        books = steptrace.StepBooks(per_instance=4)
        books.ingest("w0", [{"seq": i} for i in range(1, 11)])
        assert [r["seq"] for r in books.tail("w0")] == [7, 8, 9, 10]


# ---------------------------------------------------------------------------
# Units: the merged chrome-trace builder + offline validator
# ---------------------------------------------------------------------------
T0 = 1_700_000_000.0


def _fixture_inputs():
    spans = [{
        "request_id": "rid-a", "attrs": {},
        "events": [
            {"stage": "received", "plane": "service", "t_wall": T0},
            {"stage": "scheduled", "plane": "service",
             "t_wall": T0 + 0.01},
            {"stage": "finished", "plane": "service",
             "t_wall": T0 + 0.30},
            {"stage": "first_token", "plane": "worker", "source": "w0",
             "t_wall": T0 + 0.05},
        ],
    }, {
        # Span-only rid: no step carried it → slices, but NO flow.
        "request_id": "rid-orphan", "attrs": {},
        "events": [
            {"stage": "received", "plane": "service",
             "t_wall": T0 + 0.02},
            {"stage": "finished", "plane": "service",
             "t_wall": T0 + 0.04},
        ],
    }]
    sections = [{"name": "schedule", "t_wall": T0 + 0.011,
                 "dur_ms": 0.4, "thread": "http.pool.0"}]
    workers = {
        "w0": {"steps": [
            {"seq": 1, "t_wall": T0 + 0.06, "kind": "prefill",
             "step_ms": 12.0, "members": ["rid-a"],
             "phases": {"prefill.dispatch": 8.0, "prefill.sample": 2.0},
             "kv_usage": 0.125, "compiled": ["prefill:B1xT64xmp2"]},
            {"seq": 2, "t_wall": T0 + 0.09, "kind": "decode",
             "step_ms": 5.0, "members": ["rid-a"],
             "phases": {"decode.dispatch": 4.0}, "kv_usage": 0.25},
        ], "sections": [
            {"name": "relay.frame", "t_wall": T0 + 0.07,
             "dur_ms": 0.2, "thread": "worker.engine"},
        ]},
        "w1": {"steps": [
            {"seq": 1, "t_wall": T0 + 0.08, "kind": "decode",
             "step_ms": 3.0, "members": [], "phases": {},
             "kv_usage": 0.0},
        ], "sections": []},
    }
    return spans, sections, workers


def _build():
    spans, sections, workers = _fixture_inputs()
    return build_timeline(
        service_id="svc-test", spans=spans, sections=sections,
        workers=workers, window_s=60.0,
        master_counters={"instances": 2.0})


class TestTimelineMerge:
    def test_render_is_byte_stable(self):
        assert render(_build()) == render(_build())
        # And survives a JSON round-trip unchanged (int µs, no floats
        # in ts/dur).
        assert render(json.loads(render(_build()))) == render(_build())

    def test_validates_and_has_all_tracks(self):
        trace = _build()
        assert validate_trace(trace) == []
        s = summarize(trace)
        assert s["instances"] == ["w0", "w1"]
        # Master pid 1 + two workers, named tracks.
        assert s["track_names"]["1/0"] == "service:svc-test"
        assert s["track_names"]["2/0"] == "worker:w0"
        assert s["track_names"]["3/0"] == "worker:w1"
        # Every emitted phase is in the closed catalog.
        assert set(s["phases"]) <= set(CHROME_PHASES)
        # Counter tracks: kv_usage+batch per step, master counters.
        assert s["tracks"]["2/0"]["C"] >= 4
        assert s["tracks"]["1/0"]["C"] == 1

    def test_step_slices_carry_phase_subslices(self):
        evs = _build()["traceEvents"]
        steps = [e for e in evs if e.get("cat") == "step"]
        assert {e["name"] for e in steps} == \
            {"step:prefill", "step:decode"}
        phases = [e for e in evs if e.get("cat") == "phase"]
        assert {e["name"] for e in phases} == \
            {"prefill.dispatch", "prefill.sample", "decode.dispatch"}
        # Sub-slices nest inside their parent step slice.
        parent = next(e for e in steps if e["name"] == "step:prefill")
        for sub in phases:
            if sub["pid"] != parent["pid"]:
                continue
            if sub["ts"] >= parent["ts"] + parent["dur"]:
                continue
            assert sub["ts"] >= parent["ts"]
            assert sub["ts"] + sub["dur"] <= \
                parent["ts"] + parent["dur"]

    def test_flow_chain_complete_and_orphan_gets_none(self):
        evs = _build()["traceEvents"]
        flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
        # rid-a: s on the master's first stage slice, one t per step
        # that carried it, exactly one f. rid-orphan: NO flow events.
        assert all(e["args"]["request_id"] == "rid-a" for e in flows)
        assert [e["ph"] for e in sorted(flows, key=lambda e: (
            e["ts"], {"s": 0, "t": 1, "f": 2}[e["ph"]]))] == \
            ["s", "t", "t", "f"]
        assert {e["id"] for e in flows} == {1}

    def test_window_clips_old_events(self):
        spans, sections, workers = _fixture_inputs()
        workers["w0"]["steps"][0]["t_wall"] = T0 - 3600.0  # ancient
        trace = build_timeline(
            service_id="svc-test", spans=spans, sections=sections,
            workers=workers, window_s=60.0)
        names = [e["name"] for e in trace["traceEvents"]]
        assert "step:prefill" not in names
        assert validate_trace(trace) == []

    def test_empty_inputs_validate(self):
        trace = build_timeline(service_id="svc", spans=[], sections=[],
                               workers={})
        assert trace["traceEvents"] == []
        assert validate_trace(trace) == []


class TestTraceView:
    def test_validator_catches_corruption(self):
        trace = _build()
        evs = trace["traceEvents"]
        evs.append({"ph": "Q", "ts": 0})                  # bogus phase
        evs.append({"ph": "X", "ts": -5, "dur": 0,
                    "name": "bad", "pid": 1, "tid": 1})   # ts/dur
        # Drop the flow finish: the chain becomes incomplete.
        trace["traceEvents"] = [e for e in evs if e["ph"] != "f"]
        errs = validate_trace(trace)
        assert any("unknown ph 'Q'" in e for e in errs)
        assert any("must be an int ≥ 0" in e for e in errs)
        assert any("dur" in e for e in errs)
        assert any("finish" in e for e in errs)

    def test_cli_valid_and_invalid(self, tmp_path, capsys):
        good = tmp_path / "good.json"
        good.write_text(render(_build()), encoding="utf-8")
        assert trace_view_main([str(good)]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["events"] > 0 and summary["flows"] == 1
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(
            {"traceEvents": [{"ph": "Z"}], "metadata": {}}),
            encoding="utf-8")
        assert trace_view_main([str(bad)]) == 1
        assert trace_view_main([]) == 2
        assert trace_view_main([str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------------------
# E2E: two CPU workers, one named request, one merged timeline
# ---------------------------------------------------------------------------
def small_engine_cfg() -> EngineConfig:
    return EngineConfig(page_size=16, num_pages=64, max_model_len=256,
                        max_batch_size=4, max_prefill_tokens=256,
                        prefill_buckets=(32, 64, 128))


NAMED_RID = "rid-observatory-e2e"


def _stream_named(http_addr, rid, max_tokens=16):
    body = {"model": "tiny", "prompt": "observe this request ",
            "max_tokens": max_tokens, "temperature": 0.0,
            "stream": True, "ignore_eos": True}
    text, done = "", False
    for payload in iter_sse_events(http_stream(
            "POST", http_addr, "/v1/completions", body,
            timeout=120.0, headers={"x-request-id": rid})):
        if payload == "[DONE]":
            done = True
            break
        obj = json.loads(payload)
        for ch in obj.get("choices") or []:
            text += ch.get("text", "")
    return text, done


def _scrape(http_addr):
    import http.client
    host, _, port = http_addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    return text


class TestStepObservatoryE2E:
    def test_timeline_spans_steps_flows_and_metrics(self, monkeypatch,
                                                    tmp_path):
        # CPU workers skip warmup by default (tests boot dozens); warm
        # these up, so that a recorded step names no compile — the short
        # sweep, or two engines' pow2 sweeps dominate the test.
        monkeypatch.setenv("XLLM_WARMUP_EXTENDED", "0")
        store = InMemoryStore(sweep_interval_s=0.02)
        opts = ServiceOptions(
            http_port=0, rpc_port=0, num_output_pools=4,
            load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
            block_size=16, heartbeat_interval_s=0.2,
            master_upload_interval_s=0.2,
            detect_disconnected_instance_interval_s=1.0)
        master = Master(opts, store=store).start()
        workers = []
        try:
            for _ in range(2):
                wopts = WorkerOptions(
                    port=0, instance_type=InstanceType.DEFAULT,
                    service_addr=master.rpc_address, model="tiny",
                    heartbeat_interval_s=0.2, lease_ttl_s=1.5,
                    warmup=True)
                workers.append(Worker(
                    wopts, store,
                    engine_cfg=small_engine_cfg()).start())
            assert wait_until(
                lambda: len(master.scheduler.instance_mgr
                            .prefill_instances()) == 2,
                timeout=20.0), "workers never registered"

            text, done = _stream_named(master.http_address, NAMED_RID)
            assert done and text

            # --- the worker that served it: the ring -----------------
            served = [w for w in workers
                      if len(w.steptrace) > 0]
            assert served, "no worker recorded a step"
            w = served[0]
            status, st = http_json("GET", w.name, "/admin/steptrace",
                                   timeout=10.0)
            assert status == 200
            assert st["enabled"] is True
            assert st["devtrace"] is None      # no trace is running
            assert st["steps"], "empty flight recorder after a request"
            rec = st["steps"][-1]
            # Fixed schema end-to-end: only declared fields, and each
            # step says what it compiled (shapes outside the short
            # warm-up may: each names its program and shape key).
            assert set(rec) <= set(steptrace.STEP_FIELDS)
            assert rec["kind"] in ("prefill", "decode", "mixed")
            for r in st["steps"]:
                for c in r["compiled"]:
                    prog, _, shape = c.partition(":")
                    assert prog in ("prefill", "decode")
                    assert shape.startswith(("B", "mp")), c
            carried = [r for r in st["steps"]
                       if NAMED_RID in (r.get("members") or ())]
            assert carried, "no step recorded the named rid"
            # --- the merged timeline ---------------------------------
            status, raw = http_json(
                "GET", master.http_address,
                "/admin/timeline?seconds=120", timeout=30.0)
            assert status == 200
            trace = raw if isinstance(raw, dict) else json.loads(raw)
            assert validate_trace(trace) == [], \
                validate_trace(trace)[:5]
            s = summarize(trace)
            assert set(s["instances"]) == {w.name for w in workers}
            evs = trace["traceEvents"]
            # Service-plane stage slices on the master track.
            svc = [e for e in evs if e.get("cat") == "span"
                   and e["ph"] == "X" and e["pid"] == MASTER_PID]
            assert svc, "no service-plane stage slices"
            assert any(e["args"].get("request_id") == NAMED_RID
                       for e in svc)
            # Worker step slices with phase sub-events.
            steps = [e for e in evs if e.get("cat") == "step"]
            assert steps and all(
                e["name"].startswith("step:") for e in steps)
            assert [e for e in evs if e.get("cat") == "phase"], \
                "step slices carry no phase sub-slices"
            # ≥1 counter track.
            counters = [e for e in evs if e["ph"] == "C"]
            assert {e["name"] for e in counters} >= \
                {"kv_usage", "batch"}
            # Complete flow chain for the NAMED rid.
            flows = [e for e in evs if e["ph"] in ("s", "t", "f")
                     and e["args"].get("request_id") == NAMED_RID]
            kinds = sorted(e["ph"] for e in flows)
            assert kinds.count("s") == 1 and kinds.count("f") == 1 \
                and "t" in kinds, kinds

            # --- master-side surfaces --------------------------------
            sm = _scrape(master.http_address)
            exports = [
                float(ln.rsplit(" ", 1)[1]) for ln in sm.splitlines()
                if ln.startswith("xllm_service_timeline_exports_total ")]
            assert exports and exports[0] >= 1, \
                "timeline export counter never moved"
            # Heartbeats ship the tail into the master's StepBooks →
            # the debug bundle embeds it even without a live pull.
            assert wait_until(
                lambda: master.http_service.step_books.instances(),
                timeout=10.0), "heartbeat never shipped step records"
            status, bundle = http_json(
                "GET", master.http_address, "/admin/debug_bundle",
                timeout=30.0)
            assert status == 200
            assert bundle["steptrace"], "debug bundle has no steptrace"
            booked = [r for recs in bundle["steptrace"].values()
                      for r in recs]
            assert any(r.get("seq") for r in booked)

            # --- loadgen's artifact fetch against the same cluster ---
            from benchmarks.loadgen import fetch_timeline
            art = tmp_path / "timeline.json"
            info = fetch_timeline(master.http_address, str(art), 120.0)
            assert "error" not in info, info
            assert info["events"] > 0
            assert trace_view_main([str(art)]) == 0
        finally:
            for w in workers:
                w.stop()
            master.stop()
            store.close()
