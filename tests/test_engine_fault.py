"""Device-plane fault containment (tier-1).

The engine-step fault boundary (docs/ROBUSTNESS.md, device-plane fault
contract): units for fault classification (transient device errors
retry in place, deterministic ones are blamed), culprit bisection under
the XLLM_FAULT_BISECT_BUDGET probe budget, and the PoisonLedger strike
book; then one e2e chaos run on two IN-PROCESS CPU workers — a
`worker.fault_step` injection is contained (survivors byte-identical to
the unfaulted temperature=0 baseline, engine loop still alive), and a
`worker.fault_step_req` poison pill hops exactly XLLM_POISON_STRIKES
workers before failing clean to the client with the typed
`engine_fault` 500 and a quarantined prompt digest.
"""

import json
import threading
import time
import types

import pytest

from xllm_service_tpu.config import (
    EngineConfig, InstanceType, LoadBalancePolicyType, ServiceOptions)
from xllm_service_tpu.runtime.worker import (
    StepFaultInjected, Worker, WorkerOptions, _classify_step_fault)
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import (
    http_json, http_stream, iter_sse_events)
from xllm_service_tpu.service.master import Master
from xllm_service_tpu.service.recovery import PoisonLedger
from xllm_service_tpu.utils.hashing import prompt_digest


def wait_until(cond, timeout=15.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


# ---------------------------------------------------------------------------
# Units: transient-vs-deterministic classification
# ---------------------------------------------------------------------------
class XlaRuntimeError(Exception):
    """Stand-in matched by NAME (the boundary classifies by
    ``type(exc).__name__`` so it needs no jaxlib import)."""


class TestClassification:
    def test_transport_and_timeout_are_transient(self):
        assert _classify_step_fault(TimeoutError("device sync")) \
            == "transient"
        assert _classify_step_fault(
            ConnectionResetError("ice path reset")) == "transient"

    def test_xla_runtime_error_split_by_status_tag(self):
        for tag in ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                    "CANCELLED"):
            exc = XlaRuntimeError(f"{tag}: device temporarily gone")
            assert _classify_step_fault(exc) == "transient", tag
        assert _classify_step_fault(
            XlaRuntimeError("INTERNAL: scan body mismatch")) \
            == "deterministic"

    def test_everything_else_is_deterministic(self):
        assert _classify_step_fault(ValueError("nan in logits")) \
            == "deterministic"
        assert _classify_step_fault(
            StepFaultInjected("worker.fault_step")) == "deterministic"


# ---------------------------------------------------------------------------
# Units: culprit bisection under the probe budget
# ---------------------------------------------------------------------------
class FakeFaultEngine:
    """Scripted engine for ``Worker._bisect_step_fault``: ``step()``
    faults whenever a culprit rid is in the active (isolated) set."""

    def __init__(self, rids, culprits=()):
        self.rids = list(rids)
        self.culprits = set(culprits)
        self.iso = None
        self.steps = 0
        self.resets = []

    def isolate(self, keep):
        assert self.iso is None, "nested isolation"
        self.iso = list(keep)

    def release_isolation(self):
        self.iso = None

    def fault_reset(self, blamed):
        self.resets.append(tuple(blamed))

    def step(self):
        self.steps += 1
        active = self.iso if self.iso is not None else self.rids
        if self.culprits.intersection(active):
            raise StepFaultInjected("probe reproduced the fault")
        return [types.SimpleNamespace(request_id=r) for r in active]


def _bisect(eng, suspects, budget=4):
    fake_self = types.SimpleNamespace(_fault_bisect_budget=budget)
    return Worker._bisect_step_fault(fake_self, eng, suspects)


class TestBisection:
    def test_culprit_found_within_budget(self):
        eng = FakeFaultEngine("r0 r1 r2 r3".split(), culprits={"r2"})
        blamed, probe_outs = _bisect(eng, ["r0", "r1", "r2", "r3"])
        assert blamed == ["r2"]
        # Probe trace: [r0,r1] clean (exonerated, outputs returned for
        # dispatch), [r2] faults → narrowed to the culprit. 2 probes
        # fit the default budget of 4.
        assert eng.steps == 2
        assert [o.request_id for o in probe_outs[0][0]] == ["r0", "r1"]
        assert eng.iso is None, "isolation must be released"

    def test_culprit_in_final_singleton_blamed_by_elimination(self):
        eng = FakeFaultEngine("r0 r1 r2 r3".split(), culprits={"r3"})
        blamed, probe_outs = _bisect(eng, ["r0", "r1", "r2", "r3"])
        assert blamed == ["r3"]
        # Both probed halves ([r0,r1] then [r2]) came back clean; the
        # remaining singleton is blamed by elimination.
        assert eng.steps == 2
        assert len(probe_outs) == 2

    def test_whole_batch_blamed_on_budget_exhaustion(self):
        eng = FakeFaultEngine("r0 r1 r2 r3".split(), culprits={"r2"})
        blamed, _ = _bisect(eng, ["r0", "r1", "r2", "r3"], budget=1)
        # One probe ([r0,r1] clean) spends the whole budget; the
        # un-probed remainder is blamed wholesale.
        assert blamed == ["r2", "r3"]
        assert eng.steps == 1

    def test_zero_budget_blames_every_suspect_without_probing(self):
        eng = FakeFaultEngine("r0 r1".split(), culprits={"r0"})
        blamed, probe_outs = _bisect(eng, ["r0", "r1"], budget=0)
        assert blamed == ["r0", "r1"]
        assert eng.steps == 0 and probe_outs == []

    def test_single_suspect_needs_no_probe(self):
        eng = FakeFaultEngine(["r7"], culprits={"r7"})
        blamed, _ = _bisect(eng, ["r7"])
        assert blamed == ["r7"]
        assert eng.steps == 0

    def test_faulting_probe_resets_before_renarrowing(self):
        eng = FakeFaultEngine("r0 r1 r2 r3".split(), culprits={"r0"})
        blamed, _ = _bisect(eng, ["r0", "r1", "r2", "r3"])
        assert blamed == ["r0"]
        # A known-good reset precedes probing, and every faulting probe
        # resets again before the next one.
        assert eng.resets[0] == ()
        assert len(eng.resets) >= 2


# ---------------------------------------------------------------------------
# Units: the poison strike ledger
# ---------------------------------------------------------------------------
class TestPoisonLedger:
    def test_strikes_accumulate_to_poisoning(self):
        led = PoisonLedger(strikes=2, ttl_s=60.0)
        assert led.strike("req-a", "digest-1") == (1, False)
        assert led.strike("req-a", "digest-1") == (2, True)
        assert led.quarantined("digest-1")
        assert not led.quarantined("digest-2")

    def test_digest_carries_strikes_across_request_ids(self):
        # The poison-pill rampage: the same prompt resubmitted under a
        # fresh request id must not start from a clean slate.
        led = PoisonLedger(strikes=2, ttl_s=60.0)
        assert led.strike("req-a", "digest-1") == (1, False)
        n, poisoned = led.strike("req-b", "digest-1")
        assert (n, poisoned) == (2, True)

    def test_quarantine_ttl_expires_and_clears_strikes(self):
        led = PoisonLedger(strikes=1, ttl_s=0.05)
        assert led.strike("req-a", "digest-1") == (1, True)
        assert led.quarantined("digest-1")
        time.sleep(0.08)
        assert not led.quarantined("digest-1")
        # Post-TTL retry starts over: strike count was cleared.
        assert led.strike("req-c", "digest-1")[0] == 1

    def test_strike_book_is_bounded(self):
        led = PoisonLedger(strikes=2, ttl_s=60.0)
        for i in range(PoisonLedger.MAX_ENTRIES + 10):
            led.strike(f"req-{i}", f"digest-{i}")
        assert len(led.state()["strikes"]) <= PoisonLedger.MAX_ENTRIES

    def test_prompt_digest_is_content_keyed(self):
        a = prompt_digest([1, 2, 3])
        assert a == prompt_digest([1, 2, 3])
        assert a != prompt_digest([1, 2, 4])
        assert a != prompt_digest([1, 2, 3], seed=7)
        assert a != prompt_digest([1, 2, 3, 3])


# ---------------------------------------------------------------------------
# A fault inside the tail dispatch (PR 39): the iteration's sections are
# read and posted when the next decode step is packed and launched
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("where", ["hook", "launch"])
def test_fault_in_the_tail_dispatch_loses_no_committed_output(where):
    """``Engine.step()`` dispatches the next decode step after its
    sections' post. A fault raised there (by the injection hook, or by
    the launch itself) escapes with every output of the iteration in
    ``last_step_partial_outs``, the running rows as its members, nothing
    pending; ``fault_reset`` drops the carry, and the streams go on to
    the unfaulted engine's."""
    import dataclasses

    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams

    def engine():
        eng = Engine(dataclasses.replace(ModelConfig.tiny(vocab_size=64),
                                         dtype="float32"),
                     EngineConfig(page_size=8, num_pages=32,
                                  max_model_len=128, max_batch_size=4,
                                  max_prefill_tokens=64,
                                  prefill_buckets=(8, 16, 32)), seed=0)
        # every iteration that may ends with a tail dispatch
        eng._ahead_eligible = lambda *a: False
        for rid, prompt in (("a", range(1, 7)), ("b", range(2, 9))):
            eng.add_request(EngineRequest(
                request_id=rid, token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=12, temperature=0.0,
                                        ignore_eos=True)))
        return eng

    def drain(eng, toks):
        while eng.has_work():
            for o in eng.step():
                toks.setdefault(o.request_id, []).extend(o.new_token_ids)
        return toks

    want = drain(engine(), {})
    eng, toks = engine(), {}
    for _ in range(3):
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
    assert eng._pending is not None

    def boom(*a, **kw):
        # the tail's call: the iteration's decode section is posted
        if eng.last_step_decode_tokens:
            raise StepFaultInjected("tail")
        return real(*a, **kw) if real is not None else None
    if where == "hook":
        real, eng.fault_hook = None, boom
    else:
        real, eng._jit_decode = eng._jit_decode, boom
    with pytest.raises(StepFaultInjected):
        eng.step()
    if where == "launch":
        eng._jit_decode = real
    eng.fault_hook = None
    outs = list(eng.last_step_partial_outs)
    assert sorted(o.request_id for o in outs) == ["a", "b"]
    assert sorted(eng.step_members) == ["a", "b"]
    for o in outs:     # committed: the tokens are on the sequences
        assert eng._by_id[o.request_id].tokens[-1] == o.new_token_ids[-1]
        toks[o.request_id].extend(o.new_token_ids)
    assert eng._pending is None
    eng.fault_reset(())
    assert eng._pending is None and eng._decode_carry is None
    assert drain(eng, toks) == want
    assert eng.phase_counts["decode.tail_discard"] == 0


# ---------------------------------------------------------------------------
# e2e chaos: contained fault, then the poison pill (tier-1)
# ---------------------------------------------------------------------------
def small_engine_cfg() -> EngineConfig:
    return EngineConfig(page_size=16, num_pages=64, max_model_len=256,
                        max_batch_size=4, max_prefill_tokens=256,
                        prefill_buckets=(32, 64, 128))


def make_cluster(store, n_workers=2):
    opts = ServiceOptions(
        http_port=0, rpc_port=0, num_output_pools=4,
        load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
        block_size=16, heartbeat_interval_s=0.2,
        master_upload_interval_s=0.2,
        detect_disconnected_instance_interval_s=1.0)
    master = Master(opts, store=store).start()
    workers = []
    for _ in range(n_workers):
        wopts = WorkerOptions(
            port=0, instance_type=InstanceType.DEFAULT,
            service_addr=master.rpc_address, model="tiny",
            heartbeat_interval_s=0.2, lease_ttl_s=1.5)
        workers.append(Worker(wopts, store,
                              engine_cfg=small_engine_cfg()).start())
    assert wait_until(
        lambda: len(master.scheduler.instance_mgr.prefill_instances())
        == n_workers, timeout=20.0), "workers never registered"
    return master, workers


@pytest.fixture()
def store():
    s = InMemoryStore(sweep_interval_s=0.02)
    yield s
    s.close()


PROMPT = "contain the fault "
POISON_MARK = "POISON"
POISON_PROMPT = "POISON pill prompt do not serve "


def _stream_completion(http_addr, prompt=PROMPT, max_tokens=24,
                       timeout=120.0):
    body = {"model": "tiny", "prompt": prompt,
            "max_tokens": max_tokens, "temperature": 0.0,
            "stream": True, "ignore_eos": True,
            "stream_options": {"include_usage": True}}
    out = {"text": "", "chunks": [], "finish": None, "usage": None,
           "done": False, "error": None}
    try:
        for payload in iter_sse_events(http_stream(
                "POST", http_addr, "/v1/completions", body,
                timeout=timeout)):
            if payload == "[DONE]":
                out["done"] = True
                break
            obj = json.loads(payload)
            out["chunks"].append(obj)
            for ch in obj.get("choices") or []:
                out["text"] += ch.get("text", "")
                if ch.get("finish_reason"):
                    out["finish"] = ch["finish_reason"]
            if obj.get("usage"):
                out["usage"] = obj["usage"]
    except Exception as e:  # noqa: BLE001 — the failure mode under test
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def _scrape(http_addr):
    import http.client
    host, _, port = http_addr.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    return text


def _metric_value(text, name, **labels):
    """Sum of samples of ``name`` whose label set includes ``labels``
    (label ORDER in the rendered line is not part of the contract)."""
    total, seen = 0.0, False
    for ln in text.splitlines():
        if not ln.startswith(name):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            total += float(ln.split()[-1])
            seen = True
    return total if seen else None


def _events(http_addr):
    status, resp = http_json("GET", http_addr, "/admin/events?limit=512",
                             timeout=30.0)
    assert status == 200
    return [e["type"] for e in resp["events"]], resp["events"]


def _assert_byte_identical(stream, baseline):
    assert stream["error"] is None, stream
    assert stream["done"] and stream["finish"] == "length", stream
    assert stream["text"] == baseline["text"], \
        f"survivor diverged:\n {stream['text']!r}\n vs baseline\n " \
        f"{baseline['text']!r}"
    assert stream["usage"] == baseline["usage"], stream["usage"]


class TestEngineFaultE2E:
    def test_contained_fault_then_poison_pill_quarantine(self, store):
        """One 2-worker relay cluster, three acts. (1) worker.fault_step
        count:1 on worker A: the blamed stream is evicted, struck once,
        and resumed on B — every client stream ends byte-identical to
        the unfaulted temperature=0 baseline and A's engine loop keeps
        serving (gauge 1, outcome=culprit counted, a phase="fault" obs
        flush). (2) worker.fault_step_req armed fleet-wide with a
        marker string: the marked NON-STREAM request faults whichever
        worker it lands on, hops once (strike 1 → redispatch), faults
        again (strike 2 = XLLM_POISON_STRIKES) and comes back as a
        clean typed engine_fault 500; a concurrent unmarked survivor
        stream is exonerated by bisection and stays byte-identical.
        (3) resubmitting the identical prompt is refused at admission —
        the digest is quarantined."""
        master, workers = make_cluster(store, n_workers=2)
        try:
            baseline = _stream_completion(master.http_address)
            assert baseline["error"] is None and baseline["done"], \
                baseline
            assert baseline["finish"] == "length"

            # --- act 1: one injected step fault, contained -----------
            status, resp = http_json(
                "POST", workers[0].name, "/admin/failpoint",
                {"name": "worker.fault_step", "mode": "count", "n": 1},
                timeout=10.0)
            assert status == 200, resp

            results = [None, None]
            threads = [threading.Thread(
                target=lambda i=i: results.__setitem__(
                    i, _stream_completion(master.http_address)))
                for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert all(not t.is_alive() for t in threads), \
                "a client hung after the injected engine fault"
            for s in results:
                _assert_byte_identical(s, baseline)

            assert workers[0].failpoints.trips("worker.fault_step") \
                == 1, "fault_step never fired on the armed worker"
            assert workers[0]._engine_loop_alive, \
                "engine loop died despite containment"
            wa = _scrape(workers[0].name)
            assert _metric_value(
                wa, "xllm_engine_faults_total", model="tiny",
                outcome="culprit") >= 1, wa
            assert _metric_value(
                wa, "xllm_worker_engine_alive", model="tiny") == 1
            # Satellite: the faulted iteration's obs flush is not lost —
            # it lands with its own phase label.
            assert _metric_value(
                wa, "xllm_worker_steps_total", model="tiny",
                phase="fault") >= 1, wa
            types_, events = _events(master.http_address)
            assert "engine_fault" in types_, types_
            ef = [e for e in events if e["type"] == "engine_fault"]
            assert ef[0]["attrs"]["instance"] == workers[0].name
            assert "culprit" in ef[0]["attrs"]["verdict"]

            # --- act 2: the poison pill ------------------------------
            status, resp = http_json(
                "POST", master.http_address, "/admin/failpoint",
                {"instance": "*", "name": "worker.fault_step_req",
                 "mode": "always", "value": POISON_MARK}, timeout=10.0)
            assert status == 200, resp
            assert all(v == 200 for v in resp["results"].values()), resp

            # A concurrent unmarked survivor: bisection must exonerate
            # it when it shares the faulting batch.
            survivor = [None]
            st = threading.Thread(
                target=lambda: survivor.__setitem__(
                    0, _stream_completion(master.http_address)))
            st.start()
            time.sleep(0.3)
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": POISON_PROMPT,
                 "max_tokens": 8, "temperature": 0.0,
                 "ignore_eos": True}, timeout=60.0)
            st.join(timeout=120)
            assert not st.is_alive(), "survivor stream hung"

            # Clean typed 500 after exactly XLLM_POISON_STRIKES (2)
            # worker hops — never a broken socket, never a 200.
            assert status == 500, (status, resp)
            assert resp["error"]["type"] == "engine_fault", resp
            assert resp["error"]["message"].startswith("engine_fault"), \
                resp
            assert "culprit" in resp["error"]["message"], resp
            _assert_byte_identical(survivor[0], baseline)

            types_, events = _events(master.http_address)
            assert "request_quarantined" in types_, types_
            quar = [e for e in events
                    if e["type"] == "request_quarantined"][0]
            assert quar["attrs"]["strikes"] == 2
            assert quar["attrs"]["ttl_s"] > 0
            srid = quar["attrs"]["service_request_id"]
            hops = [e for e in events if e["type"] == "engine_fault"
                    and e["attrs"]["service_request_id"] == srid]
            assert len(hops) == 2, hops
            assert {h["attrs"]["instance"] for h in hops} \
                == {w.name for w in workers}, hops

            sm = _scrape(master.http_address)
            assert _metric_value(
                sm, "xllm_requests_poisoned_total") >= 1, sm

            # --- act 3: the quarantine admission gate ----------------
            status, resp = http_json(
                "POST", master.http_address, "/v1/completions",
                {"model": "tiny", "prompt": POISON_PROMPT,
                 "max_tokens": 8, "temperature": 0.0,
                 "ignore_eos": True}, timeout=30.0)
            assert status == 500, (status, resp)
            assert resp["error"]["type"] == "engine_fault", resp
            assert "quarantined" in resp["error"]["message"], resp

            # Both engine loops survived the whole scenario: a fresh
            # unmarked stream still reproduces the baseline.
            for w in workers:
                assert w._engine_loop_alive
                assert _metric_value(
                    _scrape(w.name), "xllm_worker_engine_alive",
                    model="tiny") == 1
            final = _stream_completion(master.http_address)
            _assert_byte_identical(final, baseline)
        finally:
            for w in workers:
                w.stop()
            master.stop()
