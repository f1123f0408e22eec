"""A long prompt through the master: packed once a request, and every
digest the master computes over it is the worker's.

The master routes by matching ITS block hashes of a prompt against the
digests workers publish in heartbeats (``PrefixCacheIndex.
extend_digests`` on the worker), and gates admission on a whole-prompt
digest (the poison ledger). Both read one packed int32 buffer kept on
the request (``Scheduler.prompt_buffer``); a redispatch, which
schedules the same request again, converts nothing.
"""

import struct
import time

import pytest

from benchmarks.service_bench import FakeWorker, _scrape_prom, run
from xllm_service_tpu.config import InstanceType, ServiceOptions
from xllm_service_tpu.runtime.kv_cache import PageAllocator, PrefixCacheIndex
from xllm_service_tpu.service import scheduler as scheduler_mod
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import Response, http_json
from xllm_service_tpu.service.instance_types import (
    Heartbeat, LatencyMetrics, LoadMetrics)
from xllm_service_tpu.service.master import Master
from xllm_service_tpu.utils import hashing
from xllm_service_tpu.utils.wire import stamp

DOCUMENT = [(i * 40503 + 17) % 151936 for i in range(16150)]
FULL_BLOCKS = len(DOCUMENT) // 128


class DigestWorker(FakeWorker):
    """A fake worker that holds ``DOCUMENT``: its heartbeats publish
    the digests its own prefix index computed, page by page. ``refuse``
    503s that many forwards; ``faulty`` answers the typed engine-fault
    500 of a worker's step fault boundary."""

    def __init__(self, store, service_rpc, seed, kv_usage=0.0):
        index = PrefixCacheIndex(PageAllocator(4), page_size=128,
                                 seed=seed)
        self.digests = []
        index.extend_digests(self.digests, DOCUMENT, len(DOCUMENT))
        self.kv_usage = kv_usage
        self.refuse = 0
        self.faulty = False
        self.forwards = []      # the token ids of every forward
        super().__init__(store, service_rpc, gen_tokens=2)

    def _heartbeat_once(self):
        hb = Heartbeat(
            name=self.name, instance_type=InstanceType.DEFAULT,
            load=LoadMetrics(kv_cache_usage=self.kv_usage),
            latency=LatencyMetrics(),
            cache_stored=[d.hex() for d in self.digests],
            model_states={"fake": "awake"})
        http_json("POST", self.service_rpc, "/rpc/heartbeat",
                  stamp(hb.to_json()), timeout=10.0)

    def _generate(self, req, is_chat):
        self.forwards.append(req.json()["token_ids"])
        if self.refuse:
            self.refuse -= 1
            return Response.error(503, "draining")
        if self.faulty:
            return Response.error(500, "engine_fault: blamed",
                                  "engine_fault")
        return super()._generate(req, is_chat)


@pytest.fixture
def cluster():
    store = InMemoryStore()
    opts = ServiceOptions(http_port=0, rpc_port=0,
                          heartbeat_interval_s=0.3,
                          master_upload_interval_s=0.3)
    master = Master(opts, store=store).start()
    # Both hold the document; b is fuller, so a cache-aware router
    # elects a while a is a candidate.
    a = DigestWorker(store, master.rpc_address, opts.murmur_hash3_seed)
    b = DigestWorker(store, master.rpc_address, opts.murmur_hash3_seed,
                     kv_usage=0.25)
    deadline = time.monotonic() + 15
    sched = master.scheduler
    while time.monotonic() < deadline:
        matched, scores = sched.kvcache_mgr.match(DOCUMENT)
        if len(sched.instance_mgr.prefill_instances()) == 2 \
                and len(scores) == 2:
            break
        time.sleep(0.05)
    else:
        raise RuntimeError("fake workers never published their digests")
    try:
        yield master, a, b
    finally:
        a.stop()
        b.stop()
        master.stop()
        store.close()


def _post(master, token_ids, srid):
    return http_json("POST", master.http_address, "/v1/completions",
                     {"model": "fake", "token_ids": token_ids,
                      "max_tokens": 2},
                     headers={"x-request-id": srid}, timeout=30.0)


def _candidates(master, srid):
    status, span = http_json("GET", master.http_address,
                             f"/admin/trace/{srid}")
    assert status == 200, span
    audit = span["attrs"]["schedule_decision"]
    assert audit["policy"] == "cache_aware"
    assert audit["total_blocks"] == FULL_BLOCKS
    return audit["prefill"]["winner"], {
        c["instance"]: c["match_ratio"]
        for c in audit["prefill"]["candidates"]}


def _metric(master, name):
    return _scrape_prom(master.http_address)[name]


def test_long_prompt_routes_on_the_workers_digests(cluster, monkeypatch):
    master, a, b = cluster
    # The master's hashes of the prompt ARE the worker's: every full
    # block of the document matches, on both holders.
    matched, scores = master.scheduler.kvcache_mgr.match(DOCUMENT)
    assert matched == FULL_BLOCKS == 126
    assert scores == {a.name: 126.0, b.name: 126.0}

    packs = []
    real = scheduler_mod.pack_tokens
    monkeypatch.setattr(scheduler_mod, "pack_tokens",
                        lambda t: packs.append(len(t)) or real(t))

    status, resp = _post(master, DOCUMENT, "doc-1")
    assert status == 200, resp
    assert a.forwards == [DOCUMENT] and b.forwards == []
    winner, ratios = _candidates(master, "doc-1")
    assert winner == a.name
    assert ratios == {a.name: 1.0, b.name: 1.0}
    assert packs == [len(DOCUMENT)]

    # a refuses the next forward: the redispatch schedules the SAME
    # request again (twice: a is elected, excluded, elected again) and
    # the request lands on b, every full block still matched, with no
    # second conversion of its tokens.
    a.refuse = 1
    status, resp = _post(master, DOCUMENT, "doc-2")
    assert status == 200, resp
    assert len(a.forwards) == 2 and b.forwards == [DOCUMENT]
    _, ratios = _candidates(master, "doc-2")
    assert ratios == {a.name: 1.0, b.name: 1.0}
    assert packs == [len(DOCUMENT)] * 2
    assert _metric(master, "xllm_service_prompt_hashed_tokens_total") \
        == 2 * len(DOCUMENT)
    assert _metric(master, "xllm_service_prompt_hash_seconds_total") > 0
    assert _metric(master, "xllm_service_prompt_hash_fallback_total") == 0


def test_quarantine_gate_digests_the_packed_prompt(cluster):
    master, a, b = cluster
    a.faulty = b.faulty = True
    # Two blames (a, then b after the redispatch) poison the prompt.
    status, resp = _post(master, DOCUMENT, "pill-1")
    assert status == 500 and resp["error"]["type"] == "engine_fault"
    assert len(a.forwards) == 1 and len(b.forwards) == 1
    # The quarantined digest is the reference's over the same ids.
    want = hashing.murmur3_x64_128_py(
        struct.pack(f"<{len(DOCUMENT)}i", *DOCUMENT),
        master.opts.murmur_hash3_seed).hex()
    assert list(master.scheduler.poison.state()["quarantined"]) == [want]

    a.faulty = b.faulty = False
    status, resp = _post(master, DOCUMENT, "pill-2")
    assert status == 500 and resp["error"]["type"] == "engine_fault"
    assert "quarantined" in resp["error"]["message"]
    assert len(a.forwards) == 1 and len(b.forwards) == 1   # refused
    # One token's difference is another prompt.
    status, resp = _post(master, DOCUMENT[:-1] + [7], "doc-3")
    assert status == 200, resp
    # An id outside int32 takes the wrap, is counted, and still routes.
    status, resp = _post(master, DOCUMENT[:-1] + [2**40 + 3], "doc-4")
    assert status == 200, resp
    assert _metric(master, "xllm_service_prompt_hash_fallback_total") == 1


def test_service_bench_prompt_tokens():
    """``--prompt-tokens``: the CPU service bench sends token ids of a
    cell's prompt length and reads the master's stage off the forward."""
    res = run(num_requests=8, concurrency=2, n_workers=1, gen_tokens=2,
              stream=True, prompt_tokens=700)
    detail = res["detail"]
    assert detail["errors"] == 0 and detail["prompt_tokens"] == 700
    assert 0 < detail["master_in_ms_p50"] <= detail["master_in_ms_p99"]
