"""Worker-process behaviors that no other suite pins: boot warmup.

Reference parity: the reference's service assumes a warmed engine behind
every registered instance (its TTFT SLO default is 1000 ms,
xllm_service/common/global_gflags.cpp:95-97) — an instance that compiles
on first request violates that by tens of seconds a program.
"""

import json
from http.client import HTTPConnection


def _post(addr, path, obj):
    host, port = addr.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=120)
    try:
        conn.request("POST", path, body=json.dumps(obj),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read().decode("utf-8", "replace")
    finally:
        conn.close()




class TestBootWarmup:
    """Worker boot warmup (opts.warmup): every steady-state engine
    program compiles BEFORE registration, so no routed request pays a
    compile — one step program compiles in tens of seconds, an order
    of magnitude over the reference's 1000 ms target_ttft default
    (global_gflags.cpp:95-97)."""

    def test_warmed_worker_serves_without_recompile(self, monkeypatch):
        monkeypatch.setenv("XLLM_WARMUP_EXTENDED", "0")
        from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
        from xllm_service_tpu.service.coordination import InMemoryStore
        w = Worker(WorkerOptions(model="tiny", warmup=True),
                   InMemoryStore()).start()
        try:
            eng = w.primary_runtime().engine
            recompiles_at_boot = {
                k: v for k, v in eng.phase_counts.items()
                if k.endswith(".recompile")}
            status, body = _post(w.name, "/v1/completions", {
                "model": "tiny", "prompt": "warm hello",
                "max_tokens": 4, "temperature": 0.0})
            assert status == 200, body
            # The smallest bucket was warmed (XLLM_WARMUP_EXTENDED=0
            # covers the scoped subset); this request fits it, so the
            # compile counters must not have moved.
            assert {k: v for k, v in eng.phase_counts.items()
                    if k.endswith(".recompile")} == recompiles_at_boot
        finally:
            w.stop()

    def test_warmup_defaults_off_on_cpu(self):
        from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
        from xllm_service_tpu.service.coordination import InMemoryStore
        w = Worker(WorkerOptions(model="tiny"), InMemoryStore())
        w2 = Worker(WorkerOptions(model="tiny", warmup=True),
                    InMemoryStore())
        try:
            assert w._should_warmup() is False  # CPU backend → auto-off
            assert w2._should_warmup() is True  # explicit opt-in wins
        finally:
            # Never start()ed — only the HTTP sockets need releasing.
            w._srv.stop()
            w2._srv.stop()


class TestShardedWorkerServing:
    """Full-stack tensor parallelism: a Worker whose engine is sharded
    over a real 2-device mesh (virtual CPU devices here, the same
    Mesh/pjit path a multi-chip TPU slice uses) must serve identical
    greedy tokens to a single-device worker through the SAME HTTP
    surface — the deployable shape of SURVEY §5.8's data plane.

    Runs each worker in its OWN subprocess: in-process, the second
    mesh-sharded engine after a long suite triggered a CPython GC
    segfault while formatting an unrelated exception (observed once in
    the full-suite run; never standalone) — process isolation removes
    the shared-state interplay entirely."""

    _SCRIPT = r'''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.environ["XLLM_REPO"])
from http.client import HTTPConnection
from xllm_service_tpu.config import EngineConfig
from xllm_service_tpu.parallel import MeshSpec, make_mesh
from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
from xllm_service_tpu.service.coordination import InMemoryStore

tp = int(sys.argv[1])
mesh = make_mesh(MeshSpec(tp=tp)) if tp > 1 else None
ecfg = EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                    max_batch_size=4, max_prefill_tokens=128,
                    prefill_buckets=(32,), tp=tp)
w = Worker(WorkerOptions(model="tiny"), InMemoryStore(),
           engine_cfg=ecfg, mesh=mesh).start()
try:
    host, port = w.name.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps(
        {"model": "tiny", "prompt": "the quick brown fox jumps",
         "max_tokens": 12, "temperature": 0.0}),
        headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    body = r.read().decode()
    assert r.status == 200, body
    print("TEXT:" + json.loads(body)["choices"][0]["text"])
finally:
    w.stop()
'''

    def test_tp2_worker_matches_tp1_greedy(self):
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, XLLM_REPO=repo, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8")
                   .strip())
        # Both workers at once: they share nothing but the CPU.
        procs = {tp: subprocess.Popen(
            [sys.executable, "-c", self._SCRIPT, str(tp)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for tp in (1, 2)}
        outs = {}
        for tp, p in procs.items():
            try:
                stdout, stderr = p.communicate(timeout=600)
            finally:
                p.kill()
            assert p.returncode == 0, stderr[-1500:]
            line = [ln for ln in stdout.splitlines()
                    if ln.startswith("TEXT:")][-1]
            outs[tp] = line[len("TEXT:"):]
        assert outs[1], "empty completion — parity would be vacuous"
        assert outs[1] == outs[2], outs


class TestRetargetRaceRegression:
    """XLINT13-001 (xlint thread-root-race): the (service_addr,
    config_stale) pair is written from BOTH the store watch thread
    (_on_master_addr → _retarget) and the heartbeat thread. Before the
    worker.addr lock, the hb loop's `stale = not fetched` could clobber
    a retarget's stale=True landing mid-fetch — the worker then never
    re-fetched the NEW master's /rpc/config."""

    def _bare_worker(self):
        from xllm_service_tpu.runtime.worker import Worker
        from xllm_service_tpu.utils.locks import make_lock
        w = Worker.__new__(Worker)
        w._addr_mu = make_lock("worker.addr", 89)
        w._service_addr = "a:1"
        w._service_config_stale = False
        return Worker, w

    def test_retarget_is_compare_and_swap(self):
        Worker, w = self._bare_worker()
        assert Worker._retarget(w, {"rpc": "b:2", "service_id": "s"})
        assert w._service_addr == "b:2"
        assert w._service_config_stale is True
        # same address again: no-op, stale untouched
        w._service_config_stale = False
        assert not Worker._retarget(w, {"rpc": "b:2"})
        assert w._service_config_stale is False
        assert not Worker._retarget(w, {})        # no rpc key
        assert not Worker._retarget(w, None)      # no advert at all

    def test_mid_fetch_retarget_keeps_stale(self):
        """The exact lost-update: fetch succeeds for the OLD address
        while a takeover retargets mid-flight — the retarget's
        stale=True must survive the fetch result."""
        Worker, w = self._bare_worker()

        def fetch_with_concurrent_takeover():
            Worker._retarget(w, {"rpc": "c:3"})   # lands mid-fetch
            return True                            # fetch of a:1 "succeeded"

        w._fetch_service_config = fetch_with_concurrent_takeover
        Worker._refresh_service_config(w)
        assert w._service_addr == "c:3"
        assert w._service_config_stale is True, \
            "retarget's stale flag was clobbered by the stale fetch"

    def test_refresh_clears_stale_when_stable(self):
        Worker, w = self._bare_worker()
        w._service_config_stale = True
        w._fetch_service_config = lambda: True
        Worker._refresh_service_config(w)
        assert w._service_config_stale is False
        # failed fetch for a live address re-arms the flag
        w._fetch_service_config = lambda: False
        Worker._refresh_service_config(w)
        assert w._service_config_stale is True
