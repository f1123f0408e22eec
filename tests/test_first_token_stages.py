"""The time to the first token, accounted for inside the program: the
closed table of stamps and stages (``obs/spans.py``
``FIRST_TOKEN_STAMPS``), the histogram
``xllm_worker_first_token_stage_ms`` the thread that writes a request's
first frame folds its chain into once that frame is written (the
worker's stream writer under the native front door, the handler's own
thread on the pull path), the same chain at
``GET /admin/trace/<id>``, the master's share on the forward, and which
serving path observes which stage (docs/OBSERVABILITY.md has the
table)."""

import ast
import json
import os
import re
import time
import tracemalloc
from http.client import HTTPConnection

import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.obs import (
    FIRST_TOKEN_STAGES, FIRST_TOKEN_STAMPS, WORKER_STAGES, SERVICE_STAGES,
    first_token_stages)
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.service.httpd import (
    http_json, http_stream, iter_sse_events)
from xllm_service_tpu.utils.types import SamplingParams

from tests.test_e2e import make_cluster, wait_until

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "xllm_service_tpu")
STAMPS = [name for name, _ in FIRST_TOKEN_STAMPS]
WORKER_OWN = [stage for _, stage in FIRST_TOKEN_STAMPS if stage]
FAMILY = "xllm_worker_first_token_stage_ms"


# ---------------------------------------------------------------------------
# The table
# ---------------------------------------------------------------------------
def _stamp_sites():
    """Every stamp name a site in the package takes, read off its source:
    the literal of a ``<live>.stamp("<name>", ...)`` call, and the keys
    of the dict an engine site hands to ``first_token_stamps``."""
    names = set()
    for root, _, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, fn)).read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Attribute) \
                        and node.func.attr == "stamp":
                    assert isinstance(node.args[0], ast.Constant), \
                        ast.unparse(node)       # a literal, or no entry
                    names.add(node.args[0].value)
                targets = []
                if isinstance(node, ast.Assign):
                    targets = [(t, node.value) for t in node.targets]
                elif isinstance(node, ast.keyword):
                    targets = [(node, node.value)]
                for t, value in targets:
                    if getattr(t, "attr", getattr(t, "arg", "")) \
                            == "first_token_stamps" \
                            and isinstance(value, ast.Dict):
                        names.update(k.value for k in value.keys)
    return names


def test_every_stamp_site_names_an_entry_and_every_entry_has_a_site():
    assert _stamp_sites() == set(STAMPS)
    assert len(set(STAMPS)) == len(STAMPS) == 8
    # every stamp is a stage of the worker's timeline, in the table's order
    order = [s for s in WORKER_STAGES if s in STAMPS]
    assert order == STAMPS
    assert SERVICE_STAGES[:2] == ("accepted", "received")
    assert FIRST_TOKEN_STAGES == (
        "master_in", "parse", "lock_wait", "queue", "prefill_host",
        "prefill_device", "post_emit", "stream_out", "total")


@pytest.mark.parametrize("have,want", [
    (STAMPS, WORKER_OWN + ["total"]),
    # no frame is written by this path: the chain ends at first_token
    (STAMPS[:-1], WORKER_OWN[:-1]),
    # the fan-in's ack: the admission alone
    (STAMPS[:3], ["parse", "lock_wait"]),
    # a stamp missing in the middle takes the two stages around it
    ([s for s in STAMPS if s != "launched"],
     [s for s in WORKER_OWN if s not in ("prefill_host", "prefill_device")]
     + ["total"]),
    ([], []),
])
def test_stages_of_the_stamps_a_path_has(have, want):
    stamps = {name: 10.0 + 0.001 * (STAMPS.index(name) + 1) ** 2
              for name in have}
    got = first_token_stages(stamps)
    assert sorted(got) == sorted(want)
    assert all(v > 0 for v in got.values())
    if "total" in got and len(have) == len(STAMPS):
        assert sum(v for k, v in got.items() if k != "total") \
            == pytest.approx(got["total"], abs=1e-9)


# ---------------------------------------------------------------------------
# The engine's three stamps
# ---------------------------------------------------------------------------
def _engine(**kw):
    d = dict(page_size=16, num_pages=32, max_model_len=128,
             max_batch_size=2, max_prefill_tokens=64, prefill_buckets=(32,))
    d.update(kw)
    return Engine(ModelConfig.tiny(vocab_size=256), EngineConfig(**d))


def _req(rid, n_prompt, n=3):
    return EngineRequest(
        request_id=rid, token_ids=[3 + i % 200 for i in range(n_prompt)],
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True))


def test_launched_is_the_last_window_of_a_chunked_prompt():
    eng = _engine()
    eng.add_request(_req("two-windows", 50))    # windows of 32 and 18
    t_added = time.monotonic()
    assert eng.step() == []                     # the first window
    t_between = time.monotonic()
    assert eng.phase_counts["prefill.dispatch"] == 1
    outs = eng.step()
    t_after = time.monotonic()
    assert eng.phase_counts["prefill.dispatch"] == 2
    st = outs[0].first_token_stamps
    assert list(st) == ["slotted", "launched", "ready"]
    assert t_added <= st["slotted"] < t_between     # its FIRST slot
    assert t_between < st["launched"] <= st["ready"] <= t_after


def test_a_preempted_request_keeps_its_first_slotted():
    eng = _engine()
    eng.add_request(_req("preempted", 50))
    assert eng.step() == []
    seq = eng._by_id["preempted"]
    first = seq.slotted_time
    assert first > 0
    eng._preempt_seq(seq)
    outs = []
    while not outs:
        outs = eng.step()
    assert seq.preemptions == 1
    assert outs[0].first_token_stamps["slotted"] == first
    assert outs[0].first_token_stamps["launched"] > first


def test_a_sequence_past_its_first_token_carries_nothing():
    eng = _engine()
    eng.add_request(_req("r0", 20, n=6))
    outs = []
    while eng.has_work():
        outs.extend(eng.step())
    assert len(outs) == 6
    assert outs[0].first_token_stamps is not None
    assert all(o.first_token_stamps is None for o in outs[1:])


def test_the_phase_ledger_books_the_engine_threads_own_time():
    """``thread_time`` beside the wall clock, for every phase ``_phase``
    brackets and for none of ``_read_host``'s two, which wait."""
    eng = _engine()
    eng.add_request(_req("r0", 20, n=4))
    while eng.has_work():
        eng.step()
    waits = {k for k in eng.phase_times
             if k.endswith((".device_wait", ".host_copy"))}
    assert waits and set(eng.phase_cpu) == set(eng.phase_times) - waits
    assert {"sched", "prefill.pack", "prefill.dispatch", "decode.post"} \
        <= set(eng.phase_cpu)
    assert all(v >= 0 for v in eng.phase_cpu.values())
    # its own time cannot pass the wall time but by the clocks' grain
    assert sum(eng.phase_cpu.values()) \
        <= sum(eng.phase_times[k] for k in eng.phase_cpu) + 0.02


# ---------------------------------------------------------------------------
# Served requests: what each path observes
# ---------------------------------------------------------------------------
_LINE = re.compile(FAMILY + r'_(sum|count)\{model="[^"]*",stage="(\w+)"\} '
                   r'(\S+)')


def _get(addr, path):
    host, port = addr.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read().decode("utf-8", "replace")
    finally:
        conn.close()


def _stage_books(worker):
    """{stage: (count, sum in ms)} of the worker's histogram now."""
    _, text = _get(worker.name, "/metrics")
    books = {}
    for kind, stage, v in _LINE.findall(text):
        c, s = books.get(stage, (0, 0.0))
        books[stage] = (int(float(v)), s) if kind == "count" \
            else (c, float(v))
    return books


def _delta(after, before):
    return {st: (c - before.get(st, (0, 0.0))[0],
                 s - before.get(st, (0, 0.0))[1])
            for st, (c, s) in after.items()
            if c - before.get(st, (0, 0.0))[0]}


def _stream(addr, srid, **body):
    headers = {"x-request-id": srid}
    payloads = list(iter_sse_events(http_stream(
        "POST", addr, "/v1/completions",
        {"model": "tiny", "prompt": "where does the time go " * 4,
         "max_tokens": 4, "temperature": 0.0, "stream": True,
         "ignore_eos": True, **body}, timeout=120.0, headers=headers)))
    assert payloads[-1] == "[DONE]"
    return payloads


@pytest.fixture()
def store():
    from xllm_service_tpu.service.coordination import InMemoryStore
    s = InMemoryStore(sweep_interval_s=0.02)
    yield s
    s.close()


@pytest.fixture(scope="module")
def worker():
    """A worker with no master in front: direct callers. Under the native
    front door (wherever it builds) its streams are the stream writer's."""
    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
    w = Worker(WorkerOptions(model="tiny"), InMemoryStore()).start()
    try:
        yield w
    finally:
        w.stop()


@pytest.fixture(scope="module")
def pulled_worker():
    """The same behind the Python server: a blocking ``wfile``, so each
    stream is pulled by its connection's own thread (``_stream_sse``)."""
    from xllm_service_tpu.runtime import worker as worker_mod
    from xllm_service_tpu.service.coordination import InMemoryStore
    from xllm_service_tpu.service.httpd import PyHttpServer
    real, worker_mod.HttpServer = worker_mod.HttpServer, PyHttpServer
    try:
        w = worker_mod.Worker(worker_mod.WorkerOptions(model="tiny"),
                              InMemoryStore()).start()
    finally:
        worker_mod.HttpServer = real
    try:
        yield w
    finally:
        w.stop()


def _stream_path(w):
    """Who writes this worker's streams."""
    return "handler" if w._srv.chunks_block else "writer"


@pytest.mark.parametrize("which", ["worker", "pulled_worker"],
                         ids=["the_writer", "_stream_sse"])
def test_the_stages_sum_to_the_first_tokens_time_whoever_writes(
        request, which):
    """``first_frame`` is stamped, and the chain folded, on the thread
    that writes the frame: on either path every stage is observed once
    and the seven between ``received`` and ``first_frame`` sum to
    ``total``."""
    w = request.getfixturevalue(which)
    _stream(w.name, "", service_request_id=f"sum-{which}-0")    # compiles
    before = _stage_books(w)
    _stream(w.name, "", service_request_id=f"sum-{which}-1")
    assert wait_until(lambda: "total" in _delta(_stage_books(w), before))
    got = _delta(_stage_books(w), before)
    assert sorted(got) == sorted(WORKER_OWN + ["total"])
    assert all(c == 1 and ms >= 0 for c, ms in got.values()), got
    assert sum(got[st][1] for st in WORKER_OWN) \
        == pytest.approx(got["total"][1], abs=1e-6)
    stages = [e["stage"] for e in
              w.spans.get(f"sum-{which}-1")["events"]]
    assert stages.index("first_token") < stages.index("first_frame")
    _, text = _get(w.name, "/metrics")
    paths = set(re.findall(
        r'xllm_worker_stream_outputs_total\{model="tiny",path="(\w+)"\}',
        text))
    assert paths == {_stream_path(w)}


def test_a_streamed_request_through_the_master(store):
    master, workers = make_cluster(store)
    w = workers[0]
    try:
        _stream(master.http_address, "warm-0")      # compiles here
        before = _stage_books(w)
        _stream(master.http_address, "chain-1")
        # the fold follows the first frame's write, on the thread that writes
        assert wait_until(lambda: "total" in _delta(_stage_books(w), before))
        got = _delta(_stage_books(w), before)
        assert sorted(got) == sorted(FIRST_TOKEN_STAGES)
        assert all(c == 1 and ms >= 0 for c, ms in got.values()), got
        assert sum(got[st][1] for st in WORKER_OWN) \
            == pytest.approx(got["total"][1], abs=1e-6)

        def merged():
            status, text = _get(master.http_address, "/admin/trace/chain-1")
            if status != 200:
                return None
            span = json.loads(text)
            stages = [e["stage"] for e in span["events"]
                      if e["plane"] == "worker"]
            return span if {"finished", "first_frame"} <= set(stages) \
                else None
        assert wait_until(lambda: merged() is not None, timeout=15.0)
        span = merged()
        # the master's share is the header it sent, to the digit
        front = span["attrs"]["worker"]
        assert got["master_in"][1] == pytest.approx(front["front_ms"],
                                                    abs=1e-9)
        assert 0 <= front["schedule_ms"] <= front["front_ms"]
        # the same chain for one request, in order, on both planes
        by_plane = {p: [e["stage"] for e in span["events"]
                        if e["plane"] == p] for p in ("service", "worker")}
        assert by_plane["worker"] == [
            "received", "parsed", "locked", "scheduled", "slotted",
            "launched", "ready", "first_token", "first_frame", "finished"]
        assert by_plane["service"] == [
            "accepted", "received", "admitted", "scheduled", "dispatched",
            "first_token", "finished"]
        mono = {e["stage"]: e["t_mono"] for e in span["events"]
                if e["plane"] == "worker"}
        for (a, _), (b, stage) in zip(FIRST_TOKEN_STAMPS,
                                      FIRST_TOKEN_STAMPS[1:]):
            assert 1000.0 * (mono[b] - mono[a]) \
                == pytest.approx(got[stage][1], abs=1e-6), stage
    finally:
        w.stop()
        master.stop()


def test_a_folded_request_allocates_and_observes_nothing(worker):
    """Once the thread that writes has folded the chain the request holds
    no dict, and whatever reaches a stamp or the fold again (an output
    past the first) is one branch: nothing retained, nothing observed."""
    seen, real = [], worker._fold_first_token
    worker._fold_first_token = lambda live: (seen.append(live),
                                             real(live))[1]
    try:
        _stream(worker.name, "", service_request_id="folded-1")
    finally:
        del worker._fold_first_token
    live = seen[0]
    assert live.stamps is None and live.first_out_time > 0
    before = _stage_books(worker)

    def hot(n):
        for _ in range(n):
            live.stamp("first_frame")
            worker._fold_first_token(live)

    hot(10)     # warm any lazy allocations out of the measurement
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    hot(10_000)
    grown = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert grown < 512, f"a folded request retained {grown} bytes"
    assert live.stamps is None and _stage_books(worker) == before


def test_the_scrape_carries_the_cpu_column_of_the_phase_ledger(worker):
    _stream(worker.name, "", service_request_id="cpu-1")
    _, text = _get(worker.name, "/metrics")
    from xllm_service_tpu.obs import validate_exposition
    assert validate_exposition(text) == []
    phases = {fam: set(re.findall(fam + r'\{model="tiny",phase="([\w.]+)"\}',
                                  text))
              for fam in ("xllm_worker_phase_cpu_seconds_total",
                          "xllm_worker_phase_seconds_total")}
    cpu = phases["xllm_worker_phase_cpu_seconds_total"]
    assert {"sched", "prefill.pack", "decode.post"} <= cpu
    assert cpu < phases["xllm_worker_phase_seconds_total"]   # no waits


def test_direct_to_the_worker_there_is_no_master_in(worker):
    before = _stage_books(worker)
    _stream(worker.name, "", service_request_id="direct-1")
    assert wait_until(
        lambda: "total" in _delta(_stage_books(worker), before))
    got = _delta(_stage_books(worker), before)
    assert sorted(got) == sorted(WORKER_OWN + ["total"])
    assert all(c == 1 and ms >= 0 for c, ms in got.values()), got


def test_queue_exceeds_the_queue_wait_by_the_locked_stretch_at_most(worker):
    """``queue`` runs from ``locked``, ``xllm_worker_queue_wait_ms`` from
    ``add_request``'s own stamp inside the locked stretch: the same end,
    so the difference is the part of that stretch before the stamp."""
    def queue_wait_sum():
        _, text = _get(worker.name, "/metrics")
        return float(re.search(
            r'xllm_worker_queue_wait_ms_sum\{[^}]*\} (\S+)', text).group(1))

    _stream(worker.name, "", service_request_id="queue-0")   # books exist
    before, qw_before = _stage_books(worker), queue_wait_sum()
    _stream(worker.name, "", service_request_id="queue-1")
    assert wait_until(
        lambda: "total" in _delta(_stage_books(worker), before))
    queue = _delta(_stage_books(worker), before)["queue"][1]
    assert wait_until(lambda: queue_wait_sum() > qw_before)
    extra = queue - (queue_wait_sum() - qw_before)
    span = worker.spans.get("queue-1")
    mono = {e["stage"]: e["t_mono"] for e in span["events"]}
    # "scheduled" is recorded once _parse_generate is back: past the
    # release of the lock
    assert 0 <= extra <= 1000.0 * (mono["scheduled"] - mono["locked"])


def test_a_non_stream_request_ends_its_chain_at_first_token(worker):
    before = _stage_books(worker)
    status, resp = http_json(
        "POST", worker.name, "/v1/completions",
        {"model": "tiny", "prompt": "all at once", "max_tokens": 3,
         "temperature": 0.0, "ignore_eos": True,
         "service_request_id": "full-1"}, timeout=120.0)
    assert status == 200 and resp["usage"]["completion_tokens"] == 3
    got = _delta(_stage_books(worker), before)
    assert sorted(got) == sorted(WORKER_OWN[:-1])       # no stream_out
    stages = [e["stage"] for e in worker.spans.get("full-1")["events"]]
    assert "first_frame" not in stages and "ready" in stages


def _token_out(worker):
    """(tokens, wake seconds, write seconds) of the worker's emit-to-wire
    counters now."""
    _, text = _get(worker.name, "/metrics")

    def one(series):
        m = re.search(re.escape(series) + r" (\S+)", text)
        return float(m.group(1)) if m else 0.0
    fam = 'xllm_worker_token_out_seconds_total{model="tiny",stage="%s"}'
    return (one('xllm_worker_token_out_tokens_total{model="tiny"}'),
            one(fam % "wake"), one(fam % "write"))


@pytest.mark.parametrize("which,stream", [
    ("worker", True), ("pulled_worker", True), ("worker", False)],
    ids=["the_writer", "_stream_sse", "_collect_full"])
def test_every_token_is_timed_from_emit_to_the_wire(request, which, stream):
    """Three requests of N tokens leave 3N in the count and both stages
    ahead, on every path a token takes out (the stream writer's thread,
    a handler's own pulling a stream, a handler's collecting); the
    engine's thread gave each output the ONE clock read its emit makes."""
    worker = request.getfixturevalue(which)
    n = 70                              # past the fold at 64 tokens
    seen, real = [], worker._dispatch_outputs
    worker._dispatch_outputs = lambda rt, outs, ms: (
        real(rt, outs, ms), seen.extend(outs))[0]
    before = _token_out(worker)
    try:
        for k in range(3):
            if stream:
                _stream(worker.name, "", max_tokens=n,
                        service_request_id=f"tok-s{k}")
            else:
                status, resp = http_json(
                    "POST", worker.name, "/v1/completions",
                    {"model": "tiny", "prompt": "all at once",
                     "max_tokens": n, "temperature": 0.0,
                     "ignore_eos": True,
                     "service_request_id": f"tok-f{k}"}, timeout=120.0)
                assert status == 200 \
                    and resp["usage"]["completion_tokens"] == n
    finally:
        del worker._dispatch_outputs
    assert wait_until(lambda: _token_out(worker)[0] - before[0] == 3 * n)
    after = _token_out(worker)
    assert after[1] > before[1] and after[2] > before[2]
    # a token's mean way out is far under a second on any machine
    assert (after[1] - before[1]) / (3 * n) < 1.0
    # one stamp an emit: the outputs of one call share it, to the bit
    mine = [o for o in seen if o.request_id.startswith("tok-")]
    assert len(mine) >= 3 * n - 3 and all(o.emit_t > 0 for o in mine)
    assert len({o.emit_t for o in mine}) <= len(mine)
    src = open(os.path.join(PKG, "runtime", "worker.py")).read()
    emit = next(f for f in ast.walk(ast.parse(src))
                if isinstance(f, ast.FunctionDef)
                and f.name == "_dispatch_outputs")
    clock_reads = [c for c in ast.walk(emit) if isinstance(c, ast.Call)
                   and ast.unparse(c.func) == "time.monotonic"]
    # the call's own read, and the first token's (PR 40): none a token
    assert len(clock_reads) == 2


def test_the_fan_in_folds_the_admission_at_its_ack(store):
    master, workers = make_cluster(store, decode_to_service=True)
    w = workers[0]
    try:
        assert wait_until(lambda: w._decode_to_service, timeout=5.0)
        before = _stage_books(w)
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "to the service", "max_tokens": 3,
             "temperature": 0.0, "ignore_eos": True}, timeout=120.0)
        assert status == 200 and resp["usage"]["completion_tokens"] == 3
        got = _delta(_stage_books(w), before)
        assert sorted(got) == ["lock_wait", "master_in", "parse"]
    finally:
        w.stop()
        master.stop()


def test_a_pd_prefill_hand_off_ends_its_chain_at_first_token(store):
    from tests.test_pd_disagg import make_pd_cluster
    master, workers = make_pd_cluster(store)
    prefill_w, decode_w = workers
    try:
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "migrate me please",
             "max_tokens": 5, "temperature": 0.0, "ignore_eos": True},
            timeout=120.0)
        assert status == 200 and resp["usage"]["completion_tokens"] == 5
        assert prefill_w.kv_migration_bytes > 0
        got = _stage_books(prefill_w)
        assert sorted(got) == sorted(["master_in"] + WORKER_OWN[:-1])
        assert all(c == 1 and ms >= 0 for c, ms in got.values()), got
        # the adopted sequence has no chain of its own
        assert _stage_books(decode_w) == {}
    finally:
        for w in workers:
            w.stop()
        master.stop()
