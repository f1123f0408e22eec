"""The program's spans on the profiler's clock, the worker's device-trace
control, the queue-wait histogram and the name of a late compile.

Spans: the catalog is closed and every span site in the tree is in it;
with the switch off a site builds nothing; they are written only between
start and stop of the control (checked on a real CPU trace too: the
profiler runs without a chip, its host plane is all a CPU has)."""

import ast
import json
import logging
import os
import re
from http.client import HTTPConnection

import pytest

from xllm_service_tpu.obs import steptrace

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "xllm_service_tpu")


def _post(addr, path, obj):
    host, port = addr.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=120)
    try:
        conn.request("POST", path, body=json.dumps(obj),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read().decode("utf-8", "replace"))
    finally:
        conn.close()


def _complete(w, prompt, n=4):
    return _post(w.name, "/v1/completions", {
        "model": "tiny", "prompt": prompt, "max_tokens": n,
        "temperature": 0.0, "ignore_eos": True})


@pytest.fixture
def worker():
    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
    w = Worker(WorkerOptions(model="tiny"), InMemoryStore()).start()
    try:
        yield w
    finally:
        w.stop()            # stops a trace a failed test left running


@pytest.fixture
def counted(monkeypatch):
    """``jax.profiler.TraceAnnotation`` replaced by a counter of names."""
    import jax
    built = []

    class Counted:
        def __init__(self, name, **args):
            built.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    yield built
    steptrace.set_spans(False)


# ---------------------------------------------------------------------------
# The catalog and the helper
# ---------------------------------------------------------------------------
def _span_sites():
    """Every span name the package can emit, read off its source: the
    literals of ``steptrace.span(...)`` calls, and ``xllm.step.<phase>``
    for every ``_phase("<phase>")`` / ``_read_host("<phase>")``."""
    names = set()
    for root, _, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(root, fn)).read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call) or not isinstance(
                        node.func, ast.Attribute):
                    continue
                lits = [a.value for a in node.args
                        if isinstance(a, ast.Constant)
                        and isinstance(a.value, str)]
                if node.func.attr == "span" and isinstance(
                        node.func.value, ast.Name) and \
                        node.func.value.id == "steptrace" and lits:
                    if len(lits) == len(node.args):
                        names.add("".join(lits))
                elif node.func.attr == "_phase" and lits:
                    names.add("xllm.step." + lits[0])
                elif node.func.attr == "_read_host" and lits:
                    names.add(f"xllm.step.{lits[0]}.device_wait")
                    names.add(f"xllm.step.{lits[0]}.host_copy")
    return names


def test_span_names_is_closed_and_holds_every_site():
    sites = _span_sites()
    assert len(sites) > 30
    assert sites == set(steptrace.SPAN_NAMES)
    assert len(set(steptrace.SPAN_NAMES)) == len(steptrace.SPAN_NAMES)
    assert all(re.fullmatch(r"xllm\.[a-z_.]+", n)
               for n in steptrace.SPAN_NAMES)


def test_decode_upload_is_a_span_with_one_site():
    """The block's upload is bracketed once, inside ``decode.pack``, and
    nowhere else."""
    assert "xllm.step.decode.upload" in steptrace.SPAN_NAMES
    src = open(os.path.join(PKG, "runtime", "engine.py")).read()
    assert src.count('_phase("decode.upload")') == 1
    run_decode = next(n for n in ast.walk(ast.parse(src))
                      if isinstance(n, ast.FunctionDef)
                      and n.name == "_dispatch_decode")
    withs = [n for n in ast.walk(run_decode) if isinstance(n, ast.With)]

    def phase(w):
        c = w.items[0].context_expr
        return c.args[0].value if isinstance(c, ast.Call) and getattr(
            c.func, "attr", "") == "_phase" else None
    pack = next(w for w in withs if phase(w) == "decode.pack")
    inner = [phase(w) for w in ast.walk(pack) if isinstance(w, ast.With)]
    assert "decode.upload" in inner
    # and what it brackets is the upload alone: one statement
    up = next(w for w in withs if phase(w) == "decode.upload")
    assert len(up.body) == 1 and "device_put" in ast.unparse(up.body[0])


def test_idle_under_decode_upload_goes_to_it_and_not_to_the_pack():
    """``chipbench/spans.py`` gives an idle stretch to the innermost
    span over it: with the new phase inside ``decode.pack`` the pack's
    idle time splits into upload and bookkeeping by itself."""
    from chipbench import spans
    dev, host = "/device:TPU:0", "/host:CPU"

    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name,
                "start": start, "dur": dur}
    prog = "%while.1 = (s32[], bf16[8,1,64]"
    events = [
        ev(dev, "XLA Modules", "jit__unknown(1)", 0, 100),
        ev(dev, "XLA Ops", prog, 0, 100),
        ev(dev, "XLA Modules", "jit__unknown(1)", 200, 100),
        ev(dev, "XLA Ops", prog, 200, 100),
        # a miss step: the device is idle 100..200 under the pack
        # (110..180), of which the upload is 140..170
        ev(host, "python", "xllm.loop.step", 105, 200),
        ev(host, "python", "xllm.step.decode.pack", 110, 70),
        ev(host, "python", "xllm.step.decode.upload", 140, 30),
        ev(host, "python", "xllm.step.decode.dispatch", 180, 20),
    ]
    got = {k: round(v * 1e9) for k, v in spans.idle_by_span(events)}
    assert got["xllm.step.decode.upload"] == 30
    assert got["xllm.step.decode.pack"] == 40
    assert got["xllm.step.decode.dispatch"] == 20
    assert sum(got.values()) == 100
    # the metric file's reduction: a mean over steps, most have none
    assert spans.per_step_ms(events, r"^xllm\.step\.decode\.upload$",
                             "mean_per_step") == pytest.approx(30 / 1e6)


def test_span_off_is_one_shared_noop_and_builds_nothing(counted):
    a = steptrace.span("xllm.loop.step", seq=1)
    b = steptrace.span("xllm.step.", "decode", ".device_wait")
    assert a is b                       # the one shared no-op
    with a:
        pass
    # off, not even the name is looked at: nothing is joined or checked
    assert steptrace.span("not", "a", "span") is a
    assert counted == []


def test_span_on_builds_the_named_annotation_and_rejects_others(counted):
    steptrace.set_spans(True)
    with steptrace.span("xllm.step.", "decode", ".device_wait"):
        pass
    with steptrace.span("xllm.loop.step", seq=7):
        pass
    assert counted == [("xllm.step.decode.device_wait", {}),
                       ("xllm.loop.step", {"seq": 7})]
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        steptrace.span("xllm.loop.", "coffee")


def test_engine_phase_builds_no_annotation_while_off(counted):
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams
    eng = Engine(ModelConfig.tiny(vocab_size=256), EngineConfig(
        page_size=16, num_pages=32, max_model_len=128, max_batch_size=2,
        prefill_buckets=(32,)))
    eng.add_request(EngineRequest(
        request_id="r0", token_ids=list(range(3, 20)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0,
                                ignore_eos=True)))
    while eng.has_work():
        eng.step()
    assert eng.phase_counts["decode.tail_dispatch"] >= 1  # phases did run
    assert counted == []
    # the same engine, switch on: its phases are spans now, and the
    # dispatch carries the shape key the program was launched with
    steptrace.set_spans(True)
    eng.add_request(EngineRequest(
        request_id="r1", token_ids=list(range(43, 60)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0,
                                ignore_eos=True)))
    while eng.has_work():
        eng.step()
    names = [n for n, _ in counted]
    for want in ("xllm.step.sched", "xllm.kv.match_prefix",
                 "xllm.step.prefill.pack", "xllm.step.prefill.dispatch",
                 "xllm.step.prefill.device_wait",
                 "xllm.step.prefill.host_copy", "xllm.step.decode.pack",
                 "xllm.step.decode.post", "xllm.kv.register_pages"):
        assert want in names, want
    # the step the prefill iteration dispatched at its tail (PR 39)
    args = dict(counted)["xllm.step.decode.tail_dispatch"]
    # (no window in this model: the attention walks the whole table; the
    # CPU's plan is the XLA reference: no kernel folds a block of pages
    # and none reads a page flat)
    assert args == {"program": "decode", "B": 2, "T": 1, "MP": args["MP"],
                    "walk": args["MP"], "fold": 1, "flat": 1}
    assert dict(counted)["xllm.kv.match_prefix"] == {"tokens": 17}
    # the prompt's one full page, registered with the first token (its
    # digest is the admission's: nothing hashed); the two tokens after
    # it fill no page and open no span
    assert [a for n, a in counted if n == "xllm.kv.register_pages"] \
        == [{"tokens": 0, "pages": 1}]


def test_a_decode_step_in_which_no_page_fills_registers_nothing(counted):
    """Eight rows of six full pages each: the per-token registration
    starts at a row's watermark, so a step in which no row's page fills
    opens no ``xllm.kv.register_pages`` span and walks no page, a step
    in which one row's fills opens one with ``pages`` 1 (and the 16
    tokens of that page hashed), and the engine's total of walked pages
    reads the spans' sum."""
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams
    ps = 16
    eng = Engine(ModelConfig.tiny(vocab_size=256), EngineConfig(
        page_size=ps, num_pages=96, max_model_len=256, max_batch_size=8,
        prefill_buckets=(128,)))
    for i in range(8):              # 6 pages and 1 to 15 tokens, by twos
        eng.add_request(EngineRequest(
            request_id=f"r{i}",
            token_ids=[(i * 31 + j * 7) % 250 + 3
                       for j in range(6 * ps + 1 + 2 * i)],
            sampling=SamplingParams(max_tokens=48, temperature=0.0,
                                    ignore_eos=True)))
    while eng.waiting or len(eng.running) < 8:
        eng.step()
    assert all(s.pages_settled == s.num_computed // ps >= 6
               for s in eng.running)

    def walked():
        return eng.prefix_cache_stats()["walked_pages_total"]

    steptrace.set_spans(True)
    total, seen = walked(), set()
    for _ in range(20):
        fills = [s for s in eng.running if (s.num_computed + 1) % ps == 0]
        n, w = len(counted), walked()
        eng.step()
        spans = [a for name, a in counted[n:]
                 if name == "xllm.kv.register_pages"]
        assert spans == [{"tokens": ps, "pages": 1}] * len(fills)
        assert walked() - w == len(fills)
        seen.add(len(fills))
    assert len(eng.running) == 8 and {0, 1} <= seen
    assert walked() - total == sum(
        a["pages"] for name, a in counted
        if name == "xllm.kv.register_pages") > 0


# ---------------------------------------------------------------------------
# The control
# ---------------------------------------------------------------------------
def test_spans_are_emitted_only_between_start_and_stop(worker, counted,
                                                       tmp_path):
    assert _complete(worker, "before the trace")[0] == 200
    assert counted == []
    assert worker.start_device_trace(str(tmp_path / "t")) == \
        str(tmp_path / "t")
    assert steptrace.spans_on()
    assert _complete(worker, "inside the trace")[0] == 200
    assert worker.stop_device_trace() == str(tmp_path / "t")
    assert not steptrace.spans_on()
    inside = [n for n, _ in counted]
    for want in ("xllm.loop.lock_wait", "xllm.loop.step", "xllm.loop.emit",
                 "xllm.loop.obs_flush", "xllm.admit",
                 "xllm.admit.lock_wait", "xllm.admit.locked"):
        assert want in inside, want
    rid = dict(counted)["xllm.admit"]["rid"]
    assert rid and dict(counted)["xllm.admit.locked"] == {"rid": rid}
    n = len(counted)
    assert _complete(worker, "after the trace")[0] == 200
    assert len(counted) == n


def test_endpoint_start_stop_and_its_refusals(worker, tmp_path):
    d = str(tmp_path / "trace")
    assert _post(worker.name, "/admin/devtrace", {"action": "stop"})[0] \
        == 409                        # an error, not a crash
    status, body = _post(worker.name, "/admin/devtrace",
                         {"action": "start", "dir": d})
    assert (status, body["dir"]) == (200, d)
    status, body = _post(worker.name, "/admin/devtrace",
                         {"action": "start", "dir": d + "2"})
    assert status == 409 and "already running" in body["error"]["message"]
    assert _complete(worker, "traced for real")[0] == 200
    assert _post(worker.name, "/admin/devtrace",
                 {"action": "stop"}) == (200, {"ok": True, "action": "stop",
                                               "dir": d})
    assert _post(worker.name, "/admin/devtrace", {"action": "go"})[0] == 400
    assert _post(worker.name, "/admin/devtrace", {"action": "start"})[0] \
        == 400
    assert _complete(worker, "and it still serves")[0] == 200
    # what the control wrote is a trace the benchmark's loader reads,
    # with the program's spans on its host plane
    from chipbench import spans, trace
    events = trace.load_events(trace.find_xplane(d))
    names = {e["name"] for e in spans.program_spans(events)}
    assert {"xllm.loop.step", "xllm.loop.emit", "xllm.loop.obs_flush",
            "xllm.step.decode.tail_dispatch", "xllm.admit"} <= names
    steps = spans.program_spans(events, r"^xllm\.loop\.step$")
    inner = spans.program_spans(events, r"^xllm\.step\.")
    assert steps and all(any(
        s["start"] <= e["start"] and e["start"] + e["dur"]
        <= s["start"] + s["dur"] for s in steps) for e in inner)


def test_a_streamed_token_is_a_span_on_the_line_of_the_thread_that_writes(
        worker, tmp_path):
    """``xllm.stream.token`` (an output in hand -> its frames written)
    is a span of the thread that WRITES the stream: the worker's one
    stream writer under the native front door (all of a trace's on ONE
    line, which admits nothing), a handler's own under the Python
    server (a line that also admits). In a real CPU trace it lies on a
    line that holds no span of the engine loop, its name is none the
    benchmark nests under the engine's thread, and no stream writes one
    before start or after stop."""
    from jax.profiler import ProfileData
    from chipbench import spans, trace

    def stream(prompt, n):
        host, port = worker.name.rsplit(":", 1)
        conn = HTTPConnection(host, int(port), timeout=120)
        try:
            conn.request("POST", "/v1/completions", body=json.dumps({
                "model": "tiny", "prompt": prompt, "max_tokens": n,
                "temperature": 0.0, "ignore_eos": True, "stream": True}))
            r = conn.getresponse()
            assert r.status == 200 and b"[DONE]" in r.read()
        finally:
            conn.close()

    name = "xllm.stream.token"
    assert name in steptrace.SPAN_NAMES
    assert not spans.ENGINE_THREAD.match(name)
    stream("before the trace", 3)
    d = str(tmp_path / "t")
    worker.start_device_trace(d)
    stream("inside the trace", 6)
    worker.stop_device_trace()
    stream("after the trace", 3)
    lines = [{e.name for e in ln.events if e.name.startswith("xllm.")}
             for plane in ProfileData.from_file(
                 trace.find_xplane(d)).planes for ln in plane.lines]
    writers = [names for names in lines if name in names]
    assert writers
    for names in writers:
        assert not any(spans.ENGINE_THREAD.match(n) for n in names), names
    if worker._srv.chunks_block:        # a handler admits and streams
        assert all("xllm.admit" in names for names in writers)
    else:                               # the one writer only writes
        assert len(writers) == 1 and "xllm.admit" not in writers[0]
    assert any("xllm.loop.emit" in names and name not in names
               for names in lines)
    events = trace.load_events(trace.find_xplane(d))
    # one a token's output of the ONE traced stream (6), none of the
    # other two's
    assert len(spans.program_spans(events, r"^xllm\.stream\.token$")) == 6
    assert name not in {seg[2] for seg in spans.innermost_segments(
        spans.program_spans(events, spans.ENGINE_THREAD.pattern))}


# ---------------------------------------------------------------------------
# The two counters
# ---------------------------------------------------------------------------
def _metric(w, name):
    host, port = w.name.rsplit(":", 1)
    conn = HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith(name + "{") or ln.startswith(name + " "))


def test_queue_wait_counts_one_observation_per_admitted_request(worker):
    assert _metric(worker, "xllm_worker_queue_wait_ms_count") == 0
    for i in range(3):
        assert _complete(worker, f"request number {i}", n=6)[0] == 200
    assert _metric(worker, "xllm_worker_queue_wait_ms_count") == 3
    assert 0 < _metric(worker, "xllm_worker_queue_wait_ms_sum") < 60e3
    eng = worker.primary_runtime().engine
    assert eng.queue_waits_ms == []            # drained into the histogram


def test_a_compile_after_warmup_is_named_in_the_log_and_the_record(
        worker, caplog):
    """A CPU worker boots unwarmed, so its first request compiles its
    programs under serving: each is reported with its shape key."""
    with caplog.at_level(logging.WARNING,
                         logger="xllm_service_tpu.runtime.engine"):
        assert _complete(worker, "compile me")[0] == 200
    logged = re.findall(r"post-warmup compile of (\S+) \(cache",
                        caplog.text)
    assert logged and all(re.fullmatch(
        r"(prefill:B\d+xT\d+xmp\d+|decode(_multi)?:mp\d+)", c)
        for c in logged), logged
    recorded = [c for r in worker.steptrace.tail()
                for c in r["compiled"]]
    assert sorted(recorded) == sorted(logged)
    # a second request of the same shape compiles nothing, and says so
    n = worker.steptrace.last_seq()
    assert _complete(worker, "compile me")[0] == 200
    later = worker.steptrace.tail(since_seq=n)
    assert later and all(r["compiled"] == () for r in later)
    assert _metric(worker, "xllm_worker_recompiles_total") == len(logged)


# ---------------------------------------------------------------------------
# The seam the benchmark's precompile holds the decode program by
# ---------------------------------------------------------------------------
def test_benchmark_precompile_lowers_the_step_programs_as_they_are_served():
    """``chipbench.cluster.precompile`` builds the step programs'
    arguments itself (nine for ``_jit_decode``, ``E._PACK_COLS + mp``
    columns, a ``PRNGKey(0)``) and raises if they move: a checkout's
    first benchmark run would fail. What it lowers from uploaded
    arguments is also the module the engine serves with carried ones
    (their placement is stated in ``_pin``): warm-up and serving add no
    cache entry for the width beyond the one."""
    import jax
    from chipbench import cluster
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.runtime import engine as E
    from xllm_service_tpu.utils.types import SamplingParams
    eng = E.Engine(ModelConfig.tiny(vocab_size=256), EngineConfig(
        page_size=16, num_pages=32, max_model_len=128, max_batch_size=2,
        prefill_buckets=(32,)))
    mp = 2
    shapes = {"prefill": [(1, 32, mp)], "decode_widths": [mp]}
    assert cluster.precompile(eng, shapes, threads=1) > 0
    B = eng.ecfg.max_batch_size
    args = [eng.params,
            jax.numpy.zeros((B, E._PACK_COLS + mp), jax.numpy.int32),
            eng.kv, *eng._sampling_tensors([], B), jax.random.PRNGKey(0),
            None, *eng._batch_bias([], B, eng.cfg.vocab_size)]
    uploaded = eng._jit_decode.lower(*args).as_text()
    for i in (1, 5):                    # the block and the key, carried
        args[i] = jax.device_put(args[i], eng._carry_place)
    assert eng._jit_decode.lower(*args).as_text() == uploaded
    eng.warmup(prefill_shapes=shapes["prefill"], decode_widths=[mp])
    assert eng.compile_report()["decode"] == 1
    eng.add_request(E.EngineRequest(
        request_id="r0", token_ids=list(range(3, 20)),
        sampling=SamplingParams(max_tokens=8, temperature=0.0,
                                ignore_eos=True)))
    while eng.has_work():
        eng.step()
    assert eng._decode_carry[1].shape[1] == E._PACK_COLS + mp
    assert eng.phase_counts["decode.resident_hit"] >= 5
    assert eng.compile_report()["decode"] == 1
    assert eng.compile_report()["prefill"] == 1
