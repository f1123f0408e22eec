"""Who holds the interpreter, as the benchmark reads it (PR 55): the
readers of the per-root thread clocks (``readers/thread_cpu_share.py``),
of a token's way from ``emit`` to the wire (``readers/token_out_ms.py``)
and of the handlers' convoy behind an ``emit``
(``readers/stream_convoy_ms.py``), each on a hand-made context with its
arithmetic written out; their eight metric files against the program's
roots, phases and spans and against the benchmark's cells. A program
without the series or the span (the parent) gives each reader nothing,
and none raises."""

import os

import pytest

from chipbench import spans, spec
from chipbench.readers import (phase_cpu_share, stream_convoy_ms,
                               thread_cpu_share, token_out_ms)
from xllm_service_tpu.obs import profiler, steptrace

CPU = "xllm_thread_cpu_seconds_total"
RUNQ = "xllm_thread_runq_wait_seconds_total"
ADDED = ["engine_thread_cpu_share.docqa", "handler_threads_cpu_share.docqa",
         "other_threads_cpu_share.docqa",
         "engine_thread_runq_wait_share.docqa",
         "decode_pack_own_share.docqa", "token_wake_ms.docqa",
         "token_write_ms.docqa", "stream_convoy_ms.docqa"]


def series(family, **labels):
    return family + "{" + ",".join(
        f'{k}="{v}"' for k, v in labels.items()) + "}"


def scrape(cpu, runq=None, source="schedstat"):
    out = {series(CPU, root=r): v for r, v in cpu.items()}
    out.update({series(RUNQ, root=r): v for r, v in (runq or {}).items()})
    if source:
        out[series("xllm_thread_clock", source="schedstat")] = \
            float(source == "schedstat")
        out[series("xllm_thread_clock", source="stat")] = \
            float(source == "stat")
    return out


# A window of 50 s. Close minus open: the engine's loop 20 s on a core
# and 1.5 s waiting for one, the handlers 12.5 s (a root that was not
# there at the open counts from nothing), main 0.5, the heartbeat 0.25,
# the native threads 60.
OPEN = scrape({"worker.engine_loop": 100.0, "main": 9.0,
               "worker.hb_loop": 1.0, "unregistered": 300.0},
              {"worker.engine_loop": 2.0, "unregistered": 40.0})
CLOSE = scrape({"worker.engine_loop": 120.0, "httpd.handler": 12.5,
                "main": 9.5, "worker.hb_loop": 1.25, "unregistered": 360.0},
               {"worker.engine_loop": 3.5, "httpd.handler": 4.0,
                "unregistered": 45.0})


def ctx_of(opened, closed, **more):
    return {"counters_open": opened, "counters_close": closed,
            "open_t": 100.0, "close_t": 150.0, **more}


@pytest.mark.parametrize("name,want", [
    ("engine_thread_cpu_share.docqa", 100.0 * 20.0 / 50.0),
    ("handler_threads_cpu_share.docqa", 100.0 * 12.5 / 50.0),
    # main 0.5 + the heartbeat 0.25 + the native threads 60: over 100%
    # of one core, which is what native threads are for
    ("other_threads_cpu_share.docqa", 100.0 * 60.75 / 50.0),
    ("engine_thread_runq_wait_share.docqa", 100.0 * 1.5 / 50.0),
])
def test_a_roots_share_is_its_seconds_over_the_window(name, want):
    info = spec.layer_metric_file(name)
    assert info["reader"] == "thread_cpu_share"
    assert thread_cpu_share.read(ctx_of(OPEN, CLOSE), info) \
        == pytest.approx(want)


@pytest.mark.parametrize("name,closed", [
    # the parent: supervised roots alone, ticks, no handlers' root, no
    # sum that could be called "the others"
    ("handler_threads_cpu_share.docqa",
     scrape({"worker.engine_loop": 120.0, "worker.hb_loop": 1.25},
            source=None)),
    ("other_threads_cpu_share.docqa",
     scrape({"worker.engine_loop": 120.0, "worker.hb_loop": 1.25},
            source=None)),
    ("engine_thread_runq_wait_share.docqa",
     scrape({"worker.engine_loop": 120.0}, source=None)),
    # a host without schedstats: the wait's series are there and read 0;
    # 0 would be a lie
    ("engine_thread_runq_wait_share.docqa",
     scrape({"worker.engine_loop": 120.0, "unregistered": 1.0},
            {"worker.engine_loop": 0.0}, source="stat")),
    ("engine_thread_cpu_share.docqa", {}),
])
def test_a_program_or_a_host_without_the_clock_gives_nothing(name, closed):
    info = spec.layer_metric_file(name)
    assert thread_cpu_share.read(ctx_of({}, closed), info) is None


def test_the_parents_engine_thread_reads_in_ticks_all_the_same():
    # the family and the engine loop's root are older than this PR
    info = spec.layer_metric_file("engine_thread_cpu_share.docqa")
    old = scrape({"worker.engine_loop": 100.0}, source=None)
    new = scrape({"worker.engine_loop": 125.0}, source=None)
    assert thread_cpu_share.read(ctx_of(old, new), info) == 50.0


def test_the_listed_roots_are_roots_the_program_has():
    import ast
    import inspect
    from xllm_service_tpu.runtime import worker
    spawned = {n.args[0].value for n in ast.walk(ast.parse(
        inspect.getsource(worker))) if isinstance(n, ast.Call)
        and ast.unparse(n.func).endswith("spawn") and n.args
        and isinstance(n.args[0], ast.Constant)}
    assert "worker.engine_loop" in spawned
    roots = spawned | {profiler.HANDLER_ROOT, profiler.MAIN_ROOT,
                       profiler.UNREGISTERED}
    for name in ADDED[:4]:
        info = spec.layer_metric_file(name)
        assert set(info.get("roots", []) + info.get("all_but", [])) <= roots
        assert ("roots" in info) != ("all_but" in info)
    both = spec.layer_metric_file("other_threads_cpu_share.docqa")["all_but"]
    assert sorted(both) == sorted(
        spec.layer_metric_file(n)["roots"][0] for n in ADDED[:2])


def test_the_packs_own_share_is_the_old_reader_on_one_phase():
    info = spec.layer_metric_file("decode_pack_own_share.docqa")
    assert (info["reader"], info["phases"]) == ("phase_cpu_share",
                                                ["decode.pack"])

    def books(wall, cpu):
        out = {}
        for fam, ph in (("xllm_worker_phase_seconds_total", wall),
                        ("xllm_worker_phase_cpu_seconds_total", cpu)):
            out.update({series(fam, model="m", phase=p): v
                        for p, v in ph.items()})
        return out
    ctx = ctx_of(books({"decode.pack": 10.0, "prefill.pack": 5.0},
                       {"decode.pack": 2.0, "prefill.pack": 4.0}),
                 books({"decode.pack": 18.4, "prefill.pack": 9.0},
                       {"decode.pack": 3.5, "prefill.pack": 8.0}))
    # the pack alone: 1.5 s of its own in 8.4 s on the wall (PR 39's
    # reading of one pack at 64 rows, in milliseconds)
    assert phase_cpu_share.read(ctx, info) == pytest.approx(
        100.0 * 1.5 / 8.4)


def token_scrape(tokens, wake_s, write_s):
    fam = "xllm_worker_token_out_seconds_total"
    return {"xllm_worker_token_out_tokens_total": float(tokens),
            series("xllm_worker_token_out_tokens_total", model="m"):
                float(tokens),
            series(fam, model="m", stage="wake"): wake_s,
            series(fam, model="m", stage="write"): write_s,
            fam: wake_s + write_s}


def test_a_tokens_stage_is_its_seconds_over_the_tokens_between_scrapes():
    ctx = ctx_of(token_scrape(1000, 4.0, 1.0), token_scrape(3000, 16.0, 2.5))
    # 2,000 tokens: 12 s from emit to the wake, 1.5 s from there to the
    # frame written
    assert token_out_ms.read(ctx, {"stage": "wake"}) == pytest.approx(6.0)
    assert token_out_ms.read(ctx, {"stage": "write"}) == pytest.approx(0.75)
    for name, stage in (("token_wake_ms.docqa", "wake"),
                        ("token_write_ms.docqa", "write")):
        info = spec.layer_metric_file(name)
        assert (info["reader"], info["stage"]) == ("token_out_ms", stage)
    same = ctx_of(token_scrape(1000, 4.0, 1.0), token_scrape(1000, 4.0, 1.0))
    assert token_out_ms.read(same, {"stage": "wake"}) is None
    assert token_out_ms.read(ctx_of({}, {}), {"stage": "wake"}) is None


def sp(name, start, dur):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "start": start, "dur": dur}


# Three emits, a dozen events. The first (at 1,000) wakes three handlers
# whose writes end at 1,400, 2,900 and 2,100: the convoy is 1,900 long.
# The second (at 5,000) wakes two, the later write ending at 5,700: 700.
# The third (at 9,000) woke nobody the trace saw. A write that began at
# 900, before the first emit, belongs to an emit before the trace.
EVENTS = [
    sp("xllm.stream.token", 900, 50),
    sp("xllm.loop.emit", 1000, 300),
    sp("xllm.stream.token", 1100, 300),
    sp("xllm.stream.token", 1250, 1650),
    sp("xllm.stream.token", 1300, 800),
    sp("xllm.loop.step", 1400, 3500),
    sp("xllm.step.decode.pack", 1400, 900),
    sp("xllm.loop.emit", 5000, 200),
    sp("xllm.stream.token", 5100, 100),
    sp("xllm.stream.token", 5150, 550),
    sp("xllm.loop.emit", 9000, 100),
    {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion.1",
     "start": 1000, "dur": 9000},
]


def test_the_convoy_is_the_last_write_behind_an_emit():
    info = spec.layer_metric_file("stream_convoy_ms.docqa")
    assert info["reader"] == "stream_convoy_ms"
    # the median of 1,900 and 700 ns, in milliseconds
    assert stream_convoy_ms.read({"trace": {"events": EVENTS}}, info) \
        == pytest.approx(1300 / 1e6)
    parent = [e for e in EVENTS if e["name"] != "xllm.stream.token"]
    assert stream_convoy_ms.read({"trace": {"events": parent}}, info) is None
    assert stream_convoy_ms.read({"trace": None}, info) is None
    assert stream_convoy_ms.read({}, info) is None


def test_the_handlers_span_names_no_idle_gap():
    """``breakdown.idle_gaps`` lays the device's idle time over the
    ENGINE thread's spans: a handler's write, which overlaps them in
    time, is no row of it."""
    info = spec.layer_metric_file("stream_convoy_ms.docqa")
    import re
    for key, name in (("emit_pattern", "xllm.loop.emit"),
                      ("token_pattern", "xllm.stream.token")):
        assert re.search(info[key], name) and name in steptrace.SPAN_NAMES
        assert [n for n in steptrace.SPAN_NAMES
                if re.search(info[key], n)] == [name]
    assert not spans.ENGINE_THREAD.match("xllm.stream.token")
    segs = spans.innermost_segments(spans.program_spans(
        EVENTS, spans.ENGINE_THREAD.pattern))
    assert {s[2] for s in segs} == {"xllm.loop.emit", "xllm.loop.step",
                                    "xllm.step.decode.pack"}


@pytest.mark.parametrize("name", ADDED)
def test_an_added_metrics_file_is_what_its_entry_will_say(name, root):
    """The eight arrive as files and readers: an entry that lists a cell
    the benchmark has changes that cell's set of metrics, which the
    accepted tests hold by name, so a ``benchmark`` PR enters them. A
    file holds all its entry needs: it moves ``out_tok_s``, carries a
    layer name the benchmark has, and does not say ``program_counter``:
    times, which a CPU rehearsal's line leaves out
    (``test_chipbench_rehearsal.rehearsal_counters``). An entry, once
    there, says what the file says."""
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    info = spec.layer_metric_file(name, root)
    assert info["name"] == name and info["moves"] == "out_tok_s"
    assert info["source"] == ("device_trace"
                              if name == "stream_convoy_ms.docqa"
                              else "program_span")
    assert info["layer"] in {m["layer"] for m in bench["per_layer"]
                             if m["name"] not in ADDED}
    assert info["unit"] == ("ms" if "_ms." in name else "%")
    assert info["better"] == (
        "higher" if name == "decode_pack_own_share.docqa" else "lower")
    assert callable(spec.load_reader(info["reader"], root).read)
    for entry in (m for m in bench["per_layer"] if m["name"] == name):
        assert {k: entry[k] for k in entry if k != "workloads"} == {
            k: info[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")}


def test_the_readers_read_a_live_workers_two_scrapes():
    """What ``run.py`` hands the counter readers (``cluster.scrape`` at
    the window's two ends) from a worker that streamed between them."""
    import time

    from chipbench import cluster
    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
    from xllm_service_tpu.service.httpd import http_stream, iter_sse_events
    w = Worker(WorkerOptions(model="tiny"), InMemoryStore()).start()
    try:
        def ask(n):
            frames = list(iter_sse_events(http_stream(
                "POST", w.name, "/v1/completions",
                {"model": "tiny", "prompt": "who holds the interpreter",
                 "max_tokens": n, "temperature": 0.0, "stream": True,
                 "ignore_eos": True}, timeout=120.0)))
            assert frames[-1] == "[DONE]"
        ask(3)
        t0 = time.monotonic()
        c_open = cluster.scrape(w.name)
        for _ in range(3):
            ask(20)
        c_close = cluster.scrape(w.name)
        ctx = {"counters_open": c_open, "counters_close": c_close,
               "open_t": t0, "close_t": time.monotonic()}
    finally:
        w.stop()
    got = {}
    for name in ADDED[:7]:
        info = spec.layer_metric_file(name)
        got[name] = spec.load_reader(info["reader"]).read(ctx, info)
    assert c_close["xllm_worker_token_out_tokens_total"] \
        - c_open["xllm_worker_token_out_tokens_total"] == 60
    assert got["token_wake_ms.docqa"] > 0 and got["token_write_ms.docqa"] > 0
    for name in ADDED[:3] + ["decode_pack_own_share.docqa"]:
        assert got[name] is not None and got[name] > 0, name
    assert got["engine_thread_cpu_share.docqa"] <= 105
    assert got["handler_threads_cpu_share.docqa"] < \
        got["engine_thread_cpu_share.docqa"]
    wait = got["engine_thread_runq_wait_share.docqa"]
    if profiler._source() == "schedstat":
        assert wait is not None and 0 <= wait < 100
    else:
        assert wait is None
