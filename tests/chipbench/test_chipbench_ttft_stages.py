"""The readers of the program's first-token stages
(``readers/first_token_stage_ms.py``, ``readers/ttft_unattributed_ms.py``)
and of the engine thread's own time (``readers/phase_cpu_share.py``) on
hand-made scrapes and records; their metric files against the program's
table of stages and the benchmark's cells; and all three readers on the
two scrapes of a live worker, since a CPU rehearsal prints no metric
whose source is not ``program_counter``."""

import os

import pytest

from chipbench import spec
from chipbench.readers import (first_token_stage_ms, phase_cpu_share,
                               ttft_unattributed_ms)
from xllm_service_tpu.obs import FIRST_TOKEN_STAGES
from xllm_service_tpu.runtime.engine import Engine

# the cells PR 40 entered its ten metrics for, in its order; a cell added
# since stands behind them
ENTERED_FOR = ["mistral7b-v01-docqa", "joyai-flash-docqa32",
               "lfm2-24b-docqa64"]
STAGE_METRICS = {
    "ttft_master_in_ms.docqa": "master_in",
    "ttft_parse_ms.docqa": "parse",
    "ttft_lock_wait_ms.docqa": "lock_wait",
    "ttft_queue_ms.docqa": "queue",
    "ttft_prefill_host_ms.docqa": "prefill_host",
    "ttft_prefill_device_ms.docqa": "prefill_device",
    "ttft_post_emit_ms.docqa": "post_emit",
    "ttft_stream_out_ms.docqa": "stream_out",
}
ADDED = list(STAGE_METRICS) + ["ttft_unattributed_ms.docqa",
                               "engine_thread_own_share.docqa"]


def series(family, **labels):
    return family + "{" + ",".join(
        f'{k}="{v}"' for k, v in labels.items()) + "}"


def stage_scrape(books):
    """A scrape's samples of the histogram: {stage: (count, sum)}, under a
    model label in front of the stage's as the program writes them."""
    out = {}
    for stage, (count, total) in books.items():
        for suffix, v in (("_count", count), ("_sum", total)):
            out[series("xllm_worker_first_token_stage_ms" + suffix,
                       model="m", stage=stage)] = float(v)
    return out


def ctx_of(open_books, close_books, records=(), open_t=100.0, close_t=151.0):
    return {"counters_open": stage_scrape(open_books),
            "counters_close": stage_scrape(close_books),
            "records": list(records), "open_t": open_t, "close_t": close_t}


def test_a_stage_is_its_sum_over_its_count_between_the_two_scrapes():
    ctx = ctx_of({"queue": (10, 100.0), "parse": (10, 20.0)},
                 {"queue": (14, 160.0), "parse": (14, 30.0),
                  "queue_not": (4, 4000.0)})
    assert first_token_stage_ms.read(ctx, {"stage": "queue"}) == 15.0
    assert first_token_stage_ms.read(ctx, {"stage": "parse"}) == 2.5
    # a stage nothing observed in the window, and a program without the
    # series (the parent): nothing, and no raise
    assert first_token_stage_ms.read(ctx, {"stage": "lock_wait"}) is None
    empty = {"counters_open": {"xllm_worker_steps_total": 3.0},
             "counters_close": {"xllm_worker_steps_total": 9.0}}
    assert first_token_stage_ms.read(empty, {"stage": "queue"}) is None


def record(due, first, ok=True):
    return {"ok": ok, "due": due, "frames": [[first, 1]] if first else []}


def test_unattributed_is_the_generators_mean_less_both_planes_means():
    records = [record(101.0, 101.080), record(120.0, 120.100),
               record(150.9, 151.020),
               record(99.0, 99.500),            # due before the window
               record(151.0, 151.050),          # due at its close
               record(130.0, None),             # no first frame
               record(None, 140.0)]             # a set-up request
    ctx = ctx_of({}, {"total": (3, 210.0), "master_in": (3, 12.0)}, records)
    # generator: (80 + 100 + 120) / 3 = 100; master 4; worker 70
    assert ttft_unattributed_ms.read(ctx, {}) == pytest.approx(26.0)
    # a direct deployment: no master's share to take off
    ctx = ctx_of({}, {"total": (3, 210.0)}, records)
    assert ttft_unattributed_ms.read(ctx, {}) == pytest.approx(30.0)
    # a program without `total` (the parent), or no request in the window
    assert ttft_unattributed_ms.read(ctx_of({}, {}, records), {}) is None
    assert ttft_unattributed_ms.read(
        ctx_of({}, {"total": (3, 210.0)}, records[3:]), {}) is None


def test_the_engine_threads_share_is_cpu_over_wall_of_the_listed_phases():
    def scrape(wall, cpu):
        out = {}
        for fam, books in (("xllm_worker_phase_seconds_total", wall),
                           ("xllm_worker_phase_cpu_seconds_total", cpu)):
            for phase, v in books.items():
                out[series(fam, model="m", phase=phase)] = v
        return out
    info = spec.layer_metric_file("engine_thread_own_share.docqa")
    ctx = {"counters_open": scrape(
               {"sched": 1.0, "decode.pack": 2.0, "decode.device_wait": 50.0},
               {"sched": 1.0, "decode.pack": 1.0}),
           "counters_close": scrape(
               {"sched": 2.0, "decode.pack": 6.0, "prefill.dispatch": 1.0,
                "decode.tail_dispatch": 2.0, "decode.device_wait": 90.0,
                "decode.upload": 3.0, "kv_restore": 7.0},
               {"sched": 1.5, "decode.pack": 2.0, "prefill.dispatch": 0.5,
                "decode.tail_dispatch": 1.0, "decode.upload": 3.0,
                "kv_restore": 7.0})}
    # wall 1 + 4 + 1 + 2 = 8, cpu 0.5 + 1 + 0.5 + 1 = 3: the waits, the
    # upload nested in the pack and a phase not listed stay out
    assert phase_cpu_share.read(ctx, info) == pytest.approx(37.5)
    no_cpu = {k: {s: v for s, v in c.items() if "_cpu_" not in s}
              for k, c in ctx.items()}
    assert phase_cpu_share.read(no_cpu, info) is None       # the parent


def test_the_listed_phases_are_phases_the_engine_has():
    """Every exact name, and at least one phase under every ``*.suffix``,
    is a literal of an ``Engine._phase`` site; no listed phase waits on
    the device (``_read_host`` books those, without CPU time)."""
    import ast
    import inspect
    sites = {n.args[0].value for n in ast.walk(ast.parse(
        inspect.getsource(Engine))) if isinstance(n, ast.Call)
        and getattr(n.func, "attr", "") == "_phase"
        and isinstance(n.args[0], ast.Constant)}
    info = spec.layer_metric_file("engine_thread_own_share.docqa")
    for p in info["phases"]:
        assert any(phase_cpu_share._listed(s, [p]) for s in sites), p
        assert not p.endswith(("device_wait", "host_copy"))
    assert set(info["nested_left_out"]) <= sites
    assert not any(phase_cpu_share._listed(s, info["phases"])
                   for s in info["nested_left_out"])


@pytest.mark.parametrize("name", ADDED)
def test_an_added_metric_lists_the_cells_it_was_entered_for_first(name, root):
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    info = spec.layer_metric_file(name, root)
    assert entry["workloads"][:len(ENTERED_FOR)] == ENTERED_FOR
    assert set(entry["workloads"]) <= set(cells)
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    # read from /metrics, so a `program_counter` by nature; PR 40 declared
    # it `program_span` when a rehearsal's line was held to every
    # `program_counter` name in the file; that pin is gone (PR 42), and a
    # `benchmark` PR may tell the source truthfully in both places
    assert entry["source"] == info["source"]
    assert entry["source"] in ("program_span", "program_counter")
    share = name == "engine_thread_own_share.docqa"
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        ("%", "higher", "out_tok_s") if share
        else ("ms", "lower", "ttft_p50_ms"))
    assert hasattr(spec.load_reader(info["reader"], root), "read")
    # the ten stand together, in this order, wherever that run of ten lies
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(ADDED[0])
    assert names[at:at + len(ADDED)] == ADDED


@pytest.mark.parametrize("name,stage", sorted(STAGE_METRICS.items()))
def test_a_stage_metric_names_a_stage_of_the_programs_table(name, stage):
    info = spec.layer_metric_file(name)
    assert info["reader"] == "first_token_stage_ms"
    assert info["stage"] == stage and stage in FIRST_TOKEN_STAGES


def test_the_stage_metrics_cover_the_table_but_its_total():
    # `total` is read by ttft_unattributed_ms alone: it is the sum of the
    # worker's seven, which are each entered
    assert set(STAGE_METRICS.values()) == set(FIRST_TOKEN_STAGES) - {"total"}


def test_the_readers_read_a_live_workers_two_scrapes():
    """What ``run.py`` hands a reader (``cluster.scrape`` at the window's
    two ends) from a worker that served two streamed requests between."""
    import time

    from chipbench import cluster
    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
    from xllm_service_tpu.service.httpd import http_stream, iter_sse_events
    w = Worker(WorkerOptions(model="tiny"), InMemoryStore()).start()
    try:
        def ask(srid):
            t = time.monotonic()
            frames = list(iter_sse_events(http_stream(
                "POST", w.name, "/v1/completions",
                {"model": "tiny", "prompt": "where does the time go",
                 "max_tokens": 3, "temperature": 0.0, "stream": True,
                 "ignore_eos": True, "service_request_id": srid},
                timeout=120.0, headers={"x-xllm-front-ms": "2.500"})))
            assert frames[-1] == "[DONE]"
            return {"ok": True, "due": t, "frames": [[time.monotonic(), 1]]}

        ask("warm")                             # compiles here
        t_open, c_open = time.monotonic(), cluster.scrape(w.name)
        records = [ask("a"), ask("b")]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:      # the fold follows the frame
            c_close = cluster.scrape(w.name)
            ctx = {"counters_open": c_open, "counters_close": c_close,
                   "records": records, "open_t": t_open,
                   "close_t": time.monotonic()}
            if first_token_stage_ms.stage_mean_ms(ctx, "total") is not None:
                break
            time.sleep(0.05)
        got = {name: spec.load_reader(
            spec.layer_metric_file(name)["reader"]).read(
                ctx, spec.layer_metric_file(name)) for name in ADDED}
        assert all(v is not None for v in got.values()), got
        assert got["ttft_master_in_ms.docqa"] == pytest.approx(2.5)
        seven = sum(v for k, v in got.items() if k in STAGE_METRICS
                    and k != "ttft_master_in_ms.docqa")
        total = first_token_stage_ms.stage_mean_ms(ctx, "total")
        assert seven == pytest.approx(total, abs=1e-6)
        # by construction: the generator's mean is the three added up
        ttft = [1000.0 * (r["frames"][0][0] - r["due"]) for r in records]
        assert got["ttft_unattributed_ms.docqa"] + 2.5 + total \
            == pytest.approx(sum(ttft) / 2, abs=1e-6)
        assert 0 < got["engine_thread_own_share.docqa"] <= 150.0
    finally:
        w.stop()
