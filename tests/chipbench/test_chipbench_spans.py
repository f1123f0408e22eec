"""The program's spans in a trace: idle time by the innermost span that
covers it, the per-step reductions of the span reader, the gap between
step programs (on hand-made events and on the recorded v5e slice, its
number worked out once with plain loops), and the counter reader.
A program without spans or counters (the parent of the PR that added
them) gives every reader nothing to read, and none raises."""

import os
import types

import pytest

from chipbench import spans, spec, trace
from chipbench.readers import (decode_batch_occupancy, host_span_ms,
                               launch_gap_ms)

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(line, name, start, dur, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur}


def sp(name, start, dur):
    return ev("python", name, start, dur, plane=HOST)


# Two engine iterations. Device: a decode program 0..100, idle 100..200,
# a decode program 200..300, idle 300..340, a key split 340..350, idle
# 350..400, a prefill program 400..600.
DEC, PRE = "%while.1 = (s32[], bf16[8,1,64]", "%while.2 = (s32[], bf16[1,256,64]"
HAND = [
    ev("XLA Modules", "jit__unknown(1)", 0, 100),
    ev("XLA Ops", DEC, 0, 100),
    ev("XLA Modules", "jit__unknown(1)", 200, 100),
    ev("XLA Ops", DEC, 200, 100),
    ev("XLA Modules", "jit__threefry_split(3)", 340, 10),
    ev("XLA Ops", "fusion.7", 340, 10),
    ev("XLA Modules", "jit__unknown(2)", 400, 200),
    ev("XLA Ops", PRE, 400, 200),
    # host, engine-loop thread: step 1 covers 0..130, then emit, flush
    sp("xllm.loop.step", 0, 130),
    sp("xllm.step.decode.pack", 0, 5),
    sp("xllm.step.decode.device_wait", 10, 95),      # to 105
    sp("xllm.step.decode.post", 105, 25),            # to 130
    sp("xllm.kv.register_pages", 110, 12),           # inside post
    sp("xllm.loop.emit", 130, 20),                   # to 150
    sp("xllm.loop.obs_flush", 150, 30),              # to 180
    sp("xllm.loop.lock_wait", 185, 5),               # 180..185 uncovered
    sp("xllm.loop.step", 190, 420),                  # to 610
    sp("xllm.step.decode.pack", 190, 8),
    sp("xllm.step.decode.device_wait", 200, 100),    # to 300
    sp("xllm.step.decode.post", 300, 20),            # to 320
    sp("xllm.step.sched", 320, 30),                  # to 350
    sp("xllm.kv.match_prefix", 325, 20),             # inside sched
    sp("xllm.step.prefill.pack", 350, 45),           # to 395
    sp("xllm.step.prefill.dispatch", 395, 5),
    sp("xllm.step.prefill.device_wait", 400, 205),
    # a handler's thread: never names a gap of the engine loop's
    sp("xllm.admit", 100, 300),
    sp("xllm.admit.locked", 182, 6),
    sp("chipbench.traced_window", 0, 600),
]


def test_innermost_segments_nest_and_cut():
    segs = spans.innermost_segments(
        [sp("a", 0, 100), sp("b", 10, 20), sp("c", 15, 5),
         sp("d", 90, 30),             # outlasts its parent: cut at 100
         sp("e", 200, 10)])
    assert segs == [(0, 10, "a"), (10, 15, "b"), (15, 20, "c"),
                    (20, 30, "b"), (30, 90, "a"), (90, 100, "d"),
                    (200, 210, "e")]
    assert spans.innermost_segments([]) == []


def test_idle_goes_to_the_innermost_engine_span_by_overlap():
    got = dict(spans.idle_by_span(HAND))
    # idle 100..200: device_wait to 105, post 105..110, register_pages
    # 110..122, post 122..130, emit 130..150, obs_flush 150..180, nothing
    # 180..185, lock_wait 185..190, pack 190..198, step itself 198..200
    want = {"xllm.step.decode.device_wait": 5, "xllm.step.decode.post": 13
            + 20, "xllm.kv.register_pages": 12, "xllm.loop.emit": 20,
            "xllm.loop.obs_flush": 30, spans.NO_SPAN: 5,
            "xllm.loop.lock_wait": 5, "xllm.step.decode.pack": 8,
            "xllm.loop.step": 2,
            # idle 300..340 and 350..400
            "xllm.step.sched": 5 + 0, "xllm.kv.match_prefix": 15,
            "xllm.step.prefill.pack": 45, "xllm.step.prefill.dispatch": 5}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    idle = (600 - 410) * 1e-9
    assert sum(got.values()) == pytest.approx(idle)
    assert sum(got.values()) == pytest.approx(
        trace.idle_share(HAND) * trace.busy(HAND)["window_s"])
    assert not any(k.startswith("xllm.admit") for k in got)
    # largest first, as the breakdown prints it
    secs = [s for _, s in spans.idle_by_span(HAND)]
    assert secs == sorted(secs, reverse=True)


def test_a_trace_without_program_spans_is_all_no_span():
    bare = [e for e in HAND if not e["name"].startswith("xllm.")]
    assert spans.idle_by_span(bare) == [
        [spans.NO_SPAN, pytest.approx(190e-9)]]
    assert spans.idle_by_span([e for e in HAND if e["plane"] == HOST]) == []


def info(name):
    return spec.layer_metric_file(name, spec.ROOT)


@pytest.mark.parametrize("metric,want_ns", [
    # per step: sched + pack children: 5 and 8 + 30 + 45
    ("sched_pack_ms.docqa", (5 + 83) / 2),
    ("emit_ms.docqa", 20),
    ("obs_flush_ms.docqa", 30),
    # (12 + 20) over two steps
    ("kv_index_ms.docqa", 16),
])
def test_span_reader_reduces_as_its_file_says(metric, want_ns):
    got = host_span_ms.read({"trace": {"events": HAND}}, info(metric))
    assert got == pytest.approx(want_ns / 1e6)


NARROW = r"^xllm\.kv\.(match_prefix|register_pages)$"     # before PR 52


@pytest.mark.parametrize("events,want", [("hand-made", 16e-6),
                                         ("recorded", None)])
def test_the_index_metrics_wider_pattern_reads_the_narrower_ones_number(
        events, want):
    """``kv_index_ms.docqa`` took ``state_slots`` into its alternation
    when the six cells' files folded into one (PR 52). Where the program
    writes no ``xllm.kv.state_slots`` span (every model whose state is
    pages alone) the wider pattern reads what the narrower read: on the
    hand-made iterations, and on the recorded v5e slice (a trace of PR
    26, before the program wrote spans: both read nothing)."""
    i = info("kv_index_ms.docqa")
    assert i["span_pattern"] \
        == r"^xllm\.kv\.(match_prefix|register_pages|state_slots)$"
    evs = HAND if events == "hand-made" else trace.read_events(os.path.join(
        spec.ROOT, "chipbench", "testdata", "trace_small.json.gz"))
    assert spans.per_step_ms(evs, NARROW, i["reduce"]) \
        == host_span_ms.read({"trace": {"events": evs}}, i) \
        == (want if want is None else pytest.approx(want))


def test_the_index_metric_reads_a_state_slots_span_where_one_is_written():
    """As ``kv_index_ms.syschat32`` and ``.statedoc64`` did: a slot
    reserved inside the prefill's pack, 6 more over two steps."""
    i = info("kv_index_ms.docqa")
    slots = HAND + [sp("xllm.kv.state_slots", 360, 6)]
    assert host_span_ms.read({"trace": {"events": slots}}, i) \
        == pytest.approx(19e-6)
    assert spans.per_step_ms(slots, NARROW, i["reduce"]) \
        == pytest.approx(16e-6)


def test_per_step_reductions_and_their_empty_cases():
    assert spans.per_step_ms(HAND, r"^xllm\.loop\.emit$",
                             "median_per_span") == pytest.approx(20e-6)
    assert spans.per_step_ms(HAND, r"^xllm\.loop\.nothing$",
                             "median_per_span") is None
    # steps there, spans not: a mean over steps is a true zero
    assert spans.per_step_ms(HAND, r"^xllm\.kv\.nothing$",
                             "mean_per_step") == 0.0
    with pytest.raises(ValueError):
        spans.per_step_ms(HAND, "x", "mode")


SPAN_METRICS = ["launch_gap_ms.docqa", "sched_pack_ms.docqa",
                "emit_ms.docqa", "obs_flush_ms.docqa", "kv_index_ms.docqa"]


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_trace_readers_give_nothing_without_a_trace_or_spans(metric):
    reader = spec.load_reader(info(metric)["reader"])
    assert reader.read({"trace": None}, info(metric)) is None
    if metric != "launch_gap_ms.docqa":
        bare = [e for e in HAND if not e["name"].startswith("xllm.")]
        assert reader.read({"trace": {"events": bare}},
                           info(metric)) is None


def test_launch_gap_on_hand_made_events():
    i = info("launch_gap_ms.docqa")
    # 100..200 idle; 300..400 less the key split's 10
    assert launch_gap_ms.gaps_ns(HAND, i["program_op_patterns"]) == \
        [100, 90]
    assert launch_gap_ms.read({"trace": {"events": HAND}}, i) == \
        pytest.approx(95e-6)
    one = [e for e in HAND if e["start"] < 100]
    assert launch_gap_ms.read({"trace": {"events": one}}, i) is None


def test_launch_gap_on_the_recorded_trace():
    """decode, prefill, three decodes: four gaps, each holding a key
    split and an unstack (~3 us of operations) that count as busy."""
    events = trace.read_events(os.path.join(
        spec.ROOT, "chipbench", "testdata", "trace_small.json.gz"))
    i = info("launch_gap_ms.docqa")
    assert launch_gap_ms.gaps_ns(events, i["program_op_patterns"]) == \
        [10571399, 8048463, 9095451, 8904762]
    assert launch_gap_ms.read({"trace": {"events": events}}, i) == \
        pytest.approx(9.0001065, rel=1e-9)


def test_decode_batch_occupancy_counts_decode_bearing_steps():
    tok, stp = "xllm_worker_step_tokens_total", "xllm_worker_steps_total"

    def c(dec_tok, pre_tok, dec, mixed, pre):
        return {f'{tok}{{model="m",phase="decode"}}': dec_tok,
                f'{tok}{{model="m",phase="prefill"}}': pre_tok,
                f'{stp}{{model="m",phase="decode"}}': dec,
                f'{stp}{{model="m",phase="mixed"}}': mixed,
                f'{stp}{{model="m",phase="prefill"}}': pre}

    cell = types.SimpleNamespace(traffic={"engine": {"max_batch_size": 8}})
    ctx = {"cell": cell, "counters_open": c(100, 900, 20, 5, 7),
           "counters_close": c(700, 5000, 100, 25, 30)}
    # 600 tokens over (80 + 20) decode-bearing steps of 8 rows
    assert decode_batch_occupancy.read(ctx, {}) == pytest.approx(75.0)
    ctx["counters_close"] = ctx["counters_open"]
    assert decode_batch_occupancy.read(ctx, {}) is None


def test_every_new_metric_has_its_file_entry_and_reader(root):
    bench = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    assert bench["trace_in_run"] is True
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS + ["decode_batch_occupancy.docqa"]:
        i, e = spec.layer_metric_file(name, root), entries[name]
        assert (i["layer"], i["unit"], i["source"], i["moves"]) == \
            (e["layer"], e["unit"], e["source"], e["moves"])
        # entered with the Mistral cell; the cells that came later are
        # appended to the list (PR 52: no twin under a name of theirs)
        assert e["workloads"][0] == "mistral7b-v01-docqa"
        assert callable(spec.load_reader(i["reader"], root).read)
