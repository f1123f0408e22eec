"""The trace reduction: busy union, idle share, idle gaps by host span,
per-program and per-kernel time, on hand-made events and on the small
recorded trace kept with the benchmark."""

import os

import pytest

from chipbench import spec, trace

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV, **kw):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur, **kw}


HAND = [
    ev("XLA Modules", "jit__decode_step(1)", 0, 100),
    ev("XLA Ops", "fusion.1", 0, 40),
    ev("XLA Ops", "attn_kernel", 40, 30),
    ev("XLA Ops", "fusion.2", 60, 40),          # overlaps the kernel
    ev("XLA Modules", "jit__prefill_step(2)", 200, 300),
    ev("XLA Ops", "fusion.9", 200, 300),
    ev("XLA Modules", "jit__decode_step(1)", 600, 120),
    ev("XLA Ops", "attn_kernel", 600, 50),
    ev("XLA Ops", "fusion.2", 650, 70),
    ev("python", "engine.step", 90, 120, plane="/host:CPU"),
    ev("python", "chipbench.traced_window", 0, 720, plane="/host:CPU"),
    ev("python", "sample.readback", 500, 100, plane="/host:CPU"),
]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0
    assert trace.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]
    assert trace.gaps_ns([], 3, 9) == [(3, 9)]


def test_busy_and_idle_share():
    b = trace.busy(HAND)
    assert b["window_s"] == pytest.approx(720e-9)
    assert b["busy_s"] == pytest.approx((100 + 300 + 120) * 1e-9)
    assert trace.idle_share(HAND) == pytest.approx(1 - 520 / 720)
    with pytest.raises(ValueError):
        trace.busy([e for e in HAND if e["plane"] != DEV])


def test_program_and_kernel_time():
    dec = trace.modules_containing(HAND, "attn_kernel")
    assert [e["dur"] for e in dec] == [100, 120]
    assert trace.median_ms(dec) == pytest.approx(110e-6)
    assert trace.median_ms([]) is None
    k = trace.op_events(HAND, "attn_kernel", within=dec)
    assert sum(e["dur"] for e in k) == 80
    pre = trace.modules_containing(HAND, r"fusion\.9")
    assert [e["dur"] for e in pre] == [300]
    assert trace.op_events(HAND, "attn_kernel", within=pre) == []
    assert trace.top_ops(HAND, 2) == [["fusion.9", 300e-9],
                                      ["fusion.2", 110e-9]]


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    gaps = dict(trace.top_idle_gaps(HAND))
    # 100..200 idle: engine.step covers it; 500..600: sample.readback
    assert gaps["engine.step"] == pytest.approx(100e-9)
    assert gaps["sample.readback"] == pytest.approx(100e-9)


def test_recorded_trace_reduces_to_its_known_numbers():
    """A slice of a real v5e trace of this benchmark (events as
    ``load_events`` gives them), with the numbers worked out once by
    hand from the file and pinned here."""
    path = os.path.join(spec.ROOT, "chipbench", "testdata",
                        "trace_small.json.gz")
    events = trace.read_events(path)
    want = spec.load_json(os.path.join(spec.ROOT, "chipbench", "testdata",
                                       "trace_small.expect.json"))
    b = trace.busy(events)
    assert b["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert b["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < b["busy_s"] <= b["window_s"]
    dec = trace.modules_containing(events, want["decode_pattern"])
    assert len(dec) == want["decode_steps"]
    pre = trace.modules_containing(events, want["prefill_pattern"])
    assert len(pre) == want["prefill_steps"] and not (
        {m["start"] for m in pre} & {m["start"] for m in dec})
    assert trace.median_ms(dec) == pytest.approx(want["decode_step_ms"],
                                                 rel=1e-9)
    k = trace.op_events(events, want["kernel_pattern"], within=dec)
    assert sum(e["dur"] for e in k) == want["kernel_ns"]
    assert trace.top_ops(events, 1)[0][0] == want["top_op"]
