"""The single-step decode launches step N+1 before it reads step N
(PR 37, ``Engine._launch_ahead``), and an iteration that could not
dispatches its successor from host truth at its tail (PR 39,
``Engine._tail_eligible``): the streams are a sequential engine's,
whatever falls on a step that was on the device before its iteration
began; the counters say how often each engaged; nothing is launched
while a request waits; and either launch is the decode program's one
call signature."""

import collections
import dataclasses
import functools
import json

import numpy as np
import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import FinishReason, SamplingParams

# The latent cell's configuration at its rehearsal widths: ONE latent
# pool and the dropless expert layer.
from tests.test_latent_pool import tiny_model


def _tiny(window=None, **kw):
    return dataclasses.replace(ModelConfig.tiny(vocab_size=64, **kw),
                               dtype="float32", sliding_window=window)


def _engine(model=None, sequential=False, ahead=True, **kw):
    d = dict(page_size=16, num_pages=32, max_model_len=128,
             max_batch_size=4, max_prefill_tokens=64,
             prefill_buckets=(8, 16, 32))
    d.update(kw)
    eng = Engine(model or _tiny(), EngineConfig(**d), seed=0)
    if sequential or not ahead:
        # The same engine with the predicate forced false ...
        eng._ahead_eligible = lambda *a: False
    if sequential:
        # ... and the control: with both forced false.
        eng._tail_eligible = lambda *a: False
    return eng


def _req(rid, prompt, n, sampling="greedy", eos=None, offline=False,
         **sp):
    sp.update(max_tokens=n, ignore_eos=eos is None)
    if sampling == "greedy":
        sp["temperature"] = 0.0
    else:
        sp.update(temperature=1.0, seed=1000 + len(rid) * 7 + prompt[0])
    return EngineRequest(
        request_id=rid, token_ids=list(prompt), offline=offline,
        sampling=SamplingParams(**sp),
        eos_token_ids=() if eos is None else (eos,))


_COUNTS = {"launch": "decode.ahead_dispatch", "hit": "decode.ahead_hit",
           "discard": "decode.ahead_discard",
           "dropped": "decode.ahead_dropped_rows",
           "tail": "decode.tail_dispatch", "tail_hit": "decode.tail_hit",
           "tail_discard": "decode.tail_discard",
           "upload": "decode.upload", "resident": "decode.resident_hit"}


def _drive(eng, feed, cancel=(), drain=()):
    """Feed ``{step: [requests]}``, cancel ``{step: rid}`` and, before
    the steps of ``drain``, throw away whatever step is on the device
    ahead (``drain_pipeline``, as an import or a sleep would); returns
    ``({rid: (tokens, logprobs, reason, top logprobs)}, [a record per
    step])``. A record holds the step's counter deltas (a drain's
    discard among them), its kind, who finished, whose table grew, who
    was trimmed, how many were preempted, whether a request waited when
    it began and when it ended, and the members of the step it left
    pending (None: nothing)."""
    cancel = dict(cancel)
    toks, lps, tops, reasons, recs = {}, {}, {}, {}, []
    pc, step = eng.phase_counts, 0
    while eng.has_work() or step < max(feed):
        step += 1
        for r in feed.get(step, ()):
            eng.add_request(r)
        if step in cancel:
            eng.cancel(cancel[step])
        was = {k: pc[v] for k, v in _COUNTS.items()}
        pre = eng.num_preemptions
        pages = {s.req.request_id: len(s.pages) for s in eng.running}
        trim = {s.req.request_id: s.num_trimmed for s in eng.running}
        rec = {"step": step, "waiting": bool(eng.waiting),
               "pending": eng._pending is not None, "fin": {}}
        if step in drain:
            eng.drain_pipeline()
        for out in eng.step():
            toks.setdefault(out.request_id, []).extend(out.new_token_ids)
            lps.setdefault(out.request_id, []).extend(out.logprobs)
            tops.setdefault(out.request_id, []).extend(
                out.top_logprobs or ())
            if out.finished:
                reasons[out.request_id] = rec["fin"][out.request_id] = \
                    out.finish_reason
        rec.update({k: pc[v] - was[k] for k, v in _COUNTS.items()})
        rec.update(
            kind=eng.last_step_kind, preempted=eng.num_preemptions - pre,
            grew=[s.req.request_id for s in eng.running
                  if len(s.pages) > pages.get(s.req.request_id, 1 << 30)],
            trimmed=[s.req.request_id for s in eng.running
                     if s.num_trimmed > trim.get(s.req.request_id, 1 << 30)],
            left=eng._pending and [m[0] for m in eng._pending["members"]],
            waits=bool(eng.waiting))
        recs.append(rec)
        assert step < 400, "engine did not drain"
    return {r: (toks[r], lps[r], reasons.get(r), tops[r])
            for r in toks}, recs


# ---------------------------------------------------------------------------
# The same streams, whatever falls on a step launched ahead
# ---------------------------------------------------------------------------
def _event_schedule(event, sampling, eos=None):
    """(engine options, feed, cancels, drains) of the schedule that makes
    ``event`` fall on a step that was launched ahead."""
    a = _req("a", range(1, 7), 40, sampling)
    b = _req("b", range(2, 9), 40, sampling)
    opts, cancel, drain = {}, {}, ()
    feed = {1: [a, b]}
    if event == "admit":
        feed[6] = [_req("late", range(5, 12), 12, sampling)]
    elif event == "max_tokens":
        feed[1] = [a, _req("b", range(2, 9), 5, sampling)]
    elif event == "eos":
        feed[1] = [a, _req("b", range(2, 9), 40, sampling, eos=eos)]
    elif event == "cancel":
        cancel[6] = "b"
    elif event == "preempt":
        # 10 usable pages of 4 tokens for three rows that want 21
        opts = dict(page_size=4, num_pages=11, max_model_len=64)
        feed = {1: [_req("a", range(1, 7), 22, sampling),
                    _req("off", range(9, 14), 20, sampling, offline=True)],
                3: [_req("c", range(3, 11), 14, sampling)]}
    elif event == "page_growth":
        opts = dict(page_size=8)
    elif event == "swa_trim":
        opts = dict(page_size=4, num_pages=24, model=_tiny(window=8))
    elif event == "top_logprobs":
        # a asks for the alternatives, b does not: the step on the
        # device ahead carries them for both, and after a discard (5, 9)
        # its replacement does
        opts = dict(num_top_logprobs=2)
        feed[1] = [_req("a", range(1, 7), 40, sampling, logprobs=True,
                        top_logprobs=2), b]
        drain = (5, 9)
    elif event == "penalties":
        # the histogram rides from step to step on the device; a prefill
        # (6) and a discard (4, 12) rebuild it from the host's tokens
        feed = {1: [_req("a", range(1, 7), 40, sampling,
                         presence_penalty=0.8, frequency_penalty=0.4), b],
                6: [_req("late", range(5, 12), 12, sampling,
                         presence_penalty=0.5)]}
        drain = (4, 12)
    elif event == "model_len":
        # both rows decode up to the 32 positions there are
        opts = dict(max_model_len=32)
    return opts, feed, cancel, drain


_EVENTS = ["admit", "max_tokens", "eos", "cancel", "preempt",
           "page_growth", "swa_trim", "top_logprobs", "penalties",
           "model_len"]


@functools.lru_cache(maxsize=None)
def _eos_of(sampling):
    """The token the control's stream of b reaches fifth or later, first."""
    opts, feed, cancel, _ = _event_schedule("steady", sampling)
    st = _drive(_engine(sequential=True, **opts), feed, cancel)[0]["b"][0]
    return next(t for i, t in enumerate(st) if i >= 4 and t not in st[:i])


@functools.lru_cache(maxsize=None)
def _event_run(event, sampling, mode):
    """``(streams, records, counts)`` of one engine: ``both`` (as built),
    ``tail`` (nothing is launched ahead, so every iteration that may ends
    with its successor dispatched and ``event`` falls on such a step) or
    ``sequential`` (the control: neither). ``counts``: the engine's
    ``phase_counts`` and, as ``tail_preempted``, the preemptions made
    inside a tail dispatch (the engine itself is not kept: dozens of live
    ones, each with its executables, crash the CPU backend's loader)."""
    eos = _eos_of(sampling) if event == "eos" else None
    opts, feed, cancel, drain = _event_schedule(event, sampling, eos)
    eng = _engine(sequential=mode == "sequential", ahead=mode == "both",
                  **opts)
    real = eng._dispatch_decode

    def dispatch(at_tail=False):
        pre = eng.num_preemptions
        step = real(at_tail=at_tail)
        eng.phase_counts["tail_preempted"] += \
            at_tail * (eng.num_preemptions - pre)
        return step
    eng._dispatch_decode = dispatch
    return _drive(eng, feed, cancel, drain) + (collections.Counter(
        eng.phase_counts),)


def _a_alone_got_its_alternatives(got):
    """``top_logprobs``: every token of a's came with its two
    alternatives, off a step that was on the device ahead as off the one
    that replaced a discarded one; b, who asked for none, got none."""
    toks, _, _, tops = got["a"]
    assert len(tops) == len(toks) == 40 and got["b"][3] == []
    assert all(len(t) == 2 for t in tops)


def _ends_at_the_last_position(got, recs):
    """``model_len``: each row fills the 32 positions and ends by
    length, and nothing is put on the device behind the step that makes
    a row's last token (known a step ahead, as ``max_tokens`` is)."""
    for rid, prompt in (("a", 6), ("b", 7)):
        toks, _, reason, _ = got[rid]
        assert prompt + len(toks) == 32 and reason == FinishReason.LENGTH
        last = next(r for r in recs if rid in r["fin"])
        assert not last["launch"] and not last["tail"]
        assert last["left"] is None


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("event", _EVENTS)
def test_streams_are_the_sequential_engines(event, sampling):
    """Token ids, logprobs and finish reasons are those of the same
    engine with the predicate forced false, with ``event`` falling on a
    step that was launched ahead (greedy and seeded sampling: the key
    chain is the program's own, and a discard puts the key back)."""
    got, recs, pc = _event_run(event, sampling, "both")
    want, control, _ = _event_run(event, sampling, "sequential")
    assert got == want and not pc["tail_preempted"]
    assert not any(r["launch"] or r["hit"] or r["tail"] for r in control)
    hits = [r for r in recs if r["hit"]]
    assert len(hits) >= 4
    by_step = {r["step"]: r for r in recs}
    if event == "admit":
        r = by_step[6]      # the step in flight is taken, then the prefill
        assert r["hit"] and r["kind"] == "mixed" and not r["launch"]
        assert len(got["late"][0]) == 12
        # ... behind which the next step goes out with the new row
        assert r["tail"] and "late" in r["left"] and by_step[7]["tail_hit"]
    elif event == "max_tokens":
        # known a step ahead: nothing is launched behind the step that
        # ends b, and the next one is packed without it
        r = next(r for r in hits if r["fin"].get("b") == FinishReason.LENGTH)
        nxt = by_step[r["step"] + 1]
        assert not r["launch"] and not nxt["hit"] and nxt["launch"]
        assert not r["tail"] and r["left"] is None and not nxt["tail_hit"]
        assert not any(r["dropped"] or r["discard"] for r in recs)
    elif event == "eos":
        r = next(r for r in hits if r["fin"].get("b") == FinishReason.STOP)
        # ... and the step launched behind it ran b's row for nothing
        nxt = by_step[r["step"] + 1]
        assert r["launch"] and nxt["hit"] and nxt["dropped"] == 1
        assert not nxt["launch"] and not any(r["discard"] for r in recs)
    elif event == "cancel":
        r = by_step[6]
        assert r["pending"] and r["hit"] and r["dropped"] == 1
        assert got["b"][2] == FinishReason.CANCELLED
    elif event == "preempt":
        assert any(r["preempted"] for r in hits)
        assert all(v[2] == FinishReason.LENGTH for v in got.values())
    elif event == "page_growth":
        grown = [r for r in hits if r["grew"]]
        # the step after a grown table is packed and uploaded again: by
        # the iteration that found no page to launch ahead onto, at its
        # tail, where the page is grown
        assert grown and not any(by_step[r["step"] + 1]["hit"]
                                 for r in grown)
        assert all(r["tail"] and r["upload"] and not r["launch"]
                   and by_step[r["step"] + 1]["tail_hit"] for r in grown)
    elif event == "swa_trim":
        assert any(r["trimmed"] for r in hits)
    elif event == "top_logprobs":
        _a_alone_got_its_alternatives(got)
        assert [by_step[s]["discard"] for s in (5, 9)] == [1, 1]
    elif event == "penalties":
        # a discarded step had counted a token that its replacement
        # makes again: the histogram is the host's tokens' once more
        # when the replacement goes out, and the next launch follows it
        # (4: a step launched ahead; 12: one dispatched at a tail, behind
        # a page grown)
        assert [(by_step[s]["discard"], by_step[s]["tail_discard"],
                 by_step[s]["launch"]) for s in (4, 12)] == \
            [(1, 0, 1), (0, 1, 1)]
        eng = _engine()
        eng.add_request(_req("a", range(1, 7), 40, sampling,
                             presence_penalty=0.8))
        eng.step(), eng.step()
        assert eng._pending is not None and eng._counts is not None
        eng.drain_pipeline()
        # ... and nothing is launched onto a histogram that is not there
        assert eng._counts is None and not eng._ahead_eligible()
        eng.step()
        assert eng._pending is not None and eng._counts is not None
    else:
        _ends_at_the_last_position(got, recs)
        assert not any(r["discard"] or r["dropped"] for r in recs)


# ---------------------------------------------------------------------------
# The tail dispatch alone: the same streams with it forced off
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("event", _EVENTS)
def test_streams_are_those_of_the_tail_dispatch_forced_off(event, sampling):
    """Token ids, logprobs and finish reasons are those of the same
    engine with ``_tail_eligible`` forced false: the step dispatched at
    the tail is the step the next iteration would have packed, from the
    same host truth, with the same key."""
    got, recs, pc = _event_run(event, sampling, "tail")
    want, control, _ = _event_run(event, sampling, "sequential")
    assert got == want
    assert not any(r["tail"] or r["tail_hit"] for r in control)
    assert not any(r["launch"] or r["hit"] for r in recs)
    hits = [r for r in recs if r["tail_hit"]]
    assert len(hits) >= 8
    by_step = {r["step"]: r for r in recs}
    # taken by the very next iteration, or (a cancel took the last row,
    # or rows that share an expert) thrown away by it
    for r in recs:
        if r["tail"]:
            nxt = by_step[r["step"] + 1]
            assert r["left"] and nxt["pending"]
            assert nxt["tail_hit"] + nxt["tail_discard"] == 1
    # never after a finish, never while anything waits
    assert not any(r["tail"] for r in recs if r["fin"] or r["waits"])
    # discarded by a drain alone, and then it had counted its block
    drains = _event_schedule(event, sampling)[3]
    assert [r["step"] for r in recs if r["tail_discard"]] == list(drains)
    assert pc["decode.upload"] + pc["decode.resident_hit"] == \
        pc["decode.tail_hit"] + pc["decode.dispatch"] \
        + pc["decode.tail_discard"]
    if event == "admit":
        r = by_step[6]      # the step in flight is taken, then the prefill
        assert r["tail_hit"] and r["kind"] == "mixed"
        # ... and the tail dispatch packs the new row
        assert r["tail"] and r["upload"] and "late" in r["left"]
    elif event == "max_tokens":
        r = next(r for r in recs if r["fin"].get("b") == FinishReason.LENGTH)
        assert r["left"] is None and not by_step[r["step"] + 1]["pending"]
    elif event == "eos":
        r = next(r for r in recs if r["fin"].get("b") == FinishReason.STOP)
        assert r["left"] is None and not by_step[r["step"] + 1]["pending"]
    elif event == "cancel":
        # it lands between the tail dispatch and the iteration that
        # takes it: b's row ran for nothing, a's stands
        r = by_step[6]
        assert by_step[5]["tail"] and "b" in by_step[5]["left"]
        assert r["pending"] and r["tail_hit"] and r["dropped"] == 1
        assert got["b"][2] == FinishReason.CANCELLED
    elif event == "preempt":
        # where growing a table needs a victim the dispatch is left to
        # the head of the next iteration
        assert sum(r["preempted"] for r in recs) >= 1
        assert pc["tail_preempted"] == 0
        assert all(v[2] == FinishReason.LENGTH for v in got.values())
    elif event == "page_growth":
        grown = [r for r in recs if r["grew"] and r["kind"] == "decode"]
        assert grown and all(r["tail"] and r["upload"] for r in grown)
        steady = [r for r in recs if r["tail"] and not r["grew"]
                  and r["kind"] == "decode"]
        assert steady and not any(r["upload"] for r in steady)
    elif event == "swa_trim":
        assert any(r["trimmed"] for r in hits)
    elif event == "top_logprobs":
        _a_alone_got_its_alternatives(got)
    elif event == "model_len":
        _ends_at_the_last_position(got, recs)


def test_what_step_leaves_pending():
    """``step()`` leaves the next decode on the device after a page
    grown and after a prefill section; nothing after a finish or while
    anything waits."""
    def left(eng, feed, cancel=()):
        return [r["left"] for r in _drive(eng, feed, cancel)[1]]
    a, b = _req("a", range(1, 7), 12), _req("b", range(2, 9), 3)
    # a prefill section (1), a page grown for position 8 (2), b's last
    # token (3), a finish behind it; 4 packs at its head
    assert left(_engine(ahead=False, page_size=8), {1: [a, b]})[:4] == \
        [["a", "b"], ["a", "b"], None, ["a"]]
    # a chunked prompt keeps waiting between its windows
    eng = _engine(ahead=False, max_prefill_tokens=16,
                  prefill_buckets=(8, 16))
    recs = _drive(eng, {1: [_req("a", range(1, 7), 30)],
                        4: [_req("long", range(1, 41), 6)]})[1]
    windows = [r for r in recs if r["kind"] == "mixed"]
    assert len(windows) >= 3
    assert [r["left"] for r in windows[:-1]] == [None] * (len(windows) - 1)
    assert windows[-1]["left"] == ["a", "long"]


def test_a_discarded_tail_dispatch_is_run_again_from_the_same_key():
    """A drain between the tail dispatch and the iteration that would
    take it (import, export, sleep, fault_reset, warm-up) discards it:
    the key goes back, the page it grew stays with its row, and the
    block it was given is uploaded again."""
    def run(drain):
        eng = _engine(ahead=False, page_size=8)
        eng.add_request(_req("a", range(1, 7), 20, "seeded"))
        eng.add_request(_req("b", range(2, 9), 20, "seeded"))
        toks, step = {}, 0
        while eng.has_work():
            step += 1
            if drain and step % 3 == 0:
                key, pages = eng._pending["key_before"], [
                    list(s.pages) for s in eng.running]
                eng.drain_pipeline()
                assert eng._rng_key is key and eng._decode_carry is None
                assert [list(s.pages) for s in eng.running] == pages
            for o in eng.step():
                toks.setdefault(o.request_id, []).extend(o.new_token_ids)
        return toks, eng.phase_counts
    (plain, pc0), (drained, pc) = run(False), run(True)
    assert plain == drained
    assert pc0["decode.tail_discard"] == 0 and pc["decode.tail_discard"] >= 4
    # a discarded dispatch had counted its block once already
    assert pc["decode.upload"] + pc["decode.resident_hit"] == \
        pc["decode.tail_hit"] + pc["decode.dispatch"] \
        + pc["decode.tail_discard"]


def test_an_uploaded_block_is_the_steps_own_at_the_full_table_width(
        monkeypatch):
    """At the full table width the block a step uploads is the whole
    slot array, and the CPU backend's upload aliases host memory where
    its alignment lets it: a step dispatched at a tail would read what
    the next iteration's ``_fill_slots`` writes (zero, then the rows
    again) while it runs. The step is given a copy of its own."""
    import jax
    eng = _engine(ahead=False, max_model_len=32)     # two pages a row
    uploaded = []
    real = jax.device_put

    def put(x, *a, **kw):
        if isinstance(x, np.ndarray) and x.shape == eng._slot_packed.shape:
            uploaded.append(np.shares_memory(x, eng._slot_packed))
        return real(x, *a, **kw)
    monkeypatch.setattr(jax, "device_put", put)
    eng.add_request(_req("a", range(1, 18), 6))      # 17 tokens: 2 pages
    eng.step()
    assert eng._pending is not None                  # dispatched at the tail
    assert uploaded == [False]


# ---------------------------------------------------------------------------
# The counters, by hand
# ---------------------------------------------------------------------------
def test_the_counters_equal_a_hand_count():
    """Two rows on pages of 16: a (6 prompt tokens, 9 to make) and b
    (7, 4 to make); b is cancelled before step 7. Step 1 prefills both
    (each has 1 token) and dispatches the first decode at its tail.
    Step 2 takes it and launches step 3 behind it; 3 takes that and
    launches 4; 4 takes it and launches nothing (it makes b's fourth and
    last token: known a step ahead), ends b and, after a finish,
    dispatches nothing at its tail; 5 packs again (a alone) and launches
    6; "late" (7, 30 to make) arrives before 6, which takes its step,
    launches nothing, prefills, and packs both rows at its tail; 7 takes
    that and launches 8; late is cancelled before 8, which takes a's
    row, drops late's, launches nothing (late is still active on the
    device) and, having finished a row, nothing at its tail; 9 packs a
    alone and takes a's ninth token: nothing is launched, no row would
    be left."""
    eng = _engine()
    _, recs = _drive(eng, {1: [_req("a", range(1, 7), 9),
                               _req("b", range(2, 9), 4)],
                           6: [_req("late", range(3, 10), 30)]},
                     cancel={8: "late"})
    assert [r["kind"] for r in recs] == \
        ["prefill"] + ["decode"] * 4 + ["mixed"] + ["decode"] * 3
    assert [r["launch"] for r in recs] == [0, 1, 1, 0, 1, 0, 1, 0, 0]
    assert [r["hit"] for r in recs] == [0, 0, 1, 1, 0, 1, 0, 1, 0]
    assert [r["tail"] for r in recs] == [1, 0, 0, 0, 0, 1, 0, 0, 0]
    assert [r["tail_hit"] for r in recs] == [0, 1, 0, 0, 0, 0, 1, 0, 0]
    assert [r["left"] for r in recs] == [
        ["a", "b"], ["a", "b"], ["a", "b"], None, ["a"], ["a", "late"],
        ["a", "late"], None, None]
    assert [r["dropped"] for r in recs] == [0] * 7 + [1, 0]
    assert not any(r["discard"] or r["tail_discard"] for r in recs)
    pc = eng.phase_counts
    assert pc["decode.ahead_dispatch"] == pc["decode.ahead_hit"] == 4
    assert pc["decode.tail_dispatch"] == pc["decode.tail_hit"] == 2
    assert pc["decode.dispatch"] == 2 and pc["decode.pack"] == 4
    # every pack uploaded (the batch had changed), every launch ahead
    # was handed its block: uploads + resident hits = decode steps
    assert [r["upload"] for r in recs] == [1, 0, 0, 0, 1, 1, 0, 0, 1]
    assert pc["decode.upload"] == 4 and pc["decode.resident_hit"] == 4
    assert eng.overlap_metrics() == {
        "ahead_dispatches": 6, "ahead_hits": 6, "ahead_discards": 0}
    assert eng._pending is None and not eng.has_work()


def test_a_launch_whose_rows_have_all_gone_is_dropped():
    """A cancel takes the only row while a step launched ahead is in
    flight: nothing would take it, so it is discarded at once, with the
    key it was given put back."""
    eng = _engine()
    eng.add_request(_req("a", range(1, 7), 30, "seeded"))
    for _ in range(4):
        eng.step()
    assert eng._pending is not None
    key = eng._pending["key_before"]
    eng.cancel("a")
    outs = eng.step()
    assert [o.finish_reason for o in outs] == [FinishReason.CANCELLED]
    assert eng._pending is None and not eng.has_work()
    assert eng._rng_key is key
    assert eng.phase_counts["decode.ahead_discard"] == 1


def test_drain_pipeline_takes_the_step_launched_ahead():
    """Whatever changes membership outside the loop drains first (import,
    export, sleep, fault_reset, isolate, warm-up): the discarded step is
    run again from the same key, so the streams do not move."""
    def run(drain):
        eng = _engine()
        eng.add_request(_req("a", range(1, 7), 20, "seeded"))
        eng.add_request(_req("b", range(2, 9), 20, "seeded"))
        toks, step = {}, 0
        while eng.has_work():
            step += 1
            if drain and step % 3 == 0:
                eng.drain_pipeline()
            for o in eng.step():
                toks.setdefault(o.request_id, []).extend(o.new_token_ids)
        return toks, eng.phase_counts["decode.ahead_discard"]
    (plain, none), (drained, some) = run(False), run(True)
    assert plain == drained and none == 0 and some >= 4


# ---------------------------------------------------------------------------
# Nothing is launched while a request waits
# ---------------------------------------------------------------------------
def test_no_launch_while_a_request_waits():
    """An arriving request's prefill is dispatched directly after the
    decode it would have followed anyway: the step in flight when it
    arrived is taken, nothing is launched behind it, and the prefill is
    that very iteration's. A chunked prompt keeps waiting between its
    windows: no launch until its last."""
    eng = _engine(max_prefill_tokens=16, prefill_buckets=(8, 16))
    feed = {1: [_req("a", range(1, 7), 30)],
            5: [_req("long", range(1, 41), 6)]}     # 40 tokens: windows
    _, recs = _drive(eng, feed)
    assert not any(r["launch"] for r in recs if r["waiting"])
    arrival = recs[4]
    assert arrival["pending"] and arrival["hit"] and \
        arrival["kind"] == "mixed"
    windows = [r for r in recs if r["kind"] == "mixed"]
    assert len(windows) >= 3 and not any(r["launch"] for r in windows)
    assert sum(r["launch"] for r in recs) >= 10
    assert not any(r["discard"] for r in recs)


def test_a_mixed_program_does_not_throw_the_step_in_flight_away():
    """Under the ragged mixed program an arrival finds the decode of its
    iteration already on the device: the iteration takes it and prefills
    behind it (the split sections) where the mixed program would first
    have to discard it."""
    eng = _engine(ragged_attn=True)
    assert eng._jit_ragged is not None
    _, recs = _drive(eng, {1: [_req("a", range(1, 7), 20)],
                           5: [_req("late", range(3, 9), 8)]})
    arrival = recs[4]
    assert arrival["pending"] and arrival["hit"] and \
        arrival["kind"] == "mixed"
    assert not eng.phase_counts["ragged.dispatch"]
    assert not any(r["discard"] for r in recs)


# ---------------------------------------------------------------------------
# One program, one call signature
# ---------------------------------------------------------------------------
def test_one_cache_entry_a_width_after_a_mixed_schedule():
    """A launch ahead gives the decode program exactly what a resident
    hit gives it, a tail dispatch what a miss step gives it: after
    warm-up, a schedule of packs, uploads, resident hits, launches ahead
    and tail dispatches over three table widths adds no entry to the
    program's cache and counts no recompile."""
    eng = _engine(page_size=4, max_model_len=64)
    eng.warmup(prefill_shapes=[(2, 8, 2), (1, 8, 2)],
               decode_widths=[2, 4, 8])
    warm = eng.compile_report()
    assert warm["decode"] == 3
    _, recs = _drive(eng, {1: [_req("a", range(1, 7), 20, "seeded"),
                               _req("b", range(2, 9), 9)],
                           6: [_req("late", range(11, 17), 5, "seeded")]})
    pc = eng.phase_counts
    assert pc["decode.ahead_hit"] >= 5 and pc["decode.upload"] >= 5
    assert pc["decode.tail_hit"] >= 4
    assert pc["decode.resident_hit"] >= pc["decode.ahead_hit"]
    assert eng.compile_report() == warm
    assert not [k for k, v in eng.phase_report().items()
                if k.endswith(".recompile") and v]


# ---------------------------------------------------------------------------
# Every family: one rule, by what the engine can observe
# ---------------------------------------------------------------------------
def _family_streams(model, sequential, eos=None, ahead=True, **opts):
    eng = _engine(model=model, sequential=sequential, ahead=ahead, **opts)
    got, recs = _drive(eng, {
        1: [_req("a", range(1, 9), 24), _req("b", range(2, 12), 24,
                                             eos=eos)],
        7: [_req("late", range(5, 14), 16)]}, cancel={12: "late"})
    return got, recs, eng


def test_a_latent_pool_under_the_dropless_experts_launches_ahead():
    """The latent cell's family at its rehearsal widths: ONE pool, the
    dropless expert layer. Its rows do not see each other, so a row that
    leaves is dropped and the others stand."""
    opts = dict(max_model_len=256, max_batch_size=4, max_prefill_tokens=256,
                prefill_buckets=(32, 64, 128))
    got, recs, eng = _family_streams(tiny_model(), False, **opts)
    want, _, _ = _family_streams(tiny_model(), True, **opts)
    assert eng.cfg.mla and eng.cfg.is_moe and len(eng.kv) == 1
    assert not eng._rows_interfere
    assert got == want
    assert sum(r["hit"] for r in recs) >= 15
    assert sum(r["dropped"] for r in recs) >= 1
    assert not any(r["discard"] for r in recs)


def test_rows_that_share_an_experts_capacity_are_taken_whole():
    """``_mlp``'s bucketed sparse layer: a row that has left still
    competes for an expert's capacity in a step launched ahead. There a
    known finish forbids the launch, and an EOS discards the step
    whole: the streams are the sequential engine's."""
    model = _tiny(num_experts=4)
    st = _family_streams(model, True)[0]["b"][0]
    eos = next(t for i, t in enumerate(st) if i >= 4 and t not in st[:i])
    got, recs, eng = _family_streams(model, False, eos=eos)
    want, _, _ = _family_streams(model, True, eos=eos)
    assert eng._rows_interfere
    assert got == want and got["b"][2] == FinishReason.STOP
    assert sum(r["hit"] for r in recs) >= 10
    assert not any(r["dropped"] for r in recs)
    stop = next(r for r in recs if r["fin"].get("b") == FinishReason.STOP)
    assert stop["launch"] and stop["discard"] == 1
    # the cancel discards one more; a finish by length is known a step
    # ahead: not launched, so not discarded
    assert sum(r["discard"] for r in recs) == 2


def test_every_family_dispatches_at_the_tail():
    """The same two families with nothing launched ahead: the latent
    pool's rows stand one by one behind a cancel that lands after the
    tail dispatch; rows that share an expert's capacity are discarded
    whole there and packed again."""
    opts = dict(max_model_len=256, max_batch_size=4, max_prefill_tokens=256,
                prefill_buckets=(32, 64, 128))
    for model, opts, discards in ((tiny_model(), opts, 0),
                                  (_tiny(num_experts=4), {}, 1)):
        got, recs, eng = _family_streams(model, False, ahead=False, **opts)
        want, _, _ = _family_streams(model, True, **opts)
        assert eng._rows_interfere == bool(discards)
        assert got == want
        assert sum(r["tail_hit"] for r in recs) >= 15
        cancel = recs[11]
        assert cancel["pending"] and "late" in recs[10]["left"]
        assert (cancel["tail_hit"], cancel["dropped"],
                cancel["tail_discard"]) == (1 - discards, 1 - discards,
                                            discards)
        assert sum(r["tail_discard"] for r in recs) == discards


# ---------------------------------------------------------------------------
# What a scrape shows
# ---------------------------------------------------------------------------
def test_worker_exports_the_launches_beside_its_steps():
    from http.client import HTTPConnection

    from xllm_service_tpu.obs import steptrace, validate_exposition
    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
    assert "decode.ahead_dispatch" in steptrace.STEP_PHASES
    assert "xllm.step.decode.ahead_dispatch" in steptrace.SPAN_NAMES
    assert "decode.tail_dispatch" in steptrace.STEP_PHASES
    assert "xllm.step.decode.tail_dispatch" in steptrace.SPAN_NAMES
    w = Worker(WorkerOptions(model="tiny"), InMemoryStore()).start()

    def http(method, path, body=None):
        host, port = w.name.rsplit(":", 1)
        conn = HTTPConnection(host, int(port), timeout=120)
        try:
            conn.request(method, path, body=body and json.dumps(body),
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, r.read().decode()
        finally:
            conn.close()
    try:
        assert http("POST", "/v1/completions", {
            "model": "tiny", "prompt": "count my launches",
            "max_tokens": 24, "temperature": 0.0,
            "ignore_eos": True})[0] == 200
        text = http("GET", "/metrics")[1]
    finally:
        w.stop()
    validate_exposition(text)

    def total(name, where=""):
        return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                   if ln.startswith(name + "{") and where in ln)
    pc = w.primary_runtime().engine.phase_counts
    assert pc["decode.ahead_hit"] >= 10 and pc["decode.tail_hit"] >= 1
    for result, phase in (("launched", "decode.ahead_dispatch"),
                          ("hit", "decode.ahead_hit"),
                          ("discarded", "decode.ahead_discard"),
                          ("tail_launched", "decode.tail_dispatch"),
                          ("tail_hit", "decode.tail_hit"),
                          ("tail_discarded", "decode.tail_discard")):
        assert total("xllm_worker_decode_ahead_total",
                     f'result="{result}"') == pc[phase]
    assert total("xllm_worker_decode_ahead_dropped_rows_total") == \
        pc["decode.ahead_dropped_rows"]
    for phase in ("decode.ahead_dispatch", "decode.tail_dispatch"):
        assert total("xllm_worker_phase_calls_total",
                     f'phase="{phase}"') == pc[phase]
