"""Token-budget prefill/decode interleaving (staggered admission).

The deterministic tentpole e2e: time is measured in ENGINE STEPS, not
wall clock, so the pins hold on any CPU. Unloaded, a decode stream
receives tokens every iteration (gap 1); the interleaver keeps that
true under a burst of long prompts (TPOT bounded by construction), and
each prompt of the burst reaches its first token within the staggered
bound.
"""

import dataclasses

import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

MCFG = ModelConfig.tiny(vocab_size=64)


def _ecfg(**kw):
    d = dict(page_size=4, num_pages=128, max_model_len=128,
             max_batch_size=4, max_prefill_tokens=32,
             prefill_buckets=(8, 16, 32))
    d.update(kw)
    return EngineConfig(**d)


def _req(rid, toks, max_tokens, **kw):
    return EngineRequest(
        request_id=rid, token_ids=list(toks),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                ignore_eos=True), **kw)


def _drive(eng, feed=None, max_steps=300):
    """Drive to idle; returns (tokens-per-rid, steps-delivering-per-rid).
    ``feed`` = {step_number: [EngineRequest, ...]} applied before that
    step runs: arrival points in step time."""
    toks, deliver = {}, {}
    fed = set()
    step = 0
    while eng.has_work() or (feed and len(fed) < len(feed)):
        step += 1
        if feed and step in feed and step not in fed:
            for r in feed[step]:
                eng.add_request(dataclasses.replace(r))
            fed.add(step)
        for out in eng.step():
            if out.new_token_ids:
                toks.setdefault(out.request_id, []).extend(
                    out.new_token_ids)
                deliver.setdefault(out.request_id, []).append(step)
        assert step < max_steps, "engine did not drain"
    return toks, deliver


def _gaps(steps):
    return [b - a for a, b in zip(steps, steps[1:])]


class TestInterleaver:
    STREAMS = [_req("s0", range(1, 9), 30), _req("s1", range(3, 11), 30)]
    BURST = [_req("b0", range(2, 102), 4), _req("b1", range(5, 105), 4)]
    BURST_STEP = 4

    @pytest.fixture(scope="class")
    def run(self):
        """(tokens, delivering steps) per request: two streams, and a
        burst of two 100-token prompts before step 4."""
        eng = Engine(MCFG, _ecfg(), seed=0)
        for r in self.STREAMS:
            eng.add_request(dataclasses.replace(r))
        return _drive(eng, feed={self.BURST_STEP: self.BURST})

    def test_decode_gap_bounded_under_burst(self, run):
        """Running streams receive a token EVERY iteration even while
        200 prompt tokens prefill — every gap is 1, the unloaded gap —
        and every request gets all of its tokens."""
        toks, deliver = run
        assert {r: len(t) for r, t in toks.items()} == \
            {"s0": 30, "s1": 30, "b0": 4, "b1": 4}
        for rid in ("s0", "s1"):
            gaps = _gaps(deliver[rid])
            assert gaps and max(gaps) == 1, (rid, deliver[rid])

    def test_burst_ttft_meets_staggered_bound(self, run):
        """Each burst prompt's first token lands within the analytic
        bound: the front waiting prompt is guaranteed a quantum of the
        largest bucket <= residual budget (32 - 2 decode = 30 -> 16)
        every iteration, so 200 burst tokens drain within ceil(200/16)
        steps, plus one step of arrival slack and one of admission
        order."""
        _, deliver = run
        bound = self.BURST_STEP + -(-200 // 16) + 2
        for rid in ("b0", "b1"):
            assert deliver[rid][0] <= bound, (rid, deliver[rid], bound)

    def test_mixed_step_ledger_and_backlog(self):
        """The interleaved iteration reports the split the worker's obs
        flush exports: kind "mixed", per-phase token counts, shrunken
        quantum windows, and the waiting_prefill_tokens backlog the
        heartbeat advertises."""
        eng = Engine(MCFG, _ecfg(), seed=0)
        for r in self.STREAMS:
            eng.add_request(dataclasses.replace(r))
        for _ in range(3):
            eng.step()
        for r in self.BURST:
            eng.add_request(dataclasses.replace(r))
        assert eng.waiting_prefill_tokens() == 200
        assert eng.load_metrics()["waiting_prefill_tokens"] == 200
        outs = eng.step()
        assert eng.last_step_kind == "mixed"
        assert eng.last_step_decode_tokens == 2
        assert eng.last_step_prefill_tokens > 0
        assert eng.last_step_prefill_windows
        # The quantum shrank below the 32 cap: snapped DOWN to the
        # largest bucket <= residual budget (32 - 2 decode tokens = 30
        # -> bucket 16), so windows stay compiled-program shaped.
        assert max(eng.last_step_prefill_windows) <= 16
        assert eng.last_step_tokens == (eng.last_step_prefill_tokens
                                        + eng.last_step_decode_tokens)
        assert eng.waiting_prefill_tokens() == 200 - \
            eng.last_step_prefill_tokens
        assert outs


def test_the_budget_and_the_deadline_resolve_from_env_and_defaults(
        monkeypatch):
    # Env overrides land on EngineConfig in __post_init__ (cheap to
    # pin); one Engine covers the engine-side default resolution.
    monkeypatch.setenv("XLLM_STEP_TOKEN_BUDGET", "16")
    monkeypatch.setenv("XLLM_PREFILL_DEADLINE_MS", "125")
    assert _ecfg().step_token_budget == 16
    assert _ecfg().prefill_deadline_ms == 125.0
    monkeypatch.delenv("XLLM_STEP_TOKEN_BUDGET")
    monkeypatch.delenv("XLLM_PREFILL_DEADLINE_MS")
    eng = Engine(MCFG, _ecfg(), seed=0)
    assert eng.step_token_budget == 32       # 0 = max_prefill_tokens
    assert eng.prefill_deadline_ms == 500.0


def test_skip_ahead_admits_small_prompt_behind_page_starved_giant():
    """Head-of-line fix: a giant whose pages don't fit must not block a
    small prompt behind it from admitting this step; queue order is
    untouched so the giant admits as soon as pages free up."""
    eng = Engine(MCFG, _ecfg(num_pages=16, max_model_len=64,
                             max_prefill_tokens=64,
                             prefill_buckets=(8, 16, 32, 64)), seed=0)
    # Blocker holds 10 of the 15 pages; the giant's first 32-token
    # window needs 8 > 5 free pages, the small prompt only 3.
    eng.add_request(_req("blocker", range(1, 37), 12))
    early = list(eng.step())
    eng.add_request(_req("giant", range(2, 42), 2))
    eng.add_request(_req("small", range(4, 12), 2))
    outs = eng.step()
    early += outs
    got = {o.request_id for o in outs if o.new_token_ids}
    assert "small" in got, outs       # admitted past the stuck giant
    assert any(s.req.request_id == "giant" for s in eng.waiting)
    # Sort contract: the giant keeps queue priority and still finishes
    # once the blocker's pages free.
    toks, _ = _drive(eng)
    for o in early:
        if o.new_token_ids:
            toks[o.request_id] = (list(o.new_token_ids)
                                  + toks.get(o.request_id, []))
    assert len(toks["giant"]) == 2
    assert len(toks["small"]) == 2
    assert len(toks["blocker"]) == 12


def test_starvation_deadline_grants_quantum():
    """With the budget fully consumed by decode, a waiting prompt
    starves until the TTFT-derived deadline passes — then it is
    guaranteed a minimum quantum per iteration."""
    # Budget 8 admits the stream's 8-token prompt unloaded; once the
    # stream decodes, the residual (8 - 1 = 7) is below the smallest
    # bucket, so no prefill window fits and the prompt waits.
    eng = Engine(MCFG, _ecfg(step_token_budget=8,
                             prefill_deadline_ms=1e9), seed=0)
    eng.add_request(_req("s", range(1, 9), 24))
    eng.step()
    eng.add_request(_req("p", range(2, 18), 2))
    starved = [eng.step() for _ in range(6)]
    assert all(o.request_id == "s" for outs in starved for o in outs)
    assert eng.waiting_prefill_tokens() == 16
    # Deadline elapses (engine-side knob is live per-iteration): the
    # prompt now gets one minimum-bucket quantum per step and reaches
    # its first token in ceil(16/8) = 2 iterations.
    eng.prefill_deadline_ms = 0.0
    outs = [o for _ in range(2) for o in eng.step()]
    assert any(o.request_id == "p" and o.new_token_ids for o in outs)


class TestRaggedMixedStep:
    """One-dispatch ragged mixed iterations (XLLM_RAGGED_ATTN /
    EngineConfig.ragged_attn): a mixed iteration packs decode rows and
    prefill windows into ONE ragged batch served by ONE attention
    program. Streams must be byte-identical to the split sections', and
    the dispatch ledger must prove the single launch."""

    # 40 tokens, two windows (32 + 8). The first goes out behind the
    # decode step that was on the device when the prompt arrived (the
    # split sections: tests/test_decode_ahead.py,
    # test_a_mixed_program_does_not_throw_the_step_in_flight_away);
    # while a prompt waits nothing is launched ahead, so the second
    # finds no step there and is the ragged program's.
    LATE = range(3, 43)

    @staticmethod
    def _ecfg(ragged=None):
        return EngineConfig(
            page_size=32, num_pages=16, max_model_len=64,
            max_batch_size=2, max_prefill_tokens=64,
            prefill_buckets=(8, 16, 32), ragged_attn=ragged)

    def _run(self, ragged):
        eng = Engine(MCFG, self._ecfg(ragged), seed=0)
        eng.add_request(_req("a", range(1, 9), 16))
        toks, _ = _drive(eng, feed={3: [_req("b", self.LATE, 16)]})
        return toks, eng

    def test_streams_byte_identical_ragged_on_vs_off(self):
        """Ragged on against off: the step STRUCTURE differs (one
        ragged launch against a decode step plus a prefill call), but
        at temperature=0 the streams are prefix-determined, so both
        must emit identical bytes."""
        (on, eng), (off, _) = self._run(True), self._run(False)
        assert eng.phase_counts["ragged.dispatch"] == 1
        assert on == off
        assert len(on["a"]) == 16 and len(on["b"]) == 16

    def test_mixed_step_is_one_dispatch(self):
        """The acceptance pin: a ragged mixed iteration executes exactly
        ONE attention dispatch, where the split sections need the
        decode step plus one per prefill call."""
        seen = {}
        for ragged in (True, False):
            eng = Engine(MCFG, self._ecfg(ragged=ragged), seed=0)
            eng.add_request(_req("a", range(1, 9), 16))
            for step in range(40):
                if step == 2:
                    eng.add_request(_req("b", self.LATE, 16))
                eng.step()
                if eng.last_step_kind == "mixed":
                    seen.setdefault(ragged, []).append(
                        (eng.last_step_ragged,
                         eng.last_step_attn_dispatches))
        # b's first window behind the step in flight, its second ragged
        assert seen[True] == [(False, 2), (True, 1)], seen
        assert seen[False] == [(False, 2), (False, 2)], seen

    def test_ragged_step_ledger_and_reports(self):
        """The ragged iteration keeps the worker-visible ledger: kind
        "mixed" with the per-phase token split, the ragged flag and
        phase counters the obs flush exports, and a "ragged" entry in
        compile_report."""
        eng = Engine(MCFG, self._ecfg(ragged=True), seed=0)
        assert eng.plan.mixed_step and eng._jit_ragged is not None
        assert "ragged" in eng.compile_report()
        eng.add_request(_req("a", range(1, 9), 16))
        hit = False
        for step in range(40):
            if step == 2:
                eng.add_request(_req("b", self.LATE, 16))
            eng.step()
            if eng.last_step_ragged:
                hit = True
                assert eng.last_step_kind == "mixed"
                assert eng.last_step_decode_tokens == 1
                assert eng.last_step_prefill_tokens == 8
                assert eng.last_step_prefill_windows == (8,)
                break
        assert hit
        assert eng.phase_counts["ragged.dispatch"] == 1
        assert eng.phase_counts["ragged.pack"] == 1
        assert eng.phase_counts["ragged.post"] == 1
        # Drain; decode-only and prefill-only iterations never go ragged.
        toks, _ = _drive(eng)
        assert eng.phase_counts["ragged.dispatch"] == 1
        assert eng.compile_report()["ragged"] == 1

    def test_penalized_decode_falls_back_to_split_path(self):
        """Presence/frequency penalties need the output-token histogram
        the ragged program doesn't carry — those iterations must take
        the split sections (and still produce correct streams)."""
        def drive(ragged):
            eng = Engine(MCFG, self._ecfg(ragged=ragged), seed=0)
            eng.add_request(EngineRequest(
                request_id="a", token_ids=list(range(1, 9)),
                sampling=SamplingParams(max_tokens=8, temperature=0.0,
                                        presence_penalty=0.5,
                                        ignore_eos=True)))
            toks, ragged_steps = {}, 0
            for step in range(60):
                if step == 2:
                    eng.add_request(_req("b", self.LATE, 8))
                for o in eng.step():
                    toks.setdefault(o.request_id, []).extend(
                        o.new_token_ids)
                ragged_steps += int(eng.last_step_ragged)
                if step >= 2 and not eng.has_work():
                    break
            return toks, ragged_steps

        on, rs_on = drive(True)
        off, rs_off = drive(False)
        # The penalized decoder forces the split path every iteration —
        # and the fallback is stream-invisible.
        assert rs_on == 0 and rs_off == 0
        assert on == off
        assert len(on["a"]) == 8 and len(on["b"]) == 8

    def test_env_resolution_and_default_off(self, monkeypatch):
        """Off by default; the variable decides for an engine built
        under it, over the field (tests/test_kernel_plan.py holds the
        resolver's whole table)."""
        assert self._ecfg().ragged_attn is None
        eng = Engine(MCFG, self._ecfg(), seed=0)
        assert not eng.plan.mixed_step and eng._jit_ragged is None
        assert "ragged" not in eng.compile_report()
        monkeypatch.setenv("XLLM_RAGGED_ATTN", "1")
        on = Engine(MCFG, self._ecfg(), seed=0, params=eng.params)
        assert on.plan.mixed_step and on._jit_ragged is not None
        monkeypatch.setenv("XLLM_RAGGED_ATTN", "0")
        off = Engine(MCFG, self._ecfg(ragged=True), seed=0,
                     params=eng.params)
        assert not off.plan.mixed_step and off._jit_ragged is None
        assert eng._jit_ragged is None and on._jit_ragged is not None
