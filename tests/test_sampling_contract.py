"""OpenAI sampling-contract tests: every accepted field must be honored
end to end (stop strings, max_completion_tokens, n>1, logprobs,
penalties, per-request seeds) — the reference carries these in its protos
(xllm/chat.proto:1-192, completion.proto:1-143); the rebuild must not
silently drop them (round-1 verdict, item 4)."""

import pytest

from xllm_service_tpu.config import (
    EngineConfig, InstanceType, LoadBalancePolicyType, ModelConfig,
    ServiceOptions)
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.runtime.worker import Worker, WorkerOptions, _StopWatcher
from xllm_service_tpu.service.coordination import InMemoryStore
from xllm_service_tpu.service.httpd import http_json
from xllm_service_tpu.service.master import Master
from xllm_service_tpu.utils.types import SamplingParams, parse_openai_sampling

from test_e2e import wait_until


def engine_cfg(**kw) -> EngineConfig:
    base = dict(page_size=16, num_pages=64, max_model_len=256,
                max_batch_size=4, max_prefill_tokens=256,
                prefill_buckets=(32, 64, 128), num_top_logprobs=4)
    base.update(kw)
    return EngineConfig(**base)


def make_cluster(store):
    opts = ServiceOptions(
        http_port=0, rpc_port=0, num_output_pools=4,
        load_balance_policy=LoadBalancePolicyType.ROUND_ROBIN,
        block_size=16, heartbeat_interval_s=0.2,
        master_upload_interval_s=0.2)
    master = Master(opts, store=store).start()
    wopts = WorkerOptions(
        port=0, instance_type=InstanceType.DEFAULT,
        service_addr=master.rpc_address, model="tiny",
        heartbeat_interval_s=0.2, lease_ttl_s=2.0)
    worker = Worker(wopts, store, engine_cfg=engine_cfg()).start()
    assert wait_until(
        lambda: len(master.scheduler.instance_mgr.prefill_instances()) == 1,
        timeout=15.0), "worker never registered"
    return master, worker


@pytest.fixture()
def store():
    s = InMemoryStore(sweep_interval_s=0.02)
    yield s
    s.close()


@pytest.fixture()
def cluster(store):
    master, worker = make_cluster(store)
    yield master, worker
    worker.stop()
    master.stop()


# ---------------------------------------------------------------------------
# Parsing
# ---------------------------------------------------------------------------

def test_parse_openai_sampling_normalization():
    sp = parse_openai_sampling(
        {"max_completion_tokens": 9, "stop": "END", "n": 3,
         "presence_penalty": 0.5, "frequency_penalty": 0.25,
         "logprobs": True, "top_logprobs": 2, "seed": 7}, is_chat=True)
    assert sp.max_tokens == 9
    assert sp.stop == ["END"]
    assert sp.n == 3
    assert sp.presence_penalty == 0.5
    assert sp.frequency_penalty == 0.25
    assert sp.logprobs and sp.top_logprobs == 2
    assert sp.seed == 7
    # Completion API: logprobs is an int (top-k count).
    sp = parse_openai_sampling({"logprobs": 3}, is_chat=False)
    assert sp.logprobs and sp.top_logprobs == 3
    sp = parse_openai_sampling({}, is_chat=False)
    assert not sp.logprobs


def test_stop_watcher_holdback_across_chunks():
    w = _StopWatcher(["STOP"])
    assert w.feed("hello ST") == "hello "     # holdback: "ST" may start STOP
    assert w.feed("ILL going") == "STILL going"   # false alarm released
    assert w.feed("almost S") == "almost "
    assert w.feed("TOP and more") == ""       # "S"+"TOP..." completes STOP
    assert w.stopped
    # Earliest stop wins across multiple candidates.
    w2 = _StopWatcher(["xx", "yy"])
    assert w2.feed("a yy b xx") == "a "
    assert w2.stopped


# ---------------------------------------------------------------------------
# API level (service -> worker -> engine and back)
# ---------------------------------------------------------------------------

class TestApiContract:
    def test_max_completion_tokens_honored(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/chat/completions",
            {"model": "tiny",
             "messages": [{"role": "user", "content": "hi there"}],
             "max_completion_tokens": 4, "temperature": 0.0,
             "ignore_eos": True}, timeout=120.0)
        assert status == 200, resp
        assert resp["usage"]["completion_tokens"] == 4

    def test_stop_string_truncates_and_finishes(self, cluster):
        master, _ = cluster
        # Probe what greedy emits, then stop on a mid-output substring.
        status, probe = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "stop contract", "max_tokens": 12,
             "temperature": 0.0, "ignore_eos": True}, timeout=120.0)
        assert status == 200, probe
        text = probe["choices"][0]["text"]
        assert len(text) >= 4
        stop = text[2:4]
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "stop contract", "max_tokens": 12,
             "temperature": 0.0, "ignore_eos": True, "stop": stop},
            timeout=120.0)
        assert status == 200, resp
        got = resp["choices"][0]["text"]
        assert resp["choices"][0]["finish_reason"] == "stop"
        assert stop not in got
        assert got == text[:text.find(stop)]

    def test_n_choices(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "many choices", "max_tokens": 4,
             "n": 2, "temperature": 0.0, "ignore_eos": True},
            timeout=120.0)
        assert status == 200, resp
        choices = resp["choices"]
        assert [c["index"] for c in choices] == [0, 1]
        assert all(c["finish_reason"] == "length" for c in choices)
        # Usage counts all choices' tokens, prompt once.
        assert resp["usage"]["completion_tokens"] == 8
        assert resp["usage"]["prompt_tokens"] == len("many choices")
        # Greedy: both choices identical text.
        assert choices[0]["text"] == choices[1]["text"]

    def test_best_of_selects_highest_mean_logprob(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "pick the best", "max_tokens": 4,
             "best_of": 3, "n": 1, "temperature": 1.5, "seed": 7,
             "ignore_eos": True}, timeout=120.0)
        assert status == 200, resp
        choices = resp["choices"]
        assert len(choices) == 1 and choices[0]["index"] == 0
        # OpenAI billing: every candidate's tokens count.
        assert resp["usage"]["completion_tokens"] == 12
        # The survivor must be the greedy-favored candidate — rerank by
        # asking for all 3 candidates' logprobs via n=3 with same seed.
        status, all3 = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "pick the best", "max_tokens": 4,
             "n": 3, "temperature": 1.5, "seed": 7, "logprobs": 0,
             "ignore_eos": True}, timeout=120.0)
        assert status == 200, all3
        means = []
        for c in all3["choices"]:
            lps = c["logprobs"]["token_logprobs"]
            means.append(sum(lps) / len(lps))
        best_text = all3["choices"][means.index(max(means))]["text"]
        assert choices[0]["text"] == best_text

    def test_best_of_validation(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "x", "max_tokens": 2,
             "best_of": 1, "n": 2}, timeout=60.0)
        assert status == 400
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "x", "max_tokens": 2,
             "best_of": 3, "n": 1, "stream": True}, timeout=60.0)
        assert status == 400
        # Non-numeric best_of is a 400, not a 500.
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "x", "max_tokens": 2,
             "best_of": "three"}, timeout=60.0)
        assert status == 400

    def test_echo_prepends_prompt_text(self, cluster):
        master, _ = cluster
        status, plain = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "echo me", "max_tokens": 3,
             "temperature": 0.0, "ignore_eos": True}, timeout=120.0)
        assert status == 200, plain
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "echo me", "max_tokens": 3,
             "temperature": 0.0, "ignore_eos": True, "echo": True},
            timeout=120.0)
        assert status == 200, resp
        assert resp["choices"][0]["text"] == \
            "echo me" + plain["choices"][0]["text"]
        # Usage is unchanged by echo — prompt tokens aren't billed twice.
        assert resp["usage"] == plain["usage"]

    def test_echo_with_logprobs_scores_prompt(self, cluster):
        master, _ = cluster
        prompt = "score the prompt"
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": prompt, "max_tokens": 2,
             "temperature": 0.0, "ignore_eos": True, "echo": True,
             "logprobs": 0}, timeout=120.0)
        assert status == 200, resp
        ch = resp["choices"][0]
        lp = ch["logprobs"]
        n_prompt = resp["usage"]["prompt_tokens"]
        n_total = n_prompt + resp["usage"]["completion_tokens"]
        assert len(lp["tokens"]) == n_total
        assert len(lp["token_logprobs"]) == n_total
        # First prompt token has nothing to condition on → null; the
        # rest are real (negative) log-probabilities.
        assert lp["token_logprobs"][0] is None
        assert all(isinstance(v, float) and v <= 0.0
                   for v in lp["token_logprobs"][1:])
        # The token strings reassemble exactly the echoed text.
        assert "".join(lp["tokens"]) == ch["text"]
        # Offsets line up with the echoed text.
        assert lp["text_offset"][0] == 0
        assert lp["text_offset"][-1] < len(ch["text"])

    def test_echo_logprobs_with_candidates(self, cluster):
        """echo + logprobs + n>1: the prompt is scored ONCE (candidate 0)
        and every choice's arrays still lead with the prompt tokens."""
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "shared scoring", "max_tokens": 2,
             "n": 2, "temperature": 0.0, "ignore_eos": True,
             "echo": True, "logprobs": 0}, timeout=120.0)
        assert status == 200, resp
        n_prompt = resp["usage"]["prompt_tokens"]
        assert len(resp["choices"]) == 2
        prompt_arrays = []
        for ch in resp["choices"]:
            lp = ch["logprobs"]
            assert len(lp["tokens"]) == n_prompt + 2
            assert lp["token_logprobs"][0] is None
            assert "".join(lp["tokens"]) == ch["text"]
            prompt_arrays.append(tuple(lp["token_logprobs"][1:n_prompt]))
        # Same prompt scores on both choices (computed once, shared).
        assert prompt_arrays[0] == prompt_arrays[1]

    def test_completion_logprobs(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/completions",
            {"model": "tiny", "prompt": "logprob me", "max_tokens": 3,
             "temperature": 0.0, "ignore_eos": True, "logprobs": 2},
            timeout=120.0)
        assert status == 200, resp
        lp = resp["choices"][0]["logprobs"]
        assert lp is not None
        assert len(lp["tokens"]) == 3
        assert len(lp["token_logprobs"]) == 3
        assert all(isinstance(x, float) and x <= 0.0
                   for x in lp["token_logprobs"])
        assert len(lp["top_logprobs"]) == 3
        assert all(0 < len(t) <= 2 for t in lp["top_logprobs"])
        assert lp["text_offset"][0] == 0

    def test_chat_logprobs(self, cluster):
        master, _ = cluster
        status, resp = http_json(
            "POST", master.http_address, "/v1/chat/completions",
            {"model": "tiny",
             "messages": [{"role": "user", "content": "chat logprobs"}],
             "max_tokens": 3, "temperature": 0.0, "ignore_eos": True,
             "logprobs": True, "top_logprobs": 2}, timeout=120.0)
        assert status == 200, resp
        lp = resp["choices"][0]["logprobs"]
        assert lp is not None and len(lp["content"]) == 3
        entry = lp["content"][0]
        assert set(entry) == {"token", "logprob", "bytes", "top_logprobs"}
        assert len(entry["top_logprobs"]) == 2


# ---------------------------------------------------------------------------
# Engine level (penalties, seeds)
# ---------------------------------------------------------------------------

def _run_engine(sp: SamplingParams, engine_seed: int = 0,
                prompt=None) -> list:
    cfg = ModelConfig.tiny(vocab_size=128)
    ecfg = EngineConfig(page_size=8, num_pages=32, max_model_len=64,
                        max_batch_size=2, max_prefill_tokens=64,
                        prefill_buckets=(16,))
    eng = Engine(cfg, ecfg, seed=engine_seed)
    eng.add_request(EngineRequest(
        request_id="r", token_ids=list(prompt or range(1, 9)), sampling=sp))
    toks = []
    while eng.has_work():
        for out in eng.step():
            toks.extend(out.new_token_ids)
    return toks


def test_frequency_penalty_blocks_repeats():
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True,
                        frequency_penalty=100.0)
    toks = _run_engine(sp)
    assert len(toks) == 8
    # -100 per occurrence dwarfs the logit range: greedy never repeats.
    assert len(set(toks)) == 8
    # Control: without the penalty the tiny random model does repeat.
    toks_free = _run_engine(SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True))
    assert len(set(toks_free)) < 8


def test_seeded_sampling_deterministic_across_engines():
    sp = SamplingParams(max_tokens=8, temperature=1.0, ignore_eos=True,
                        seed=42)
    a = _run_engine(sp, engine_seed=0)
    b = _run_engine(sp, engine_seed=123)   # different global RNG stream
    assert a == b
    c = _run_engine(SamplingParams(max_tokens=8, temperature=1.0,
                                   ignore_eos=True, seed=43))
    assert c != a


def test_echo_scoring_source_cancelled_releases_held_choices():
    """echo+logprobs with n>1: if candidate 0 (the score source) is
    cancelled before its prefill scores the prompt, held choices must be
    released (with empty prompt scores) instead of hanging forever."""
    from xllm_service_tpu.nlp.tokenizer import TokenizerFactory
    from xllm_service_tpu.runtime.engine import StepOutput
    from xllm_service_tpu.runtime.worker import _LiveRequest
    from xllm_service_tpu.utils.types import FinishReason

    tok = TokenizerFactory.create_tokenizer(None)
    req = EngineRequest(request_id="r", token_ids=[65, 66, 67],
                        sampling=SamplingParams())
    live = _LiveRequest(req, tok, "r", "tiny", is_chat=False, stream=False,
                        include_usage=False, stream_to_service=False, n=2)
    live.sampling = parse_openai_sampling(
        {"echo": True, "logprobs": 0, "n": 2}, is_chat=False)
    live.prompt_tokens = 3

    class _W:  # only the two methods under test, unbound from a Worker
        _process_step_output = Worker._process_step_output
        _to_request_output = Worker._to_request_output
        _cancel_engine_request = lambda self, live, rid: None  # noqa: E731
    w = _W()

    # Choice 1 finishes first — held (no scores yet).
    out1 = StepOutput(request_id="r#1", new_token_ids=[70], logprobs=[-0.5],
                      finish_reason=FinishReason.LENGTH,
                      num_prompt_tokens=3, num_generated=1)
    assert w._process_step_output(live, out1) == []
    assert live.choices[1].pending
    # Candidate 0 is cancelled before scoring: everything must flush.
    out0 = StepOutput(request_id="r#0", new_token_ids=[], logprobs=[],
                      finish_reason=FinishReason.CANCELLED,
                      num_prompt_tokens=3, num_generated=0)
    ros = w._process_step_output(live, out0)
    texts = {ro.outputs[0].index: ro.outputs[0].text for ro in ros}
    assert 1 in texts          # held choice released
    assert live.prompt_lps == []
    assert live.all_finished


def test_logit_bias_forces_and_bans():
    """OpenAI logit_bias inside the fused sampling step: +100 forces a
    token even under greedy; -100 bans the would-be argmax. (The
    reference carries logit_bias only as a proto TODO.)"""
    sp_force = SamplingParams(max_tokens=6, temperature=0.0,
                              ignore_eos=True, logit_bias={5: 100.0})
    toks = _run_engine(sp_force)
    assert toks == [5] * 6

    free = _run_engine(SamplingParams(max_tokens=1, temperature=0.0,
                                      ignore_eos=True))
    banned = free[0]
    toks = _run_engine(SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True,
        logit_bias={banned: -100.0}))
    assert banned not in toks


def test_logit_bias_parses_from_json_body():
    sp = parse_openai_sampling(
        {"logit_bias": {"17": 55, "3": -20}}, is_chat=True)
    assert sp.logit_bias == {17: 55.0, 3: -20.0}
    # Wire round-trip restores int keys.
    again = SamplingParams.from_json(
        __import__("json").loads(__import__("json").dumps(sp.to_json())))
    assert again.logit_bias == {17: 55.0, 3: -20.0}


def test_logit_bias_validation(cluster=None):
    import pytest as _pytest
    from xllm_service_tpu.utils.types import _parse_logit_bias
    with _pytest.raises(ValueError):
        _parse_logit_bias([1, 2])                       # not an object
    with _pytest.raises(ValueError):
        _parse_logit_bias({"5": float("nan")})          # non-finite
    with _pytest.raises(ValueError):
        _parse_logit_bias({"5": 1000})                  # out of range
    with _pytest.raises(ValueError):
        _parse_logit_bias({"-3": 1.0})                  # negative id
    with _pytest.raises(ValueError):
        _parse_logit_bias({str(i): 0.0 for i in range(301)})  # cap
    assert _parse_logit_bias({"5": -100, "9": 100}) == \
        {5: -100.0, 9: 100.0}


def test_logit_bias_out_of_vocab_rejected(cluster):
    master, _ = cluster
    status, resp = http_json(
        "POST", master.http_address, "/v1/completions",
        {"model": "tiny", "prompt": "x", "max_tokens": 2,
         "logit_bias": {"99999999": -100}}, timeout=60.0)
    assert status == 400, resp       # relay mode forwards the worker's 400
