"""Engine-level tests: allocator, prefix cache, continuous batching,
online-over-offline preemption — all on CPU with a tiny model."""

import collections
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import (
    init_params, init_kv_cache, forward_prefill, forward_decode)
from xllm_service_tpu.ops.sampling import greedy
from xllm_service_tpu.runtime.kv_cache import PageAllocator, PrefixCacheIndex
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import FinishReason, SamplingParams


# ---------------------------------------------------------------------------
# Allocator + prefix index
# ---------------------------------------------------------------------------

def test_page_allocator_basics():
    a = PageAllocator(8)
    assert a.num_free == 7          # page 0 reserved
    p = a.alloc(3)
    assert len(p) == 3 and 0 not in p
    assert a.alloc(5) is None       # only 4 left
    a.free(p)
    assert a.num_free == 7
    with pytest.raises(ValueError):
        a.free([0])


def test_prefix_cache_match_register_reclaim():
    a = PageAllocator(8)
    idx = PrefixCacheIndex(a, page_size=4)
    toks = list(range(12))
    pages = idx.alloc(3)
    idx.register_full_pages(toks, pages)
    ev = idx.drain_event()
    assert len(ev.stored) == 3

    # Full-prompt match is trimmed so at least one token is recomputed.
    m, n = idx.match_prefix(toks)
    assert n == 8 and m == pages[:2]
    idx.release_pages(m)

    # Longest-prefix semantics: diverging tokens stop the walk.
    m2, n2 = idx.match_prefix(toks[:8] + [99, 98, 97, 96])
    assert n2 == 8
    idx.release_pages(m2)

    # Release makes pages reclaimable (not free) until pressure demands.
    idx.release_pages(pages)
    assert a.num_free == 4
    big = idx.alloc(6)               # forces reclamation of 2 LRU pages
    assert big is not None and len(big) == 6
    ev = idx.drain_event()
    assert len(ev.removed) == 2


def _tiny_engine(**eng_kw) -> Engine:
    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="float32")
    defaults = dict(page_size=4, num_pages=32, max_model_len=64,
                    max_batch_size=4, max_prefill_tokens=64,
                    prefill_buckets=(8, 16, 32, 64))
    defaults.update(eng_kw)
    return Engine(cfg, EngineConfig(**defaults), seed=0)


def _collect(engine, max_steps=200):
    """Drive the engine until idle; return {request_id: (tokens, reason)}."""
    done = {}
    toks = {}
    for _ in range(max_steps):
        if not engine.has_work():
            break
        for out in engine.step():
            toks.setdefault(out.request_id, []).extend(out.new_token_ids)
            if out.finished:
                done[out.request_id] = out.finish_reason
    assert not engine.has_work(), "engine did not drain"
    return toks, done


# ---------------------------------------------------------------------------
# Generation correctness
# ---------------------------------------------------------------------------

def test_engine_greedy_matches_direct_model_loop():
    """The batched, paged, continuously-scheduled engine must produce exactly
    the tokens a naive prefill+decode loop produces."""
    eng = _tiny_engine()
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    eng.add_request(EngineRequest(
        request_id="r1", token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=10, temperature=0.0)))
    toks, done = _collect(eng)
    assert done["r1"] == FinishReason.LENGTH
    got = toks["r1"]
    assert len(got) == 10

    # Direct loop with the same params.
    cfg = eng.cfg
    kv = init_kv_cache(cfg, 32, 4, jnp.float32)
    pt = jnp.asarray([np.arange(1, 17)], jnp.int32)
    last, _, kv = forward_prefill(
        eng.params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.asarray([len(prompt)], jnp.int32),
        kv, pt)
    ref = [int(greedy(last)[0])]
    pos = len(prompt)
    for _ in range(9):
        logits, kv = forward_decode(
            eng.params, cfg, jnp.asarray(ref[-1:], jnp.int32),
            jnp.asarray([pos], jnp.int32), jnp.asarray([True]), kv, pt)
        ref.append(int(greedy(logits)[0]))
        pos += 1
    assert got == ref


def test_add_request_rejects_prompt_larger_than_pool():
    """A prompt whose KV can never fit the page pool must fail fast at
    add_request, not self-preempt forever (review finding)."""
    eng = _tiny_engine(num_pages=8)          # 7 usable pages × 4 tokens
    with pytest.raises(ValueError):
        eng.add_request(EngineRequest(
            "big", token_ids=[1] * 29,        # needs 8 pages (29+1 tokens)
            sampling=SamplingParams(max_tokens=2)))
    eng.add_request(EngineRequest(            # 27+1 tokens → 7 pages: fits
        "ok", token_ids=[1] * 27,
        sampling=SamplingParams(max_tokens=1, temperature=0.0)))
    toks, done = _collect(eng)
    assert done["ok"] == FinishReason.LENGTH


def test_chunked_prefill_long_prompt_matches_single_shot():
    """A prompt longer than the largest prefill bucket must prefill over
    multiple windows and generate exactly what a single-shot prefill of the
    same prompt produces (round-1 capped prompts at the largest bucket)."""
    prompt = [(i * 7 + 3) % 50 for i in range(30)]
    sp = SamplingParams(max_tokens=6, temperature=0.0)

    e1 = _tiny_engine()                      # bucket 64: one-shot prefill
    e1.add_request(EngineRequest("a", list(prompt), sampling=sp))
    toks1, done1 = _collect(e1)

    e2 = _tiny_engine(prefill_buckets=(8,), max_prefill_tokens=8)
    e2.add_request(EngineRequest("a", list(prompt), sampling=sp))
    toks2, done2 = _collect(e2)

    assert done1["a"] == done2["a"] == FinishReason.LENGTH
    assert toks1["a"] == toks2["a"]


def test_chunked_prefill_interleaves_with_short_requests():
    """Long and short prompts complete together; short ones are not
    starved by a long prompt's windows."""
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    eng = _tiny_engine(prefill_buckets=(8,), max_prefill_tokens=8)
    long_prompt = [(i * 3 + 1) % 50 for i in range(28)]
    eng.add_request(EngineRequest("long", long_prompt, sampling=sp))
    eng.add_request(EngineRequest("short", [5, 6, 7], sampling=sp))
    toks, done = _collect(eng)
    assert done["long"] == FinishReason.LENGTH and len(toks["long"]) == 4
    assert done["short"] == FinishReason.LENGTH and len(toks["short"]) == 4

    # Same outputs as solo runs.
    for rid, prompt in (("long", long_prompt), ("short", [5, 6, 7])):
        solo = _tiny_engine()
        solo.add_request(EngineRequest(rid, list(prompt), sampling=sp))
        st, _ = _collect(solo)
        assert st[rid] == toks[rid]


def test_long_context_8k_chunked_prefill_and_decode():
    """8k-context serving end to end on one engine: a ~3k-token prompt
    prefills in 1k windows through the O(T·chunk) attention path
    (S > 1024 engages mha_prefill_chunked), then decodes against the
    full context."""
    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="float32",
                              max_position_embeddings=8192)
    eng = Engine(cfg, EngineConfig(
        page_size=64, num_pages=160, max_model_len=8192,
        max_batch_size=2, max_prefill_tokens=1024,
        prefill_buckets=(256, 1024)), seed=0)
    prompt = [(i * 13 + 5) % 250 for i in range(3100)]
    eng.add_request(EngineRequest(
        "long8k", list(prompt),
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    import time as _time
    t0 = _time.monotonic()
    toks, done = _collect(eng, max_steps=60)
    elapsed = _time.monotonic() - t0
    assert done["long8k"] == FinishReason.LENGTH
    assert len(toks["long8k"]) == 4
    print(f"8k-context prefill+4 tokens in {elapsed:.1f}s on CPU")

    # Value check: a second engine with a different window partition
    # (512-token windows → different chunked-attention call shapes) must
    # produce the identical greedy continuation — catches q_start /
    # kv_lengths plumbing bugs the count assertions above cannot.
    eng2 = Engine(cfg, EngineConfig(
        page_size=64, num_pages=160, max_model_len=8192,
        max_batch_size=2, max_prefill_tokens=512,
        prefill_buckets=(512,)), seed=0)
    eng2.add_request(EngineRequest(
        "long8k", list(prompt),
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    toks2, done2 = _collect(eng2, max_steps=60)
    assert done2["long8k"] == FinishReason.LENGTH
    assert toks2["long8k"] == toks["long8k"]


def test_ring_prefill_long_prompt_matches_single_chip():
    """Engine on an sp=8 mesh must prefill a prompt longer than the largest
    bucket in ONE ring step and generate exactly what the single-chip
    (chunked-window) engine produces."""
    from xllm_service_tpu.parallel import MeshSpec, make_mesh

    prompt = [(i * 11 + 2) % 50 for i in range(40)]
    sp = SamplingParams(max_tokens=5, temperature=0.0)

    ref = _tiny_engine(prefill_buckets=(8,), max_prefill_tokens=8)
    ref.add_request(EngineRequest("a", list(prompt), sampling=sp))
    toks_ref, done_ref = _collect(ref)

    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="float32")
    from xllm_service_tpu.config import EngineConfig as EC
    mesh = make_mesh(MeshSpec(sp=8))
    eng = Engine(cfg, EC(page_size=4, num_pages=32, max_model_len=64,
                         max_batch_size=4, max_prefill_tokens=8,
                         prefill_buckets=(8,)),
                 mesh=mesh, seed=0)
    assert eng._jit_prefill_ring is not None
    eng.add_request(EngineRequest("a", list(prompt), sampling=sp))
    # First step must take the whole prompt (ring), not an 8-token window.
    outs = eng.step()
    assert outs and outs[0].new_token_ids, "ring prefill did not emit"
    toks = {"a": list(outs[0].new_token_ids)}
    done = {}
    for _ in range(50):
        if not eng.has_work():
            break
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
            if out.finished:
                done[out.request_id] = out.finish_reason
    assert done["a"] == done_ref["a"] == FinishReason.LENGTH
    assert toks["a"] == toks_ref["a"]


def test_ring_prefill_moe_matches_single_chip():
    """MoE layers must compose with the sp ring path: a tiny-moe long
    prompt rings in one step and generates exactly what the single-chip
    chunked engine produces (sparse dispatch runs outside the ring's
    shard island, so expert routing sees the full sequence)."""
    from xllm_service_tpu.config import EngineConfig as EC
    from xllm_service_tpu.parallel import MeshSpec, make_mesh

    prompt = [(i * 13 + 5) % 50 for i in range(40)]
    sp_ = SamplingParams(max_tokens=5, temperature=0.0)
    cfg = dataclasses.replace(ModelConfig.tiny(num_experts=4),
                              dtype="float32")

    ref = Engine(cfg, EC(page_size=4, num_pages=32, max_model_len=64,
                         max_batch_size=4, max_prefill_tokens=8,
                         prefill_buckets=(8,)), seed=0)
    ref.add_request(EngineRequest("a", list(prompt), sampling=sp_))
    toks_ref, done_ref = _collect(ref)

    mesh = make_mesh(MeshSpec(sp=8))
    eng = Engine(cfg, EC(page_size=4, num_pages=32, max_model_len=64,
                         max_batch_size=4, max_prefill_tokens=8,
                         prefill_buckets=(8,)), mesh=mesh, seed=0)
    assert eng._jit_prefill_ring is not None
    eng.add_request(EngineRequest("a", list(prompt), sampling=sp_))
    outs = eng.step()
    assert outs and outs[0].new_token_ids, "moe ring prefill did not emit"
    toks = {"a": list(outs[0].new_token_ids)}
    done = {}
    for _ in range(50):
        if not eng.has_work():
            break
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
            if out.finished:
                done[out.request_id] = out.finish_reason
    assert done["a"] == done_ref["a"]
    assert toks["a"] == toks_ref["a"]


def test_ring_preferred_over_small_cached_prefix():
    """Deployment eligibility of the sp ring path (VERDICT r2 weak #8):
    a long prompt with a SMALL cached prefix must forgo the hit and ring
    the whole prompt in one step (len/sp beats len-cached sequential
    window tokens); a near-complete prefix must keep the cache hit and
    the chunked path."""
    from xllm_service_tpu.config import EngineConfig as EC
    from xllm_service_tpu.parallel import MeshSpec, make_mesh

    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="float32")
    mesh = make_mesh(MeshSpec(sp=8))
    eng = Engine(cfg, EC(page_size=4, num_pages=64, max_model_len=64,
                         max_batch_size=4, max_prefill_tokens=8,
                         prefill_buckets=(8,)), mesh=mesh, seed=0)
    sp_ = SamplingParams(max_tokens=3, temperature=0.0)
    base = [(i * 7 + 3) % 50 for i in range(40)]

    def ring_calls():
        return eng.phase_report().get("prefill_ring.dispatch",
                                      {}).get("calls", 0)

    eng.add_request(EngineRequest("a", list(base), sampling=sp_))
    _collect(eng)                 # registers base's pages in the cache
    n0 = ring_calls()
    assert n0 >= 1                # the long cold prompt itself rang

    # 16 shared tokens then divergence: cached 16 < 35 = 40*(1-1/8) →
    # the policy drops the hit; the whole prompt runs as ONE ring step
    # (the chunked path would need >= 3 sequential 8-token windows and
    # could not emit a token on the first step).
    b = base[:16] + [(i * 5 + 1) % 50 for i in range(24)]
    eng.add_request(EngineRequest("b", list(b), sampling=sp_))
    outs = eng.step()
    assert outs and outs[0].new_token_ids, "prefix-cached prompt " \
        "did not ring in one step"
    assert ring_calls() == n0 + 1
    _collect(eng)

    # The identical prompt re-matches 36 cached tokens (9 full pages;
    # the last page is withheld) >= 35: keep the hit, chunked path.
    eng.add_request(EngineRequest("c", list(base), sampling=sp_))
    eng.step()
    seq_c = eng._by_id.get("c")
    assert seq_c is not None and seq_c.num_cached_tokens >= 35
    assert ring_calls() == n0 + 1
    _collect(eng)


def test_engine_batched_matches_solo():
    """Concurrent requests must not perturb each other's greedy outputs."""
    prompts = [[1, 2, 3], [7, 7, 7, 7, 7], [9, 8, 7, 6]]
    solo_results = []
    for i, p in enumerate(prompts):
        eng = _tiny_engine()
        eng.add_request(EngineRequest(
            request_id=f"s{i}", token_ids=list(p),
            sampling=SamplingParams(max_tokens=6, temperature=0.0)))
        toks, _ = _collect(eng)
        solo_results.append(toks[f"s{i}"])

    eng = _tiny_engine()
    for i, p in enumerate(prompts):
        eng.add_request(EngineRequest(
            request_id=f"b{i}", token_ids=list(p),
            sampling=SamplingParams(max_tokens=6, temperature=0.0)))
    toks, _ = _collect(eng)
    for i in range(len(prompts)):
        assert toks[f"b{i}"] == solo_results[i], f"request {i} diverged"


def test_engine_eos_stops():
    eng = _tiny_engine()
    # Discover the greedy first token, then use it as the EOS id.
    eng.add_request(EngineRequest(
        request_id="probe", token_ids=[5, 5, 5],
        sampling=SamplingParams(max_tokens=1, temperature=0.0)))
    toks, _ = _collect(eng)
    eos = toks["probe"][0]
    eng.add_request(EngineRequest(
        request_id="r", token_ids=[5, 5, 5],
        sampling=SamplingParams(max_tokens=10, temperature=0.0),
        eos_token_ids=(eos,)))
    toks, done = _collect(eng)
    assert done["r"] == FinishReason.STOP
    assert toks["r"] == [eos]


def test_engine_prefix_cache_reuse():
    eng = _tiny_engine()
    prompt = list(range(1, 13))           # 12 tokens = 3 full pages
    eng.add_request(EngineRequest(
        request_id="a", token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    toks_a, _ = _collect(eng)
    eng.add_request(EngineRequest(
        request_id="b", token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    toks_b, _ = _collect(eng)
    assert toks_b["b"] == toks_a["a"]     # identical despite cached prefill
    # The second request must have hit the cache (8 tokens = 2 pages; the
    # third page is excluded by the never-full-prompt rule... prompt is 12
    # tokens so blocks 0,1,2 are cached; trimming keeps 2).
    # Engine metrics expose the hit via num_preemptions==0 and event flow.
    assert eng.prefix_cache.num_cached_pages >= 3


def _drive_one(eng, rid, prompt, max_tokens):
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                ignore_eos=True)))
    return eng._by_id[rid]


def test_prefix_index_hashes_a_page_once():
    """A P-page prompt decoded across two page boundaries feeds the block
    hash P pages at admission and one page at each boundary: the cost
    does not grow with the number of sampled tokens times the length."""
    ps, P = 8, 2
    eng = _tiny_engine(page_size=ps, prefill_buckets=(16, 32))
    before = eng.prefix_cache_stats()["hashed_tokens_total"]
    seq = _drive_one(eng, "r", range(1, P * ps + 1), 2 * ps + 3)
    _collect(eng)
    assert (len(seq.tokens) - 1) // ps == P + 2     # two pages filled
    assert len(seq.page_digests) == P + 2
    assert eng.prefix_cache_stats()["hashed_tokens_total"] - before \
        == P * ps + 2 * ps
    assert seq.page_digests == eng.prefix_cache.block_hashes(seq.tokens)


def test_no_call_site_rehashes_or_slices_the_token_list(monkeypatch):
    """Per token, at a preemption, at readmission and at the finish the
    engine hands the index the sequence's own list and digests: nothing
    calls the from-block-0 ``block_hashes``, nothing builds
    ``tokens[:n]``, and a readmitted sequence hashes nothing twice."""
    ps = 8
    eng = _tiny_engine(page_size=ps, prefill_buckets=(16, 32))
    idx = eng.prefix_cache
    seq = _drive_one(eng, "r", range(1, 2 * ps + 4), 2 * ps + 3)
    calls = []
    real = idx.register_pages

    def spy(digests, tokens, num_computed, pages, settled=0):
        calls.append((digests is seq.page_digests, tokens is seq.tokens))
        return real(digests, tokens, num_computed, pages, settled)

    def banned(*a, **kw):
        raise AssertionError("the engine rehashed a list from block 0")

    monkeypatch.setattr(idx, "register_pages", spy)
    monkeypatch.setattr(idx, "block_hashes", banned)
    monkeypatch.setattr(idx, "register_full_pages", banned)
    for _ in range(6):
        eng.step()
    assert seq.num_generated >= 4
    eng._preempt_seq(seq)
    hashed = idx.hashed_tokens
    assert hashed == len(seq.page_digests) * ps
    while seq.num_computed < len(seq.tokens) - 1:   # readmitted, re-prefilled
        eng.step()
    assert idx.hashed_tokens == hashed
    _collect(eng)
    assert len(calls) >= seq.num_generated
    assert set(calls) == {(True, True)}
    n_full = (len(seq.tokens) - 1) // ps
    assert idx.hashed_tokens == n_full * ps == len(seq.page_digests) * ps


def test_online_preempts_offline():
    """With pages for only ~1 long sequence, an online arrival must preempt
    the running offline one and still complete; the offline request finishes
    afterwards via recompute."""
    eng = _tiny_engine(num_pages=9, max_model_len=32,
                       prefill_buckets=(8, 16, 32))
    eng.ecfg.enable_prefix_cache = False
    eng.prefix_cache.enable = False
    eng.add_request(EngineRequest(
        request_id="off", token_ids=[2] * 8, offline=True,
        sampling=SamplingParams(max_tokens=20, temperature=0.0)))
    # Let the offline request start and generate a few tokens.
    early = []
    for _ in range(5):
        early.extend(eng.step())
    eng.add_request(EngineRequest(
        request_id="on", token_ids=[3] * 16,
        sampling=SamplingParams(max_tokens=8, temperature=0.0)))
    toks, done = _collect(eng, max_steps=400)
    # Prepend the tokens emitted during the manual warm-start steps.
    pre = {}
    for out in early:
        pre.setdefault(out.request_id, []).extend(out.new_token_ids)
    for rid, t in pre.items():
        toks[rid] = t + toks.get(rid, [])
    assert done["on"] == FinishReason.LENGTH
    assert done["off"] == FinishReason.LENGTH
    assert len(toks["on"]) == 8 and len(toks["off"]) == 20
    assert eng.num_preemptions >= 1


def test_online_preempts_offline_mid_chunked_prefill():
    """An offline prompt between chunked-prefill windows holds a slot and
    pages while sitting in ``waiting`` — it must still be a preemption
    victim when an online arrival needs pages (review finding: the victim
    scan only covered ``running``)."""
    eng = _tiny_engine(num_pages=8, max_model_len=32,
                       prefill_buckets=(8,), max_prefill_tokens=8)
    eng.ecfg.enable_prefix_cache = False
    eng.prefix_cache.enable = False
    eng.add_request(EngineRequest(
        request_id="off", token_ids=[2] * 24, offline=True,
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    eng.step()          # first window only: "off" now waits mid-prefill
    off = eng._by_id["off"]
    assert off.slot >= 0 and 0 < off.num_computed < 24
    eng.add_request(EngineRequest(
        request_id="on", token_ids=[3] * 20,
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    toks, done = _collect(eng, max_steps=400)
    assert done["on"] == FinishReason.LENGTH and len(toks["on"]) == 4
    assert done["off"] == FinishReason.LENGTH and len(toks["off"]) == 4
    assert eng.num_preemptions >= 1


def test_finished_request_slot_sampling_resets():
    """A finished top-p request must not leave its sampling params in the
    slot array — later greedy-only batches would pay the full-vocab
    filter sort every step (review finding)."""
    eng = _tiny_engine()
    eng.add_request(EngineRequest(
        "p", [1, 2, 3], sampling=SamplingParams(
            max_tokens=2, temperature=1.0, top_p=0.5)))
    _collect(eng)
    assert all(sp.top_p == 1.0 and sp.temperature in (0.0, 1.0)
               for sp in eng._slot_sampling)
    assert all(sp.top_p == 1.0 for sp in eng._slot_sampling)


def test_cancel_request():
    eng = _tiny_engine()
    eng.add_request(EngineRequest(
        request_id="c", token_ids=[1, 2, 3],
        sampling=SamplingParams(max_tokens=30, temperature=0.0)))
    eng.step()                        # prefill + first token
    eng.cancel("c")
    toks, done = _collect(eng)
    assert done["c"] == FinishReason.CANCELLED
    # All pages returned.
    assert eng.allocator.num_free + eng.prefix_cache.num_cached_pages == \
        eng.ecfg.num_pages - 1


def test_load_metrics_and_events():
    eng = _tiny_engine()
    eng.add_request(EngineRequest(
        request_id="m", token_ids=[4, 5, 6, 7, 8, 9, 10, 11],
        sampling=SamplingParams(max_tokens=6, temperature=0.0)))
    eng.step()
    lm = eng.load_metrics()
    assert lm["running_requests"] == 1 and 0 < lm["kv_cache_usage"] <= 1
    _collect(eng)
    ev = eng.drain_kvcache_event()
    assert len(ev.stored) >= 2        # full pages registered while finishing


class TestKvMigration:
    """PD disaggregation: prefill-side export + decode-side import must be
    bit-equivalent to running the whole request on one engine."""

    def _cfg(self):
        from xllm_service_tpu.config import EngineConfig, ModelConfig
        mcfg = ModelConfig.tiny(vocab_size=128)
        ecfg = EngineConfig(page_size=8, num_pages=32, max_model_len=128,
                            max_batch_size=2, max_prefill_tokens=128,
                            prefill_buckets=(16, 32))
        return mcfg, ecfg

    def test_export_import_continuation_matches_monolithic(self):
        import dataclasses as dc

        from xllm_service_tpu.runtime.engine import Engine, EngineRequest
        from xllm_service_tpu.utils.types import SamplingParams

        mcfg, ecfg = self._cfg()
        prompt = list(range(1, 21))
        sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

        # Monolithic reference run.
        mono = Engine(mcfg, ecfg, seed=0)
        mono.add_request(EngineRequest(
            request_id="m", token_ids=list(prompt), sampling=sp))
        mono_tokens = []
        while mono.has_work():
            for out in mono.step():
                mono_tokens.extend(out.new_token_ids)
        assert len(mono_tokens) == 8

        # Disaggregated: prefill on A (one token, hold), decode on B.
        a = Engine(mcfg, ecfg, seed=0)
        b = Engine(mcfg, ecfg, seed=0)
        a.add_request(EngineRequest(
            request_id="r", token_ids=list(prompt),
            sampling=dc.replace(sp, max_tokens=1),
            hold_after_finish=True))
        first = []
        while a.has_work():
            for out in a.step():
                first.extend(out.new_token_ids)
        assert first == mono_tokens[:1]

        exported = a.export_held("r")
        assert exported is not None
        tokens, k, v = exported
        assert tokens == prompt + first
        assert k.shape[0] == mcfg.num_layers
        assert a.export_held("r") is None   # single-shot

        ok = b.import_sequence(
            EngineRequest(request_id="r", token_ids=list(prompt),
                          sampling=sp),
            tokens, k, v)
        assert ok
        cont = []
        while b.has_work():
            for out in b.step():
                cont.extend(out.new_token_ids)
        assert first + cont == mono_tokens

    def test_import_respects_capacity(self):
        import numpy as np

        from xllm_service_tpu.runtime.engine import Engine, EngineRequest
        from xllm_service_tpu.utils.types import SamplingParams

        mcfg, ecfg = self._cfg()
        b = Engine(mcfg, ecfg, seed=0)
        # Fill both slots.
        for i in range(2):
            b.add_request(EngineRequest(
                request_id=f"f{i}", token_ids=list(range(1, 17)),
                sampling=SamplingParams(max_tokens=64, temperature=0.0,
                                        ignore_eos=True)))
        while b.waiting:
            b.step()
        L, ps = mcfg.num_layers, ecfg.page_size
        k = np.zeros((L, 2, ps, mcfg.num_kv_heads, mcfg.head_dim),
                     np.float32)
        ok = b.import_sequence(
            EngineRequest(request_id="x", token_ids=list(range(1, 16)),
                          sampling=SamplingParams(max_tokens=4)),
            list(range(1, 17)), k, k)
        assert not ok   # no free slot → clean refusal


# ---------------------------------------------------------------------------
# The single-step decode keeps its inputs on the device
# ---------------------------------------------------------------------------

# Sampled token ids of ``_carry_scenario``, recorded on the parent commit
# (host-side ``jax.random.split`` before every decode step, the block
# uploaded whole every step): the key the step program splits itself
# gives the same stream, byte for byte.
_PARENT_STREAMS = {
    ("unseeded", None): {
        "a": [49, 15, 14, 5, 18, 17, 25, 43, 30, 24, 33, 33, 3, 22, 36, 1, 61, 34, 19, 4, 1, 28],
        "off": [20, 5, 54, 13, 19, 33, 8, 15, 55, 5, 58, 17, 31, 61, 49, 24, 36, 57, 22, 50],
        "c": [32, 52, 46, 61, 57, 54, 32, 6, 18, 12, 22, 19, 32, 36],
        "short": [63, 34, 40],
        "x": [44, 23, 15, 7],
    },
    ("unseeded", 8): {
        "a": [49, 15, 14, 5, 6, 14, 39, 43, 45, 30, 12, 49, 17, 22, 5, 15, 61, 24, 10, 19, 4, 1],
        "off": [20, 5, 54, 13, 19, 33, 3, 61, 8, 1, 21, 43, 16, 57, 1, 32, 10, 47, 31, 43],
        "c": [32, 52, 46, 61, 39, 50, 45, 43, 6, 55, 23, 31, 13, 19],
        "short": [63, 34, 40],
        "x": [7, 26, 15, 33, 11, 5],
    },
    ("seeded", None): {
        "a": [58, 50, 20, 26, 4, 12, 1, 58, 21, 62, 0, 52, 55, 45, 25, 34, 4, 15, 56, 51, 51, 50],
        "off": [9, 16, 9, 25, 30, 31, 60, 7, 49, 11, 18, 40, 39, 30, 49, 58, 44, 33, 50, 58],
        "c": [12, 18, 5, 58, 2, 9, 49, 13, 41, 42, 53, 49, 33, 13],
        "short": [4, 56, 2],
        "x": [7, 13, 11, 26],
    },
    ("seeded", 8): {
        "a": [58, 50, 20, 26, 58, 12, 1, 56, 7, 51, 60, 52, 55, 46, 26, 41, 39, 23, 23, 51, 23, 38],
        "off": [9, 16, 9, 25, 58, 31, 25, 7, 44, 11, 18, 40, 58, 46, 2, 22, 37, 33, 50, 23],
        "c": [12, 18, 5, 5, 12, 24, 49, 13, 41, 42, 5, 49, 33, 44],
        "short": [4, 56, 2],
        "x": [7, 13, 11, 26, 32, 3],
    },
}


def _carry_scenario(sampling, window, drop_carry):
    """Page growth across page boundaries (4-token pages), a mid-decode
    admit, a finish, a cancel, page-pressure preemptions of the offline
    request and, with a window, trims. ``drop_carry``: the engine is
    made to forget what the device holds before every step, so every
    decode step uploads its block as the parent did.
    Returns ({rid: (tokens, logprobs, reason)}, engine, trimmed)."""
    cfg = dataclasses.replace(ModelConfig.tiny(vocab_size=64),
                              dtype="float32", sliding_window=window)
    eng = Engine(cfg, EngineConfig(
        page_size=4, num_pages=16 if window is None else 10,
        max_model_len=64, max_batch_size=4, max_prefill_tokens=64,
        prefill_buckets=(8, 16, 32)), seed=0)

    def req(rid, prompt, n, offline=False):
        sp = dict(max_tokens=n, ignore_eos=True)
        if sampling == "greedy":
            sp["temperature"] = 0.0
        elif sampling == "unseeded":
            sp["temperature"] = 1.0
        else:
            sp.update(temperature=1.0, seed=1000 + len(rid) * 7 + prompt[0])
        return EngineRequest(request_id=rid, token_ids=list(prompt),
                             sampling=SamplingParams(**sp), offline=offline)

    feed = {1: [req("a", range(1, 7), 22),
                req("off", range(9, 14), 20, offline=True)],
            4: [req("c", range(3, 11), 14)],
            6: [req("short", range(20, 23), 3),
                req("x", range(30, 35), 30)]}
    toks, lps, reasons = {}, {}, {}
    step = trimmed = 0
    while eng.has_work() or step < max(feed):
        step += 1
        for r in feed.get(step, ()):
            eng.add_request(r)
        if step == 12:
            eng.cancel("x")
        trimmed = max([trimmed] + [s.num_trimmed for s in eng.running])
        if drop_carry:
            eng._decode_carry = None
            eng._ahead_eligible = eng._tail_eligible = lambda *a: False
        for out in eng.step():
            toks.setdefault(out.request_id, []).extend(out.new_token_ids)
            lps.setdefault(out.request_id, []).extend(out.logprobs)
            if out.finished:
                reasons[out.request_id] = out.finish_reason
        assert step < 300, "engine did not drain"
    return ({r: (toks[r], lps[r], reasons.get(r)) for r in toks}, eng,
            trimmed)


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("sampling", ["greedy", "unseeded", "seeded"])
def test_decode_carry_streams_are_the_uploaded_ones(sampling, window):
    """Token ids, logprobs and finish reasons do not depend on whether a
    decode step was handed the resident block or an upload, and the
    sampled streams are the parent commit's."""
    kept, eng, trimmed = _carry_scenario(sampling, window, False)
    dropped, eng_d, _ = _carry_scenario(sampling, window, True)
    assert kept == dropped
    # the scenario did what it says
    assert {r: v[2] for r, v in kept.items()} == {
        "a": FinishReason.LENGTH, "off": FinishReason.LENGTH,
        "c": FinishReason.LENGTH, "short": FinishReason.LENGTH,
        "x": FinishReason.CANCELLED}
    assert eng.num_preemptions >= 1 and len(kept["x"][0]) >= 2
    assert (trimmed > 0) == (window is not None)
    hits = eng.phase_counts["decode.resident_hit"]
    ups = eng.phase_counts["decode.upload"]
    assert hits > 0 and ups > 0
    # a step is launched from its own pack, at the head of its iteration
    # or at the tail of the one before it, or ahead, by the one before
    # it; each counts its block where it chooses it
    assert hits + ups == eng.phase_counts["decode.dispatch"] \
        + eng.phase_counts["decode.ahead_dispatch"] \
        + eng.phase_counts["decode.tail_dispatch"]
    assert eng.phase_counts["decode.tail_hit"] >= 3
    assert eng_d.phase_counts["decode.resident_hit"] == 0
    assert eng_d.phase_counts["decode.upload"] == hits + ups
    if sampling != "greedy":
        assert {r: v[0] for r, v in kept.items()} == \
            _PARENT_STREAMS[sampling, window]


def _count_calls(monkeypatch, *targets):
    """Counting wrappers over ``module.function`` pairs."""
    calls = collections.Counter()

    def wrap(mod, name):
        real = getattr(mod, name)

        def counted(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)
        monkeypatch.setattr(mod, name, counted)
    for mod, name in targets:
        wrap(mod, name)
    return calls


def _steady_engine(**kw):
    d = dict(page_size=16, num_pages=32, max_model_len=128,
             max_batch_size=4, max_prefill_tokens=64,
             prefill_buckets=(8, 16))
    d.update(kw)
    return Engine(dataclasses.replace(ModelConfig.tiny(vocab_size=64),
                                      dtype="float32"),
                  EngineConfig(**d), seed=0)


def _req(rid, prompt, n, temperature=1.0):
    return EngineRequest(request_id=rid, token_ids=list(prompt),
                         sampling=SamplingParams(
                             max_tokens=n, temperature=temperature,
                             ignore_eos=True))


def _uploads_per_step(eng, steps):
    out = []
    for _ in range(steps):
        before = eng.phase_counts["decode.upload"]
        eng.step()
        out.append(eng.phase_counts["decode.upload"] - before)
    return out


def test_steady_decode_steps_make_no_device_call_in_the_pack(monkeypatch):
    """N steps between two events: no block upload, no host-side key
    split, no other host-to-device call: sampled traffic included."""
    eng = _steady_engine()
    eng.add_request(_req("a", range(1, 7), 40))
    eng.add_request(_req("b", range(2, 9), 40, temperature=0.0))
    # the prefill iteration packs the first decode at its tail
    assert _uploads_per_step(eng, 2) == [1, 0]
    calls = _count_calls(monkeypatch, (jax.random, "split"),
                         (jax, "device_put"), (jnp, "asarray"))
    hits = eng.phase_counts["decode.resident_hit"]
    assert _uploads_per_step(eng, 6) == [0] * 6  # 8..13 of a 16-token page
    assert eng.phase_counts["decode.resident_hit"] == hits + 6
    assert not calls, calls
    assert eng._decode_carry is not None
    dev, mirror = eng._decode_carry
    assert np.array_equal(np.asarray(dev), mirror)


@pytest.mark.parametrize("event", ["page_growth", "admit", "finish",
                                   "cancel", "width"])
def test_an_event_costs_exactly_one_upload(event):
    eng = _steady_engine()
    eng.add_request(_req("a", range(1, 7), 60))
    eng.add_request(_req("b", range(2, 9), 5 if event == "finish" else 60))
    # the prefill iteration ends with the first decode packed and uploaded
    assert _uploads_per_step(eng, 3) == [1, 0, 0]
    if event == "page_growth":
        # a: 6 prompt tokens + 1 from prefill + 2 decoded = 9; the step
        # that samples token 16 grows the page for position 16, at its
        # tail (nothing was launched ahead: no page to write onto)
        got = _uploads_per_step(eng, 10)
        assert got == [0] * 6 + [1, 1, 0, 0], got   # b (7 tokens) first
    elif event == "admit":
        eng.add_request(_req("late", range(5, 12), 60))
        # decode (held block), then the prefill that admits, then, at
        # the same iteration's tail, the decode that sees the new row
        assert _uploads_per_step(eng, 3) == [1, 0, 0]
    elif event == "finish":
        # b: 1 token from prefill, 2 decoded; its 5th ends it two steps on
        assert _uploads_per_step(eng, 4) == [0, 0, 1, 0]
    elif event == "cancel":
        eng.cancel("b")
        # the step in flight is taken for a's row first
        assert _uploads_per_step(eng, 3) == [0, 1, 0]
    else:
        # another table width is another shape: a miss, not an error
        # (once the step in flight at the old width is taken: the
        # iteration that takes it packs the wider one at its tail)
        wide = eng._table_width() * 2
        eng._table_width = lambda: wide
        assert _uploads_per_step(eng, 3) == [1, 0, 0]
        assert eng._decode_carry[1].shape[1] == 4 + wide


def test_a_fault_reset_drops_the_carry():
    eng = _steady_engine()
    eng.add_request(_req("a", range(1, 7), 30))
    _uploads_per_step(eng, 3)
    assert eng._decode_carry is not None
    eng.fault_reset(())
    assert eng._decode_carry is None
    toks, done = _collect(eng)
    assert done["a"] == FinishReason.LENGTH


def test_warmup_compiles_side_by_side_and_its_calls_compile_nothing():
    """Warm-up lowers every program, compiles them in threads, and only
    then calls them: each call finds the executable its own lowering
    holds, so the step programs are compiled ONCE each, all of them
    before the first is run (a latent model's ten programs were 217 s one
    after another on a v5e, where its pools' pin bans the persistent
    cache; PERF.md, PR 36)."""
    from jax import monitoring
    eng = _steady_engine(page_size=4)
    compiled = []
    monitoring.register_event_duration_secs_listener(
        lambda event, _dur, **kw: compiled.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)
    walks = []
    real = Engine._warm_programs

    def walk(self, launch, *a):
        walks.append(len(compiled))
        real(self, launch, *a)
        walks.append(len(compiled))

    Engine._warm_programs = walk
    try:
        eng.warmup(prefill_shapes=[(2, 8, 2), (1, 8, 2)],
                   decode_widths=[2, 4])
    finally:
        Engine._warm_programs = real
    calling = walks[3] - walks[2]
    programs = 2 + 2        # two prefill shapes, one decode program a width
    # The threads have compiled every step program (and the lowering walk
    # the tiny programs that make its inert arguments) before the first
    # one is called.
    assert walks[2] - walks[0] >= programs
    # The calling walk compiles no step program either: at most the tiny
    # programs around them (a key split, a slice of its result).
    report = eng.compile_report()
    assert report["prefill"] == 2
    assert report["decode"] == 2
    assert calling <= 2, (walks, compiled)


def test_warmup_leaves_the_key_and_one_cache_entry_per_width():
    """Warm-up runs the decode program with a throwaway key, and a hit
    step, a miss step and warm-up share ONE call signature per width:
    serving adds no cache entry (``compile_report``) and counts no
    recompile."""
    eng = _steady_engine(page_size=4)
    key0 = np.asarray(eng._rng_key)
    eng.warmup(prefill_shapes=[(2, 8, 2), (1, 8, 2)],
               decode_widths=[2, 4, 8])
    assert np.array_equal(np.asarray(eng._rng_key), key0)
    assert eng._decode_carry is None
    warm = eng.compile_report()
    assert warm["decode"] == 3
    eng.add_request(_req("a", range(1, 7), 20))
    eng.add_request(_req("b", range(2, 9), 9, temperature=0.0))
    for step in range(1, 40):
        if step == 6:
            eng.add_request(_req("late", range(11, 17), 5))
        eng.step()
    assert not eng.has_work()
    assert eng.phase_counts["decode.resident_hit"] >= 5
    assert eng.phase_counts["decode.upload"] >= 5
    assert eng.compile_report() == warm
    assert not [k for k, v in eng.phase_report().items()
                if k.endswith(".recompile") and v]


def test_worker_counts_block_uploads_beside_its_steps():
    from http.client import HTTPConnection
    import json

    from xllm_service_tpu.runtime.worker import Worker, WorkerOptions
    from xllm_service_tpu.service.coordination import InMemoryStore
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
            "model": "tiny", "prompt": "count my uploads",
            "max_tokens": 24, "temperature": 0.0,
            "ignore_eos": True})[0] == 200
        text = http("GET", "/metrics")[1]
    finally:
        w.stop()

    def total(name, where=""):
        return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                   if ln.startswith(name + "{") and where in ln)
    eng = w.primary_runtime().engine
    ups = eng.phase_counts["decode.upload"]
    hits = eng.phase_counts["decode.resident_hit"]
    assert ups >= 1 and hits >= 10
    assert total("xllm_worker_decode_block_uploads_total") == ups
    assert ups + hits == total("xllm_worker_steps_total",
                               'phase="decode"') \
        + total("xllm_worker_steps_total", 'phase="mixed"')


# ---------------------------------------------------------------------------
# Scoped warmup (Engine.warmup prefill_shapes / decode_widths) covers a
# schedule whose shapes the caller states
# ---------------------------------------------------------------------------

def test_scoped_warmup_covers_bench_schedule():
    """A budgeted caller warms only the programs its workload compiles
    (a step program compiles in tens of seconds), as the benchmark does
    from its mix's ``warmup`` data. After a scoped warmup with the
    schedule's shapes, the run must trigger ZERO post-warmup
    recompiles."""
    cfg = ModelConfig.tiny(vocab_size=256)
    ecfg = EngineConfig(page_size=16, num_pages=256, max_model_len=256,
                        max_batch_size=16, max_prefill_tokens=128,
                        prefill_buckets=(32,))
    engine = Engine(cfg, ecfg, seed=0)
    batch, prompt_len, gen_len = 16, 32, 64
    # 128 prefill tokens admit 4 prompts at once; under the token budget
    # later admissions shrink down the pow2 ladder (B 4, 2, 1) at the one
    # bucket, table width pow2(pages(33)) = 4; contexts 33..96 walk the
    # decode widths 4 and 8.
    engine.warmup(prefill_shapes=[(1, 32, 4), (2, 32, 4), (4, 32, 4)],
                  decode_widths=[4, 8])

    sp = SamplingParams(max_tokens=gen_len, temperature=0.0,
                       ignore_eos=True)
    for i in range(batch):
        # Distinct prompts: identical ones prefix-cache hit after the
        # first batch and change later batch shapes.
        engine.add_request(EngineRequest(
            request_id=f"bench-{i}",
            token_ids=[(i + j) % (cfg.vocab_size - 1) + 1
                       for j in range(prompt_len)], sampling=sp))
    done = 0
    while engine.has_work():
        for out in engine.step():
            if out.finish_reason != FinishReason.NONE:
                done += 1
    assert done == batch
    recompiles = {k: v for k, v in engine.phase_report().items()
                  if k.endswith(".recompile") and v}
    assert not recompiles, f"scoped warmup missed programs: {recompiles}"


def test_scoped_warmup_covers_ragged_bucket_ladder():
    """Ragged twin of the scoped-warmup pin: with the one-dispatch
    mixed step on, warmup pre-compiles the ragged bucket ladder (pow2
    combined batch × prefill bucket × table width), so the bench-shaped
    run still triggers ZERO post-warmup recompiles — and actually
    exercises the ragged program while doing so."""
    cfg = ModelConfig.tiny(vocab_size=256)
    ecfg = EngineConfig(page_size=16, num_pages=128, max_model_len=128,
                        max_batch_size=8, max_prefill_tokens=64,
                        prefill_buckets=(32,), ragged_attn=True)
    engine = Engine(cfg, ecfg, seed=0)
    batch, prompt_len, gen_len = 8, 32, 24
    # 64 prefill tokens admit 2 prompts at once (then 1 under decode
    # load); contexts 33..56 stay at table width 4.
    engine.warmup(prefill_shapes=[(1, 32, 4), (2, 32, 4)],
                  decode_widths=[4])

    sp = SamplingParams(max_tokens=gen_len, temperature=0.0,
                        ignore_eos=True)
    for i in range(batch):
        engine.add_request(EngineRequest(
            request_id=f"bench-{i}",
            token_ids=[(i + j) % (cfg.vocab_size - 1) + 1
                       for j in range(prompt_len)], sampling=sp))
    done = 0
    while engine.has_work():
        for out in engine.step():
            if out.finish_reason != FinishReason.NONE:
                done += 1
    assert done == batch
    assert engine.phase_counts["ragged.dispatch"] > 0
    recompiles = {k: v for k, v in engine.phase_report().items()
                  if k.endswith(".recompile") and v}
    assert not recompiles, f"ragged warmup missed programs: {recompiles}"
