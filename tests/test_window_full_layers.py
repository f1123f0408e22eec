"""Sliding-window attention layers that rotate between full attention
layers that rotate nothing, their keys and values in TWO pools with an
allocator and a page table each (``afmoe``, PR 57): the operator "swa"
of the loop over layer kinds, the four-norm body, the window pool's
trimming and the tails the prefix index keeps there. At a tiny size on
the CPU:

(a) prefill then decode through BOTH pools equals the plain reference's
    full forward (``chipbench/reference/swa_gqa_moe.py``: the whole
    sequence under a mask), at a context of several windows, so that
    trimming has happened;
(b) the same tokens served with and without trimming give the same
    logits, and a row never holds more than ``W / page_size + 2`` window
    pages in decode;
(c) a follow-up resumed at a cached document's tail equals the cold run;
    a boundary whose tail was evicted is not matched and the row
    recomputes from the deepest live one; tails are evicted only when no
    row holds them;
(d) the eight shares of an expert layer, the shared expert counted once,
    add up to the uncut reference's layer;
(e) a discarded launch ahead leaves both pools as they were;
(f) the step programs of the window-free configurations lower to the
    parent's text (``tests/pins/step_programs_pr57.json``);
(g) ``from_hf_config`` reads the published ``config.json`` and refuses
    each unserved key by name; what two pools cannot do is refused at
    each door.
"""

import dataclasses
import functools
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec, weights
from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer as T
from xllm_service_tpu.obs import steptrace
from xllm_service_tpu.ops.plan import KernelPlan, decode_walk_columns
from xllm_service_tpu.parallel import expert
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.runtime.kv_cache import (
    PageAllocator, PrefixCacheIndex)
from xllm_service_tpu.utils.types import SamplingParams

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG_DIR = os.path.join(ROOT, "chipbench", "configs", "trinity-mini")
PUBLISHED = spec.load_json(os.path.join(CONFIG_DIR, "config.json"))
PINS = os.path.join(HERE, "pins", "step_programs_pr57.json")

# Every width tiny, every ratio the family's own: 4 query heads a
# key-value head, (window, window, window, full) twice with two leading
# dense layers, 4 experts held of 8 x 4 routed, 8 a token; a window of
# four pages.
PS, W = 8, 32
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_hidden_layers=8, vocab_size=512,
            sliding_window=W, layer_types=PUBLISHED["layer_types"][:8])
SEED = 5


def hf(dtype="float32", **over):
    return {**PUBLISHED, **TINY, "torch_dtype": dtype, **over}


def model(dtype="float32", **over) -> ModelConfig:
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf(dtype, **over), "trinity-tiny"),
        dtype=dtype)


@pytest.fixture(scope="module")
def made():
    """(hf config, ModelConfig, program tree, reference params) from one
    seed: the program's tree and the reference's per-layer leaves hold
    the same values."""
    wts = spec.load_weights(CONFIG_DIR)
    cfg = hf()
    key = weights.root_key(SEED)
    return (cfg, model(), wts.program_tree(cfg, SEED), {
        **wts.head_params(cfg, key),
        "layers": [wts.layer_params(cfg, key, i, kind)
                   for i, kind in enumerate(wts.layer_kinds(cfg))]})


@pytest.fixture(scope="module")
def params(made):
    return made[2]


TOKENS = [int(t) for t in np.random.default_rng(0).integers(1, 512, 160)]


# ---------------------------------------------------------------------------
# (a), (b) the program through both pools against the plain reference
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def compiled(mc, plan):
    """(prefill, decode) of ``mc`` under ``plan``, each compiled once a
    shape."""
    return (jax.jit(lambda p, w, s, n, kv, pt: T.forward_prefill(
                p, mc, w, s, n, kv, pt, return_all_logits=True,
                plan=plan)[:3]),
            jax.jit(lambda p, t, pos, kv, pt: T.forward_decode(
                p, mc, t, pos, jnp.asarray([True]), kv, pt, plan=plan)[:2]))


def through_pools(params, mc, toks, n_prefill, bucket, trim, plan):
    """Logits [len(toks), V]: ``n_prefill`` tokens in windows of
    ``bucket``, the rest one decode step each, the window table trimmed
    behind the window after every step as the engine trims it (or never),
    and the most window pages the row held in decode."""
    prefill, decode = compiled(mc, plan)
    mp = -(-len(toks) // PS)
    kv = T.init_kv_cache(mc, mp + 1, PS, jnp.float32, window_pages=mp + 1)
    full = np.arange(1, mp + 1, dtype=np.int32)
    win = full.copy()

    def tables(upto):
        # pages the row has grown into (the engine allocates as it goes)
        live = (np.arange(mp) < -(-upto // PS)).astype(np.int32)
        return jnp.asarray(np.concatenate([full * live, win * live])[None])

    def trimmed(computed):
        if trim:
            win[:max((computed - W) // PS, 0)] = 0

    got, start, held = [], 0, 0
    while start < n_prefill:
        n = min(bucket, n_prefill - start)
        window = np.zeros((1, bucket), np.int32)
        window[0, :n] = toks[start:start + n]
        _, everything, kv = prefill(
            params, jnp.asarray(window), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), kv, tables(start + bucket))
        got.append(np.asarray(everything)[0, :n])
        start += n
        trimmed(start)
    for pos in range(n_prefill, len(toks)):
        pt = tables(pos + 1)
        held = max(held, int((np.asarray(pt)[0, mp:] != 0).sum()))
        logits, kv = decode(params, jnp.asarray([toks[pos]]),
                            jnp.asarray([pos]), kv, pt)
        got.append(np.asarray(logits))
        trimmed(pos + 1)
    return np.concatenate(got, axis=0), held


KERNELS = KernelPlan(decode_attn=True, kv_writers=True,
                     write_then_attend=True, page_aligned=True,
                     interpret=True)


@pytest.mark.parametrize("plan,n,n_prefill", [
    (KernelPlan(), 160, 120), (KERNELS, 88, 48)],
    ids=["xla", "kernels_interpreted"])
def test_prefill_then_decode_through_both_pools_equals_the_reference(
        made, plan, n, n_prefill):
    cfg, mc, params, ref_params = made
    ref = spec.load_reference(CONFIG_DIR)
    toks = TOKENS[:n]
    want = np.asarray(ref.forward(ref_params, toks, cfg))
    # prefilled in windows of 24 (which cross pages), 40 decoded: several
    # windows of context, trimmed behind each step
    got, held = through_pools(params, mc, toks, n_prefill, 24, True, plan)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert held <= W // PS + 2
    if plan.interpret:
        return          # the interpreter is slow: the XLA plan runs both
    # ... and the trimming changed nothing that anybody reads
    untrimmed, all_of_them = through_pools(params, mc, toks, n_prefill, 24,
                                           False, plan)
    np.testing.assert_array_equal(got, untrimmed)
    assert all_of_them == n // PS


def test_a_fault_in_the_window_or_the_rotation_fails_the_comparison(made):
    """The comparison sees what it is there to see: a full layer that
    rotates, a window one position wider, a dropped output gate."""
    cfg, mc, params, ref_params = made
    ref = spec.load_reference(CONFIG_DIR)
    want = np.asarray(ref.forward(ref_params, TOKENS, cfg))
    for fault in (dict(use_rope=True), dict(sliding_window=W + 1),
                  dict(attn_gate=False), dict(sandwich_norm=False),
                  dict(embedding_multiplier=1.0)):
        got, _ = through_pools(params, dataclasses.replace(mc, **fault),
                               TOKENS[:80], 64, 32, True, KernelPlan())
        assert np.abs(got - want[:80]).max() > 1e-3 * np.abs(want).max(), \
            fault


def test_a_window_layers_prefill_gathers_the_columns_its_window_reaches():
    """The gather of a window layer's prefill is (W + T - 2) // ps + 2
    columns wide whatever the table's width; a full layer's is the
    table's."""
    mc = model()
    params = jax.eval_shape(lambda: T.init_params(mc, jax.random.PRNGKey(0)))
    mp, bucket = 64, 16
    kv = jax.eval_shape(lambda: T.init_kv_cache(
        mc, mp + 1, PS, jnp.float32, window_pages=mp + 1))
    text = jax.jit(lambda p, kv, toks, s, n, pt: T.forward_prefill(
        p, mc, toks, s, n, kv, pt)[0]).lower(
        params, kv, jnp.zeros((1, bucket), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 2 * mp), jnp.int32)).as_text()
    reach = (W + bucket - 2) // PS + 2
    assert f"tensor<1x{reach}x{PS}x1x32xf32>" in text       # the window's
    assert f"tensor<1x{mp}x{PS}x1x32xf32>" in text          # a full layer's


# ---------------------------------------------------------------------------
# the engine: two allocators, two tables, tails
# ---------------------------------------------------------------------------

DOC = [int(t) for t in np.random.default_rng(7).integers(1, 512, 100)]
ASK = [int(t) for t in np.random.default_rng(8).integers(1, 512, 13)]
N_OUT = 12


def engine(params, **kw) -> Engine:
    defaults = dict(page_size=PS, num_pages=96, max_model_len=192,
                    max_batch_size=4, max_prefill_tokens=64,
                    prefill_buckets=(8, 16, 32))
    defaults.update(kw)
    return Engine(model(), EngineConfig(**defaults), params=params, seed=0)


def add(eng, rid, prompt, n=N_OUT):
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True)))
    return eng._by_id[rid]


def drain(eng, got=None, each=None, max_steps=600):
    got = got if got is not None else {}
    for i in range(max_steps):
        if not eng.has_work():
            break
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
        if each is not None:
            each(i)
    assert not eng.has_work()
    return got


def pool_is_sound(eng):
    """Every window page is free or held, the counts agree, and with no
    row alive the tails hold what is held."""
    w = eng.window
    assert w.pages_live + w.allocator.num_free == w.num_pages - 1
    if not eng._by_id:
        held = {p for pages, _ in w._tails.values() for p in pages}
        assert set(w._ref) == held


@pytest.fixture(scope="module")
def cold(params, made):
    """The unshared run of DOC + ASK, and that its tokens are the
    reference's greedy choices (teacher-forced, as the benchmark's check
    reads them)."""
    eng = engine(params)
    seq = add(eng, "cold", DOC + ASK)
    seen = []
    toks = drain(eng, each=lambda i: seen.append(
        sum(p != 0 for p in seq.wpages)))["cold"]
    cfg, _, _, ref_params = made
    ref = spec.load_reference(CONFIG_DIR)
    logits = np.asarray(ref.forward(ref_params, DOC + ASK + toks[:-1], cfg))
    best = logits[len(DOC + ASK) - 1:].argmax(axis=-1)
    assert toks == [int(t) for t in best]
    # 113 prompt tokens in windows of 32: never more than the window's
    # pages and one window's growth, and W / ps + 2 once it decodes
    assert max(seen) <= (W + 32) // PS + 1
    assert max(seen[-N_OUT + 2:]) <= W // PS + 2
    st = eng.window_stats()
    assert st["trimmed"] > 0 and st["taken"] == 1 and st["hits"] == 0
    pool_is_sound(eng)
    # what the sparse layers counted: 8 choices a valid row a layer,
    # each computed here or held elsewhere, none dropped
    moe = eng.moe_stats
    rows = len(DOC + ASK) + N_OUT - 1
    assert moe["assignments"] + moe["elsewhere"] == 8 * 6 * rows
    assert moe["dropped"] == 0 and 0 < moe["assignments"] < moe["elsewhere"]
    return toks


def test_a_follow_up_resumes_at_the_documents_tail_and_equals_the_cold_run(
        params, cold):
    eng = engine(params)
    add(eng, "doc", DOC, 1)                 # the document, as set-up does
    drain(eng)
    w = eng.window
    # 100 tokens over pages of 8: the deepest boundary is 96 = page 12,
    # and its tail is the W / ps = 4 window pages that end there
    assert w.num_tails == 1 and w.pages_live == W // PS
    pool_is_sound(eng)
    seq = add(eng, "ask", DOC + ASK)
    got = drain(eng)
    assert got["ask"] == cold
    assert seq.num_cached_tokens == 96
    st = eng.window_stats()
    assert (st["hits"], st["misses"]) == (1, 0)
    # the follow-up's own prompt left a tail too (at 112), never hit
    assert st["taken"] == 2
    pool_is_sound(eng)
    # a prompt that shares its first pages with the document and not its
    # boundary: the chain matches pages that have no tail, nothing is
    # resumed from, and that is counted
    seq = add(eng, "half", DOC[:40] + ASK)
    drain(eng)
    assert seq.num_cached_tokens == 0
    assert eng.window_stats()["misses"] == 1


def test_only_the_blocks_under_a_tail_are_told_to_the_cluster(params):
    eng = engine(params)
    seq = add(eng, "doc", DOC, 3)
    drain(eng)
    ev = eng.drain_kvcache_event()
    assert ev.stored == seq.page_digests[:12] and not ev.removed
    # the tail goes: so does what was told
    eng.window.drop(next(iter(eng.window._tails)))
    ev = eng.drain_kvcache_event()
    assert ev.removed == seq.page_digests[:12] and not ev.stored
    pool_is_sound(eng)
    assert eng.window.pages_live == 0


def test_an_evicted_tail_is_not_matched_and_the_row_recomputes(params, cold):
    eng = engine(params)
    add(eng, "doc", DOC, 1)
    drain(eng)
    add(eng, "short", DOC[:48], 1)         # a second boundary, at 48
    drain(eng)
    w = eng.window
    assert w.num_tails == 2
    w.drop(eng.prefix_cache.page_of(
        eng.prefix_cache.block_hashes(DOC)[11]))   # the document's goes
    seq = add(eng, "ask", DOC + ASK)
    got = drain(eng)
    # the deepest live boundary is the short one's: resumed there, the
    # rest recomputed, the same tokens
    assert seq.num_cached_tokens == 48
    assert got["ask"] == cold
    # (the short prompt's own lookup matched the document's first five
    # pages, which end at no tail: the first miss)
    st = eng.window_stats()
    assert st["misses"] == 2 and st["hits"] == 1
    pool_is_sound(eng)


def test_tails_are_evicted_least_recently_hit_and_never_under_a_row(params):
    eng = engine(params, max_batch_size=2)   # two tails, 2 x 6 + 2 x 4 + 6
    w = eng.window
    assert (w.max_tails, w.tail_pages) == (2, W // PS)
    add(eng, "doc", DOC, 1)
    drain(eng)
    add(eng, "ask", DOC + ASK, 2)          # hits the document's tail
    drain(eng)
    assert w.num_tails == 2 and w.tail_hits == 1
    doc_pid = eng.prefix_cache.page_of(eng.prefix_cache.block_hashes(DOC)[11])
    # a third finished prefill needs a tail: the follow-up's own, never
    # hit, goes first; the document's, hit, stays
    other = [int(t) for t in np.random.default_rng(9).integers(1, 512, 50)]
    add(eng, "other", other, 1)
    drain(eng)
    assert w.num_tails == 2 and w.tail_evictions == 1
    assert w.tail_of(doc_pid) is not None
    # while a row reads the document's tail it cannot go, whatever asks
    seq = add(eng, "reader", DOC + ASK, 4)
    eng.step()
    assert seq.num_cached_tokens == 96 and set(w.tail_of(doc_pid)) \
        & set(seq.wpages)
    w._unhit.clear(), w._hit.move_to_end(doc_pid, last=False)
    before = w.tail_evictions
    while w._evict_one():
        pass
    assert w.tail_of(doc_pid) is not None
    assert w.tail_evictions - before <= 1
    drain(eng)
    pool_is_sound(eng)


def test_preemption_lets_go_of_both_pools_and_resumes_at_a_tail(params, cold):
    eng = engine(params)
    seq = add(eng, "victim", DOC + ASK)
    got = {}
    for _ in range(8):
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    assert seq.wpages and eng.window.num_tails == 1
    eng.drain_pipeline()
    eng._preempt_seq(seq)
    assert seq.wpages == [] and seq.pages == []
    pool_is_sound(eng)
    assert drain(eng, got)["victim"] == cold
    # readmitted at the tail its own prompt left at 112
    assert seq.preemptions == 1 and seq.num_cached_tokens == 112
    pool_is_sound(eng)


def test_a_short_window_pool_queues_and_preempts_like_a_short_full_pool(
        params, cold):
    """Admission and growth over both pools: with the window pool nearly
    all held, a second row waits, and the tokens are the cold run's."""
    eng = engine(params, max_batch_size=2, enable_prefix_cache=False)
    w = eng.window
    hold = w.alloc(w.allocator.num_free - 9)    # 9 pages left: one row's
    a = add(eng, "a", DOC + ASK)
    b = add(eng, "b", DOC + ASK)
    got = drain(eng)
    assert got["a"] == cold and got["b"] == cold
    assert a.preemptions + b.preemptions >= 0
    w.release(hold)
    pool_is_sound(eng)
    assert w.pages_live == 0


def test_the_matched_tail_is_held_before_the_rows_own_pages_are_asked_for(
        params, cold):
    """A short window pool makes room by evicting tails nobody holds: the
    tail an admission has just matched is held first, so the room its
    own pages need is never made of it."""
    eng = engine(params, max_batch_size=2)
    add(eng, "doc", DOC, 1)
    drain(eng)
    w = eng.window
    hold = w.alloc(w.allocator.num_free)    # room only by evicting a tail
    seq = add(eng, "ask", DOC + ASK)
    eng.step()
    assert seq.slot < 0 and w.num_tails == 1 and w.tail_evictions == 0
    pool_is_sound(eng)
    w.release(hold)
    assert drain(eng)["ask"] == cold and seq.num_cached_tokens == 96
    pool_is_sound(eng)


# (e) ----------------------------------------------------------------------

def mixed_traffic(eng, each=None):
    """Three rows that start apart, two of them on the cached document."""
    add(eng, "seed", DOC, 1)
    got = drain(eng)
    add(eng, "a", DOC + ASK, 30)
    add(eng, "b", ASK + DOC[:30], 21)
    for _ in range(5):
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    add(eng, "c", DOC + ASK[:5], 14)
    return drain(eng, got, each)


def test_a_discarded_launch_ahead_leaves_both_pools_as_they_were(params):
    plain = mixed_traffic(engine(params))
    eng = engine(params)
    discards = []

    def force(i):
        if i % 3 == 0 and eng._pending is not None:
            eng.drain_pipeline()
            discards.append(i)

    forced = mixed_traffic(eng, force)
    assert discards and forced == plain
    counts = eng.phase_counts
    assert counts["decode.ahead_discard"] + counts["decode.tail_discard"] \
        >= len(discards)
    pool_is_sound(eng)


def test_a_decode_step_writes_only_the_rows_newest_position_in_each_pool(
        params):
    eng = engine(params)
    seq = add(eng, "row", DOC[:40])
    for _ in range(4):
        eng.step()
    eng.drain_pipeline()
    before = [np.asarray(p) for p in eng.kv]
    pos = len(seq.tokens) - 1
    eng.step()
    eng.drain_pipeline()
    after = [np.asarray(p) for p in eng.kv]
    for pool, (b, a), table in ((0, (before[0], after[0]), seq.pages),
                                (3, (before[3], after[3]), seq.wpages)):
        changed = np.argwhere((b != a).any(axis=(0, 3, 4)))
        # the step read here and the one it launched ahead: the row's
        # position and the next, nothing else
        assert {(int(p), int(s)) for p, s in changed} <= {
            (table[q // PS], q % PS) for q in (pos, pos + 1)}, pool


# ---------------------------------------------------------------------------
# (d) the share ties to the model
# ---------------------------------------------------------------------------

def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        made):
    cfg, mc, params, ref_params = made
    ref = spec.load_reference(CONFIG_DIR)
    wts = spec.load_weights(CONFIG_DIR)
    key = weights.root_key(SEED)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    # the uncut layer: all 32 routed experts held by one chip
    whole_cfg = {**cfg, "num_experts": 32, "expert_share_chips": 1}
    lp = wts.layer_params(whole_cfg, key, 2, "swa+moe")
    whole = np.asarray(ref.experts(h, lp, whole_cfg, ref.mm_f32))
    shared = np.asarray(jax.nn.silu(h @ lp["mlp.shared_experts.gate_proj"])
                        * (h @ lp["mlp.shared_experts.up_proj"])
                        ) @ np.asarray(lp["mlp.shared_experts.down_proj"])
    parts = np.zeros_like(whole)
    stats = np.zeros(len(expert.MOE_STATS), np.int64)
    for rank in range(8):
        share = {**cfg, "expert_share_rank": rank}
        part_lp = {**lp, **{
            f"mlp.experts.{w}_proj":
                lp[f"mlp.experts.{w}_proj"][4 * rank:4 * rank + 4]
            for w in ("gate", "up", "down")}}
        # the reference's share: its routed part and the shared expert
        one = np.asarray(ref.experts(h, part_lp, share, ref.mm_f32)) - shared
        # the program's: the same gate over all 32, its 4 alone computed
        rmc = dataclasses.replace(mc, expert_share_rank=rank)
        topi, topw = T._deepseek_gate(rmc, h, lp["mlp.router.gate"],
                                      lp["mlp.expert_bias"])
        got, st = expert.dropless_moe(
            h, topi, topw, jnp.ones((24,), bool),
            *(part_lp[f"mlp.experts.{w}_proj"][None]
              for w in ("gate", "up", "down")), layer=jnp.int32(0),
            first_held=rmc.first_held_expert, routed=rmc.router_experts)
        np.testing.assert_allclose(np.asarray(got), one, atol=2e-5)
        parts += one
        stats += np.asarray(st)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-4)
    st = dict(zip(expert.MOE_STATS, stats.tolist()))
    assert st["assignments"] == 8 * 24 and st["dropped"] == 0
    assert st["elsewhere"] == 7 * 8 * 24


# ---------------------------------------------------------------------------
# (f) the window-free configurations' step programs
# ---------------------------------------------------------------------------

WINDOW_FREE = ("joyai-llm-flash", "lfm2-24b-a2b", "ouro-2.6b",
               "falcon-h1-34b", "solar-open2-250b", "brumby-14b")


def digests(name: str):
    """{program: sha256 of its lowered text} for configuration ``name``
    at its rehearsal widths, page 128, batch 4, under the CPU's default
    plan. (``mistral-7b-v01``: tests/test_step_program_pins.py.)"""
    from xllm_service_tpu.runtime import engine as E
    d = os.path.join(ROOT, "chipbench", "configs", name)
    cfg = spec.load_json(os.path.join(d, "config.json"))
    cfg.update(spec.load_json(os.path.join(d, "meta.json"))
               ["rehearsal_widths"])
    eng = E.Engine(ModelConfig.from_hf_config(cfg, name),
                   EngineConfig(page_size=128, num_pages=32,
                                max_model_len=2048, max_batch_size=4))
    key, out = jax.random.PRNGKey(0), {}
    tail = E._STATE_COLS if eng.cfg.num_state_layers else 0
    for B, T_, mp in ((1, 256, 16), (2, 128, 8)):
        st = eng._sampling_tensors([], B)
        bias = eng._batch_bias([], B, eng.cfg.vocab_size)
        out[f"prefill:B{B}xT{T_}xmp{mp}"] = eng._jit_prefill.lower(
            eng.params,
            jnp.zeros((B, E._PREFILL_HDR + T_ + mp + tail), jnp.int32),
            eng.kv, *st, key, None, None, None, *bias, None, T_).as_text()
    B = eng.ecfg.max_batch_size
    st = eng._sampling_tensors([], B)
    bias = eng._batch_bias([], B, eng.cfg.vocab_size)
    out["decode:mp16"] = eng._jit_decode.lower(
        eng.params, jnp.zeros((B, E._PACK_COLS + 16), jnp.int32),
        eng.kv, *st, key, None, *bias).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in out.items()}


@pytest.mark.parametrize("name", WINDOW_FREE)
def test_a_window_free_configurations_step_programs_lower_to_the_parents_text(
        name):
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), name], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
             "XLLM_PALLAS": "0"},
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    with open(PINS) as f:
        pins = json.load(f)["digests"][name]
    assert json.loads(p.stdout.splitlines()[-1]) == pins


def test_a_window_free_model_keeps_no_window_pool_and_one_table():
    d = os.path.join(ROOT, "chipbench", "configs", "solar-open2-250b")
    cfg = spec.load_json(os.path.join(d, "config.json"))
    cfg.update(spec.load_json(os.path.join(d, "meta.json"))
               ["rehearsal_widths"])
    mc = ModelConfig.from_hf_config(cfg, "solar")
    assert mc.num_swa_layers == 0 and T._ranks(mc) == T._RANKS
    assert len(T.init_kv_cache(mc, 8, 128, state_slots=4,
                               window_pages=64)) == 4
    eng = Engine(mc, EngineConfig(page_size=128, num_pages=16,
                                  max_model_len=1024, max_batch_size=2))
    assert eng.window is None and eng.window_stats() is None
    assert eng._slot_wpt.shape[1] == 0 and eng._tables == 1
    assert eng.prefix_cache.tails is None


# ---------------------------------------------------------------------------
# (g) the config door, and what two pools refuse
# ---------------------------------------------------------------------------

def test_the_published_config_reads_into_layer_kinds():
    mc = ModelConfig.from_hf_config(
        {**PUBLISHED, "num_experts": 128, "vocab_size": 200192,
         "expert_share_chips": 1}, "trinity-mini")
    kinds = mc.layer_kinds
    assert kinds[:2] == ("swa+dense",) * 2 and len(kinds) == 32
    assert kinds[2:30] == ("swa+moe", "attn+moe", "swa+moe", "swa+moe") * 7
    assert kinds[30:] == ("swa+moe", "attn+moe")
    assert T.kinds_pattern(kinds) == (2, 4, 7)
    assert (mc.num_swa_layers, mc.num_attn_layers) == (24, 8)
    assert mc.sliding_window == 2048 and not mc.use_rope
    assert mc.qk_norm and mc.attn_gate and mc.sandwich_norm
    assert mc.embedding_multiplier == 2048 ** 0.5
    assert mc.lm_head_multiplier == 1.0
    assert (mc.num_experts, mc.router_experts) == (128, 128)
    assert mc.moe_scoring == "sigmoid" and mc.norm_topk_prob
    assert mc.routed_scaling_factor == 2.826 and mc.moe_gate_eps == 1e-20
    assert mc.n_shared_experts == 1 and mc.dropless_experts
    # the file as run: 16 held of the same 128
    run = ModelConfig.from_hf_config(PUBLISHED, "trinity-mini")
    assert (run.num_experts, run.router_experts) == (16, 128)
    assert run.vocab_size == 25024


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("n_group", 2), ("topk_group", 2), ("score_func", "softmax"),
    ("hidden_act", "gelu"), ("num_shared_experts", 2),
    ("route_norm", False), ("mup_enabled", False),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("layer_types", ["sliding_attention"] * 31 + ["chunked_attention"]),
    ("sliding_window", None)])
def test_from_hf_config_refuses_each_unserved_key_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**PUBLISHED, key: value}, "x")


def test_a_window_layer_beside_a_state_or_a_tail_is_refused():
    with pytest.raises(ValueError, match="swa"):
        ModelConfig(layer_kinds=("swa+dense", "conv+dense"),
                    sliding_window=32, conv_kernel=3)
    with pytest.raises(ValueError, match="sliding_window"):
        ModelConfig(layer_kinds=("swa+dense", "attn+dense"))


def test_what_two_pools_cannot_do_is_refused_at_each_door(params):
    eng = engine(params)
    assert not eng.pages_only and not eng.keeps_state
    assert eng.host_tier is None
    assert eng.state_stats() is None
    seq = add(eng, "doc", DOC, 1)
    drain(eng)
    # PD import, block export and adoption: refused, cleanly
    assert eng.import_sequence(
        EngineRequest(request_id="x", token_ids=DOC[:9],
                      sampling=SamplingParams(max_tokens=1)),
        DOC[:9], None, None) is False
    assert eng.export_blocks(seq.page_digests[:1]) in (None, [], ([], None),
                                                       ([], [], None))
    with pytest.raises(ValueError, match="one device"):
        Engine(model(), EngineConfig(page_size=PS, num_pages=16,
                                     max_model_len=64, max_batch_size=2),
               params=params, mesh=object())
    with pytest.raises(NotImplementedError):
        T.forward_embedding(params, model(), jnp.zeros((1, 8), jnp.int32),
                            jnp.asarray([8], jnp.int32))
    from xllm_service_tpu.runtime import checkpoint
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        checkpoint.save_checkpoint(params, model(), "/nonexistent")


def test_the_plan_line_says_the_two_walks(params, caplog):
    import logging
    with caplog.at_level(logging.INFO):
        eng = engine(params)
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("engine plan"))
    mp = eng.ecfg.max_pages_per_seq
    # (on the CPU the XLA reference serves and gathers the whole table;
    # where the kernel serves, the window's pages and one)
    assert f"the 6 window layers walk {eng._decode_walk(mp)} columns" in line
    assert f"the 2 full layers all {mp} of theirs" in line
    assert decode_walk_columns(mp, PS, W) == W // PS + 1
    assert decode_walk_columns(264, 128, 2048) == 17
    assert "swa+dense 2" in line and "attn+moe 2" in line
    assert "xllm.kv.window_trim" in steptrace.SPAN_NAMES
    assert "xllm.kv.window_tail" in steptrace.SPAN_NAMES


def test_a_checkpoint_under_the_published_names_loads_into_the_stacks(
        made, tmp_path):
    """The loader against the generator's leaves, written out under the
    family's names [out, in], the held experts at their own indices."""
    from safetensors.numpy import save_file
    from xllm_service_tpu.runtime import checkpoint
    cfg, mc, params, ref_params = made
    rank = dataclasses.replace(mc, expert_share_rank=2)
    out = {"model.embed_tokens.weight": ref_params["embed"],
           "model.norm.weight": ref_params["final_norm"],
           "lm_head.weight": ref_params["lm_head"].T}
    for i, lp in enumerate(ref_params["layers"]):
        for name, v in lp.items():
            v = np.asarray(v)
            key = f"model.layers.{i}.{name}"
            if name.startswith("mlp.experts."):
                for e in range(v.shape[0]):
                    out[key.replace("experts.", f"experts.{8 + e}.")
                        + ".weight"] = v[e].T
            elif name == "mlp.expert_bias":
                out[key] = v
            elif v.ndim == 2:
                out[key + ".weight"] = v.T
            else:
                out[key + ".weight"] = v
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    loaded = checkpoint.load_checkpoint(str(tmp_path), rank)
    assert jax.tree_util.tree_structure(loaded) \
        == jax.tree_util.tree_structure(params)
    # (a float32 norm weight drawn inside ``lax.map`` and alone differ by
    # a unit in the last place now and then: chipbench/weights.py)
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# the window pool on its own
# ---------------------------------------------------------------------------

def test_the_window_pool_counts_rows_and_tails_apart():
    from xllm_service_tpu.runtime.kv_cache import WindowPool
    w = WindowPool(num_pages=12, tail_pages=2, max_tails=2)
    dropped = []
    w.on_drop = dropped.append
    row = w.alloc(4)
    assert w.attach(100, row[2:], [b"a", b"b"])
    assert not w.attach(100, row[2:], [b"a", b"b"])      # has one already
    assert not w.attach(101, [0, row[3]], [b"c"])        # a trimmed page
    # the row lets go: the tail's two pages stay, the others are free
    w.release(row)
    assert w.pages_live == 2 and w.tail_of(100) == row[2:]
    # a reader of the tail pins it against eviction
    w.acquire(w.tail_of(100))
    assert w.alloc(10) is None and w.num_tails == 1
    w.release(row[2:])
    got = w.alloc(10)                                    # now it can go
    assert got is not None and w.num_tails == 0
    assert dropped == [[b"a", b"b"]] and w.tail_evictions == 1
    w.release(got)
    assert w.pages_live == 0 and w.pages_peak == 10
    # where max_tails are held a new tail takes a never-hit one's place
    # and never a hit one's: not even when every never-hit one is pinned
    w = WindowPool(num_pages=20, tail_pages=2, max_tails=2)
    a, b, c = w.alloc(2), w.alloc(2), w.alloc(2)
    assert w.attach(1, a, []) and w.attach(2, b, [])
    w.note_hit(1)
    w.release(a)                # the hit tail: no row reads it now
    assert not w.attach(3, c, []) and w.tail_of(1) == a
    w.release(b)                # the never-hit one's row has finished
    assert w.attach(3, c, []) and w.tail_of(2) is None
    assert w.tail_of(1) == a and w.tail_evictions == 1


def test_the_index_without_tails_tells_the_cluster_what_it_told():
    idx = PrefixCacheIndex(PageAllocator(8), 4)
    toks = list(range(12))
    pages = idx.alloc(3)
    idx.register_pages([], toks, 12, pages)
    assert len(idx.drain_event().stored) == 3


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    print(json.dumps(digests(sys.argv[1])))
