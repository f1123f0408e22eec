"""A Mamba-2 mixer beside attention in every layer (``falcon_h1``, PR 45):
the operator "mix" of the loop over layer kinds, its matrix state in a
pool addressed by SLOT, snapshots at page boundaries under the prefix
index, and an engine that admits, preempts, finishes and launches ahead
with slots in hand. At a tiny size on the CPU:

(a) the program through its pools equals the plain reference's full
    forward (``chipbench/reference/gqa_ssm_decoder.py``: the recurrence
    token by token, where the program's prefill is the chunked form);
(b) a prompt prefilled in two windows equals one window, and a bucket's
    padding does not move the state;
(c) a prefix hit that restores a snapshot continues to the unshared
    run's tokens, and a chain with no snapshot recomputes from 0;
(d) the pipeline on against off, and a forced discard of a launch ahead,
    give byte-identical greedy streams;
(e) slot exhaustion queues, finish and preemption free, a reclaimed page
    frees its snapshot, the least recently hit snapshot is the one
    evicted;
(f) ``from_hf_config`` reads the published ``config.json`` verbatim and
    refuses each unsupported key with a message that names it;
(g) the paged decode kernel at a group of 5 query heads a key-value head
    in the Pallas interpreter equals the XLA reference.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec, weights
from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer as T
from xllm_service_tpu.ops.plan import KernelPlan
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.runtime.kv_cache import (
    PageAllocator, PrefixCacheIndex, SlotAllocator)
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "chipbench", "configs", "falcon-h1-34b")
PUBLISHED = spec.load_json(os.path.join(CONFIG_DIR, "config.json"))

# Every width tiny, every ratio the family's own: 5 query heads a
# key-value head, two groups of B and C, a chunk that divides the
# buckets, a page that is a whole number of chunks.
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=5,
            num_key_value_heads=1, head_dim=16, mamba_n_heads=4,
            mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=16,
            mamba_n_groups=2, mamba_chunk_size=8, num_hidden_layers=3,
            vocab_size=512)
PS = 8
SEED = 5


def hf(dtype="float32", **over):
    return {**PUBLISHED, **TINY, "torch_dtype": dtype, **over}


def model(dtype="float32", **over) -> ModelConfig:
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf(dtype, **over), "falcon-h1-tiny"),
        dtype=dtype)


@pytest.fixture(scope="module")
def made():
    """{dtype: (hf config, ModelConfig, program tree, reference params)}
    from one seed: the program's tree and the reference's per-layer
    leaves hold the same values."""
    wts = spec.load_weights(CONFIG_DIR)
    out = {}
    for dtype in ("float32", "bfloat16"):
        cfg = hf(dtype)
        key = weights.root_key(SEED)
        out[dtype] = (cfg, model(dtype), wts.program_tree(cfg, SEED), {
            **wts.head_params(cfg, key),
            "layers": [wts.layer_params(cfg, key, i, "mix+dense")
                       for i in range(TINY["num_hidden_layers"])]})
    return out


TOKENS = np.random.default_rng(0).integers(1, 512, size=64)


def pools(mc, slots=12, pages=16):
    return T.init_kv_cache(mc, pages, PS, jnp.dtype(mc.dtype),
                           state_slots=slots)


def table(*pages, width=8):
    return jnp.asarray([list(pages) + [0] * (width - len(pages))],
                       jnp.int32)


def prefill(params, mc, kv, toks, start, pt, cols, bucket, plan=KernelPlan(),
            all_logits=False):
    """One window of ``toks`` from position ``start``, padded to
    ``bucket``."""
    window = np.zeros((1, bucket), np.int32)
    window[0, :len(toks)] = toks
    last, everything, kv = T.forward_prefill(
        params, mc, jnp.asarray(window), jnp.asarray([start], jnp.int32),
        jnp.asarray([len(toks)], jnp.int32), kv, pt,
        return_all_logits=all_logits, plan=plan,
        state_cols=jnp.asarray([cols], jnp.int32))[:3]
    return (np.asarray(everything)[0, :len(toks)] if all_logits
            else np.asarray(last)[0]), kv


def decode(params, mc, kv, tok, pos, pt, row=1, plan=KernelPlan()):
    logits, kv = T.forward_decode(
        params, mc, jnp.asarray([tok]), jnp.asarray([pos]),
        jnp.asarray([True]), kv, pt, plan=plan,
        state_rows=jnp.asarray([row]))[:2]
    return np.asarray(logits)[0], kv


# ---------------------------------------------------------------------------
# (a) the program through its pools against the plain reference
# ---------------------------------------------------------------------------

# float32: the two sides differ in the order of their sums alone (the
# chunked scan against the token-by-token one). bfloat16: the program
# rounds every product's inputs and the residual stream to 8 bits of
# mantissa where the reference reads the SAME stored weights up into
# float32: a logit of size 4 moves by a few hundredths a layer.
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 0.25)])
def test_prefill_then_decode_through_the_pools_is_the_references_forward(
        made, dtype, tol):
    cfg, mc, params, ref_params = made[dtype]
    ref = spec.load_reference(CONFIG_DIR)
    n, more = 37, 7
    want = np.asarray(ref.forward(ref_params, TOKENS[:n + more], cfg))
    pt = table(1, 2, 3, 4, 5, 6)
    # one row (state row 1: slots 1 and 2), the window padded to 40
    got, kv = prefill(params, mc, pools(mc), TOKENS[:n], 0, pt,
                      (0, Engine._live_slot(1, n - 1), 0, 0), 40,
                      all_logits=True)
    scale = np.abs(want).max()
    assert np.abs(got - want[:n]).max() <= tol * scale
    for pos in range(n, n + more):
        logits, kv = decode(params, mc, kv, TOKENS[pos], pos, pt)
        assert np.abs(logits - want[pos]).max() <= tol * scale, pos


def test_the_decode_kernel_in_the_interpreter_is_the_xla_form(made):
    """``plan.ssm_decode``: the Pallas update maps each row's block by
    its slot and aliases the pool; interpreted here, compiled for the
    chip in tests/test_chip_compile.py."""
    _, mc, params, _ = made["float32"]
    pt = table(1, 2, 3)
    _, kv = prefill(params, mc, pools(mc), TOKENS[:17], 0, pt,
                    (0, Engine._live_slot(1, 16), 0, 0), 24)
    xla, kv_x = decode(params, mc, kv, TOKENS[17], 17, pt)
    kernel, kv_k = decode(params, mc, kv, TOKENS[17], 17, pt,
                          plan=KernelPlan(ssm_decode=True, interpret=True))
    np.testing.assert_allclose(kernel, xla, atol=2e-6)
    np.testing.assert_allclose(np.asarray(kv_k[3]), np.asarray(kv_x[3]),
                               atol=1e-6)
    # the ring under the same bit, written in place: the scatter's, bit
    # for bit (ops/pallas/ring_update.py)
    np.testing.assert_array_equal(np.asarray(kv_k[2]), np.asarray(kv_x[2]))
    assert np.abs(np.asarray(kv_k[2]) - np.asarray(kv[2])).max() > 0
    # the step at position 17 wrote row 1's slot of the ODD positions
    # (2) and left the state as of 16 (slot 1) as it was
    np.testing.assert_array_equal(np.asarray(kv_k[3][:, 1]),
                                  np.asarray(kv[3][:, 1]))
    assert np.abs(np.asarray(kv_k[3][:, 2])
                  - np.asarray(kv[3][:, 2])).max() > 0


# A decode step's ring write in place (ops/pallas/ring_update.py) against
# the XLA scatter it replaces, at both families' rings: a delta-rule
# layer's 4 x 24,576 over q | k | v and the mixer's 4 x 5,120. Rows, by
# position at a page of 8: in the middle of a page; an INACTIVE lane
# (its page's ring stays); one that OPENS a page (the whole ring comes
# from the inputs behind it, which lie in the page before); a sequence's
# START (zeros before it); its third token (one zero before it).
RING_POSITIONS = [13, 12, 8, 0, 2]
RING_ACTIVE = [True, False, True, True, True]


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("K, C", [(4, 24576), (4, 5120)])
def test_the_ring_written_in_place_is_the_scatters_ring(K, C, layer):
    import types
    cfg = types.SimpleNamespace(conv_kernel=K)
    rng = np.random.default_rng(C + layer)
    rows = len(RING_POSITIONS)
    tails = jnp.asarray(rng.standard_normal((3, 12, K, C)), jnp.bfloat16)
    pos = jnp.asarray(RING_POSITIONS, jnp.int32)
    one = jnp.asarray(RING_ACTIVE).astype(jnp.int32)
    # row b's pages are 2b + 1 and 2b + 2; page 0 is the null page
    pt = jnp.asarray([[2 * b + 1, 2 * b + 2, 0] for b in range(rows)],
                     jnp.int32)
    x = jnp.asarray(rng.standard_normal((rows, 1, C)), jnp.bfloat16)

    def step(plan):
        prev = T._ring_read(cfg, tails, layer, pt, pos, PS)
        zz = jnp.concatenate([prev, x], axis=1)
        after = T._ring_write(cfg, tails, layer, pt, pos, one, zz, PS, plan)
        # what the NEXT step reads behind its position
        return prev, after, T._ring_read(cfg, after, layer, pt, pos + 1, PS)
    prev, want, want_next = jax.jit(lambda: step(KernelPlan()))()
    _, got, got_next = jax.jit(lambda: step(
        KernelPlan(ssm_decode=True, interpret=True)))()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_next),
                                  np.asarray(want_next))
    got, before = np.asarray(got), np.asarray(tails)
    # the other layers, the null page and the inactive lane's pages: bit
    # for bit what they were
    others = [c for c in range(3) if c != layer]
    np.testing.assert_array_equal(got[others], before[others])
    np.testing.assert_array_equal(got[layer, [0, 3, 4]],
                                  before[layer, [0, 3, 4]])
    # every live row wrote its input to ring row t mod K of its page
    for b, (t, live) in enumerate(zip(RING_POSITIONS, RING_ACTIVE)):
        if live:
            np.testing.assert_array_equal(
                got[layer, 2 * b + 1 + t // PS, t % K], np.asarray(x)[b, 0])
    # the row that opened a page took the three inputs behind it along
    np.testing.assert_array_equal(got[layer, 6, [1, 2, 3]],
                                  before[layer, 5, [1, 2, 3]])
    # zeros before a sequence's start, and none after it
    assert not np.asarray(prev)[3].any() and not np.asarray(prev)[4][:1].any()
    assert not np.asarray(got_next)[3][:2].any()
    assert np.asarray(got_next)[3][2].any()


# ---------------------------------------------------------------------------
# (b) windows and padding
# ---------------------------------------------------------------------------

def test_two_windows_equal_one_and_padding_does_not_move_the_state(made):
    _, mc, params, _ = made["float32"]
    pt = table(1, 2, 3, 4)
    n = 29
    one, kv1 = prefill(params, mc, pools(mc), TOKENS[:n], 0, pt,
                       (0, Engine._live_slot(1, n - 1), 0, 0), 32)
    # the same tokens padded to a longer bucket: the positions behind
    # the window's end move neither the state nor the tail
    wide, kv_wide = prefill(params, mc, pools(mc), TOKENS[:n], 0, pt,
                            (0, Engine._live_slot(1, n - 1), 0, 0), 64)
    np.testing.assert_allclose(wide, one, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kv_wide[3]), np.asarray(kv1[3]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(kv_wide[2]), np.asarray(kv1[2]),
                               atol=1e-5)
    # two windows: 16 tokens, then 13 that start from the first's state
    # (an even count: read and write slot are the same slot)
    _, kv2 = prefill(params, mc, pools(mc), TOKENS[:16], 0, pt,
                     (0, Engine._live_slot(1, 15), 0, 0), 16)
    two, kv2 = prefill(params, mc, kv2, TOKENS[16:n], 16, pt,
                       (Engine._live_slot(1, 15),
                        Engine._live_slot(1, n - 1), 0, 0), 16)
    np.testing.assert_allclose(two, one, atol=1e-5)
    slot = Engine._live_slot(1, n - 1)
    np.testing.assert_allclose(np.asarray(kv2[3][:, slot]),
                               np.asarray(kv1[3][:, slot]), atol=1e-5)


def test_a_snapshot_is_the_state_at_its_page_boundary(made):
    """The window hands back, beside its final state, the state after
    ``snap_len`` of its tokens: what a window that ENDS there leaves."""
    _, mc, params, _ = made["float32"]
    pt = table(1, 2, 3, 4)
    _, kv = prefill(params, mc, pools(mc), TOKENS[:29], 0, pt,
                    (0, Engine._live_slot(1, 28), 9, 3 * PS), 32)
    _, short = prefill(params, mc, pools(mc), TOKENS[:3 * PS], 0, pt,
                       (0, 5, 0, 0), 24)
    np.testing.assert_allclose(np.asarray(kv[3][:, 9]),
                               np.asarray(short[3][:, 5]), atol=1e-5)
    assert np.abs(np.asarray(kv[3][:, 9])).max() > 0
    # a window from a copy of it (another row: slots 3 and 4) continues
    # to the logits of the unshared run
    want, _ = prefill(params, mc, pools(mc), TOKENS[:29], 0, pt,
                      (0, 1, 0, 0), 32)
    got, _ = prefill(params, mc, kv, TOKENS[3 * PS:29], 3 * PS,
                     table(1, 2, 3, 7), (9, Engine._live_slot(2, 28), 0, 0),
                     8)
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# the engine: slots in hand
# ---------------------------------------------------------------------------

PROMPT = [int(t) for t in np.random.default_rng(7).integers(1, 512, 21)]
OTHER = [int(t) for t in np.random.default_rng(8).integers(1, 512, 19)]
N_OUT = 10


def engine(params, **kw) -> Engine:
    defaults = dict(page_size=PS, num_pages=48, max_model_len=96,
                    max_batch_size=4, max_prefill_tokens=64,
                    prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return Engine(model(), EngineConfig(**defaults), params=params, seed=0)


def add(eng, rid, prompt, n=N_OUT):
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True)))
    return eng._by_id[rid]


def drain(eng, got=None, each=None, max_steps=400):
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


@pytest.fixture(scope="module")
def params(made):
    return made["float32"][2]


@pytest.fixture(scope="module")
def cold(params, made):
    """The unshared run of PROMPT, and that its tokens are the
    reference's greedy choices (teacher-forced, as the benchmark's check
    reads them)."""
    eng = engine(params)
    add(eng, "cold", PROMPT)
    toks = drain(eng)["cold"]
    cfg, _, _, ref_params = made["float32"]
    ref = spec.load_reference(CONFIG_DIR)
    logits = np.asarray(ref.forward(ref_params, PROMPT + toks[:-1], cfg))
    best = logits[len(PROMPT) - 1:].argmax(axis=-1)
    assert toks == [int(t) for t in best]
    st = eng.state_stats()
    assert (st["restored"], st["snapshotted"], st["live"]) == (0, 1, 0)
    return toks


# (c) ----------------------------------------------------------------------

def test_a_hit_restores_the_snapshot_and_continues_to_the_cold_tokens(
        params, cold):
    eng = engine(params)
    add(eng, "first", PROMPT)
    drain(eng)
    # 21 tokens over pages of 8: the snapshot sits at 16, the last full
    # page boundary, and the same prompt again resumes from it
    seq = add(eng, "again", PROMPT)
    got = drain(eng)
    assert got["again"] == cold
    assert seq.num_cached_tokens == 16
    assert eng.state_stats()["restored"] == 1
    # a prompt that shares ONE page with it: the chain matches page 0,
    # which has no snapshot, so nothing is resumed from
    seq = add(eng, "one-page", PROMPT[:PS] + OTHER)
    drain(eng)
    assert seq.num_cached_tokens == 0
    assert eng.state_stats()["restored"] == 1


def test_a_chain_with_no_snapshot_recomputes_from_zero(params, cold):
    """The snapshot evicted (the least recently hit making room), its
    pages stay registered and match, and nothing resumes from them."""
    eng = engine(params)
    add(eng, "first", PROMPT)
    drain(eng)
    pc = eng.prefix_cache
    while pc.snapshot_slots.num_free:           # hand the free slots out
        pc.reserve_snapshot()
    pc.snapshot_slots.free(pc.reserve_snapshot())   # and evict the one
    assert pc.num_cached_pages > 0 and pc.num_snapshots == 0
    seq = add(eng, "again", PROMPT)
    assert drain(eng)["again"] == cold
    assert seq.num_cached_tokens == 0
    st = eng.state_stats()
    assert (st["restored"], st["evicted"]) == (0, 1)


def test_a_prompt_longer_than_a_bucket_snapshots_in_the_window_that_crosses(
        params):
    """Chunked prefill: 37 tokens in windows of 16, 16 and 5; the last
    full page boundary (32) closes the second window, which is not the
    prompt's last."""
    long_prompt = PROMPT + OTHER[:16]
    eng = engine(params, prefill_buckets=(8, 16))
    add(eng, "whole", long_prompt)
    want = drain(eng)["whole"]
    assert eng.state_stats()["snapshotted"] == 1
    seq = add(eng, "again", long_prompt)
    assert drain(eng)["again"] == want
    assert seq.num_cached_tokens == 32
    one = engine(params)                       # the whole prompt at once
    add(one, "whole", long_prompt)
    assert drain(one)["whole"] == want


# (d) ----------------------------------------------------------------------

def mixed_traffic(eng, each=None):
    """Three rows that start apart, one of them on a cached prefix."""
    add(eng, "seed", PROMPT, 3)
    got = drain(eng)
    add(eng, "a", PROMPT, 24)
    add(eng, "b", OTHER, 17)
    for _ in range(4):
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    add(eng, "c", PROMPT[:16] + OTHER, 12)
    return drain(eng, got, each)


def test_the_pipeline_on_against_off_and_a_forced_discard_give_the_same_streams(
        params):
    sequential = engine(params)
    sequential._ahead_eligible = sequential._tail_eligible = \
        lambda *a: False
    want = mixed_traffic(sequential)
    assert sequential.phase_counts["decode.ahead_dispatch"] == 0

    ahead = engine(params)
    assert mixed_traffic(ahead) == want
    assert ahead.phase_counts["decode.ahead_hit"] > 5

    # Every third iteration the step in flight is thrown away after it
    # has run on the device: it has advanced every row's state and
    # written its tail, and the step that replaces it must read what
    # the discarded one read.
    torn = engine(params)
    assert mixed_traffic(
        torn, each=lambda i: i % 3 == 0 and torn.drain_pipeline()) == want
    discards = (torn.phase_counts["decode.ahead_discard"]
                + torn.phase_counts["decode.tail_discard"])
    assert discards > 3


# (e) ----------------------------------------------------------------------

def test_no_state_row_means_no_admission_and_a_finish_frees_one(params, cold):
    eng = engine(params)
    assert eng.kv[3].shape[1] == 1 + 2 * 4 + 4      # null, rows, snapshots
    held = [eng.state_rows.alloc() for _ in range(3)]   # one row is left
    first, second = add(eng, "first", PROMPT, 6), add(eng, "second", OTHER, 6)
    got = {out.request_id: list(out.new_token_ids) for out in eng.step()}
    assert (first.state_row, second.state_row) == (4, 0)
    assert second.slot < 0 and second in eng.waiting
    got = drain(eng, got)
    assert got["first"] == cold[:6] and len(got["second"]) == 6
    assert eng.state_rows.num_free == 1 and held == [1, 2, 3]


def test_preemption_drops_the_live_state_and_resumes_from_the_snapshot(
        params, cold):
    eng = engine(params)
    seq = add(eng, "victim", PROMPT)
    got = {}
    for _ in range(4):
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    assert seq.state_row == 1
    eng.drain_pipeline()
    eng._preempt_seq(seq)
    assert seq.state_row == 0 and eng.state_rows.num_free == 4
    assert drain(eng, got)["victim"] == cold
    assert seq.preemptions == 1
    # readmitted on its own pages: from the snapshot its first prefill
    # left at 16, not from 0 and not from the pages behind it
    assert seq.num_cached_tokens == 16
    assert eng.state_stats()["restored"] == 1


def index(pages=8, snapshots=2) -> PrefixCacheIndex:
    pc = PrefixCacheIndex(PageAllocator(pages), PS)
    pc.enable_snapshots(SlotAllocator(9, snapshots))
    return pc


def cached(pc, tokens):
    """Register ``tokens``' full pages as a finished sequence's and give
    the last of them a snapshot; returns (pages, the snapshot's slot)."""
    digests = []
    pages = pc.alloc(len(tokens) // PS)
    pc.register_pages(digests, tokens, len(tokens), pages)
    slot = pc.reserve_snapshot()
    assert pc.attach_snapshot(digests[-1], slot)
    pc.release_pages(pages)
    return pages, slot


def toks(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 99, n)]


def test_a_match_ends_at_the_deepest_page_that_has_a_snapshot():
    pc = index()
    a = toks(1, 3 * PS)
    pages, slot = cached(pc, a)
    got, n = pc.match_prefix(a + [7])
    assert (got, n) == (pages, 3 * PS) and pc.snapshot_of(got[-1]) == slot
    pc.release_pages(got)
    # the middle page gets one too: a prompt that leaves the chain after
    # two pages resumes from it
    mid = pc.reserve_snapshot()
    digests = []
    pc.extend_digests(digests, a, 2 * PS)
    assert pc.attach_snapshot(digests[1], mid)
    got, n = pc.match_prefix(a[:2 * PS] + toks(2, 5))
    assert n == 2 * PS and pc.snapshot_of(got[-1]) == mid
    pc.release_pages(got)
    # a page that has one keeps it: a second for the same page is
    # refused, and its slot is free again
    assert pc.snapshot_slots.num_free == 0
    spare = pc.reserve_snapshot()           # evicts the least recently hit
    assert not pc.attach_snapshot(digests[1], spare)
    assert pc.snapshot_slots.num_free == 1


def test_the_least_recently_hit_snapshot_is_the_one_evicted():
    pc = index(pages=12, snapshots=2)
    a, b = toks(1, PS), toks(2, PS)
    (pa,), sa = cached(pc, a)
    (pb,), sb = cached(pc, b)
    hit, _ = pc.match_prefix(a + [1])       # a is hit after b was made
    pc.release_pages(hit)
    assert pc.reserve_snapshot() == sb and pc.snapshots_evicted == 1
    # b's page stays registered and can no longer be resumed from
    assert pc.num_cached_pages == 2
    assert pc.match_prefix(b + [1]) == ([], 0)
    assert pc.match_prefix(a + [1])[1] == PS and pc.snapshot_of(pa) == sa


def test_a_snapshot_nobody_ever_hit_goes_before_one_that_was_hit():
    """A turn's own snapshot (made last, never asked for again) must not
    push out a system prompt's, however long ago that was hit."""
    pc = index(pages=12, snapshots=3)
    (pa,), sa = cached(pc, toks(1, PS))
    pc.release_pages(pc.match_prefix(toks(1, PS) + [1])[0])   # a: hit once
    (pb,), sb = cached(pc, toks(2, PS))
    (pc_,), sc = cached(pc, toks(3, PS))
    # b and c were made after a's hit and never hit: the older goes
    assert pc.reserve_snapshot() == sb
    digests = []
    page = pc.alloc(1)
    pc.register_pages(digests, toks(4, PS), PS, page)
    assert pc.attach_snapshot(digests[0], sb)
    assert pc.reserve_snapshot() == sc          # then c, then the new one
    assert pc.snapshot_of(pa) == sa and pc.snapshot_of(page[0]) == sb


def test_a_reclaimed_page_frees_its_snapshot():
    pc = index(pages=4, snapshots=2)        # three usable pages
    (page,), slot = cached(pc, toks(1, PS))
    assert pc.snapshot_slots.num_free == 1
    got = pc.alloc(3)                       # pressure: the cached page goes
    assert page in got and pc.snapshot_of(page) == 0
    assert pc.snapshot_slots.num_free == 2 and pc.snapshots_evicted == 1
    assert pc.num_snapshots == 0


def test_slot_allocators_hand_out_their_own_range_once():
    slots = SlotAllocator(3, 2)
    assert [slots.alloc(), slots.alloc(), slots.alloc()] == [3, 4, None]
    slots.free(4)
    with pytest.raises(ValueError):
        slots.free(4)
    with pytest.raises(ValueError):
        slots.free(5)
    with pytest.raises(ValueError):
        SlotAllocator(0, 2)                 # slot 0 is the null slot


def test_such_a_models_pages_do_not_move_and_it_takes_no_mesh(params):
    eng = engine(params)
    assert not eng.pages_only and eng.state_model
    assert eng.host_tier is None
    add(eng, "held", PROMPT, 2)
    drain(eng)
    assert not eng.export_blocks([b"x"])
    with pytest.raises(ValueError, match="one device"):
        Engine(model(), EngineConfig(page_size=PS, num_pages=8),
               mesh=object())


# (f) ----------------------------------------------------------------------

def test_from_hf_config_reads_the_published_config_verbatim():
    mc = ModelConfig.from_hf_config(
        {**PUBLISHED, "num_hidden_layers": 72}, "falcon-h1-34b")
    assert mc.layer_kinds == ("mix+dense",) * 72
    assert (mc.num_attn_layers, mc.num_conv_layers, mc.num_ssm_layers) \
        == (72, 72, 72)
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.intermediate_size, mc.vocab_size) \
        == (5120, 20, 4, 128, 21504, 261120)
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_groups,
            mc.ssm_chunk, mc.conv_kernel) == (32, 128, 256, 2, 128, 4)
    assert (mc.ssm_inner, mc.ssm_conv_dim) == (4096, 5120)
    # a ring of 4 inputs over the 5120 convolved channels, not
    # hidden_size by name
    assert mc.conv_tail_shape == (4, 5120)
    assert mc.rope_theta == 1e11 and mc.rope_scaling is None
    assert not mc.tie_word_embeddings and not mc.is_moe
    assert (mc.embedding_multiplier, mc.lm_head_multiplier,
            mc.attention_in_multiplier, mc.key_multiplier,
            mc.attention_out_multiplier, mc.ssm_in_multiplier,
            mc.ssm_out_multiplier) == tuple(PUBLISHED[k] for k in (
                "embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "key_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"))
    assert mc.ssm_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert mc.mlp_multipliers == tuple(PUBLISHED["mlp_multipliers"])
    # the fourth pool: a layer's state of one sequence is 4.19 MB
    kv = jax.eval_shape(lambda: T.init_kv_cache(
        dataclasses.replace(mc, layer_kinds=mc.layer_kinds[:6]), 256, 128,
        state_slots=97))
    assert [p.shape for p in kv] == [
        (6, 256, 128, 4, 128), (6, 256, 128, 4, 128), (6, 256, 4, 5120),
        (6, 97, 32, 256, 128)]
    assert kv[3].dtype == jnp.float32
    assert 32 * 128 * 256 * 4 == 4_194_304


@pytest.mark.parametrize("key, value", [
    ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
    ("mamba_use_mlp", False), ("rope_scaling", {"rope_type": "linear",
                                                "factor": 2.0}),
    ("attn_layer_indices", [0, 2]), ("attention_bias", True),
    ("mamba_proj_bias", True), ("mlp_bias", True),
    ("projectors_bias", True), ("hidden_act", "gelu"),
    ("mamba_d_ssm", 2048)])
def test_from_hf_config_refuses_what_it_does_not_run_by_name(key, value):
    with pytest.raises(ValueError, match=f"falcon_h1 with {key}="):
        ModelConfig.from_hf_config({**PUBLISHED, key: value}, "x")


def test_a_mixer_is_in_every_layer_or_in_none():
    # (since PR 49 a state layer may stand beside attention layers; what
    # one pool of tails cannot hold beside a ring is a "conv" layer's row)
    with pytest.raises(ValueError, match="'conv' operator has no 'mix'"):
        dataclasses.replace(model(), layer_kinds=("mix+dense", "conv+dense"))
    with pytest.raises(ValueError, match="'mix' operator"):
        dataclasses.replace(model(), ssm_heads=0)


def test_the_plan_says_how_the_mixer_runs(params, caplog):
    import logging
    with caplog.at_level(logging.INFO):
        eng = engine(params)
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("engine plan:"))
    assert "layer kinds mix+dense 3" in line
    assert "ssm_prefill xla_chunked, ssm_decode xla" in line
    assert "4 state rows x 2 + 4 snapshots" in line
    assert not eng.plan.ssm_decode and not eng.plan.mixed_step
    assert eng.plan.write_then_attend


# (g) ----------------------------------------------------------------------

@pytest.mark.parametrize("group", [5, 4])
def test_the_paged_decode_kernel_at_a_group_of_five(group):
    from xllm_service_tpu.ops.attention import paged_decode_attention
    from xllm_service_tpu.ops.pallas import paged_decode_attention_pallas
    rng = np.random.default_rng(group)
    hkv, d, ps, pages, B, mp = 4, 128, 16, 12, 3, 4
    q = jnp.asarray(rng.standard_normal((B, hkv * group, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, pages, ps, hkv, d)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, pages, ps, hkv, d)),
                    jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    ctx = jnp.asarray([40, 17, 64], jnp.int32)
    want = paged_decode_attention(q, k[1], v[1], pt, ctx)
    got = paged_decode_attention_pallas(q, k, v, pt, ctx, interpret=True,
                                        layer=jnp.asarray(1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_the_loader_reads_the_published_checkpoints_names(tmp_path):
    """A checkpoint written under HF's ``FalconH1*`` names (torch's [out,
    in], the ONE input projection, the depthwise filter [C, 1, K]) loads
    into the tree the benchmark's generator hands the program."""
    from safetensors.numpy import save_file
    from xllm_service_tpu.runtime.checkpoint import load_checkpoint
    cfg = hf()
    wts = spec.load_weights(CONFIG_DIR)
    key = weights.root_key(9)
    head = wts.head_params(cfg, key)
    out = {"model.embed_tokens.weight": np.asarray(head["embed"]),
           "model.final_layernorm.weight": np.asarray(head["final_norm"]),
           "lm_head.weight": np.ascontiguousarray(
               np.asarray(head["lm_head"]).T)}
    bare = ("mamba.dt_bias", "mamba.A_log", "mamba.D", "mamba.conv1d.bias")
    for i in range(cfg["num_hidden_layers"]):
        for name, leaf in wts.layer_params(cfg, key, i,
                                           "mix+dense").items():
            leaf, at = np.asarray(leaf), f"model.layers.{i}.{name}"
            if name == "mamba.conv1d.weight":
                out[at] = np.ascontiguousarray(leaf.T[:, None])
            elif name in bare:
                out[at] = leaf
            elif leaf.ndim == 2:
                out[at + ".weight"] = np.ascontiguousarray(leaf.T)
            else:
                out[at + ".weight"] = leaf
    save_file(out, str(tmp_path / "model.safetensors"))
    got = load_checkpoint(str(tmp_path), model())
    want = wts.program_tree(cfg, 9)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat_got) == set(flat_want)
    for path, leaf in flat_want.items():
        np.testing.assert_allclose(np.asarray(flat_got[path]),
                                   np.asarray(leaf), rtol=1e-6,
                                   err_msg=str(path))


def test_a_worker_serves_it_and_exports_the_slots_ledger(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: the same prompt twice,
    the second time from the first's pages and a copy of its snapshot;
    the slots' ledger on ``/metrics`` and ``state`` in the step
    records; a PREFILL instance of such a model is refused."""
    import json
    from http.client import HTTPConnection
    from chipbench import cluster
    from xllm_service_tpu.obs import validate_exposition
    from xllm_service_tpu.runtime import worker as W
    from xllm_service_tpu.service.coordination import InMemoryStore
    model_dir = cluster.write_model_dir(str(tmp_path / "model"),
                                        hf("bfloat16"))
    ecfg = dict(page_size=16, num_pages=32, max_model_len=256,
                max_batch_size=4)
    with pytest.raises(ValueError, match="PD migration"):
        W.Worker(W.WorkerOptions(model="fh1-tiny", model_dir=model_dir,
                                 instance_type=W.InstanceType.PREFILL),
                 InMemoryStore(), engine_cfg=EngineConfig(**ecfg))
    w = W.Worker(W.WorkerOptions(model="fh1-tiny", model_dir=model_dir),
                 InMemoryStore(), engine_cfg=EngineConfig(**ecfg)).start()
    try:
        host, port = w.name.rsplit(":", 1)

        def call(method, path, body=None):
            conn = HTTPConnection(host, int(port), timeout=300)
            try:
                conn.request(method, path, body=body, headers={
                    "Content-Type": "application/json"})
                r = conn.getresponse()
                return r.status, r.read().decode()
            finally:
                conn.close()

        body = json.dumps({
            "model": "fh1-tiny", "max_tokens": 6, "temperature": 0.0,
            "prompt": " ".join(f"t{i}" for i in range(5, 45)),
            "ignore_eos": True})
        first = call("POST", "/v1/completions", body)
        again = call("POST", "/v1/completions", body)
        assert first[0] == again[0] == 200
        assert json.loads(first[1])["choices"][0]["text"] \
            == json.loads(again[1])["choices"][0]["text"]
        text = call("GET", "/metrics")[1]
        validate_exposition(text)

        def metric(name, **labels):
            return sum(float(ln.rsplit(" ", 1)[1])
                       for ln in text.splitlines()
                       if ln.startswith(name + "{") and all(
                           f'{k}="{v}"' in ln for k, v in labels.items()))

        eng = w.primary_runtime().engine
        rows = "xllm_worker_state_rows_total"
        assert metric(rows, event="restored") == 1
        # 40 tokens over pages of 16: each prompt leaves a snapshot at
        # 32; the second's finds the page taken and gives its slot back
        assert metric(rows, event="snapshotted") == 1
        assert metric(rows, event="evicted") == 0
        slots = "xllm_worker_state_slots"
        assert (metric(slots, kind="live"), metric(slots, kind="snapshot"),
                metric(slots, kind="free")) == (0, 1, 3)
        assert metric("xllm_worker_state_pool_bytes") \
            == eng.kv[2].nbytes + eng.kv[3].nbytes
        assert eng.kv[3].shape == (3, 1 + 3 * 4, 4, 16, 8)
        recs = [r["state"] for r in w.steptrace.tail() if r["state"]]
        assert sum(r["restored"] for r in recs) == 1
        assert sum(r["snapshotted"] for r in recs) == 1
        assert max(r["live"] for r in recs) == 1
        assert recs[-1] == dict(live=0, snapshots=1, restored=0,
                                snapshotted=0, evicted=0)
    finally:
        w.stop()
