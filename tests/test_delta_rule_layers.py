"""Delta-rule linear-attention layers between gated attention layers
that rotate nothing, over a held share of a wider router (``solar_open2``,
PR 49): the operator "kda" of the loop over layer kinds (a ring and a
matrix state by slot, NO keys and values), the attention layers' output
gate, and ``dropless_moe`` told which experts this chip holds. At a tiny
size on the CPU:

(a) the program through its pools equals the plain reference's full
    forward (``chipbench/reference/kda_gqa_moe.py``: the delta rule token
    by token, where the program's prefill solves a chunk at a time), at
    decays that fall below 0.3 a step and windows that cross chunk and
    page boundaries;
(b) a prefix hit that restores a snapshot, and a preempted and resumed
    row, continue to the unshared run's tokens;
(c) the pipeline on against off, and a forced discard of a launch
    ahead, give byte-identical greedy streams;
(d) the share ties to the model: the 8 shares' routed parts plus the
    shared expert counted once add up to the uncut layer;
(e) ``elsewhere`` + ``assignments`` = experts a token x valid rows and
    ``dropped`` = 0; with every expert held nothing is ``elsewhere``;
(f) ``kda_decode_update`` in the Pallas interpreter equals the XLA step;
(g) ``from_hf_config`` reads the published ``config.json`` verbatim and
    refuses each unsupported key with a message that names it; the
    state's ledger, gauges and span carry the new family unchanged.
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
from xllm_service_tpu.parallel import expert
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "chipbench", "configs", "solar-open2-250b")
PUBLISHED = spec.load_json(os.path.join(CONFIG_DIR, "config.json"))

# Every width tiny, every ratio the family's own: 8 query heads a
# key-value head, one attention layer before three delta-rule layers, 4
# experts held of 8 x 4 routed, 8 a token; a chunk that divides the page.
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=8, num_key_value_heads=1, head_dim=16,
            linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                "num_heads": 4, "num_kv_heads": None},
            n_routed_experts=4, num_hidden_layers=4, vocab_size=512)
PS = 8               # and so the prefill scan's chunk (it divides the page)
SEED = 5


def hf(dtype="float32", **over):
    return {**PUBLISHED, **TINY, "torch_dtype": dtype, **over}


def model(dtype="float32", **over) -> ModelConfig:
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf(dtype, **over), "solar-tiny"),
        dtype=dtype)


def steep(leaves, by=4.0):
    """The same weights with every head's decay rate ``by`` times as
    large: a step's alpha then falls well below 0.3."""
    def one(lp):
        return {k: v + np.log(by) if k in ("self_attn.A_log", "kda_a_log")
                else v for k, v in lp.items()}
    if "stacks" in leaves:
        return {**leaves, "stacks": {k: one(st) for k, st
                                     in leaves["stacks"].items()}}
    return {**leaves, "layers": [one(lp) for lp in leaves["layers"]]}


@pytest.fixture(scope="module")
def made():
    """{dtype: (hf config, ModelConfig, program tree, reference params)}
    from one seed: the program's tree and the reference's per-layer
    leaves hold the same values."""
    wts = spec.load_weights(CONFIG_DIR)
    out = {}
    for dtype in ("float32",):
        cfg = hf(dtype)
        key = weights.root_key(SEED)
        out[dtype] = (cfg, model(dtype), wts.program_tree(cfg, SEED), {
            **wts.head_params(cfg, key),
            "layers": [wts.layer_params(cfg, key, i, kind)
                       for i, kind in enumerate(wts.layer_kinds(cfg))]})
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

# float32: the two sides differ in the order of their sums alone (a
# triangular solve a chunk against the token-by-token rule). (bfloat16,
# the served type: tests/chipbench/test_chipbench_solar_open2.py, at the
# rehearsal's widths.)
@pytest.mark.parametrize("dtype, tol, rate", [
    ("float32", 1e-5, 1.0), ("float32", 1e-5, 4.0)])
def test_prefill_then_decode_through_the_pools_is_the_references_forward(
        made, dtype, tol, rate):
    cfg, mc, params, ref_params = made[dtype]
    if rate != 1.0:
        params, ref_params = steep(params, rate), steep(ref_params, rate)
        # a step's decay does reach below 0.3: the fastest head's rate
        # times softplus(dt_bias) alone, before any input
        lp = ref_params["layers"][1]
        floor = np.exp(-np.exp(np.asarray(lp["self_attn.A_log"])).max()
                       * np.log1p(np.exp(np.asarray(
                           lp["self_attn.dt_bias"]).max())))
        assert floor < 0.3
    ref = spec.load_reference(CONFIG_DIR)
    n, more = 37, 7
    want = np.asarray(ref.forward(ref_params, TOKENS[:n + more], cfg))
    scale = np.abs(want).max()
    pt = table(1, 2, 3, 4, 5, 6)
    # one row (state row 1: slots 1 and 2): a window of two pages, then
    # one of 21 tokens padded to 24 that starts from the first's state
    # and crosses chunks and pages of 8, with a snapshot at 32
    got, kv = prefill(params, mc, pools(mc), TOKENS[:16], 0, pt,
                      (0, Engine._live_slot(1, 15), 0, 0), 16,
                      all_logits=True)
    assert np.abs(got - want[:16]).max() <= tol * scale
    got, kv = prefill(params, mc, kv, TOKENS[16:n], 16, pt,
                      (Engine._live_slot(1, 15), Engine._live_slot(1, n - 1),
                       9, 16), 24, all_logits=True)
    assert np.abs(got - want[16:n]).max() <= tol * scale
    for pos in range(n, n + more):
        logits, kv = decode(params, mc, kv, TOKENS[pos], pos, pt)
        assert np.abs(logits - want[pos]).max() <= tol * scale, pos
    # and from a COPY of the snapshot (slot 9, the state as of 32), the
    # tokens behind the boundary once more: a prefix hit's first window
    got, _ = prefill(params, mc, kv, TOKENS[32:n], 32, pt,
                     (9, Engine._live_slot(2, n - 1), 0, 0), 8,
                     all_logits=True)
    assert np.abs(got - want[32:n]).max() <= tol * scale


def test_padding_moves_neither_state_nor_ring_and_the_layers_keep_what_they_keep(
        made):
    _, mc, params, _ = made["float32"]
    # one attention layer's keys and values, three layers' rings over
    # q | k | v, three layers' states: the ranks come apart
    kv = pools(mc)
    assert [p.shape for p in kv] == [
        (1, 16, PS, 1, 16), (1, 16, PS, 1, 16), (3, 16, 4, 3 * 64),
        (3, 12, 4, 16, 16)]
    pt = table(1, 2, 3, 4)
    n = 29
    one, kv1 = prefill(params, mc, pools(mc), TOKENS[:n], 0, pt,
                       (0, Engine._live_slot(1, n - 1), 0, 0), 32)
    wide, kv_wide = prefill(params, mc, pools(mc), TOKENS[:n], 0, pt,
                            (0, Engine._live_slot(1, n - 1), 0, 0), 64)
    np.testing.assert_allclose(wide, one, atol=1e-5)
    for pool in (2, 3):
        np.testing.assert_allclose(np.asarray(kv_wide[pool]),
                                   np.asarray(kv1[pool]), atol=1e-5)
    assert np.abs(np.asarray(kv1[3][:, Engine._live_slot(1, n - 1)])
                  ).min(axis=(1, 2, 3)).shape == (3,)
    assert all(np.abs(np.asarray(kv1[3][r])).max() > 0 for r in range(3))


# (f) ----------------------------------------------------------------------

def test_the_decode_kernel_in_the_interpreter_is_the_xla_form(made):
    """``plan.ssm_decode`` on a delta-rule model: the Pallas update maps
    each row's block by its slot and aliases the pool; interpreted here,
    compiled for the chip in tests/test_chip_compile.py."""
    _, mc, params, _ = made["float32"]
    params = steep(params, 2.0)
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


@pytest.mark.parametrize("heads, rows", [(16, 3), (4, 2)])
def test_the_kernel_alone_is_the_rule_written_out(heads, rows):
    """Blocks of 8 heads (and of 4 where 8 do not divide them), rows on
    slots of their own, an inactive row on the null slot."""
    from xllm_service_tpu.ops.pallas.kda_update import kda_decode_update
    rng = np.random.default_rng(heads)
    d = 16
    state = jnp.asarray(rng.standard_normal((2, 9, heads, d, d)),
                        jnp.float32)
    q, k, v = (jnp.asarray(rng.standard_normal((rows, heads, d)),
                           jnp.float32) for _ in range(3))
    alpha = jnp.asarray(rng.uniform(0.2, 1.0, (rows, heads, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (rows, heads)), jnp.float32)
    read = jnp.asarray([1, 4, 0][:rows]); write = jnp.asarray([2, 3, 0][:rows])
    if rows == 3:                               # the inactive row
        alpha, beta = alpha.at[2].set(1.0), beta.at[2].set(0.0)
    o, moved = kda_decode_update(state, 1, read, write, q, k, v, alpha, beta,
                                 interpret=True)
    S = np.asarray(state)[1][np.asarray(read)] * np.asarray(alpha)[..., None]
    r = np.asarray(v) - np.einsum("bhkv,bhk->bhv", S, np.asarray(k))
    S = S + (np.asarray(beta)[..., None] * np.asarray(k))[..., None] \
        * r[:, :, None, :]
    np.testing.assert_allclose(np.asarray(o),
                               np.einsum("bhkv,bhk->bhv", S, np.asarray(q)),
                               atol=1e-5)
    want = np.asarray(state).copy()
    want[1][np.asarray(write)] = S
    np.testing.assert_allclose(np.asarray(moved), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(moved)[0], np.asarray(state)[0])


# ---------------------------------------------------------------------------
# (d), (e) the held share
# ---------------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(made):
    """The reference's layer over all 32 experts of this tiny deployment
    equals the 8 chips' routed parts plus the shared expert ONCE, and
    the program's dropless layer, told a rank's share, gives that rank's
    part."""
    ref = spec.load_reference(CONFIG_DIR)
    from chipbench.reference import kda_gqa_moe as body
    wts = spec.load_weights(CONFIG_DIR)
    uncut = hf(n_routed_experts=32, expert_share_chips=1)
    lp = wts.layer_params(uncut, weights.root_key(3), 1, "kda+moe")
    h = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    whole = np.asarray(ref.experts(h, lp, uncut, ref.mm_f32))
    shared = np.asarray(body.shared_expert(h, lp, ref.mm_f32))
    parts = np.zeros_like(whole)
    stats = np.zeros(len(expert.MOE_STATS), np.int64)
    assert T.moe_stats_shape(model()) == (6,)
    for rank in range(8):
        cfg = hf(expert_share_rank=rank)
        mine = {k: v[4 * rank:4 * rank + 4] if k.startswith("mlp.experts.")
                else v for k, v in lp.items()}
        part = np.asarray(ref.experts(h, mine, cfg, ref.mm_f32)) - shared
        mc = model(expert_share_rank=rank)
        topi, topw = T._deepseek_gate(
            mc, h, lp["mlp.gate"],
            lp["mlp.gate.e_score_correction_bias"])
        got, st = expert.dropless_moe(
            h, topi, topw, jnp.ones((24,), bool),
            *(mine[f"mlp.experts.{w}_proj"][None]
              for w in ("gate", "up", "down")), layer=jnp.int32(0),
            first_held=mc.first_held_expert, routed=mc.router_experts)
        np.testing.assert_allclose(np.asarray(got), part, atol=2e-5)
        parts += part
        stats += np.asarray(st)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-4)
    st = dict(zip(expert.MOE_STATS, stats.tolist()))
    # every assignment is computed by exactly one chip and is elsewhere
    # for the seven others
    assert st["assignments"] == 8 * 24 and st["dropped"] == 0
    assert st["elsewhere"] == 7 * 8 * 24


@pytest.mark.parametrize("valid_rows", [24, 17, 0])
def test_elsewhere_and_assignments_are_what_the_gate_made(valid_rows):
    rng = np.random.default_rng(valid_rows)
    N, D, F, E, k = 24, 16, 8, 4, 8
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    topi = jnp.asarray(np.stack([rng.permutation(32)[:k] for _ in range(N)]),
                       jnp.int32)
    topw = jnp.asarray(rng.uniform(0.1, 1.0, (N, k)), jnp.float32)
    valid = jnp.arange(N) < valid_rows
    g, u = (jnp.asarray(rng.standard_normal((1, E, D, F)), jnp.float32)
            for _ in range(2))
    d = jnp.asarray(rng.standard_normal((1, E, F, D)), jnp.float32)
    out, st = expert.dropless_moe(x, topi, topw, valid, g, u, d,
                                  layer=jnp.int32(0), first_held=8,
                                  routed=32)
    st = dict(zip(expert.MOE_STATS, np.asarray(st).tolist()))
    held = (np.asarray(topi) >= 8) & (np.asarray(topi) < 12) \
        & np.asarray(valid)[:, None]
    assert st["assignments"] == held.sum() and st["dropped"] == 0
    assert st["elsewhere"] + st["assignments"] == k * valid_rows
    # a row whose every choice lies elsewhere gets exactly nothing
    none = ~held.any(axis=1)
    assert np.abs(np.asarray(out)[none]).max(initial=0.0) == 0.0
    # the experts held, under the ids the gate gave them, as a dense sum
    want = np.zeros((N, D), np.float32)
    for n_, e_ in zip(*np.nonzero(held)):
        e = int(np.asarray(topi)[n_, e_]) - 8
        hmid = jax.nn.silu(x[n_] @ g[0, e]) * (x[n_] @ u[0, e])
        want[n_] += float(topw[n_, e_]) * np.asarray(hmid @ d[0, e])
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


def test_with_every_expert_held_nothing_is_elsewhere_and_nothing_changes():
    """The share's seam is static: told that the router is as wide as
    the stack (``routed`` 0 or E), the layer traces what it traced, bit
    for bit, and hands back the five counts it did (``elsewhere`` rides
    the vector under a held share alone: the other sparse models' step
    programs lower to the parent's text)."""
    rng = np.random.default_rng(1)
    N, D, F, E, k = 12, 16, 8, 6, 2
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    topi = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]),
                       jnp.int32)
    topw = jnp.asarray(rng.uniform(0.1, 1.0, (N, k)), jnp.float32)
    valid = jnp.arange(N) < 10
    g, u = (jnp.asarray(rng.standard_normal((2, E, D, F)), jnp.float32)
            for _ in range(2))
    d = jnp.asarray(rng.standard_normal((2, E, F, D)), jnp.float32)

    def run(**share):
        return jax.jit(lambda: expert.dropless_moe(
            x, topi, topw, valid, g, u, d, layer=jnp.int32(1), **share))

    plain, told = run(), run(first_held=0, routed=E)
    assert plain.lower().as_text() == told.lower().as_text()
    (out, st), (out2, st2) = plain(), told()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # ... and the vector it hands back is the five counts it was
    st = dict(zip(expert.MOE_STATS, np.asarray(st).tolist()))
    assert st == dict(dropped=0, assignments=20, layers=1,
                      experts_touched=st["experts_touched"],
                      load_max=st["load_max"])
    np.testing.assert_array_equal(np.asarray(st2), list(st.values()))


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
    # what the sparse layers counted: 8 choices a valid row a layer,
    # each computed here or held elsewhere, none dropped
    moe = eng.moe_stats
    rows = len(PROMPT) + N_OUT - 1
    assert moe["assignments"] + moe["elsewhere"] == 8 * 4 * rows
    assert moe["dropped"] == 0 and 0 < moe["assignments"] < moe["elsewhere"]
    return toks


# (b) ----------------------------------------------------------------------

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


# (c) ----------------------------------------------------------------------

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
    # written its ring row, and the step that replaces it must read what
    # the discarded one read.
    torn = engine(params)
    assert mixed_traffic(
        torn, each=lambda i: i % 3 == 0 and torn.drain_pipeline()) == want
    discards = (torn.phase_counts["decode.ahead_discard"]
                + torn.phase_counts["decode.tail_discard"])
    assert discards > 3


def test_a_discarded_launch_leaves_state_and_ring_as_they_were(made):
    """The same decode step twice from the same pools (what a discarded
    launch ahead and the step that replaces it are): the second reads
    what the first read, and gives the first's logits and pools."""
    _, mc, params, _ = made["float32"]
    pt = table(1, 2, 3)
    _, kv = prefill(params, mc, pools(mc), TOKENS[:17], 0, pt,
                    (0, Engine._live_slot(1, 16), 0, 0), 24)
    first, kv1 = decode(params, mc, kv, TOKENS[17], 17, pt)
    again, kv2 = decode(params, mc, kv1, TOKENS[17], 17, pt)
    np.testing.assert_array_equal(again, first)
    for a, b in zip(kv1, kv2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the slot it read (the even positions', as of 16) and the three
    # ring rows behind position 17 are as the prefill left them
    np.testing.assert_array_equal(np.asarray(kv1[3][:, 1]),
                                  np.asarray(kv[3][:, 1]))
    ring = lambda p: np.asarray(p[2][:, 3]).reshape(3, 4, -1)  # noqa: E731
    np.testing.assert_array_equal(ring(kv1)[:, [0, 2, 3]],
                                  ring(kv)[:, [0, 2, 3]])
    assert np.abs(ring(kv1)[:, 1] - ring(kv)[:, 1]).max() > 0


def test_a_discarded_launch_over_the_ring_written_in_place(params,
                                                           monkeypatch):
    """An engine whose plan writes the ring in place
    (``plan.ssm_decode``; the kernels interpreted): every second
    iteration the step on the device ahead is thrown away after it has
    written its rows' rings, and run again. The stream, the rings and
    the states at the end are the sequential engine's, bit for bit: the
    writer puts a row's ring into the row's own page and shifts
    nothing."""
    monkeypatch.setattr(
        KernelPlan, "from_env", classmethod(lambda cls, *a, **kw: cls(
            ssm_decode=True, write_then_attend=True, interpret=True)))

    def run(eng, each=None):
        add(eng, "a", PROMPT, 14)
        return drain(eng, each=each)["a"]
    sequential = engine(params)
    assert sequential.plan.ssm_decode
    sequential._ahead_eligible = sequential._tail_eligible = \
        lambda *a: False
    want = run(sequential)
    torn = engine(params)
    assert run(torn, each=lambda i: i % 2 == 0
               and torn.drain_pipeline()) == want
    assert (torn.phase_counts["decode.ahead_discard"]
            + torn.phase_counts["decode.tail_discard"]) > 3
    for pool in (2, 3):
        np.testing.assert_array_equal(np.asarray(torn.kv[pool]),
                                      np.asarray(sequential.kv[pool]))
    assert np.abs(np.asarray(torn.kv[2], np.float32)).max() > 0


# (g) ----------------------------------------------------------------------

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


def test_from_hf_config_reads_the_published_config_verbatim():
    mc = ModelConfig.from_hf_config(
        {**PUBLISHED, "num_hidden_layers": 48, "n_routed_experts": 320,
         "expert_share_chips": 1, "vocab_size": 196608},
        "solar-open2-250b")
    assert mc.layer_kinds == ("attn+moe", "kda+moe", "kda+moe",
                              "kda+moe") * 12
    assert (mc.num_attn_layers, mc.num_conv_layers, mc.num_state_layers,
            mc.num_kda_layers, mc.num_ssm_layers) == (12, 36, 36, 36, 0)
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.moe_intermediate_size, mc.vocab_size) \
        == (4096, 64, 8, 128, 1280, 196608)
    assert (mc.kda_heads, mc.kda_head_dim, mc.kda_gate_rank,
            mc.kda_beta_scale, mc.conv_kernel) == (64, 128, 128, 2.0, 4)
    assert (mc.num_experts, mc.router_experts, mc.num_experts_per_tok,
            mc.n_shared_experts, mc.moe_scoring, mc.norm_topk_prob) \
        == (320, 320, 8, 1, "sigmoid", True)
    assert not mc.use_rope and mc.attn_gate and not mc.tie_word_embeddings
    assert mc.dropless_experts and mc.ssm_heads == 0
    # the configuration as run: 40 held of 8 x 40 routed, rank 0
    cut = ModelConfig.from_hf_config(PUBLISHED, "solar-open2-250b")
    assert cut.layer_kinds == ("attn+moe", "kda+moe", "kda+moe", "kda+moe")
    assert (cut.num_experts, cut.router_experts, cut.first_held_expert) \
        == (40, 320, 0)
    # a ring of 4 inputs over q | k | v of 3 x 8192 channels a page, the
    # fourth pool a layer's 64 states of 128 x 128: 4.19 MB a sequence
    assert cut.conv_tail_shape == (4, 24576)
    kv = jax.eval_shape(lambda: T.init_kv_cache(cut, 1888, 128,
                                                state_slots=193))
    assert [p.shape for p in kv] == [
        (1, 1888, 128, 8, 128), (1, 1888, 128, 8, 128), (3, 1888, 4, 24576),
        (3, 193, 64, 128, 128)]
    assert kv[3].dtype == jnp.float32 and 64 * 128 * 128 * 4 == 4_194_304


@pytest.mark.parametrize("key, value", [
    ("first_k_dense_replace", 1), ("kda_use_full_proj", True),
    ("kda_allow_neg_eigval", False), ("use_rope", True),
    ("use_gqa_gate", False), ("n_shared_experts", 2),
    ("attention_bias", True)])
def test_from_hf_config_refuses_what_it_does_not_run_by_name(key, value):
    with pytest.raises(ValueError, match=f"solar_open2 with {key}="):
        ModelConfig.from_hf_config({**PUBLISHED, key: value}, "x")


def test_what_still_cannot_run_is_refused_by_name():
    mc = model()
    with pytest.raises(ValueError, match="'conv' operator has no 'mix'"):
        dataclasses.replace(mc, layer_kinds=("kda+moe", "conv+moe"))
    with pytest.raises(ValueError,
                       match="'mix', 'kda' or 'ret' layers, one of the three"):
        dataclasses.replace(mc, layer_kinds=("kda+moe", "mix+moe"),
                            ssm_heads=4)
    with pytest.raises(ValueError, match="'kda' operator gives"):
        dataclasses.replace(mc, kda_heads=0)
    with pytest.raises(ValueError, match="expert_share_rank=8"):
        dataclasses.replace(mc, expert_share_rank=8)
    # and a delta-rule layer stands beside attention layers, in any order
    ok = dataclasses.replace(mc, layer_kinds=("kda+moe", "attn+moe",
                                              "kda+moe"))
    assert (ok.num_attn_layers, ok.num_state_layers) == (1, 2)


def test_the_plan_says_how_the_delta_rule_runs(params, caplog):
    import logging
    with caplog.at_level(logging.INFO):
        eng = engine(params)
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("engine plan:"))
    assert "layer kinds attn+moe 1, kda+moe 3" in line
    assert "delta rule kda_prefill xla_chunked, kda_decode xla" in line
    assert "4 state rows x 2 + 4 snapshots" in line
    assert not eng.plan.ssm_decode and not eng.plan.mixed_step
    assert eng.plan.write_then_attend


def test_the_paged_decode_kernel_at_a_group_of_eight():
    from xllm_service_tpu.ops.attention import paged_decode_attention
    from xllm_service_tpu.ops.pallas import paged_decode_attention_pallas
    rng = np.random.default_rng(8)
    hkv, group, d, ps, pages, B = 2, 8, 128, 16, 12, 3
    q = jnp.asarray(rng.standard_normal((B, hkv * group, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, pages, ps, hkv, d)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, pages, ps, hkv, d)),
                    jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    ctx = jnp.asarray([40, 17, 64], jnp.int32)
    want = paged_decode_attention(q, k[0], v[0], pt, ctx)
    got = paged_decode_attention_pallas(q, k, v, pt, ctx, interpret=True,
                                        layer=jnp.asarray(0, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_the_loader_reads_the_familys_names(tmp_path):
    """A checkpoint written under the family's names (torch's [out, in],
    q, k, v and their filters [C, 1, K] apart, the experts one by one
    under the PUBLISHED numbering) loads into the tree the benchmark's
    generator hands the program: rank 1 holds experts 4-7."""
    from safetensors.numpy import save_file
    from xllm_service_tpu.runtime.checkpoint import load_checkpoint
    cfg = hf(expert_share_rank=1)
    wts = spec.load_weights(CONFIG_DIR)
    key = weights.root_key(9)
    head = wts.head_params(cfg, key)
    out = {"model.embed_tokens.weight": np.asarray(head["embed"]),
           "model.norm.weight": np.asarray(head["final_norm"]),
           "lm_head.weight": np.ascontiguousarray(
               np.asarray(head["lm_head"]).T)}
    bare = ("self_attn.dt_bias", "self_attn.A_log",
            "mlp.gate.e_score_correction_bias")
    for i, kind in enumerate(wts.layer_kinds(cfg)):
        for name, leaf in wts.layer_params(cfg, key, i, kind).items():
            leaf, at = np.asarray(leaf), f"model.layers.{i}.{name}"
            if name.endswith("conv1d"):
                out[at + ".weight"] = np.ascontiguousarray(leaf.T[:, None])
            elif name in bare:
                out[at] = leaf
            elif name.startswith("mlp.experts."):
                for e in range(leaf.shape[0]):
                    out[f"model.layers.{i}.mlp.experts.{4 + e}."
                        f"{name.rsplit('.', 1)[1]}.weight"] = \
                        np.ascontiguousarray(leaf[e].T)
            elif leaf.ndim == 2:
                out[at + ".weight"] = np.ascontiguousarray(leaf.T)
            else:
                out[at + ".weight"] = leaf
    save_file(out, str(tmp_path / "model.safetensors"))
    got = load_checkpoint(str(tmp_path), model(expert_share_rank=1))
    want = wts.program_tree(cfg, 9)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat_got) == set(flat_want)
    for path, leaf in flat_want.items():
        np.testing.assert_allclose(np.asarray(flat_got[path]),
                                   np.asarray(leaf), rtol=1e-6,
                                   err_msg=str(path))


def test_a_worker_serves_it_and_exports_the_ledgers_unchanged(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: the same prompt twice,
    the second time from the first's pages and a copy of its snapshot;
    the slots' ledger, gauges and step records carry the new family
    under the names they had, the sparse layers' counters gain
    ``elsewhere``; a PREFILL instance of such a model is refused."""
    import json
    from http.client import HTTPConnection
    from chipbench import cluster
    from xllm_service_tpu.obs import steptrace, validate_exposition
    from xllm_service_tpu.runtime import worker as W
    from xllm_service_tpu.service.coordination import InMemoryStore
    assert "xllm.kv.state_slots" in steptrace.SPAN_NAMES
    model_dir = cluster.write_model_dir(str(tmp_path / "model"),
                                        hf("bfloat16"))
    ecfg = dict(page_size=16, num_pages=32, max_model_len=256,
                max_batch_size=4)
    with pytest.raises(ValueError, match="PD migration"):
        W.Worker(W.WorkerOptions(model="so2-tiny", model_dir=model_dir,
                                 instance_type=W.InstanceType.PREFILL),
                 InMemoryStore(), engine_cfg=EngineConfig(**ecfg))
    w = W.Worker(W.WorkerOptions(model="so2-tiny", model_dir=model_dir),
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
            "model": "so2-tiny", "max_tokens": 6, "temperature": 0.0,
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
        assert metric(rows, event="snapshotted") == 1
        assert metric(rows, event="evicted") == 0
        slots = "xllm_worker_state_slots"
        assert (metric(slots, kind="live"), metric(slots, kind="snapshot"),
                metric(slots, kind="free")) == (0, 1, 3)
        assert metric("xllm_worker_state_pool_bytes") \
            == eng.kv[2].nbytes + eng.kv[3].nbytes
        assert eng.kv[3].shape == (3, 1 + 3 * 4, 4, 16, 16)
        done = metric("xllm_worker_moe_assignments_total")
        away = metric("xllm_worker_moe_elsewhere_assignments_total")
        assert metric("xllm_worker_moe_dropped_assignments_total") == 0
        assert 0 < done < away and (done + away) % (8 * 4) == 0
        recs = [r["state"] for r in w.steptrace.tail() if r["state"]]
        assert sum(r["restored"] for r in recs) == 1
        assert sum(r["snapshotted"] for r in recs) == 1
        assert recs[-1] == dict(live=0, snapshots=1, restored=0,
                                snapshotted=0, evicted=0)
        moe = [r["moe"] for r in w.steptrace.tail() if r["moe"]]
        assert moe and all(m["dropped"] == 0 and m["elsewhere"] > 0
                           for m in moe)
    finally:
        w.stop()
