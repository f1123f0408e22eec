"""Power-retention layers in EVERY layer and nothing else kept
(``brumby``, PR 53): the operator "ret" of the loop over layer kinds (a
matrix state and a normaliser by slot; NO keys and values, no tail, no
ring), pools of no bytes for the kinds of layer the model has none of,
and an engine whose pages are bookkeeping. At a tiny size on the CPU:

(a) the three forms of the layer agree in float32: the attention form
    (the definition, and the reference's), the chunked form the prefill
    runs and the state form token by token; ``phi(a) . phi(b) ==
    (a . b)^2`` in the pool's layout; a group's query heads read ONE
    state;
(b) the program through its pools (prefill, then decode by the XLA form
    and by the kernel in the interpreter) equals the plain reference's
    full forward (``chipbench/reference/power_retention_decoder.py``);
    a wrong decay, a dropped normaliser and a state kept in bfloat16
    each FAIL that comparison;
(c) a resume from a snapshot is the whole sequence; a prefix hit, a
    preempted row and a discarded launch ahead continue to the unshared
    run's tokens and leave every state as it was;
(d) the engine allocates 0 bytes of keys, values and tails, is not
    ``pages_only``, refuses PD roles, spill, fetch and a mesh, admits by
    its state rows, and keeps ONE table width;
(e) ``from_hf_config`` reads the catalog's config, the plan says what
    serves nothing, the loader reads the family's names, and a worker
    serves the model and exports the pools' bytes.
"""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec, weights
from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer as T
from xllm_service_tpu.ops.pallas.retention_update import (
    retention_decode_update)
from xllm_service_tpu.ops.plan import KernelPlan
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "chipbench", "configs", "brumby-14b")
PUBLISHED = spec.load_json(os.path.join(CONFIG_DIR, "config.json"))

# Every width tiny, every ratio the family's own: five query heads a
# key-value head, a retention layer in every layer.
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=10,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
            vocab_size=512)
PS = 8               # and so the prefill scan's chunk
SEED = 5
INTERPRETED = KernelPlan(ssm_decode=True, write_then_attend=True,
                         interpret=True)


def hf(dtype="float32", **over):
    return {**PUBLISHED, **TINY, "torch_dtype": dtype, **over}


def model(dtype="float32", **over) -> ModelConfig:
    return dataclasses.replace(
        ModelConfig.from_hf_config(hf(dtype, **over), "brumby-tiny"),
        dtype=dtype)


@pytest.fixture(scope="module")
def made():
    """(hf config, ModelConfig, program tree, reference params) from one
    seed: the program's tree and the reference's per-layer leaves hold
    the same values."""
    wts = spec.load_weights(CONFIG_DIR)
    cfg, key = hf(), weights.root_key(SEED)
    return cfg, model(), wts.program_tree(cfg, SEED), {
        **wts.head_params(cfg, key),
        "layers": [wts.layer_params(cfg, key, i, kind)
                   for i, kind in enumerate(wts.layer_kinds(cfg))]}


@pytest.fixture(scope="module")
def reference():
    return spec.load_reference(CONFIG_DIR)


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
# (a) the layer's three forms
# ---------------------------------------------------------------------------

def operands(t=40, hq=10, h=2, d=16, seed=1):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(t, hq, d)) * d ** -0.5
    k, v = rng.normal(size=(2, t, h, d))
    g = np.log(rng.uniform(0.7, 0.999, size=(t, h)))
    return [a.astype(np.float32) for a in (q, k, v, g)]


def attention_form(q, k, v, g):
    """The definition, in float64: the quotient of two masked sums."""
    q, k, v, g = (np.asarray(a, np.float64) for a in (q, k, v, g))
    t, hq, d = q.shape
    group = hq // k.shape[1]
    G = np.cumsum(g, axis=0)
    o = np.zeros((t, hq, d))
    for a in range(hq):
        b = a // group
        s = q[:, a] @ k[:, b].T
        w = np.tril(np.exp(G[:, None, b] - G[None, :, b]) * s * s)
        o[:, a] = (w @ v[:, b]) / w.sum(axis=-1, keepdims=True)
    return o


def test_phi_of_two_vectors_multiplies_to_the_square_of_their_product():
    rng = np.random.default_rng(3)
    for d in (16, 128):
        a, b = rng.normal(size=(2, 7, d)).astype(np.float32)
        pa, pb = T._ret_phi(jnp.asarray(a)), T._ret_phi(jnp.asarray(b))
        assert pa.shape == (7, d // 2 + 1, d)
        # a sum of signed products: exact to a few ulps of |a|^2 |b|^2
        np.testing.assert_allclose(
            np.asarray(jnp.sum(pa * pb, axis=(-2, -1))),
            np.sum(a * b, axis=-1) ** 2, rtol=0,
            atol=1e-6 * (np.sum(a * a, -1) * np.sum(b * b, -1)).max())
        # d (d + 1) / 2 products and no more: the rest of the last block
        # is zero
        assert int(np.count_nonzero(np.asarray(pa[0]))) == d * (d + 1) // 2
    mc = model(head_dim=128, hidden_size=128, num_attention_heads=5,
               num_key_value_heads=1)
    assert mc.ret_blocks == 65 and mc.state_shape == (1, 8392, 128)
    assert mc.ret_state_rows <= 8704         # never the 16,384-row product


@pytest.mark.parametrize("window", [8, 16, 40])
def test_chunked_prefill_is_the_attention_form_is_the_state_form(window):
    """In float32, to 1e-5 of the largest value: the chunked form in
    windows of ``window`` positions carried through the state, and the
    state form one token at a time, against the attention form over the
    whole sequence."""
    mc = model()
    q, k, v, g = operands()
    want = attention_form(q, k, v, g)
    tol = 1e-5 * np.abs(want).max()
    rows = jnp.zeros(mc.state_shape, jnp.float32)
    got = []
    for at in range(0, 40, window):
        o, rows, _ = T._ret_scan(
            mc, *(jnp.asarray(a[at:at + window]) for a in (q, k, v, g)),
            rows, jnp.asarray(0), PS)
        got.append(np.asarray(o))
    assert np.abs(np.concatenate(got) - want).max() < tol
    # ... and the state it leaves is the state form's, token by token
    state = jnp.zeros((1, 3) + mc.state_shape, jnp.float32)
    for t in range(40):
        o, state = T._ret_step(
            mc, state, 0, jnp.asarray([1 + t % 2]),
            jnp.asarray([2 - t % 2]), *(jnp.asarray(a[t:t + 1])
                                        for a in (q, k, v, g)), KernelPlan())
        # position 0 divides ONE weight by itself, and a weight that is
        # small beside |q|^2 |k|^2 is read off the state to fewer digits
        # than the attention form's own s * s: ten times the room there
        assert np.abs(np.asarray(o)[0] - want[t]).max() < (tol if t
                                                           else 10 * tol)
    np.testing.assert_allclose(np.asarray(state[0, 1]), np.asarray(rows),
                               atol=1e-5 * float(jnp.abs(rows).max()))


def test_a_groups_query_heads_read_one_state():
    """Five query heads a key-value head: the pool holds TWO states, and
    a query head moved to the other group reads the other state."""
    mc = model()
    q, k, v, g = operands()
    assert mc.state_shape[0] == 2 and q.shape[1] == 10
    want = attention_form(q, k, v, g)
    swapped = attention_form(q[:, ::-1], k, v, g)[:, ::-1]
    assert np.abs(want - swapped).max() > 0.1
    o, _, _ = T._ret_scan(mc, *(jnp.asarray(a) for a in (q, k, v, g)),
                          jnp.zeros(mc.state_shape), jnp.asarray(0), PS)
    assert np.abs(np.asarray(o) - want).max() < 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("d, kv_heads, group, rows", [(16, 2, 5, 3),
                                                     (128, 1, 5, 2)])
def test_the_kernel_alone_is_the_rule_written_out(d, kv_heads, group, rows):
    """``retention_decode_update`` in the interpreter against the state
    form in numpy, from states that tokens built: the slots it was told
    to, the other layer and the other slots untouched, an inactive row's
    null slot written back as it was and its output 0."""
    mc = model(head_dim=d, num_key_value_heads=kv_heads,
               num_attention_heads=kv_heads * group)
    rng = np.random.default_rng(d)
    state = jnp.zeros((2, 2 * rows + 1) + mc.state_shape, jnp.float32)
    for t in range(3):          # three tokens into every odd slot
        n = rows
        q, k, v = (rng.normal(size=(n, h, d)).astype(np.float32)
                   for h in (kv_heads * group, kv_heads, kv_heads))
        g = np.log(rng.uniform(0.8, 1.0, size=(n, kv_heads))
                   ).astype(np.float32)
        odd = jnp.arange(1, 2 * rows, 2)
        for layer in (0, 1):
            _, state = T._ret_step(mc, state, layer, odd, odd,
                                   *map(jnp.asarray, (q, k, v, g)),
                                   KernelPlan())
    active = np.arange(rows) != rows - 1        # the last row is inactive
    read = np.where(active, 2 * np.arange(rows) + 1, 0)
    write = np.where(active, 2 * np.arange(rows) + 2, 0)
    on = active[:, None, None]
    q = np.where(on, q, 0.0)
    k, v = np.where(on, k, 0.0), np.where(on, v, 0.0)
    g = np.where(active[:, None], g, 0.0)
    args = [jnp.asarray(a) for a in (read, write, q, k, v)]
    want_o, want = T._ret_step(mc, state, 1, *args, jnp.asarray(g),
                               KernelPlan())
    got_o, got = retention_decode_update(state, 1, *args,
                                         jnp.exp(jnp.asarray(g)),
                                         interpret=True)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=1e-5 * float(jnp.abs(want_o).max()))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(got[1, read]),
                                  np.asarray(state[1, read]))
    assert np.abs(np.asarray(got_o)[~active]).max() == 0
    assert np.abs(np.asarray(got[1, write[active]])
                  - np.asarray(state[1, write[active]])).max() > 0


# ---------------------------------------------------------------------------
# (b) the program through its pools against the plain reference
# ---------------------------------------------------------------------------

def through_the_pools(params, mc, plan=KernelPlan()):
    """Logits of TOKENS[:48]: a window of 24 (three chunks), one of 11
    that ends inside a page, then thirteen decode steps."""
    pt = table(1, 2, 3, 4, 5, 6)
    live = Engine._live_slot
    first, kv = prefill(params, mc, pools(mc), TOKENS[:24], 0, pt,
                        (0, live(1, 23), 0, 0), 32, plan, all_logits=True)
    second, kv = prefill(params, mc, kv, TOKENS[24:35], 24, pt,
                         (live(1, 23), live(1, 34), 0, 0), 16, plan,
                         all_logits=True)
    rows = [first, second]
    for t in range(35, 48):
        one, kv = decode(params, mc, kv, TOKENS[t], t, pt, plan=plan)
        rows.append(one[None])
    return np.concatenate(rows), kv


def test_prefill_then_decode_through_the_pools_is_the_references_forward(
        made, reference):
    cfg, mc, params, ref_params = made
    want = np.asarray(reference.forward(ref_params, TOKENS[:48], cfg))
    tol = 1e-5 * np.abs(want).max()
    got, kv = through_the_pools(params, mc)
    assert np.abs(got - want).max() < tol
    # what the layers keep: a state by slot, and not one byte else
    assert [int(x.nbytes) for x in kv[:3]] == [0, 0, 0]
    assert kv[3].shape == (3, 12) + mc.state_shape
    # the decode kernel in the interpreter is the XLA form
    again, kv_k = through_the_pools(params, mc, INTERPRETED)
    assert np.abs(again - want).max() < tol
    np.testing.assert_allclose(np.asarray(kv_k[3]), np.asarray(kv[3]),
                               atol=1e-5 * float(jnp.abs(kv[3]).max()))


def test_the_decays_the_weights_are_drawn_for(made):
    """``meta.json`` ``assumed``: a state neither forgets in ten tokens
    nor never. At these widths (a hidden size of 64, where the random
    part of a logit is wider than at 5,120) every layer's decay lies in
    (0.8, 1)."""
    import chipbench.reference.power_retention_decoder as reference
    cfg, _, _, ref_params = made
    x = reference.embed(jnp.asarray(TOKENS), ref_params["embed"])
    for lp in ref_params["layers"]:
        h = reference.rms_norm(x, lp["input_layernorm"], 1e-6)
        gamma = np.asarray(jax.nn.sigmoid(
            reference.mm_f32(h, lp["self_attn.g_proj"])))
        assert 0.8 < gamma.min() and gamma.max() < 1.0
        assert gamma.max() - gamma.min() > 1e-3
        x, _ = reference.layer(x, lp, cfg, reference.mm_f32, "ret+dense",
                               None)


@pytest.mark.parametrize("fault", ["a wrong decay", "a dropped normaliser",
                                   "a state in bfloat16"])
def test_a_seeded_fault_fails_the_comparison(made, reference, monkeypatch,
                                             fault):
    cfg, mc, params, ref_params = made
    want = np.asarray(reference.forward(ref_params, TOKENS[:48], cfg))
    if fault == "a wrong decay":
        sound = T._ret_in
        monkeypatch.setattr(T, "_ret_in", lambda *a: (
            lambda q, k, v, g: (q, k, v, 1.5 * g))(*sound(*a)))
    elif fault == "a dropped normaliser":
        monkeypatch.setattr(T, "_ret_quotient", lambda num, den: num)
    else:
        sound = T._ret_pack
        monkeypatch.setattr(T, "_ret_pack", lambda *a: sound(*a).astype(
            jnp.bfloat16).astype(jnp.float32))
    got, _ = through_the_pools(params, mc)
    assert np.abs(got - want).max() > 10 * 1e-5 * np.abs(want).max()


# ---------------------------------------------------------------------------
# (c) snapshots, the prefix cache, preemption, launch-ahead
# ---------------------------------------------------------------------------

def test_a_resume_from_a_snapshot_is_the_whole_sequence(made):
    """A window of 32 snapshots after 24 of its tokens; a second
    sequence that starts from the snapshot's copy at position 24 gives
    the first's logits, and its state at 31 is the first's."""
    _, mc, params, _ = made
    pt = table(1, 2, 3, 4)
    live = Engine._live_slot
    whole, kv = prefill(params, mc, pools(mc), TOKENS[:32], 0, pt,
                        (0, live(1, 31), 9, 24), 32, all_logits=True)
    tail, kv = prefill(params, mc, kv, TOKENS[24:32], 24, pt,
                       (9, live(2, 31), 0, 0), 8, all_logits=True)
    np.testing.assert_allclose(tail, whole[24:], atol=1e-5 * np.abs(
        whole).max())
    state = np.asarray(kv[3])
    np.testing.assert_allclose(state[:, live(2, 31)], state[:, live(1, 31)],
                               atol=1e-5 * np.abs(state).max())
    assert np.abs(state[:, 9] - state[:, live(1, 31)]).max() > 0
    assert np.abs(state[:, 0]).max() == 0           # the null slot


def test_padding_rows_move_nothing_and_write_the_null_slot_zeros(made):
    """A batch of three rows of which the middle one has no token."""
    _, mc, params, _ = made
    window = np.zeros((3, 16), np.int32)
    window[0, :16], window[2, :9] = TOKENS[:16], TOKENS[16:25]
    cols = [[0, 1, 0, 0], [0, 0, 0, 0], [0, 3, 0, 0]]
    pt = jnp.asarray([[1, 2, 0, 0], [0, 0, 0, 0], [3, 4, 0, 0]], jnp.int32)
    last, _, kv = T.forward_prefill(
        params, mc, jnp.asarray(window), jnp.zeros((3,), jnp.int32),
        jnp.asarray([16, 0, 9], jnp.int32), pools(mc), pt,
        state_cols=jnp.asarray(cols, jnp.int32))[:3]
    alone, kv1 = prefill(params, mc, pools(mc), TOKENS[16:25], 0,
                         table(3, 4, width=4), (0, 3, 0, 0), 16)
    np.testing.assert_allclose(np.asarray(last)[2], alone,
                               atol=1e-5 * np.abs(alone).max())
    state = np.asarray(kv[3])
    np.testing.assert_allclose(state[:, 3], np.asarray(kv1[3])[:, 3],
                               atol=1e-5 * np.abs(state).max())
    assert np.abs(state[:, 0]).max() == 0 and np.abs(state[:, 1]).max() > 0
    assert np.abs(state[:, 2]).max() == 0 and np.isfinite(
        np.asarray(last)).all()


def test_a_discarded_launch_leaves_every_state_as_it_was(made):
    """The same decode step twice from the same pools (what a discarded
    launch ahead and the step that replaces it are): the second reads
    what the first read, and gives the first's logits and pools."""
    _, mc, params, _ = made
    pt = table(1, 2, 3)
    for plan in (KernelPlan(), INTERPRETED):
        _, kv = prefill(params, mc, pools(mc), TOKENS[:17], 0, pt,
                        (0, Engine._live_slot(1, 16), 0, 0), 24, plan)
        first, kv1 = decode(params, mc, kv, TOKENS[17], 17, pt, plan=plan)
        again, kv2 = decode(params, mc, kv1, TOKENS[17], 17, pt, plan=plan)
        np.testing.assert_array_equal(again, first)
        np.testing.assert_array_equal(np.asarray(kv1[3]),
                                      np.asarray(kv2[3]))
        # the slot it read (the even positions', as of 16) is as the
        # prefill left it
        np.testing.assert_array_equal(np.asarray(kv1[3][:, 1]),
                                      np.asarray(kv[3][:, 1]))
        assert np.abs(np.asarray(kv1[3][:, 2])).max() > 0


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
    return made[2]


@pytest.fixture(scope="module")
def cold(params, made, reference):
    """The unshared run of PROMPT, and that its tokens are the
    reference's greedy choices (teacher-forced, as the benchmark's check
    reads them)."""
    eng = engine(params)
    add(eng, "cold", PROMPT)
    toks = drain(eng)["cold"]
    cfg, _, _, ref_params = made
    logits = np.asarray(reference.forward(ref_params, PROMPT + toks[:-1],
                                          cfg))
    best = logits[len(PROMPT) - 1:].argmax(axis=-1)
    assert toks == [int(t) for t in best]
    st = eng.state_stats()
    assert (st["restored"], st["snapshotted"], st["live"]) == (0, 1, 0)
    return toks


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
    assert seq.num_cached_tokens == 16
    assert eng.state_stats()["restored"] == 1


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


@pytest.mark.parametrize("plan", ["xla", "kernel"])
def test_the_pipeline_on_against_off_and_a_forced_discard_give_the_same_streams(
        params, monkeypatch, plan):
    if plan == "kernel":
        monkeypatch.setattr(KernelPlan, "from_env", classmethod(
            lambda cls, *a, **kw: INTERPRETED))
    sequential = engine(params)
    assert sequential.plan.ssm_decode == (plan == "kernel")
    sequential._ahead_eligible = sequential._tail_eligible = \
        lambda *a: False
    want = mixed_traffic(sequential)
    assert sequential.phase_counts["decode.ahead_dispatch"] == 0

    ahead = engine(params)
    assert mixed_traffic(ahead) == want
    assert ahead.phase_counts["decode.ahead_hit"] > 5

    # Every third iteration the step in flight is thrown away after it
    # has run on the device: it has advanced every row's state, and the
    # step that replaces it must read what the discarded one read.
    torn = engine(params)
    assert mixed_traffic(
        torn, each=lambda i: i % 3 == 0 and torn.drain_pipeline()) == want
    assert (torn.phase_counts["decode.ahead_discard"]
            + torn.phase_counts["decode.tail_discard"]) > 3
    np.testing.assert_array_equal(np.asarray(torn.kv[3]),
                                  np.asarray(sequential.kv[3]))


# ---------------------------------------------------------------------------
# (d) an engine whose pages hold no bytes
# ---------------------------------------------------------------------------

def test_no_attention_layer_no_bytes_of_keys_values_and_tails(params):
    eng = engine(params)
    assert [int(x.nbytes) for x in eng.kv[:3]] == [0, 0, 0]
    assert eng.kv[0].shape[1:3] == (48, PS)     # pages and page size stay
    assert eng.kv[3].shape == (3, 1 + 3 * 4) + model().state_shape
    assert not eng.page_bytes and not eng.page_rows and eng.state_model
    assert not eng.pages_only
    assert eng.kv_block_bytes() == 0
    # ONE table width, whatever the rows hold: no program reads the table
    assert eng._table_width() == eng._prefill_table_width(1) \
        == eng._prefill_table_width(9) == eng.ecfg.max_pages_per_seq
    # every other family keeps what it kept
    dense = Engine(ModelConfig.tiny(), EngineConfig(
        page_size=PS, num_pages=16, max_model_len=64, max_batch_size=2))
    assert dense.pages_only and dense.page_bytes and not dense.page_rows
    assert dense.kv[0].nbytes > 0 and len(dense.kv) == 2


def test_its_pages_do_not_move_and_it_takes_no_mesh_and_no_spill(params):
    eng = engine(params, kv_spill_mb=8.0)
    assert eng.host_tier is None
    add(eng, "held", PROMPT, 2)
    drain(eng)
    assert not eng.export_blocks([b"x"])
    assert eng.adopt_blocks(PROMPT, 0, np.zeros((0, 1)),
                            np.zeros((0, 1))) == 0
    assert eng.export_held("held") is None
    with pytest.raises(ValueError, match="one device"):
        Engine(model(), EngineConfig(page_size=PS, num_pages=8),
               mesh=object())


def test_admission_stops_at_the_state_rows_and_not_at_the_pages(params):
    """Two rows, plenty of pages: the third request waits for a state
    row, and is admitted when one is freed."""
    eng = engine(params, max_batch_size=2, max_num_seqs=8)
    for i in range(3):
        add(eng, f"r{i}", OTHER[i:] + PROMPT, 6)
    got = {}

    def step():
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
    while eng.state_rows.num_free:
        step()
    for _ in range(2):
        # (a row that is being prefilled still stands in ``waiting``)
        assert [s.req.request_id for s in eng.waiting
                if s.slot < 0] == ["r2"]
        assert eng.state_rows.num_free == 0
        assert eng.allocator.num_free > 20
        step()
    drain(eng, got)
    assert sorted(got) == ["r0", "r1", "r2"]
    assert all(len(v) == 6 for v in got.values())
    assert eng.state_rows.num_free == 2


# ---------------------------------------------------------------------------
# (e) the config, the plan, the loader, a worker
# ---------------------------------------------------------------------------

def test_from_hf_config_reads_the_catalogs_config():
    catalog = {**PUBLISHED, "num_hidden_layers": 40}
    mc = ModelConfig.from_hf_config(catalog, "brumby-14b")
    assert mc.layer_kinds == ("ret+dense",) * 40
    assert (mc.num_attn_layers, mc.num_conv_layers, mc.num_state_layers,
            mc.num_ret_layers, mc.kv_cache_layers) == (0, 0, 40, 40, 0)
    assert (mc.hidden_size, mc.intermediate_size, mc.num_heads,
            mc.num_kv_heads, mc.head_dim, mc.vocab_size) == (
                5120, 17408, 40, 8, 128, 151936)
    assert mc.qk_norm and mc.use_rope and not mc.tie_word_embeddings
    assert mc.sliding_window is None and mc.ret_degree == 2
    assert mc.rope_theta == 1e6 and mc.rms_norm_eps == 1e-6
    # 8 x (8,256 x 128 + 8,256) float32 a layer a sequence, in 8,392 rows
    assert mc.state_shape == (8, 8392, 128)
    assert math.prod(mc.state_shape) * 4 == 34_373_632


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("use_sliding_window", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0})])
def test_from_hf_config_refuses_what_it_does_not_run_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**PUBLISHED, key: value}, "brumby")


def test_what_still_cannot_run_is_refused_by_name():
    base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8)
    with pytest.raises(ValueError, match="one of the three"):
        ModelConfig(name="x", layer_kinds=("ret+dense", "kda+dense"),
                    ret_degree=2, kda_heads=2, kda_head_dim=8,
                    kda_gate_rank=8, conv_kernel=4, **base)
    with pytest.raises(ValueError, match="ret_degree 2"):
        ModelConfig(name="x", layer_kinds=("ret+dense",) * 2, ret_degree=4,
                    **base)
    with pytest.raises(ValueError, match="no 'conv'"):
        ModelConfig(name="x", layer_kinds=("ret+dense", "conv+dense"),
                    ret_degree=2, conv_kernel=3, **base)


def test_the_plan_says_what_serves_nothing(params, caplog, monkeypatch):
    import logging
    with caplog.at_level(logging.INFO):
        eng = engine(params)
    lines = [r.getMessage() for r in caplog.records]
    line = next(ln for ln in lines if ln.startswith("engine plan:"))
    assert "layer kinds ret+dense 3" in line
    assert "no layer keeps keys and values" in line
    assert "retention ret_prefill xla_chunked, ret_decode xla" in line
    assert "4 state rows x 2 + 4 snapshots" in line
    assert any(ln.startswith(
        "engine pools: (k, v) 0.00 GB, tails 0.00 GB, states") for ln in lines)
    assert any("NO keys and values" in ln for ln in lines)
    assert not eng.plan.ssm_decode and not eng.plan.mixed_step
    # on a TPU the state kernel is on and the attention kernels and the
    # writers, which have nothing to serve, are off
    monkeypatch.setenv("XLLM_PALLAS", "1")
    plan = KernelPlan.from_env(model(), eng.ecfg, None)
    assert plan.ssm_decode and plan.page_aligned
    assert not (plan.decode_attn or plan.prefill_attn or plan.kv_writers)
    dense = KernelPlan.from_env(ModelConfig.tiny(), eng.ecfg, None)
    assert dense.decode_attn and dense.kv_writers and not dense.ssm_decode


def test_the_loader_reads_the_familys_names(tmp_path):
    """A checkpoint written under the family's names (torch's [out, in])
    loads into the tree the benchmark's generator hands the program."""
    from safetensors.numpy import save_file
    from xllm_service_tpu.runtime.checkpoint import load_checkpoint
    cfg = hf()
    wts = spec.load_weights(CONFIG_DIR)
    key = weights.root_key(9)
    head = wts.head_params(cfg, key)
    out = {"model.embed_tokens.weight": np.asarray(head["embed"]),
           "model.norm.weight": np.asarray(head["final_norm"]),
           "lm_head.weight": np.ascontiguousarray(
               np.asarray(head["lm_head"]).T)}
    for i, kind in enumerate(wts.layer_kinds(cfg)):
        for name, leaf in wts.layer_params(cfg, key, i, kind).items():
            leaf = np.asarray(leaf)
            out[f"model.layers.{i}.{name}.weight"] = (
                np.ascontiguousarray(leaf.T) if leaf.ndim == 2 else leaf)
    assert "model.layers.0.self_attn.g_proj.weight" in out
    save_file(out, str(tmp_path / "model.safetensors"))
    got = load_checkpoint(str(tmp_path), model())
    want = wts.program_tree(cfg, 9)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat_got) == set(flat_want)
    for path, leaf in flat_want.items():
        # (the embedding is a sum of two draws: under another jit its
        # float32 add may round the last bit the other way)
        np.testing.assert_allclose(np.asarray(flat_got[path]),
                                   np.asarray(leaf), rtol=1e-6, atol=1e-7,
                                   err_msg=str(path))


def test_a_worker_serves_it_and_exports_the_pools_bytes(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: the same prompt twice,
    the second time from a copy of the first's snapshot; the slots'
    ledger, gauges and step records carry the family under the names
    they had, ``/metrics`` says that the key-value pools hold 0 bytes
    and the state pool all of the cache; a PREFILL or DECODE instance of
    such a model is refused at start-up."""
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
    for role in (W.InstanceType.PREFILL, W.InstanceType.DECODE):
        with pytest.raises(ValueError, match="PD migration"):
            W.Worker(W.WorkerOptions(model="brumby-tiny",
                                     model_dir=model_dir,
                                     instance_type=role),
                     InMemoryStore(), engine_cfg=EngineConfig(**ecfg))
    w = W.Worker(W.WorkerOptions(model="brumby-tiny", model_dir=model_dir),
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
            "model": "brumby-tiny", "max_tokens": 6, "temperature": 0.0,
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
        assert metric(rows, event="written") == 0       # no row beside a page
        slots = "xllm_worker_state_slots"
        assert (metric(slots, kind="live"), metric(slots, kind="snapshot"),
                metric(slots, kind="free")) == (0, 1, 3)
        assert metric("xllm_worker_kv_pool_bytes") == 0
        assert metric("xllm_worker_state_pool_bytes") == eng.kv[3].nbytes > 0
        assert eng.kv[3].shape == (3, 1 + 3 * 4, 2, 160, 16)
        recs = [r["state"] for r in w.steptrace.tail() if r["state"]]
        assert sum(r["restored"] for r in recs) == 1
        assert sum(r["snapshotted"] for r in recs) == 1
        # the state pool is the cache the runtime counts
        rt = w.primary_runtime()
        assert rt.memory_gb > eng.kv[3].nbytes / 1e9
    finally:
        w.stop()
