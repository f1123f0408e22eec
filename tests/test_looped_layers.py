"""A looped model (``ModelConfig.total_ut_steps`` > 1: Ouro): the dense
families' layer scan inside a loop over passes, the same weights every
pass, the final norm after every pass, each pass with its own keys and
values (pool slot ``pass * num_layers + layer``), an exit gate read off
each pass's normed state.

Against the plain reference (``chipbench/reference/looped_decoder.py``:
float32, ``highest``, no cache, nothing imported from the program) on
seeded weights at a tiny size: prefill then decode through the paged
cache equals its full forward on LOGITS; the passes' keys and values are
proven separate; a prefix hit, a preemption, a host spill and a block
fetch carry a looped model's pages; a threshold under 1 is refused at
load. And a pass count of 1 leaves every other model as it was: no loop,
no gate, pools of ``num_layers`` slots (``tests/test_step_program_pins.
py`` holds the dense step programs to the very text they lowered to
before there were passes).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from chipbench import spec, weights
from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer
from xllm_service_tpu.ops.plan import KernelPlan
from xllm_service_tpu.runtime import engine as E
from xllm_service_tpu.runtime import worker as W
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b")
L, P = 2, 3                 # layers and passes of the tiny model
# Float32 both sides: what is left is the order of the sums (the program
# batches and pages, the reference does neither). The same comparison
# with the program in bfloat16 reads 1e-2 and more (the test below).
F32_TOL = 2e-5
# bfloat16 weights and activations through P x L = 6 layer-passes, each
# output normed: 8 bits of mantissa (4e-3 a rounding) over a few dozen
# roundings on the way to a logit; measured 1.4-2.1e-2 of the largest
# logit over five seeds (3-7), so 6e-2 leaves three times of room.
BF16_TOL = 6e-2


def published():
    return spec.load_json(os.path.join(CONFIG, "config.json"))


def tiny_config(**over):
    return {**published(), "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 16, "num_hidden_layers": L, "total_ut_steps": P,
            "vocab_size": 512, **over}


def seeded(cfg, seed, dtype="float32"):
    """(the program's config, its tree, the reference's stored leaves)."""
    import jax
    import jax.numpy as jnp
    wts = spec.load_weights(CONFIG)
    key = weights.root_key(seed)
    leaves = {**wts.head_params(cfg, key),
              "layers": [wts.layer_params(cfg, key, i, kind)
                         for i, kind in enumerate(wts.layer_kinds(cfg))]}
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg, "ouro-tiny"),
                             dtype=dtype)
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.dtype(dtype)),
                                  wts.program_tree(cfg, seed))
    return mc, tree, leaves


def through_the_cache(mc, tree, toks, plan, dtype, spoil=None):
    """Logits [T, V] and exit probabilities [T', P] of the program:
    ``toks`` prefilled in two windows (24, then 16 on the first's pages),
    the rest decoded token by token. ``spoil(kv) -> kv`` is applied to
    the pools between prefill and decode."""
    import jax.numpy as jnp
    T, ps = len(toks), 16
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.dtype(dtype))
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    logits, exits = [], []
    for start, n in ((0, 24), (24, 16)):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = toks[start:start + n]
        out = transformer.forward_prefill(
            tree, mc, jnp.asarray(padded), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), kv, table, return_all_logits=True,
            return_stats=True, plan=plan)
        logits.append(np.asarray(out[1][0, :n]))
        exits.append(np.asarray(out[-1]["exit_pdf"]))    # of the last row
        kv = out[2]
        # a prefill counts its passes and no decode row
        assert np.asarray(out[-1]["loop"]).tolist() == [0, P, 0] + [0] * (
            P - 1)
    if spoil is not None:
        kv = spoil(kv)
    for p in range(40, T):
        lg, kv, st = transformer.forward_decode(
            tree, mc, jnp.asarray(toks[p:p + 1], jnp.int32),
            jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table,
            return_stats=True, plan=plan)
        logits.append(np.asarray(lg))
        exits.append(np.asarray(st["exit_pdf"]))
        vec = np.asarray(st["loop"])
        assert vec[:3].tolist() == [0, P, 1]
        np.testing.assert_allclose(
            vec[3:], np.cumsum(exits[-1][0])[:-1], rtol=1e-5)
    return np.concatenate(logits), np.concatenate(exits), kv


PLANS = {"xla": KernelPlan(), "write_then_attend":
         KernelPlan(write_then_attend=True)}


@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("seed, over", [
    (11, {}),
    (2**31 + 9, {"num_key_value_heads": 2}),      # grouped, as Mistral's
])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        plan, seed, over):
    cfg = tiny_config(**over)
    mc, tree, leaves = seeded(cfg, seed)
    ref = spec.load_reference(CONFIG)
    toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"],
                                                size=56)
    want, q = ref.forward_with_exits(leaves, toks, cfg)
    want, q = np.asarray(want), np.asarray(q)
    np.testing.assert_allclose(q.sum(-1), 1.0, rtol=1e-6)
    got, exits, kv = through_the_cache(mc, tree, toks, PLANS[plan],
                                       "float32")
    assert kv[0].shape[0] == L * P
    assert np.abs(got - want).max() < F32_TOL * np.abs(want).max()
    # exit probabilities of the rows the gate was read for: the last of
    # each prefill window, then every decoded position
    rows = [23, 39] + list(range(40, 56))
    assert np.abs(exits - q[rows]).max() < 1e-5
    assert 0.02 < q[:, 0].mean() < 0.98          # a gate that says something


def test_in_bfloat16_it_agrees_under_a_stated_tolerance_and_no_tighter():
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    errs = []
    for seed in (3, 4):
        mc, tree, leaves = seeded(cfg, seed, "bfloat16")
        toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"],
                                                    size=56)
        want = np.asarray(ref.forward(leaves, toks, cfg))
        got, _, _ = through_the_cache(mc, tree, toks, PLANS["xla"],
                                      "bfloat16")
        errs.append(np.abs(got - want).max() / np.abs(want).max())
    assert max(errs) < BF16_TOL
    # ... and float32's tolerance would catch bfloat16 where float32 is
    # stated, by two orders
    assert min(errs) > 100 * F32_TOL


def pass_slots(kv, p, fn):
    return tuple(a.at[p * L:(p + 1) * L].set(fn(a)) for a in kv)


@pytest.mark.parametrize("what", ["pass 2's slots of a page are overwritten",
                                  "the passes share one set of slots"])
def test_each_pass_keeps_its_own_keys_and_values(what):
    cfg = tiny_config()
    mc, tree, leaves = seeded(cfg, 21)
    ref = spec.load_reference(CONFIG)
    toks = np.random.default_rng(21).integers(3, cfg["vocab_size"], size=56)
    want = np.asarray(ref.forward(leaves, toks, cfg))
    if what.startswith("pass 2"):
        # page 1 holds positions 0-15: zero them in pass 2's slots alone
        def spoil(kv):
            return tuple(a.at[L:2 * L, 1].set(0) for a in kv)
    else:
        # what a model whose passes wrote ONE slot would have cached:
        # every pass reads pass 1's keys and values
        def spoil(kv):
            for p in range(1, P):
                kv = pass_slots(kv, p, lambda a: a[:L])
            return kv
    got, _, _ = through_the_cache(mc, tree, toks, PLANS["xla"], "float32",
                                  spoil)
    scale = np.abs(want).max()
    assert np.abs(got[:40] - want[:40]).max() < F32_TOL * scale  # prefill
    assert np.abs(got[40:] - want[40:]).max() > 1e-2 * scale     # decode


def looped_tiny(**over):
    return dataclasses.replace(
        ModelConfig.tiny(), name="looped-tiny", four_norm=True,
        total_ut_steps=P, dtype="float32", **over)


def tiny_engine(num_pages=24, seed=0, **kw):
    return E.Engine(looped_tiny(), EngineConfig(
        page_size=16, num_pages=num_pages, max_model_len=128,
        max_batch_size=2, prefill_buckets=(32, 64), **kw), seed=seed)


def run(eng, prompt, rid, max_tokens=8):
    eng.add_request(E.EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                ignore_eos=True)))
    toks, lps = [], []
    while eng.has_work():
        for o in eng.step():
            if o.request_id == rid:
                toks.extend(o.new_token_ids)
                lps.extend(o.logprobs)
    return toks, np.asarray(lps)


def test_a_prefix_hit_on_cached_pages_gives_the_cold_runs_logits():
    """A cached page now means every pass's slots of its tokens: request
    B starts from request A's two pages and computes the rest; a fresh
    engine computes all of B."""
    pre = list(range(40, 40 + 36))                 # 2 full pages + 4
    a, b = pre + [7, 8, 9], pre + [300, 301, 302, 303]
    warm = tiny_engine()
    run(warm, a, "a")
    hit_before = warm.prefix_hit_tokens
    toks, lps = run(warm, b, "b")
    assert warm.prefix_hit_tokens - hit_before == 32
    cold_toks, cold_lps = run(tiny_engine(), b, "b")
    assert toks == cold_toks
    np.testing.assert_allclose(lps, cold_lps, atol=2e-5)


def test_preempted_and_resumed_it_serves_the_same_tokens():
    prompt = list(range(5, 5 + 21))
    want, want_lps = run(tiny_engine(), prompt, "r", max_tokens=12)
    eng = tiny_engine()
    eng.add_request(E.EngineRequest(
        request_id="r", token_ids=prompt, sampling=SamplingParams(
            max_tokens=12, temperature=0.0, ignore_eos=True)))
    got, lps = [], []
    for _ in range(6):
        for o in eng.step():
            got.extend(o.new_token_ids)
            lps.extend(o.logprobs)
    assert 0 < len(got) < 12
    eng._preempt_seq(eng._by_id["r"])
    assert eng.num_preemptions == 1
    while eng.has_work():
        for o in eng.step():
            got.extend(o.new_token_ids)
            lps.extend(o.logprobs)
    assert got == want
    np.testing.assert_allclose(lps, want_lps, atol=2e-5)


def test_a_page_moves_between_engines_with_every_passs_slots():
    """Block fetch (what PD migration and the cross-worker fetch both
    move): the holder exports a digest run as [L x P, n, ps, Hkv, Dh],
    a second engine adopts it and serves the holder's continuation
    without computing those pages."""
    a, b = tiny_engine(), tiny_engine()
    prompt = list(range(60, 60 + 40))
    out_a, _ = run(a, prompt, "a")
    n, k, v = a.export_blocks(a.prefix_cache.block_hashes(prompt)[:2])
    assert n == 2 and k.shape == v.shape == (L * P, 2, 16, 2, 16)
    assert b.adopt_blocks(prompt, 0, k, v) == 2
    out_b, _ = run(b, prompt, "b")
    assert out_b == out_a and b.prefix_hit_tokens >= 32
    # a page of a model that runs its layers ONCE is refused by its shape
    assert b.adopt_blocks(list(range(200, 240)), 0, k[:L], v[:L]) == 0


def test_a_spilled_page_comes_back_with_every_passs_slots():
    eng = E.Engine(looped_tiny(), EngineConfig(
        page_size=16, num_pages=10, max_model_len=128, max_batch_size=2,
        prefill_buckets=(32, 64, 128), kv_spill_mb=64.0))
    p1 = [7] * 5 + list(range(40))
    out1, _ = run(eng, p1, "a")
    run(eng, list(range(100, 215)), "b", max_tokens=4)   # evicts p1's
    stats = eng.prefix_cache_stats()
    assert stats["spilled_pages"] > 0
    again, _ = run(eng, p1, "c")
    assert again == out1
    assert eng.prefix_cache_stats()["restored_pages"] > 0


def test_the_engine_books_the_passes_and_the_exit_probabilities():
    eng = tiny_engine()
    per_step = []
    eng.add_request(E.EngineRequest(
        request_id="r", token_ids=list(range(3, 24)),
        sampling=SamplingParams(max_tokens=9, temperature=0.0,
                                ignore_eos=True)))
    while eng.has_work():
        eng.step()
        per_step.append((eng.last_step_kind,
                         json.loads(json.dumps(eng.last_step_loop))))
    st = eng.loop_stats
    assert st["passes"]["prefill"] == P              # one prefill program
    decodes = st["passes"]["decode"] // P
    assert st["passes"]["decode"] == decodes * P and 8 <= decodes <= 9
    assert st["rows"] >= 8
    assert all(0 < b <= a * 1.000001 + 1e-6 or a == 0
               for a, b in zip(st["cdf_sum"][1:], st["cdf_sum"]))  # a cdf
    assert st["cdf_sum"][-1] <= st["rows"]
    for kind, book in per_step:
        passes, exit_cdf = W._loop_record(book)
        if kind == "decode":
            assert passes == P and len(exit_cdf) == P - 1
            assert 0 <= exit_cdf[0] <= exit_cdf[-1] <= 1
        elif kind == "prefill":
            assert passes == P and exit_cdf is None
    assert sum(b["rows"] for _, b in per_step) == st["rows"]


def test_a_worker_advertises_the_slots_and_exports_the_counters(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: cache ids and block
    bytes by layer x pass, the new series on ``/metrics``, ``passes`` and
    ``exit_cdf`` in the step records."""
    from http.client import HTTPConnection
    from chipbench import cluster
    from xllm_service_tpu.obs import validate_exposition
    from xllm_service_tpu.service.coordination import InMemoryStore
    cfg = tiny_config()
    model_dir = cluster.write_model_dir(str(tmp_path / "model"), cfg)
    store = InMemoryStore()
    w = W.Worker(W.WorkerOptions(model="ouro-tiny", model_dir=model_dir),
                 store).start()
    try:
        eng = w.primary_runtime().engine
        assert eng.cfg.kv_cache_layers == L * P == eng.kv[0].shape[0]
        ps, pages = eng.ecfg.page_size, eng.ecfg.num_pages
        assert eng.kv_block_bytes() == 2 * L * P * ps * 4 * 16 * 2 \
            == sum(a.nbytes for a in eng.kv) // pages
        # the weights are held ONCE: what the serverless allocator is
        # told does not grow with the pass count
        rt = w.primary_runtime()
        looped_gb = rt.memory_gb
        rt.model_cfg = dataclasses.replace(rt.model_cfg, total_ut_steps=1)
        once_gb, rt.model_cfg = rt.memory_gb, eng.cfg
        assert looped_gb == once_gb > 0
        (meta,) = [m for k, m in store.get_prefix_json("").items()
                   if k.endswith(w.name) and "k_cache_ids" in m]
        assert meta["k_cache_ids"] == meta["v_cache_ids"] \
            == list(range(L * P))
        assert meta["kv_block_bytes"] == eng.kv_block_bytes()
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

        prompt = " ".join(f"t{i}" for i in range(5, 25))
        status, _ = call("POST", "/v1/completions", json.dumps({
            "model": "ouro-tiny", "prompt": prompt, "max_tokens": 6,
            "temperature": 0.0, "ignore_eos": True}))
        assert status == 200
        text = call("GET", "/metrics")[1]
        assert validate_exposition(text) == []

        def metric(name, label=""):
            return sum(float(ln.rsplit(" ", 1)[1])
                       for ln in text.splitlines()
                       if ln.startswith(name + "{") and label in ln)

        st = eng.loop_stats
        assert metric("xllm_worker_layer_passes_total",
                      'phase="prefill"') == st["passes"]["prefill"] == P
        assert metric("xllm_worker_layer_passes_total",
                      'phase="decode"') == st["passes"]["decode"] >= 5 * P
        assert metric("xllm_worker_exit_cdf_count") == st["rows"] >= 5
        assert metric("xllm_worker_exit_cdf_sum", 'pass="1"') \
            == pytest.approx(st["cdf_sum"][0])
        assert metric("xllm_worker_exit_cdf_sum", f'pass="{P - 1}"') \
            <= st["rows"]
        recs = w.steptrace.tail()
        assert sum(r["passes"] for r in recs) \
            == sum(st["passes"].values())
        assert all(r["passes"] == P and len(r["exit_cdf"]) == P - 1
                   for r in recs if r["kind"] == "decode")
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def test_from_hf_config_reads_the_published_keys():
    mc = ModelConfig.from_hf_config(published(), "ouro-2.6b")
    assert (mc.num_layers, mc.hidden_size, mc.intermediate_size,
            mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.vocab_size) \
        == (48, 2048, 5632, 16, 16, 128, 49152)
    assert (mc.rope_theta, mc.rms_norm_eps, mc.max_position_embeddings) \
        == (1e6, 1e-6, 65536)
    assert (mc.total_ut_steps, mc.early_exit_threshold) == (4, 1.0)
    assert mc.looped and mc.four_norm_block and not mc.gemma
    assert mc.kv_cache_layers == 192
    assert not (mc.tie_word_embeddings or mc.attention_bias or mc.qk_norm
                or mc.sliding_window or mc.is_moe)
    # a token's cache: 192 slots of 2 x 16 x 128 x 2 B
    import jax
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(mc, 40, 128))
    assert [a.shape for a in kv] == [(192, 40, 128, 16, 128)] * 2
    assert sum(2 * int(np.prod(a.shape)) for a in kv) // (40 * 128) \
        == 1572864


@pytest.mark.parametrize("over, message", [
    ({"early_exit_threshold": 0.9},
     "rows of one batch that leave the layer loop at different passes"),
    ({"layer_types": ["full_attention", "sliding_attention"]},
     "layer_types"),
    ({"total_ut_steps": 0}, "at least once"),
])
def test_what_the_loop_cannot_run_is_refused_at_load(over, message):
    with pytest.raises(ValueError, match=message):
        ModelConfig.from_hf_config({**published(), **over})


@pytest.mark.parametrize("over", [
    {"num_experts": 4}, {"layer_kinds": ("attn+dense", "conv+dense"),
                         "conv_kernel": 3},
    {"kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
     "v_head_dim": 8}])
def test_a_loop_wraps_the_dense_scan_only(over):
    with pytest.raises(ValueError, match="dense families' scan only"):
        dataclasses.replace(ModelConfig.tiny(), total_ut_steps=2, **over)


def test_the_unknown_model_types_message_lists_ouro():
    with pytest.raises(ValueError, match=r"supported: .*\bouro\b"):
        ModelConfig.from_hf_config({**published(), "model_type": "nope"})


def test_a_checkpoint_round_trips_with_its_norms_and_its_gate(tmp_path):
    import jax
    from xllm_service_tpu.runtime import checkpoint
    cfg = tiny_config()
    mc, tree, _ = seeded(cfg, 5)
    checkpoint.save_checkpoint(tree, mc, str(tmp_path))
    saved = spec.load_json(str(tmp_path / "config.json"))
    assert (saved["model_type"], saved["total_ut_steps"]) == ("ouro", P)
    from safetensors.numpy import load_file
    names = set(load_file(str(tmp_path / "model.safetensors")))
    assert {"model.layers.0.input_layernorm_2.weight",
            "model.layers.1.post_attention_layernorm_2.weight",
            "model.early_exit_gate.weight", "model.early_exit_gate.bias"} \
        <= names
    again = ModelConfig.from_hf_config(saved, "ouro-tiny")
    assert (again.total_ut_steps, again.four_norm_block) == (P, True)
    back = checkpoint.load_checkpoint(
        str(tmp_path), dataclasses.replace(again, dtype="float32"))
    flat, flat_back = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (tree, back))
    assert [p for p, _ in flat] == [p for p, _ in flat_back]
    for (_, a), (_, b) in zip(flat, flat_back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# A pass count of 1: every model there was
# ---------------------------------------------------------------------------

PRESETS = sorted(
    name for name, fn in vars(ModelConfig).items()
    if isinstance(fn, classmethod) and name not in (
        "from_hf_config", "tiny"))


@pytest.mark.parametrize("preset", PRESETS)
def test_a_pass_count_of_1_leaves_a_presets_pools_as_they_were(preset):
    import jax
    mc = getattr(ModelConfig, preset)()
    assert mc.total_ut_steps == 1 and not mc.looped and not mc.four_norm
    assert mc.four_norm_block == mc.gemma
    assert mc.kv_cache_layers == mc.num_attn_layers
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(mc, 8, 16))
    assert kv[0].shape[:3] == (max(mc.num_attn_layers, 1), 8, 16)
    assert transformer.moe_stats_shape(mc) in ((), (5,))


def scans(jaxpr, depth=0):
    """(depth, length) of every scan in a jaxpr, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        is_scan = eqn.primitive.name == "scan"
        if is_scan:
            out.append((depth, eqn.params["length"]))
        for sub in inner:
            out += scans(getattr(sub, "jaxpr", sub), depth + is_scan)
    return out


def primitives(jaxpr):
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                out |= primitives(getattr(v, "jaxpr", v))
    return out


@pytest.mark.parametrize("wta", [False, True])
@pytest.mark.parametrize("passes", [1, P])
def test_the_loop_is_one_scan_around_the_layer_scan_and_absent_at_1(
        passes, wta):
    """Same HLO at a pass count of 1: ``tests/test_step_program_pins.py``
    holds Mistral's step programs to the text they lowered to before
    there were passes, under both plans. Here the structure: one scan of
    ``num_layers`` and nothing around it, no gate; above 1 ONE more scan
    of ``passes`` around it (not ``passes`` copies of the layer body)."""
    import jax
    import jax.numpy as jnp
    mc = dataclasses.replace(ModelConfig.tiny(), total_ut_steps=passes)
    params = jax.eval_shape(
        lambda: transformer.init_params(mc, jax.random.PRNGKey(0)))
    assert ("exit_gate" in params) == (passes > 1)
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(mc, 9, 16))
    plan = KernelPlan(write_then_attend=wta)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    decode = jax.make_jaxpr(
        lambda p, t, pos, act, kv, pt: transformer.forward_decode(
            p, mc, t, pos, act, kv, pt, return_stats=True, plan=plan))(
        params, i32(2), i32(2), jax.ShapeDtypeStruct((2,), bool), kv,
        i32(2, 4)).jaxpr
    prefill = jax.make_jaxpr(
        lambda p, t, sp, ln, kv, pt: transformer.forward_prefill(
            p, mc, t, sp, ln, kv, pt, return_stats=True, plan=plan))(
        params, i32(2, 32), i32(2), i32(2), kv, i32(2, 4)).jaxpr
    for jaxpr in (decode, prefill):
        layer_scans = [s for s in scans(jaxpr) if s[1] == mc.num_layers]
        if passes == 1:
            assert layer_scans == [(0, mc.num_layers)]
            assert "cumprod" not in primitives(jaxpr)       # no exit pdf
        else:
            assert layer_scans == [(1, mc.num_layers)]
            assert (0, passes) in scans(jaxpr)
            assert "cumprod" in primitives(jaxpr)
