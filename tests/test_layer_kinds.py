"""A model whose layers differ in kind (``ModelConfig.layer_kinds``;
LFM2-MoE): the published config parsed, the layer loop's reading of a
pattern, the dropless experts from this family's FFN, and above all THE
STATE'S SEAMS: a convolution layer's tail rides the page table (one row
a page), and every way a sequence can come to continue from a row it
did not just write (a prefix hit, a second prefill window, a resume
after preemption, a slot and pages another sequence has just left) must
give what a cold run gives."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer as T
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(os.path.dirname(HERE), "chipbench", "configs",
                      "lfm2-24b-a2b", "config.json")
PUBLISHED_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                      "conv"] * 9 + ["full_attention", "conv"]


def hf_config(**over):
    with open(CONFIG) as f:
        return {**json.load(f), **over}


def tiny_cfg(**over) -> ModelConfig:
    """Both operators and both FFNs, a period that repeats, float32."""
    d = hf_config(**{**dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, vocab_size=256), **over})
    return dataclasses.replace(ModelConfig.from_hf_config(d, "tiny-kinds"),
                               dtype="float32")


# ---------------------------------------------------------------------------
# (d) the config, published and cut
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers, dense, types, pattern", [
    (40, 2, PUBLISHED_TYPES, (2, 4, 9)),          # the published config
    (9, 1, PUBLISHED_TYPES[1:10], (1, 4, 2)),     # the benchmark's cut
])
def test_from_hf_config_gives_the_layer_kinds(layers, dense, types, pattern):
    cfg = ModelConfig.from_hf_config(hf_config(
        num_hidden_layers=layers, num_dense_layers=dense,
        layer_types=types), "lfm2")
    assert len(cfg.layer_kinds) == layers
    for i, (kind, t) in enumerate(zip(cfg.layer_kinds, types)):
        assert kind == ("conv" if t == "conv" else "attn") + (
            "+dense" if i < dense else "+moe")
    assert cfg.num_attn_layers == types.count("full_attention")
    assert cfg.num_conv_layers == types.count("conv")
    assert T.kinds_pattern(cfg.layer_kinds) == pattern
    # the widths and the gate, as published
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok) == (11776, 1536, 64, 4)
    assert cfg.qk_norm and cfg.tie_word_embeddings and cfg.dropless_experts
    assert (cfg.moe_scoring, cfg.topk_method, cfg.norm_topk_prob,
            cfg.moe_gate_eps, cfg.routed_scaling_factor) == (
                "sigmoid", "greedy", True, 1e-6, 1)
    assert (cfg.rope_theta, cfg.rope_scaling, cfg.rms_norm_eps,
            cfg.conv_kernel, cfg.vocab_size) == (1e6, None, 1e-5, 3, 65536)


@pytest.mark.parametrize("over, words", [
    ({"layer_types": ["conv"] * 8 + ["sliding_attention"]}, "layer_types"),
    ({"layer_types": ["conv"] * 4}, "layer_types"),
    ({"conv_bias": True}, "conv_bias"),
])
def test_from_hf_config_refuses_what_the_loop_has_no_body_for(over, words):
    with pytest.raises(ValueError, match=words):
        ModelConfig.from_hf_config(hf_config(**over), "lfm2")


@pytest.mark.parametrize("kinds, want", [
    (("a",) * 6, (0, 1, 6)),
    (("a", "b", "c"), (3, 0, 0)),                     # nothing repeats
    (("d", "a", "b", "a", "b", "a"), (1, 2, 2)),
])
def test_kinds_pattern(kinds, want):
    assert T.kinds_pattern(kinds) == want


def test_the_pools_hold_attention_layers_and_tails_alone():
    cfg = tiny_cfg()
    k, v, tails = T.init_kv_cache(cfg, 16, 8)
    # two key-value heads of 16 share a row of 32 (_kv_pack)
    assert k.shape == v.shape == (2, 16, 8, 1, 32)
    assert tails.shape == (7, 16, 2 * 64)


# ---------------------------------------------------------------------------
# (c) the dropless experts, from this family's FFN
# ---------------------------------------------------------------------------

def test_this_familys_experts_drop_nothing_and_equal_the_every_expert_oracle():
    cfg = tiny_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    small, experts = T._split_experts(params["stacks"]["conv+moe"])
    layer = 4
    lp = {k: v[layer] for k, v in small.items()}
    lp["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 16, 64), jnp.float32)
    valid = jnp.arange(16)[None, :] < jnp.asarray([16, 9, 0])[:, None]
    out, stats = T._dropless_moe_mlp(cfg, lp, experts, layer, x, valid=valid)
    assert int(stats[0]) == 0                       # moe_dropped
    assert int(stats[1]) == 25 * cfg.num_experts_per_tok
    # the oracle: every expert on every token, weighted by the gate's map
    topi, topw = T._deepseek_gate(cfg, x, lp["router"], lp["router_bias"])
    s = jax.nn.sigmoid(x @ lp["router"])
    picked = jax.lax.top_k(s + lp["router_bias"], 2)[1]
    assert np.array_equal(np.sort(topi, -1), np.sort(picked, -1))
    chosen = jnp.take_along_axis(s, topi, -1)
    np.testing.assert_allclose(
        topw, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    w = T._scatter_topk(topw, topi, 8)
    g, u, d = (experts[n][layer] for n in ("gate_proj", "up_proj",
                                           "down_proj"))
    every = jnp.einsum("btef,efd->bted",
                       jax.nn.silu(jnp.einsum("btd,edf->btef", x, g))
                       * jnp.einsum("btd,edf->btef", x, u), d)
    want = jnp.einsum("bted,bte->btd", every, w) * valid[..., None]
    np.testing.assert_allclose(out, want, atol=2e-5)


# ---------------------------------------------------------------------------
# (b) the state's seams, through the engine
# ---------------------------------------------------------------------------

PS = 4
PROMPT = [int(t) for t in np.random.default_rng(7).integers(1, 256, 21)]
OTHER = [int(t) for t in np.random.default_rng(8).integers(1, 256, 19)]
N_OUT = 10


def engine(launch="ahead", **kw) -> Engine:
    """``launch``: ``ahead`` is the engine as served; ``sequential``
    puts no decode step on the device ahead of its iteration, so a
    fault that shows under both is the seam's own and one that shows
    under ``ahead`` alone is the launch's."""
    defaults = dict(page_size=PS, num_pages=48, max_model_len=64,
                    max_batch_size=4, max_prefill_tokens=64,
                    prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    eng = Engine(tiny_cfg(), EngineConfig(**defaults), seed=0)
    if launch == "sequential":
        eng._ahead_eligible = eng._tail_eligible = lambda *a: False
    return eng


def add(eng, rid, prompt, n=N_OUT):
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True)))
    return eng._by_id[rid]


def step(eng, got):
    """One iteration; ``got`` = ({id: tokens}, {id: logprobs}) grows."""
    for out in eng.step():
        got[0].setdefault(out.request_id, []).extend(out.new_token_ids)
        got[1].setdefault(out.request_id, []).extend(out.logprobs)


def drain(eng, got=None, max_steps=400):
    got = got or ({}, {})
    for _ in range(max_steps):
        if not eng.has_work():
            break
        step(eng, got)
    assert not eng.has_work()
    return got


@pytest.fixture(scope="module")
def cold():
    eng = engine()
    add(eng, "cold", PROMPT)
    toks, lps = drain(eng)
    assert eng.state_stats()["restored"] == 0
    # 21 tokens over pages of 4: one window, six pages' rows
    assert eng.state_stats()["written"] == 6
    return toks["cold"], lps["cold"]


def same_as_cold(cold, toks, lps):
    assert toks == cold[0]
    # float32 throughout: what differs between the runs is the order of
    # a few sums (a window's own z against the stored tail is exact)
    np.testing.assert_allclose(lps, cold[1], atol=2e-5)


def hit_at_a_page_boundary(launch):
    eng = engine(launch)
    add(eng, "first", PROMPT)
    drain(eng)
    add(eng, "again", PROMPT)
    toks, lps = drain(eng)
    seq_hit = eng.prefix_hit_tokens
    assert seq_hit == 20 and eng.state_stats()["restored"] == 1
    return toks["again"], lps["again"]


def two_prefill_windows(launch):
    eng = engine(launch, prefill_buckets=(8, 16), max_prefill_tokens=16)
    seq = add(eng, "chunked", PROMPT)
    got = ({}, {})
    step(eng, got)
    assert 0 < seq.num_computed < len(PROMPT)      # mid-prompt, not done
    toks, lps = drain(eng, got)
    return toks["chunked"], lps["chunked"]


def preempted_and_resumed(launch):
    eng = engine(launch)
    seq = add(eng, "victim", PROMPT)
    got = ({}, {})
    while seq.num_generated < 5:
        step(eng, got)
    eng.drain_pipeline()
    eng._preempt_seq(seq)
    assert seq.num_computed == 0 and not seq.pages
    toks, lps = drain(eng, got)
    assert eng.num_preemptions == 1
    # the resume is a prefix hit on the victim's own registered pages
    assert eng.state_stats()["restored"] == 1
    return toks["victim"], lps["victim"]


def a_slot_and_pages_just_vacated(launch):
    # No prefix cache: a finished sequence's pages go straight back to
    # the allocator, and the next sequence is given them (and the slot)
    # with the first one's rows of tails still in them.
    eng = engine(launch, enable_prefix_cache=False, num_pages=12,
                 max_batch_size=1)
    first = add(eng, "first", OTHER)
    eng.step()
    used = set(first.pages)
    drain(eng)
    seq = add(eng, "next", PROMPT)
    got = ({}, {})
    step(eng, got)
    assert seq.slot == 0 and used & set(seq.pages)
    toks, lps = drain(eng, got)
    return toks["next"], lps["next"]


@pytest.mark.parametrize("launch", ["ahead", "sequential"])
@pytest.mark.parametrize("seam", [
    hit_at_a_page_boundary, two_prefill_windows, preempted_and_resumed,
    a_slot_and_pages_just_vacated])
def test_every_seam_of_the_state_gives_the_cold_runs_tokens(
        cold, seam, launch):
    same_as_cold(cold, *seam(launch))


def test_rows_of_one_batch_keep_their_own_tails(cold):
    """Two sequences decode side by side, one of them a prefix hit on
    the other's pages while the other still runs."""
    eng = engine()
    add(eng, "a", PROMPT)
    got = ({}, {})
    for _ in range(4):
        step(eng, got)
    add(eng, "b", PROMPT)          # hits the pages "a" has registered
    add(eng, "c", OTHER)
    toks, lps = drain(eng, got)
    same_as_cold(cold, toks["a"], lps["a"])
    same_as_cold(cold, toks["b"], lps["b"])
    assert eng.state_stats()["restored"] == 1
    solo = engine()
    add(solo, "c", OTHER)
    want, _ = drain(solo)
    assert toks["c"] == want["c"]


def test_a_hit_that_covers_the_whole_prompt_gives_back_a_page(cold):
    """The tail BEFORE a page's last token is not kept, so a prompt that
    is all cached pages recomputes its last page from the row before."""
    prompt = PROMPT[:16]                                   # four pages
    eng = engine()
    add(eng, "first", prompt)
    first, first_lps = drain(eng)
    seq = add(eng, "again", prompt)
    got = ({}, {})
    step(eng, got)
    assert seq.num_cached_tokens == 12
    toks, lps = drain(eng, got)
    assert toks["again"] == first["first"]
    np.testing.assert_allclose(lps["again"], first_lps["first"], atol=2e-5)


def test_pages_of_such_a_model_do_not_move(caplog):
    """PD migration, host spill and block fetch carry (k, v) alone: each
    door refuses, none resumes from a page without its tail."""
    import logging
    with caplog.at_level(logging.INFO):
        eng = engine(kv_spill_mb=64.0)
    assert any("convolution tail" in r.getMessage() for r in caplog.records)
    assert not eng.pages_only and eng.host_tier is None
    eng.add_request(EngineRequest(
        request_id="held", token_ids=list(PROMPT), hold_after_finish=True,
        sampling=SamplingParams(max_tokens=1, temperature=0.0)))
    drain(eng)
    free = eng.allocator.num_free + eng.prefix_cache.num_reclaimable
    assert eng.export_held("held") is None
    assert eng.allocator.num_free + eng.prefix_cache.num_reclaimable > free
    k = np.zeros((2, 6, PS, 1, 32), np.float32)
    assert not eng.import_sequence(EngineRequest("in", list(PROMPT)),
                                   PROMPT + [1], k, k)
    assert eng.export_blocks(eng.prefix_cache.block_hashes(PROMPT)) is None
    assert eng.adopt_blocks(PROMPT, 0, k, k) == 0
    with pytest.raises(ValueError, match="one device"):
        Engine(tiny_cfg(), EngineConfig(page_size=PS, num_pages=8,
                                        max_model_len=16), mesh=object())


def test_the_plan_says_what_the_loop_does():
    from xllm_service_tpu.ops.plan import KernelPlan
    plan = KernelPlan.from_env(tiny_cfg(), EngineConfig())
    assert plan.write_then_attend and not plan.mixed_step
    assert not plan.expert_gmm          # the base gate is off on the CPU
    os.environ["XLLM_PALLAS"] = "1"
    try:
        kinds = KernelPlan.from_env(tiny_cfg(), EngineConfig())
        other = KernelPlan.from_env(ModelConfig.tiny(num_experts=4),
                                    EngineConfig())
        assert kinds.expert_gmm and not other.expert_gmm
        # the prefill kernel is opt-in for this family as for the others
        assert not kinds.prefill_attn and not other.prefill_attn
        os.environ["XLLM_PALLAS_PREFILL"] = "1"
        assert KernelPlan.from_env(tiny_cfg(),
                                   EngineConfig()).prefill_attn
    finally:
        del os.environ["XLLM_PALLAS"]
        os.environ.pop("XLLM_PALLAS_PREFILL", None)


def test_the_kernels_plan_gives_the_reference_plans_logits():
    """What the chip's plan runs for this family (the paged prefill and
    decode kernels over the PACKED pools, the in-place writers), here
    under the Pallas interpreter: two prefill windows that tile pages,
    the second from the first's pages and tails, then decode steps,
    against the XLA reference plan."""
    from xllm_service_tpu.ops.plan import KernelPlan
    cfg = tiny_cfg(num_attention_heads=8, num_key_value_heads=4,
                   hidden_size=128)                 # two heads a row, twice
    assert T._kv_pack(cfg) == 4 and cfg.head_dim == 16
    params = T.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    ps, n_pages = 8, 6
    toks = np.random.default_rng(3).integers(3, cfg.vocab_size, size=40)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    kernels = KernelPlan(decode_attn=True, prefill_attn=True,
                         kv_writers=True, write_then_attend=True,
                         interpret=True)

    def run(plan):
        kv = T.init_kv_cache(cfg, n_pages + 1, ps, jnp.float32)
        outs = []
        for start, n in ((0, 16), (16, 16)):
            out = T.forward_prefill(
                params, cfg, jnp.asarray(toks[None, start:start + n]),
                jnp.asarray([start], jnp.int32), jnp.asarray([n], jnp.int32),
                kv, table, return_all_logits=True, plan=plan)
            outs.append(np.asarray(out[1][0]))
            kv = out[2]
        for p in range(32, 40):
            lg, kv = T.forward_decode(
                params, cfg, jnp.asarray(toks[p:p + 1], jnp.int32),
                jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table,
                plan=plan)
            outs.append(np.asarray(lg))
        return np.concatenate(outs)

    want, got = run(KernelPlan(write_then_attend=True)), run(kernels)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


# ---------------------------------------------------------------------------
# the normal path: a checkpoint's names, a worker's doors and counters
# ---------------------------------------------------------------------------

def rehearsal_config(**over):
    from chipbench import spec
    d = os.path.dirname(CONFIG)
    return {**spec.load_json(CONFIG),
            **spec.load_json(os.path.join(d, "meta.json"))
            ["rehearsal_widths"], **over}


def test_the_loader_reads_the_published_checkpoints_names(tmp_path):
    """A checkpoint written under HF's ``Lfm2Moe*`` names (torch's [out,
    in], the depthwise filter [D, 1, K], one tensor an expert) loads into
    the tree the benchmark's generator hands the program."""
    from safetensors.numpy import save_file
    from chipbench import spec, weights
    from xllm_service_tpu.runtime.checkpoint import load_checkpoint
    cfg = rehearsal_config(torch_dtype="float32")
    wts = spec.load_weights(os.path.dirname(CONFIG))
    key = weights.root_key(9)
    head = wts.head_params(cfg, key)
    out = {"model.embed_tokens.weight": np.asarray(head["embed"]),
           "model.embedding_norm.weight": np.asarray(head["final_norm"])}
    for i, kind in enumerate(wts.layer_kinds(cfg)):
        for name, leaf in wts.layer_params(cfg, key, i, kind).items():
            leaf, at = np.asarray(leaf), f"model.layers.{i}.{name}"
            if name == "conv.conv":
                out[at + ".weight"] = np.ascontiguousarray(leaf.T[:, None])
            elif name == "feed_forward.expert_bias":
                out[at] = leaf
            elif "experts." in name:
                for e in range(leaf.shape[0]):
                    out[at.replace("experts.", f"experts.{e}.")
                        + ".weight"] = np.ascontiguousarray(leaf[e].T)
            elif leaf.ndim == 2:
                out[at + ".weight"] = np.ascontiguousarray(leaf.T)
            else:
                out[at + ".weight"] = leaf
    save_file(out, str(tmp_path / "model.safetensors"))
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg, "ckpt"),
                             dtype="float32")
    got = load_checkpoint(str(tmp_path), mc)
    want = wts.program_tree(cfg, 9)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert set(flat_got) == set(flat_want)
    for path, leaf in flat_want.items():
        # the generator's jitted draw and its plain one differ in the
        # last bit of a float32 now and then (chipbench/weights.py)
        np.testing.assert_allclose(np.asarray(flat_got[path]),
                                   np.asarray(leaf), rtol=1e-6,
                                   err_msg=str(path))


def test_a_worker_serves_it_and_exports_the_states_ledger(tmp_path):
    """Through ``POST /v1/completions`` on a worker built from a model
    directory with the published ``model_type``: the same prompt twice,
    the second time from the first's pages and tails; the ledger on
    ``/metrics``, ``state_restored`` and ``moe`` in the step records."""
    from http.client import HTTPConnection
    from chipbench import cluster
    from xllm_service_tpu.runtime import worker as W
    from xllm_service_tpu.service.coordination import InMemoryStore
    cfg = rehearsal_config()
    model_dir = cluster.write_model_dir(str(tmp_path / "model"), cfg)
    with pytest.raises(ValueError, match="PD migration"):
        W.Worker(W.WorkerOptions(model="lfm2-tiny", model_dir=model_dir,
                                 instance_type=W.InstanceType.PREFILL),
                 InMemoryStore(),
                 engine_cfg=EngineConfig(page_size=16, num_pages=32,
                                         max_model_len=256))
    w = W.Worker(W.WorkerOptions(model="lfm2-tiny", model_dir=model_dir),
                 InMemoryStore(),
                 engine_cfg=EngineConfig(page_size=16, num_pages=32,
                                         max_model_len=256)).start()
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
            "model": "lfm2-tiny", "max_tokens": 6, "temperature": 0.0,
            "prompt": " ".join(f"t{i}" for i in range(5, 45)),
            "ignore_eos": True})
        first = call("POST", "/v1/completions", body)
        again = call("POST", "/v1/completions", body)
        assert first[0] == again[0] == 200
        assert json.loads(first[1])["choices"][0]["text"] \
            == json.loads(again[1])["choices"][0]["text"]

        def metric(name, **labels):
            return sum(float(ln.rsplit(" ", 1)[1])
                       for ln in call("GET", "/metrics")[1].splitlines()
                       if ln.startswith(name + "{") and all(
                           f'{k}="{v}"' in ln for k, v in labels.items()))

        eng = w.primary_runtime().engine
        assert metric("xllm_worker_state_rows_total", event="restored") == 1
        # 40 tokens over pages of 16: three rows, then the last page again
        assert metric("xllm_worker_state_rows_total", event="written") == 4
        assert metric("xllm_worker_state_pool_bytes") \
            == eng.kv[2].nbytes == 3 * 32 * 2 * 64 * 2
        assert metric("xllm_worker_moe_assignments_total") \
            == eng.moe_stats["assignments"] > 0
        assert metric("xllm_worker_moe_dropped_assignments_total") == 0
        recs = w.steptrace.tail()
        assert [r["state_restored"] for r in recs
                if r["state_restored"]] == [(0,), (1,)]
        assert any(r["moe"] for r in recs)
    finally:
        w.stop()
