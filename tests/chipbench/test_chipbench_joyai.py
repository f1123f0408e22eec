"""The configuration ``joyai-llm-flash`` and its cell
``joyai-flash-docqa32``: the published config is read into the latent
family's fields, the plain reference agrees with the program's prefill
and decode through the latent cache and the dropless expert layer, the
cost functions are their hand counts, and the cell walks
``run.py --rehearse-cpu`` in a copied root."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import spec, weights
from test_chipbench_rehearsal import rehearsal_counters

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "joyai-llm-flash")
CELL = "joyai-flash-docqa32"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}


def published():
    return spec.load_json(os.path.join(CONFIG, "config.json"))


def tiny_config():
    cfg = published()
    cfg.update(spec.load_json(os.path.join(CONFIG, "meta.json"))[
        "rehearsal_widths"])
    return cfg


def test_the_published_config_reads_into_the_latent_family():
    from xllm_service_tpu.config import ModelConfig
    cfg = published()
    assert cfg["model_type"] == "joyai_llm_flash" and cfg["head_dim"] == 64
    mc = ModelConfig.from_hf_config(cfg, "joyai-llm-flash")
    assert mc.mla and (mc.kv_lora_rank, mc.q_lora_rank) == (512, 1536)
    assert (mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim) \
        == (128, 64, 128)
    # the cache row is the latent one; ``head_dim: 64`` reaches nothing
    assert (mc.kv_cache_heads, mc.kv_cache_dim, mc.qk_head_dim) \
        == (1, 576, 192)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.n_shared_experts,
            mc.moe_intermediate_size) == (256, 8, 1, 768)
    assert mc.moe_scoring == "sigmoid" and mc.norm_topk_prob
    assert mc.routed_scaling_factor == 2.5
    assert mc.topk_method == "greedy"       # noaux_tc with ONE group
    assert mc.first_k_dense_replace == 1 and mc.rope_interleave
    assert mc.rope_theta == 32e6 and mc.rope_scaling is None
    assert (mc.hidden_size, mc.intermediate_size, mc.num_heads,
            mc.vocab_size) == (2048, 7168, 32, 129280)
    # a model type the parser does not know is refused, not approximated
    with pytest.raises(ValueError, match="unsupported model_type"):
        ModelConfig.from_hf_config({**cfg, "model_type": "joyai_next"})


def test_the_configuration_is_the_published_one_but_for_its_depth():
    cfg, meta = published(), spec.load_json(os.path.join(CONFIG,
                                                         "meta.json"))
    assert meta["reduced"] == ["num_hidden_layers"]
    assert meta["published"] == {"num_hidden_layers": 40}
    assert cfg["num_hidden_layers"] == 5 and cfg["n_routed_experts"] == 256
    assert "num_nextn_predict_layers" in meta["left_out"]
    assert meta["step_programs_from_cache"] is False
    assert set(meta["rehearsal_widths"]) <= set(cfg)
    tiny = tiny_config()
    assert tiny["n_routed_experts"] >= 16 \
        and tiny["num_experts_per_tok"] >= 4
    wts = spec.load_weights(CONFIG)
    assert wts.layer_kinds(cfg) == ["dense"] + ["sparse"] * 4
    for file in ("reference.py", "weights.py",
                 "../../reference/latent_moe.py",
                 "../../weight_families/latent_moe.py"):
        assert "xllm_service_tpu" not in open(
            os.path.join(CONFIG, file)).read()


@pytest.mark.parametrize("seed", [11, 2**31 + 9])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed):
    """Logits at the rehearsal widths, float32 both sides: the program's
    ``forward_prefill`` over 40 tokens (absorbed latent attention, the
    sorted grouped matmuls), then 16 decode steps through the latent
    cache, against the plain reference's one pass over all 56
    (un-absorbed, each expert over the tokens that chose it). The layer's own counts
    ride the statistics: nothing dropped, rows x 4 x sparse layers."""
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    cfg = tiny_config()
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg),
                             dtype="float32")
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  wts.program_tree(cfg, seed))
    key, kinds = weights.root_key(seed), wts.layer_kinds(cfg)
    n_sparse, k = kinds.count("sparse"), cfg["num_experts_per_tok"]
    stored = {**wts.head_params(cfg, key),
              "layers": [wts.layer_params(cfg, key, i, kind)
                         for i, kind in enumerate(kinds)]}
    assert float(jnp.abs(stored["layers"][1]
                         ["e_score_correction_bias"]).max()) > 0.01
    T, P, ps = 56, 40, 16
    toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"], size=T)
    want = np.asarray(ref.forward(stored, toks, cfg))
    scale = np.abs(want).max()
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    padded = np.zeros((1, 48), np.int32)       # 8 rows of padding: no group
    padded[0, :P] = toks[:P]
    out = transformer.forward_prefill(
        tree, mc, jnp.asarray(padded), jnp.zeros((1,), jnp.int32),
        jnp.asarray([P], jnp.int32), kv, table, return_all_logits=True,
        return_stats=True)
    assert int(out[-1]["moe_dropped"]) == 0
    assert np.asarray(out[-1]["moe"]).tolist()[:2] == [0, P * k * n_sparse]
    assert np.abs(np.asarray(out[1][0, :P]) - want[:P]).max() < 2e-4 * scale
    kv = out[2]
    for p in range(P, T):
        lg, kv, st = transformer.forward_decode(
            tree, mc, jnp.asarray(toks[p:p + 1], jnp.int32),
            jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table,
            return_stats=True)
        assert np.abs(np.asarray(lg[0]) - want[p]).max() < 2e-4 * scale, p
        assert np.asarray(st["moe"]).tolist() \
            == [0, k * n_sparse, k * n_sparse, n_sparse, n_sparse]


@pytest.mark.parametrize("tokens, block, control", [
    (56, 16, ""), (37, 16, ""), (9, 256, ""), (37, 16, "int8")])
def test_the_references_experts_are_the_sum_over_every_expert(
        tokens, block, control, monkeypatch):
    """The reference walks each expert over the tokens that chose it, a
    block at a time (the published widths: 256 experts over 10k tokens,
    where every expert on every token was a minute of the check). That is
    the plain sum over EVERY expert of its SwiGLU times the gate's map:
    with a last block that is not full, with more tokens than one block,
    with fewer, and with experts that no token chose; under the check's
    int8 control too, whose rounding is per row and per output channel
    and so does not see which rows share a block."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import latent_moe as body
    monkeypatch.setattr(body, "BLOCK", block)
    cfg = tiny_config()
    wts = spec.load_weights(CONFIG)
    key = weights.root_key(5)
    lp = wts.layer_params(cfg, key, 1, "sparse")
    h = jax.random.normal(jax.random.fold_in(key, 3),
                          (tokens, cfg["hidden_size"]), jnp.float32)
    from chipbench import check
    mm = check.CONTROLS[control] if control else body.mm_f32
    w = body.gate_map(h, lp, cfg, mm)
    assert int((w != 0).sum()) == tokens * cfg["num_experts_per_tok"]
    if tokens == 9:
        assert bool(((w != 0).sum(axis=0) == 0).any())   # an idle expert
    want = body.swiglu(h, lp["shared_experts.gate_proj"],
                       lp["shared_experts.up_proj"],
                       lp["shared_experts.down_proj"], mm)
    for e in range(cfg["n_routed_experts"]):
        want = want + body.swiglu(
            h, lp["experts.gate_proj"][e], lp["experts.up_proj"][e],
            lp["experts.down_proj"][e], mm) * w[:, e:e + 1]
    got = jax.jit(lambda h: body.experts(h, lp, cfg, mm))(h)
    # int8 rounds the second matmul's input: a sum that differs in its
    # last bit now and then lands on the other side of a rounding step
    tol = 5e-3 if control else 1e-5
    assert np.abs(np.asarray(got - want)).max() \
        < tol * np.abs(np.asarray(want)).max()


def test_the_heads_remembered_maker_gives_the_leaves_of_a_plain_draw():
    """``program_tree`` keeps the compiled maker of embedding and head;
    the check's ``head_params``, outside any jit, runs it instead of
    drawing operation by operation. Same leaves, bit for bit, and under
    a trace the draw itself."""
    import jax
    from chipbench.weight_families import latent_moe as family
    cfg, wts = tiny_config(), spec.load_weights(CONFIG)
    key = weights.root_key(2**31 + 7)
    family._HEAD_MAKERS.clear()
    plain = wts.head_params(cfg, key)
    tree = wts.program_tree(cfg, 2**31 + 7)
    assert len(family._HEAD_MAKERS) == 1
    again = wts.head_params(cfg, key)
    traced = jax.jit(lambda k: wts.head_params(cfg, k))(key)
    for name, leaf in plain.items():
        for other in (again, traced, tree):
            assert np.array_equal(np.asarray(leaf, np.float32),
                                  np.asarray(other[name], np.float32)), name
    # another configuration has no maker yet and draws
    assert wts.head_params({**cfg, "vocab_size": 256}, key)[
        "embed"].shape == (256, cfg["hidden_size"])


def test_the_cost_functions_are_their_hand_counts():
    cfg = published()
    moe = spec.load_kernel_cost("moe_experts")
    # one decode step of 32 rows: 256 assignments a layer, 4 layers, 163
    # experts touched a layer; an expert is 3 x 2048 x 768 x 2 B = 9.44 MB
    flops, bytes_ = moe.cost(1024, 652, cfg)
    assert flops == 1024 * 3 * 2 * 2048 * 768
    assert bytes_ == 652 * 9437184 + 1024 * (2 * 2048 + 2 * 768) * 2
    att = spec.load_kernel_cost("latent_decode_attention")
    flops, bytes_ = att.cost(9999, cfg)      # 10,000 positions, 5 layers
    assert flops == 5 * 2 * 10000 * 32 * (576 + 512)
    assert bytes_ == 5 * (10000 * 1152 + 32 * 576 * 2 + 32 * 512 * 2)


def test_the_mix_is_the_issues_but_for_the_pool():
    mix = spec.load_json(os.path.join(spec.ROOT, "chipbench", "traffic",
                                      "docqa32.json"))
    assert (mix["loop"], mix["clients"]) == ("closed", 32)
    eng, docs = mix["engine"], mix["shared_prefix"]["lengths"]
    assert (eng["page_size"], eng["max_batch_size"],
            eng["max_model_len"]) == (128, 32, 12288)
    assert sorted(set(docs)) == [8092, 9116, 10140]
    pages = sum(-(-n // 128) for n in docs)
    # the documents and 3 private pages a client fit beside page 0
    assert pages + 3 * mix["clients"] < eng["num_pages"] - 1
    assert all(n // 128 >= 63 for n in docs)     # table width 96 always
    shapes = mix["warmup"]["prefill"]
    assert len(shapes[0]["shapes"]) + len(shapes[1]["B"]) \
        + len(mix["warmup"]["decode_widths"]) == 10


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu`` at the configuration's tiny widths: set-up,
    window, the reference check over 12 served tokens, and the counters'
    metrics in the line. The widest gap is held to a fault's size only
    (an expert's near-tie flips a token now and then, PERF.md PR 29);
    what the walk proves is the count and the plumbing."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "5", "--trace", "2",
         "--rehearse-cpu", "--limit", "8.0"], cwd=root, env=ENV,
        timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert out["failed"] == 0 and out["attempted"] > 0
    cmp_ = out["compared"]
    assert cmp_["served_tokens_compared"] == {"value": 12, "limit": 12}
    assert cmp_["served_token_gap_max"]["value"] < 8.0
    assert "served_token_gap_p90" in cmp_
    m = out["metrics"]
    # a CPU run reports the worker's counters and never a device time
    # (nor what the step records say: run.py reads those under a trace)
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    assert m["prefix_hit_token_share.docqa"]["value"] > 50


def _trace(ops, modules):
    """A device plane: ``ops`` and ``modules`` as (name, start, dur) ns."""
    plane = "/device:TPU:0"
    evs = [{"plane": plane, "line": "XLA Ops", "name": n, "start": s,
            "dur": d} for n, s, d in ops]
    evs += [{"plane": plane, "line": "XLA Modules", "name": n, "start": s,
             "dur": d} for n, s, d in modules]
    return evs


def test_the_new_readers_on_a_hand_made_trace_and_hand_made_steps():
    """One decode program of 10 ms in which three grouped matmuls take
    2 + 2 + 1 ms and a softmax 1 ms; step records of two decode steps and
    a prefill step."""
    cfg = published()
    dec = "%while.5 = (s32[]{:T(128)}, bf16[32,1,2048]{2,0,1}) while(...)"
    ms = 1_000_000
    events = _trace(
        [(dec, 0, 10 * ms),
         ("%gmm.11 = bf16[256,768]{1,0} custom-call(%a)", 1 * ms, 2 * ms),
         ("%gmm.12 = bf16[256,768]{1,0} custom-call(%a)", 3 * ms, 2 * ms),
         ("%gmm.13 = bf16[256,2048]{1,0} custom-call(%a)", 5 * ms, 1 * ms),
         ("%fusion.9 = f32[32,32,12288]{2,1,0} fusion(%b)", 6 * ms, 1 * ms),
         ("%gmm.20 = bf16[2048,768]{1,0} custom-call(%a)", 20 * ms, 4 * ms)],
        [("jit__unknown(1)", 0, 10 * ms), ("jit__unknown(2)", 19 * ms,
                                           6 * ms)])
    moe = {"assignments": 1024, "experts_touched": 652, "dropped": 0,
           "load_max_over_mean": 2.5}
    steps = [
        {"t_wall": 100.5, "kind": "decode", "moe": moe},
        {"t_wall": 101.5, "kind": "decode",
         "moe": dict(moe, experts_touched=660, load_max_over_mean=3.5)},
        {"t_wall": 101.7, "kind": "prefill",
         "moe": dict(moe, assignments=8192, experts_touched=1024)},
        {"t_wall": 101.9, "kind": "decode", "moe": None}]
    ctx = {"trace": {"events": events, "wall0": 100.0, "wall1": 101.0},
           "steps": steps, "config": cfg, "device_kind": "TPU v5 lite",
           "root": spec.ROOT, "open_t": 50.0, "close_t": 52.0,
           "wall_minus_mono": 50.0}

    def read(metric):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    # one step ended inside the traced second: 652 experts' bytes over
    # 819 GB/s against the 9 ms the four matmuls took
    flops, bytes_ = spec.load_kernel_cost("moe_experts").cost(1024, 652, cfg)
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read("moe_gmm_roofline.docqa32") \
        == pytest.approx(100 * least / 9e-3)
    assert 80 < read("moe_gmm_roofline.docqa32") < 90
    assert read("moe_gmm_share_of_decode_step.docqa32") == pytest.approx(50.0)
    assert read("moe_experts_touched_share.docqa32") == pytest.approx(
        100 * (652 + 660) / (256 * 4 * 2))
    assert read("moe_load_max_over_mean.docqa32") == 3.0
    assert read("moe_dropped_assignments.docqa32") == 0
    # a program that counts nothing (the parent) gives the readers nothing
    old = dict(ctx, steps=[{"t_wall": 100.5, "kind": "decode"}])
    for metric in ("moe_gmm_roofline", "moe_experts_touched_share",
                   "moe_load_max_over_mean", "moe_dropped_assignments"):
        info = spec.layer_metric_file(metric + ".docqa32")
        assert spec.load_reader(info["reader"]).read(old, info) is None
