"""The configuration ``lfm2-24b-a2b`` and its cell ``lfm2-24b-docqa64``:
the cut is the published config but for its depth, the plain reference
(a causal filter over the whole sequence, no cache, no state) agrees
with the program's prefill and decode through the pools and the
convolution tails, a served token that was altered fails the check, the
cost function counts the layers that attend, the new readers read what
they say, and the cell walks ``run.py --rehearse-cpu`` in a copied
root."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import check, spec, weights
from test_chipbench_rehearsal import rehearsal_counters

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "lfm2-24b-a2b")
CELL = "lfm2-24b-docqa64"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}


def published():
    return spec.load_json(os.path.join(CONFIG, "config.json"))


def meta():
    return spec.load_json(os.path.join(CONFIG, "meta.json"))


def tiny_config(**over):
    return {**published(), **meta()["rehearsal_widths"], **over}


def stored(cfg, seed):
    wts = spec.load_weights(CONFIG)
    key = weights.root_key(seed)
    return {**wts.head_params(cfg, key),
            "layers": [wts.layer_params(cfg, key, i, kind)
                       for i, kind in enumerate(wts.layer_kinds(cfg))]}


def test_the_configuration_is_the_published_one_but_for_its_depth():
    cfg, m = published(), meta()
    assert m["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "layer_types"]
    pub = m["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            len(pub["layer_types"])) == (40, 2, 40)
    # published layers 1-9: a dense convolution layer and two periods
    assert cfg["layer_types"] == pub["layer_types"][1:10]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    wts = spec.load_weights(CONFIG)
    assert wts.layer_kinds(cfg) == ["conv+dense"] + [
        "attn+moe", "conv+moe", "conv+moe", "conv+moe"] * 2
    # every width, every expert and the whole vocabulary are held
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"]) \
        == (2048, 11776, 1536, 64, 4, 65536)
    assert set(m["assumed"]) == {"tie_word_embeddings", "torch_dtype",
                                 "head width"}
    assert m["step_programs_from_cache"] is False
    tiny = tiny_config()
    kinds = wts.layer_kinds(tiny)
    assert tiny["num_experts"] >= 8 and tiny["num_experts_per_tok"] >= 2
    assert {k.split("+")[0] for k in kinds} == {"conv", "attn"}
    assert {k.split("+")[1] for k in kinds} == {"dense", "moe"}
    for file in ("reference.py", "weights.py",
                 "../../reference/conv_gqa_moe.py",
                 "../../weight_families/conv_gqa_moe.py"):
        assert "xllm_service_tpu" not in open(
            os.path.join(CONFIG, file)).read()


@pytest.mark.parametrize("seed, over", [
    (11, {}),
    # four key-value heads of 16: two rows of the pool, two heads each
    (2**31 + 9, {"num_attention_heads": 8, "num_key_value_heads": 4,
                 "hidden_size": 128}),
])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed, over):
    """Logits at the rehearsal widths, float32 both sides: the program's
    ``forward_prefill`` in two windows (24 tokens, then 16 from the
    first window's tails and pages), then 16 decode steps, against the
    plain reference's one pass over all 56. The experts' own counts ride
    the statistics: nothing dropped."""
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    cfg = tiny_config(**over)
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg),
                             dtype="float32")
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  wts.program_tree(cfg, seed))
    leaves = stored(cfg, seed)
    assert float(jnp.abs(leaves["layers"][1]
                         ["feed_forward.expert_bias"]).max()) > 0.01
    n_moe = sum(k.endswith("+moe") for k in wts.layer_kinds(cfg))
    k = cfg["num_experts_per_tok"]
    T, ps = 56, 16
    toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"], size=T)
    want = np.asarray(ref.forward(leaves, toks, cfg))
    scale = np.abs(want).max()
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.float32)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    for start, n in ((0, 24), (24, 16)):
        padded = np.zeros((1, 32), np.int32)   # rows of padding: no group
        padded[0, :n] = toks[start:start + n]
        out = transformer.forward_prefill(
            tree, mc, jnp.asarray(padded), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), kv, table, return_all_logits=True,
            return_stats=True)
        assert np.asarray(out[-1]["moe"]).tolist()[:2] == [0, n * k * n_moe]
        assert np.abs(np.asarray(out[1][0, :n])
                      - want[start:start + n]).max() < 2e-4 * scale
        kv = out[2]
    for p in range(40, T):
        lg, kv, st = transformer.forward_decode(
            tree, mc, jnp.asarray(toks[p:p + 1], jnp.int32),
            jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table,
            return_stats=True)
        assert np.abs(np.asarray(lg[0]) - want[p]).max() < 2e-4 * scale, p
        assert np.asarray(st["moe"]).tolist() \
            == [0, k * n_moe, k * n_moe, n_moe, n_moe]


@pytest.mark.parametrize("what", [
    "the convolution's state", "the selection bias", "the head norms"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the
    program."""
    import jax.numpy as jnp
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=24)
    want = np.asarray(ref.forward(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    for lp in broken["layers"]:
        if what == "the convolution's state" and "conv.conv" in lp:
            # only the tap on the current position: no state at all
            lp["conv.conv"] = lp["conv.conv"].at[:-1].set(0)
        if what == "the selection bias" and "feed_forward.expert_bias" in lp:
            lp["feed_forward.expert_bias"] = jnp.zeros_like(
                lp["feed_forward.expert_bias"])
        if what == "the head norms" and "self_attn.q_layernorm" in lp:
            lp["self_attn.q_layernorm"] = jnp.ones_like(
                lp["self_attn.q_layernorm"])
    got = np.asarray(ref.forward(broken, toks, cfg))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights:
    the reference's own greedy continuation reads a gap of 0 at every
    served token, and one token swapped for another reads a gap."""
    cfg = tiny_config()
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    seed = 2**31 + 5
    leaves = stored(cfg, seed)
    prompt = [int(t) for t in np.random.default_rng(3).integers(
        3, cfg["vocab_size"], size=20)]
    served = []
    for _ in range(6):
        lg = np.asarray(ref.forward(leaves, prompt + served, cfg))
        served.append(int(lg[-1].argmax()))
    sample = [{"id": "r0", "prompt": prompt, "token_ids": served}]
    good = check.compare(ref, wts, cfg, seed, sample)
    assert good["gap_max"] < 1e-4 and good["served_tokens"] == 6
    altered = list(served)
    altered[3] = (altered[3] + 1) % cfg["vocab_size"]
    bad = check.compare(ref, wts, cfg, seed,
                        [dict(sample[0], token_ids=altered)])
    assert bad["gap_max"] > 0.05 and bad["not_best"] >= 1


@pytest.mark.parametrize("cfg, layers", [
    (published, 2),                                       # 2 of 9 attend
    (lambda: {k: v for k, v in published().items()
              if k != "layer_types"}, 9),                 # no key: all do
    (lambda: spec.load_json(os.path.join(
        spec.ROOT, "chipbench", "configs", "mistral-7b-v01",
        "config.json")), 16),
])
def test_the_cost_file_counts_the_layers_that_attend(cfg, layers):
    cfg = cfg()
    new = spec.load_kernel_cost("decode_attention_by_layer_type")
    old = spec.load_kernel_cost("decode_attention")
    assert new.attention_layers(cfg) == layers
    flops, bytes_ = new.cost(9999, cfg)
    if "layer_types" not in cfg:
        assert (flops, bytes_) == old.cost(9999, cfg)
    else:
        # 10,000 positions, 8 key-value heads of 64, keys and values
        assert flops == layers * 4 * 10000 * 32 * 64
        assert bytes_ == layers * (2 * 10000 * 8 * 64 + 2 * 32 * 64) * 2


def test_the_experts_cost_reads_this_configurations_widths():
    flops, bytes_ = spec.load_kernel_cost("moe_experts").cost(
        2048, 504, published())
    # 64 rows x 4 x 8 layers; an expert is 3 x 2048 x 1536 x 2 B = 18.9 MB
    assert flops == 2048 * 3 * 2 * 2048 * 1536
    assert bytes_ == 504 * 18874368 + 2048 * (2 * 2048 + 2 * 1536) * 2


def test_the_mix_is_the_issues():
    mix = spec.load_json(os.path.join(spec.ROOT, "chipbench", "traffic",
                                      "docqa64.json"))
    assert (mix["loop"], mix["clients"]) == ("closed", 64)
    assert mix["engine"] == {"page_size": 128, "num_pages": 3776,
                             "max_model_len": 12288, "max_batch_size": 64}
    docs = mix["shared_prefix"]["lengths"]
    assert len(docs) == 42 and sum(docs) == 382872
    assert sorted(set(docs)) == [8092, 9116, 10140]
    assert sum(-(-n // 128) for n in docs) == 3024
    assert all(n // 128 >= 63 for n in docs)     # table width 96 always
    assert (mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"],
            mix["output_tokens"]["min"], mix["output_tokens"]["max"]) \
        == (104, 192, 96, 160)
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["clients"] * mix["stagger_s"] <= mix["ramp_s"]


def test_the_new_readers_on_hand_made_steps():
    lo = 50.0
    steps = [
        {"t_wall": 100.5, "kind": "mixed", "state_restored": (1, 1),
         "moe": {"assignments": 9999, "experts_touched": 9, "dropped": 0,
                 "load_max_over_mean": 2.0}},
        {"t_wall": 100.6, "kind": "decode", "state_restored": (),
         "moe": {"assignments": 2048, "experts_touched": 500, "dropped": 0,
                 "load_max_over_mean": 2.0}},
        {"t_wall": 100.7, "kind": "decode", "state_restored": (),
         "moe": {"assignments": 2048, "experts_touched": 508, "dropped": 0,
                 "load_max_over_mean": 2.0}},
        {"t_wall": 101.5, "kind": "prefill", "state_restored": (0, 1),
         "moe": None},
        {"t_wall": 300.0, "kind": "prefill", "state_restored": (0,)}]
    ctx = {"steps": steps, "config": published(), "open_t": lo,
           "close_t": lo + 2.0, "wall_minus_mono": 50.0,
           "cell": spec.load_cell(CELL)}

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    assert read("state_restored_share.docqa64") == 75.0
    assert read("moe_experts_touched_share.docqa64") == pytest.approx(
        100 * (500 + 508) / (64 * 8 * 2))
    # a program without the record (the parent) gives the readers nothing
    old = dict(ctx, steps=[{"t_wall": 100.5, "kind": "decode"}])
    assert read("state_restored_share.docqa64", old) is None
    assert read("moe_experts_touched_share.docqa64", old) is None


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    assert {"decode_attn_roofline.docqa64", "state_restored_share.docqa64",
            "moe_gmm_roofline.docqa32", "attn_share_of_decode_step.docqa64",
            "prefix_hit_token_share.docqa", "hbm_peak_gb"} <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "out_tok_s", "setup_s"}
    for m in cell.per_layer:
        info = spec.layer_metric_file(m["name"], root)
        assert info["name"] == m["name"] and info["layer"] == m["layer"]
        assert (info["unit"], info["source"], info["moves"]) \
            == (m["unit"], m["source"], m["moves"])
        assert callable(spec.load_reader(info["reader"], root).read)
        if "kernel_cost" in info:
            assert callable(spec.load_kernel_cost(info["kernel_cost"],
                                                  root).cost)


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu`` at the configuration's tiny widths: set-up (the
    documents' pages and their rows of tails), a window of follow-ups
    that each begin from a cached page's tails, the reference check over
    12 served tokens, and the counters' metrics in the line."""
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
    m = out["metrics"]
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    assert m["prefix_hit_token_share.docqa"]["value"] > 50
