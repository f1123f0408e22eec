"""The configuration ``trinity-mini`` and its cell
``trinity-mini-mixdoc16``: the config is the catalog's with the held
experts and the vocabulary's slice alone reduced, the plain reference
(the whole sequence under a mask for the window, no pool) agrees with
the program's prefill and decode through BOTH pools at the rehearsal
widths, the reference changes when a mechanism is taken out of it, a
served token that was altered fails the check, the cost files'
arithmetic stands on hand-worked shapes, the mix is the issue's, the
rooflines and the two kernels' shares read a hand-made trace, and the
cell walks ``run.py --rehearse-cpu`` in a copied root."""

import dataclasses
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import check, spec, weights
from test_chipbench_rehearsal import (EVERY_CELL_REPORTS,
                                      rehearsal_counters)

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "trinity-mini")
CELL = "trinity-mini-mixdoc16"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {f"{n}.mixdoc16" for n in (
    "decode_attn_roofline", "window_attn_share_of_decode_step",
    "full_attn_share_of_decode_step", "decode_step_roofline",
    "kv_window_pages_peak_share", "window_tail_hit_share")}


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


def test_the_configuration_is_the_catalogs_with_the_share_reduced():
    cfg, m = published(), meta()
    assert m["reduced"] == ["num_experts", "vocab_size"] \
        and m["source"].endswith(
            "arcee-ai/Trinity-Mini/blob/main/config.json")
    assert m["published"] == {"num_experts": 128, "vocab_size": 200192}
    # no layer is left out, and every width is the published one
    assert (cfg["model_type"], cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["sliding_window"], cfg["num_dense_layers"],
            cfg["num_experts_per_tok"], cfg["num_shared_experts"],
            cfg["route_scale"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["tie_word_embeddings"]) == (
        "afmoe", 32, 2048, 6144, 1024, 32, 4, 128, 2048, 2, 8, 1, 2.826,
        10000, 1e-05, False)
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert (cfg["num_experts"], cfg["expert_share_chips"],
            cfg["expert_share_rank"]) == (16, 8, 0)
    assert cfg["num_experts"] * cfg["expert_share_chips"] == 128
    assert cfg["vocab_size"] * 8 == 200192
    assert set(m["rehearsal_widths"]) <= set(cfg)
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Trinity-Mini"' in ln)
        assert row["source_url"] == m["source"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_experts", "vocab_size"}
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == m["reduced"] \
        and entry["source"] == m["source"]
    assert m["step_programs_from_cache"] is False
    # every mechanism the config has no key for is stated, and the caveat
    for key in ("the layer", "rotation", "window", "output gate", "router",
                "dense layers", "weights"):
        assert key in m["assumed"], key
    for key in ("two pools", "tails", "a held share", "one table width"):
        assert key in m["departures"], key
    assert "STATED CAVEAT OF THE CUT" in m["deployment"]
    assert "an eighth" in m["deployment"] and "No layer is left out" \
        in m["deployment"]


def test_from_hf_config_on_the_catalogs_config_dict():
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models.transformer import kinds_pattern
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"Trinity-Mini"' in ln)
    mc = ModelConfig.from_hf_config(row["config"], "trinity-mini")
    assert len(mc.layer_kinds) == 32 == row["layers"]
    assert kinds_pattern(mc.layer_kinds) == (2, 4, 7)
    assert (mc.hidden_size, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.vocab_size, mc.intermediate_size,
            mc.moe_intermediate_size) == (
        row["hidden_size"], row["num_attention_heads"],
        row["num_key_value_heads"], row["head_dim"], row["vocab_size"],
        row["dense_width"], row["expert_width"])
    assert (mc.num_swa_layers, mc.num_attn_layers) == (24, 8)
    assert mc.num_conv_layers == mc.num_state_layers == 0
    assert (mc.num_experts, mc.router_experts) == (128, 128)


def test_the_weights_and_the_pools_are_what_the_files_say():
    """The deployment's arithmetic (meta.json), from shapes alone."""
    import jax
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.runtime.engine import window_pool_pages
    cfg = published()
    wts = spec.load_weights(CONFIG)
    key = weights.root_key(1)
    # a layer of each kind under the program's names (program_tree
    # stacks them, one compiled call a kind)
    kinds = wts.layer_kinds(cfg)
    one = {kind: jax.eval_shape(lambda kind=kind: wts.program_layer(
        wts.layer_params(cfg, key, 0, kind), kind)) for kind in set(kinds)}
    assert set(one) == {"swa+dense", "swa+moe", "attn+moe"}
    assert set(one["swa+moe"]) >= {"post_attn_norm", "post_mlp_norm",
                                   "attn_gate", "q_norm", "router_bias"}

    def a_layer(kind):
        return sum(int(np.prod(x.shape)) for x in one[kind].values())
    assert abs(a_layer("swa+moe") - 134.5e6) < 0.1e6
    assert a_layer("swa+moe") == a_layer("attn+moe")
    assert abs(a_layer("swa+dense") - 65.0e6) < 0.1e6
    head = jax.eval_shape(lambda: wts.head_params(cfg, key))
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(
                    [head] + [one[k] for k in kinds]))
    assert abs(total - 8.53e9) < 0.02e9
    mc = ModelConfig.from_hf_config(cfg, "trinity-mini")
    eng = spec.load_cell(CELL).traffic["engine"]
    tail, wpages = window_pool_pages(2048, 128, 16, 16, 2048)
    assert (tail, wpages) == (16, 16 * 18 + 16 * 16 + 17 + 1) == (16, 562)
    kv = jax.eval_shape(lambda: transformer.init_kv_cache(
        mc, eng["num_pages"], eng["page_size"], window_pages=wpages))
    k, v, tails, wk, wv = (int(np.prod(x.shape)) * x.dtype.itemsize
                           for x in kv)
    assert kv[0].shape == (8, 1088, 128, 4, 128) and tails == 0
    assert kv[3].shape == (24, 562, 128, 4, 128)
    assert k // 1088 == 1024 ** 2 and wk // 562 == 3 * 1024 ** 2
    assert abs(k + v - 2.28e9) < 0.01e9 and abs(wk + wv - 3.54e9) < 0.01e9
    # one pool for all 32 layers: the documents alone 7.0 GB
    assert abs(106664 * 32 * 2048 - 6.99e9) < 0.01e9
    assert total + k + v + wk + wv < 14.4e9


@pytest.mark.parametrize("seed, dtype", [(5, "bfloat16"),
                                         (2**31 + 9, "float32")])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed, dtype):
    """Prefill in windows, then decode, through both pools with the
    window table trimmed as the engine trims it, against the reference's
    full forward over three windows of context: float32 to 2e-5 of the
    largest logit; bfloat16 (the served type) to a quarter of it at
    these tiny widths."""
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu.config import ModelConfig
    from xllm_service_tpu.models import transformer as T
    cfg = tiny_config(torch_dtype=dtype)
    wts, ref = spec.load_weights(CONFIG), spec.load_reference(CONFIG)
    params = wts.program_tree(cfg, seed)
    mc = dataclasses.replace(ModelConfig.from_hf_config(cfg, "tiny"),
                             dtype=dtype)
    W, ps, n, more = cfg["sliding_window"], 128, 768, 6
    toks = np.random.default_rng(seed % 1000).integers(
        3, cfg["vocab_size"], size=n + more)
    want = np.asarray(ref.forward(stored(cfg, seed), toks, cfg))
    tol = (2e-5 if dtype == "float32" else 0.25) * np.abs(want).max()
    mp = 8
    kv = T.init_kv_cache(mc, mp + 1, ps, jnp.dtype(dtype),
                         window_pages=mp + 1)
    full = np.arange(1, mp + 1, dtype=np.int32)
    win = full.copy()
    prefill = jax.jit(lambda p, w, s, ln, kv, pt: T.forward_prefill(
        p, mc, w, s, ln, kv, pt, return_all_logits=True)[:3])
    decode = jax.jit(lambda p, t, pos, kv, pt: T.forward_decode(
        p, mc, t, pos, jnp.asarray([True]), kv, pt)[:2])

    def tables():
        return jnp.asarray(np.concatenate([full, win])[None])

    for lo in range(0, n, 256):
        tk = np.asarray(toks[lo:lo + 256], np.int32)[None]
        _, everything, kv = prefill(
            params, jnp.asarray(tk), jnp.asarray([lo], jnp.int32),
            jnp.asarray([256], jnp.int32), kv, tables())
        assert np.abs(np.asarray(everything)[0] - want[lo:lo + 256]).max() \
            <= tol, lo
        win[:max((lo + 256 - W) // ps, 0)] = 0
    assert (win != 0).sum() == W // ps + mp - n // ps
    for pos in range(n, n + more):
        lg, kv = decode(params, jnp.asarray([toks[pos]]),
                        jnp.asarray([pos]), kv, tables())
        assert np.abs(np.asarray(lg)[0] - want[pos]).max() <= tol, pos


@pytest.mark.parametrize("what", [
    "the window", "the rotation", "rotation of a full layer", "the q norm",
    "the k norm", "the output gate", "the embedding's multiplier",
    "the post-attention norm", "the post-mlp norm", "the selection bias",
    "the route scale", "the shared expert", "the held share"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the
    program."""
    import jax.numpy as jnp
    import chipbench.reference.swa_gqa_moe as body
    cfg = tiny_config(torch_dtype="float32", sliding_window=16)
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=48)
    want = np.asarray(body.forward(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    run_cfg = dict(cfg)
    ones = {"the q norm": "self_attn.q_norm",
            "the k norm": "self_attn.k_norm",
            "the post-attention norm": "post_attention_layernorm",
            "the post-mlp norm": "post_mlp_layernorm"}
    zero = {"the output gate": "self_attn.gate_proj",
            "the selection bias": "mlp.expert_bias",
            "the shared expert": "mlp.shared_experts.down_proj"}
    if what in ones:
        for lp in broken["layers"]:
            lp[ones[what]] = jnp.ones_like(lp[ones[what]])
    elif what in zero:          # a gate of zeros is a gate of one half
        for lp in broken["layers"]:
            if zero[what] in lp:
                lp[zero[what]] = jnp.zeros_like(lp[zero[what]])
    elif what == "the window":
        run_cfg["sliding_window"] = 17
    elif what == "the rotation":
        run_cfg["rope_theta"] = 1e30            # every angle 0 but one
    elif what == "rotation of a full layer":
        run_cfg["layer_types"] = ["sliding_attention"] * 8
        run_cfg["sliding_window"] = 10 ** 6     # every layer rotates
    elif what == "the embedding's multiplier":
        broken["embed"] = leaves["embed"] / 8.0
    elif what == "the route scale":
        run_cfg["route_scale"] = 1.0
    else:                                       # the held share
        run_cfg["expert_share_rank"] = 1
    got = np.asarray(body.forward(broken, toks, run_cfg))
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_reference_refuses_what_it_has_no_body_for():
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    for key, value in (("rope_scaling", {"factor": 2.0}), ("n_group", 2),
                       ("topk_group", 2), ("score_func", "softmax"),
                       ("hidden_act", "gelu"), ("num_shared_experts", 2)):
        with pytest.raises(ValueError):
            ref.forward(leaves, [5, 6, 7, 8], dict(cfg, **{key: value}))
    with pytest.raises(ValueError):
        ref.layer(None, {}, cfg, ref.mm_f32, "kda+moe", None)


def test_every_sequence_of_the_cell_is_one_shape_to_the_reference():
    """A check compiles each kind of layer once a SHAPE: what fits 4,096
    positions is padded to that (these tests, a rehearsal), everything
    longer to 33,792, the cell's 4k, 16k and 32k documents' alike."""
    import jax.numpy as jnp
    ref = spec.load_reference(CONFIG)
    table = jnp.ones((16, 4), jnp.float32)
    for n, padded in ((1, 4096), (4096, 4096), (4097, 33792),
                      (4124 + 192 + 288, 33792), (33276, 33792),
                      (33793, 2 * 33792)):
        x = ref.embed(jnp.zeros((n,), jnp.int32), table)
        assert x.shape == (padded, 4), n
        assert float(x[n - 1, 0]) == 2.0 and (
            n == padded or float(x[n, 0]) == 0.0)
    import chipbench.reference.swa_gqa_moe as body
    assert all(p % body.ROWS == 0 for p in body.SEQ_PADS)


def test_the_reference_imports_nothing_from_the_program():
    for path in (os.path.join(CONFIG, "reference.py"), os.path.join(
            spec.ROOT, "chipbench", "reference", "swa_gqa_moe.py")):
        text = open(path).read()
        assert "xllm_service_tpu" not in text.split('"""', 2)[2]
    code = open(os.path.join(spec.ROOT, "chipbench", "reference",
                             "swa_gqa_moe.py")).read().split('"""', 2)[2]
    # the whole sequence under a mask: no table of pages, no gather
    assert "page_table" not in code and "gather" not in code
    assert "HIGHEST" in code and "float32" in code


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights:
    the reference's own greedy continuation reads a gap of 0 at every
    served token, one token swapped for another reads a gap, and the
    int8 control is read at the same positions."""
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
    good = check.compare(ref, wts, cfg, seed, sample, control="int8")
    assert good["gap_max"] < 1e-4 and good["served_tokens"] == 6
    assert good["control"]["positions"] == 6
    altered = list(served)
    altered[3] = (altered[3] + 1) % cfg["vocab_size"]
    bad = check.compare(ref, wts, cfg, seed,
                        [dict(sample[0], token_ids=altered)])
    assert bad["gap_max"] > 0.05 and bad["not_best"] >= 1


def test_the_cost_files_arithmetic_on_hand_worked_shapes():
    cfg = published()
    att = spec.load_kernel_cost("decode_attention_by_window")
    step = spec.load_kernel_cost("decode_step_swa_gqa_moe")
    assert att.layers(cfg) == (24, 8)
    # a query at position 20,000: the window keeps 2,048 positions, whose
    # oldest lies 33 positions into its page: the walk reads 17 pages'
    # worth less what the newest page lacks
    n = 20001
    kept, read = att.window_positions(20000, cfg)
    assert kept == 2048 and read == n - (n - 2048) // 128 * 128 == 2081
    assert att.window_positions(99, cfg) == (100, 100)      # all there is
    assert att.window_positions(2047, cfg) == (2048, 2048)
    assert att.window_positions(2048, cfg) == (2048, 2049)
    ops, bytes_ = att.cost(20000, cfg)
    assert ops == 4.0 * 32 * 128 * (24 * 2048 + 8 * n)
    assert bytes_ == (2.0 * 4 * 128 * (24 * 2081 + 8 * n)
                      + 32 * 2.0 * 32 * 128) * 2
    # the full layers read 3.2 times what the window layers read there
    assert 3.1 < (8 * n) / (24 * 2081) < 3.3
    peaks = spec.peaks_for("TPU v5 lite")
    assert (bytes_ / peaks["hbm_bytes_s"]) > (ops / peaks["bf16_flops"])
    # every layer's own matrices
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    sparse = 2048 * 128 + 3 * 2048 * 1024
    assert step.own_weights(cfg) == 32 * attn + 2 * 3 * 2048 * 6144 \
        + 30 * sparse
    assert abs(step.own_weights(cfg) - 1.144e9) < 0.001e9
    _, walk = step.step_cost(cfg)
    assert walk == (step.own_weights(cfg) + 2048 * 25024) * 2
    r_ops, r_bytes = step.row_cost(20000, cfg)
    assert r_ops == 2.0 * (step.own_weights(cfg) + 2048 * 25024) + ops
    assert r_bytes == bytes_ + 2048 * 2
    # 16 rows at 18.1k, 64% of the 16 x 30 held experts touched, 16 x 30
    # assignments: the issue's plan of a step, near 12.7 GB
    touched, made = 0.64 * 16 * 30, 16.0 * 30
    tot_ops, tot_bytes = step.cost(1, [18100] * 16, cfg, made, touched)
    e_ops, e_bytes = spec.load_kernel_cost("moe_experts").cost(
        made, touched, cfg)
    assert tot_ops == e_ops + 16 * step.row_cost(18100, cfg)[0]
    assert tot_bytes == e_bytes + walk + 16 * step.row_cost(18100, cfg)[1]
    full = 16 * 18101 * 2048 * 8
    assert abs(full - 4.7e9) < 0.1e9
    assert 12.0e9 < tot_bytes < 13.5e9
    assert tot_bytes / peaks["hbm_bytes_s"] > tot_ops / peaks["bf16_flops"]
    # a tiny configuration, by hand: 2 window layers and 1 full, 2 query
    # heads on 1 key-value head of 4, a window of 8, pages of 4
    tiny = dict(cfg, num_attention_heads=2, num_key_value_heads=1,
                head_dim=4, num_hidden_layers=3, sliding_window=8,
                page_size=4, layer_types=["sliding_attention"] * 2
                + ["full_attention"])
    assert att.layers(tiny) == (2, 1)
    assert att.window_positions(13, tiny) == (8, 10)   # 14 - 6 // 4 * 4
    assert att.cost(13, tiny) == (
        4.0 * 2 * 4 * (2 * 8 + 14),
        (2.0 * 4 * (2 * 10 + 14) + 3 * 2.0 * 2 * 4) * 2)


def test_the_mix_is_the_issues():
    mix = spec.load_cell(CELL).traffic
    assert (mix["loop"], mix["clients"], mix["stagger_s"], mix["ramp_s"],
            mix["tail_s"]) == ("closed", 16, 0.3, 9, 2)
    sp = mix["shared_prefix"]
    assert sp["lengths"] == [4124, 16412, 32796] * 2
    assert [(n - 28) // 128 for n in sp["lengths"][:3]] == [32, 128, 256]
    assert sum(sp["lengths"]) == 106664
    # (ISSUE 57 says 834; a document of k x 128 + 28 tokens takes k + 1)
    assert sum(-(-n // 128) for n in sp["lengths"]) == 838 \
        == 2 * (33 + 129 + 257)
    assert sp["choose"] == "round_robin" and sp["prefill_in_setup"]
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 104,
                                    "max": 192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 160,
                                    "max": 288}
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["engine"] == {"page_size": 128, "num_pages": 1088,
                             "max_model_len": 33792, "max_batch_size": 16}
    # ISSUE 57's first fallback, taken: a check of 512 tokens held a
    # traced run over the driver's 360 s (the mix's check note)
    assert mix["check"]["served_tokens"] == 256
    longest = 32796 + 192 + 288
    assert longest == 33276 <= mix["engine"]["max_model_len"] == 264 * 128
    # the documents and sixteen rows' own pages fit the full pool
    # ... with 169 pages of slack: a document nobody asks about for a
    # few seconds keeps its pages (the mix's engine_note)
    assert 838 + 16 * 5 + 169 == mix["engine"]["num_pages"] - 1
    # 51 s under the ceiling hold fewer than two cycles of 16 rounds (a
    # permutation a round), and the schedule holds more than twice the
    # rounds a client can use at 5 s a request
    assert 51 * mix["max_rounds_per_s"] < 2 * mix["clients"]
    rounds = math.ceil(62 * mix["max_rounds_per_s"]) + 1
    assert rounds == 26 > 2 * 62 / 5
    from chipbench import traffic
    shapes = traffic.warmup_shapes(mix, 128)
    assert sorted(shapes["prefill"]) == sorted(
        [(1, 2048, 264), (1, 64, 264)]
        + [(B, 256, 264) for B in (1, 2, 4, 8, 16)])
    assert shapes["decode_widths"] == [264] == [33792 // 128]
    assert 28 + 104 > 128 and 28 + 192 <= 256
    cell = next(w for w in spec.load_json(os.path.join(
        spec.ROOT, "BENCHMARK.json"))["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "mixdoc16", 1)
    assert "eighth" in cell["why"] and len(cell["why"]) <= 200


def test_the_kernel_rooflines_on_a_hand_made_trace():
    """Two executions of a decode program of 20 ms each, in each 24 calls
    of the kernel over the window pool of 0.1 ms and 8 over the full
    pool of 0.9 ms, tokens of 16 rows inside the traced seconds: each
    kind's share of the step is its own calls' time over the program's,
    both kinds' share of their roofline the cost file's bytes over the
    bandwidth over both kinds' time, and the whole step's share the step
    cost's."""
    dev = "/device:TPU:0"
    hlo = "%while.6 = (s32[], bf16[16,1,2048], bf16[8,1088,128,4,128])"
    events = []
    for i in range(2):
        t0 = 1_000_000 + i * 30_000_000
        events += [
            {"plane": dev, "line": "XLA Modules", "name": "jit__unknown(1)",
             "start": t0, "dur": 20_000_000},
            {"plane": dev, "line": "XLA Ops", "name": hlo, "start": t0,
             "dur": 19_000_000}]
        events += [
            {"plane": dev, "line": "XLA Ops",
             "name": f"%paged_decode_attention_swa.{11 + j} = custom-call()",
             "start": t0 + 500_000 * j + 100_000, "dur": 100_000}
            for j in range(24)]
        events += [
            {"plane": dev, "line": "XLA Ops",
             "name": f"%paged_decode_attention_full.{51 + j} = custom-call()",
             "start": t0 + 2_000_000 * j + 300_000, "dur": 900_000}
            for j in range(8)]
    cfg = published()
    records = [{"n_prompt": 4124 + 150 + 1000 * r,
                "frames": [[10.0 + 0.01 * k, 1] for k in range(2)]}
               for r in range(16)]
    steps = [{"t_wall": 60.0 + 0.02 * i, "kind": "decode",
              "moe": {"assignments": 480, "experts_touched": 300,
                      "elsewhere": 3360, "dropped": 0, "layers": 30,
                      "load_max": 4}} for i in range(2)]
    ctx = {"trace": {"events": events, "wall0": 59.5, "wall1": 61.0},
           "records": records, "steps": steps, "config": cfg,
           "wall_minus_mono": 50.0, "device_kind": "TPU v5 lite",
           "root": spec.ROOT}
    peaks = spec.peaks_for("TPU v5 lite")

    def read(metric):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    swa_s, full_s = 2 * 24 * 1e-4, 2 * 8 * 9e-4
    assert read("window_attn_share_of_decode_step.mixdoc16") \
        == pytest.approx(100.0 * swa_s / 0.040)
    assert read("full_attn_share_of_decode_step.mixdoc16") \
        == pytest.approx(100.0 * full_s / 0.040)
    # the entered metric that reads every call of the kernel reads both
    assert read("attn_share_of_decode_step.docqa64") \
        == pytest.approx(100.0 * (swa_s + full_s) / 0.040)
    att = spec.load_kernel_cost("decode_attention_by_window")
    b = sum(att.cost(r["n_prompt"] + i, cfg)[1]
            for r in records for i in range(len(r["frames"])))
    got = read("decode_attn_roofline.mixdoc16")
    assert got == pytest.approx(
        100.0 * b / peaks["hbm_bytes_s"] / (swa_s + full_s))
    assert 0 < got < 100
    contexts = [r["n_prompt"] + i for r in records
                for i in range(1, len(r["frames"]))]
    ops, bytes_ = spec.load_kernel_cost("decode_step_swa_gqa_moe").cost(
        2, contexts, cfg, 960.0, 600.0)
    assert bytes_ / peaks["hbm_bytes_s"] > ops / peaks["bf16_flops"]
    got = read("decode_step_roofline.mixdoc16")
    assert got == pytest.approx(
        100.0 * (bytes_ / peaks["hbm_bytes_s"]) / 0.040)
    assert 0 < got < 100
    # a program without the kernels' names (the parent's, or a model with
    # one pool) gives the two shares nothing
    bare = dict(ctx, trace=dict(ctx["trace"], events=[
        dict(e, name=e["name"].replace("_swa", "_impl")
             .replace("_full", "_impl")) for e in events]))
    for name in ("window_attn_share_of_decode_step.mixdoc16",
                 "full_attn_share_of_decode_step.mixdoc16"):
        info = spec.layer_metric_file(name)
        assert spec.load_reader(info["reader"]).read(bare, info) is None
    for name in OWN - {"kv_window_pages_peak_share.mixdoc16",
                       "window_tail_hit_share.mixdoc16"}:
        info = spec.layer_metric_file(name)
        assert spec.load_reader(info["reader"]).read(
            dict(ctx, trace=None), info) is None


def test_the_window_pools_counters_on_hand_made_scrapes():
    fam = "xllm_worker_kv_window_"
    opened = {fam + 'tail_events_total{model="m",event="hit"}': 2.0,
              fam + 'tail_events_total{model="m",event="miss"}': 1.0}
    closed = {fam + 'tail_events_total{model="m",event="hit"}': 32.0,
              fam + 'tail_events_total{model="m",event="miss"}': 1.0,
              fam + 'pages{model="m",kind="size"}': 561.0,
              fam + 'pages{model="m",kind="peak"}': 374.0,
              fam + 'pages{model="m",kind="live"}': 300.0}
    ctx = {"counters_open": opened, "counters_close": closed}

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    assert read("window_tail_hit_share.mixdoc16") == 100.0
    assert read("kv_window_pages_peak_share.mixdoc16") \
        == pytest.approx(100.0 * 374 / 561)
    closed[fam + 'tail_events_total{model="m",event="miss"}'] = 11.0
    assert read("window_tail_hit_share.mixdoc16") == 75.0
    # a program without the families (the parent's) gives nothing
    nothing = {"counters_open": {}, "counters_close": {}}
    assert read("window_tail_hit_share.mixdoc16", nothing) is None
    assert read("kv_window_pages_peak_share.mixdoc16", nothing) is None


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    shared = EVERY_CELL_REPORTS | {
        "decode_batch_occupancy.docqa", "moe_gmm_roofline.docqa32",
        "moe_gmm_share_of_decode_step.docqa32",
        "moe_load_max_over_mean.docqa32", "moe_dropped_assignments.docqa32",
        "moe_experts_touched_share.docqa64",
        "attn_share_of_decode_step.docqa64"}
    assert OWN | shared == names
    # (NOT ``moe_held_assignment_share.statedoc64``, whose file fits the
    # cell as it stands: the accepted test of the cell it arrived with
    # holds that entry's ``workloads`` to that cell alone, and a PR that
    # changes the program may not edit a file of the benchmark)
    assert "moe_held_assignment_share.statedoc64" not in names
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
    assert all(m["workloads"] == [CELL] for m in cell.per_layer
               if m["name"] in OWN)
    # its experts are counted by the key its config.json has
    assert spec.layer_metric_file(
        "moe_experts_touched_share.docqa64", root)["experts_key"] \
        == "num_experts" in published()
    # NOT the state, ring, latent or loop metrics
    assert not {n for n in names if n.startswith((
        "state_", "mla_", "layer_passes", "exit_cdf", "ssm_", "kda_",
        "retention_"))}
    assert rehearsal_counters(CELL, root) == {
        "prefix_hit_token_share.docqa", "kv_pages_peak_share.docqa",
        "compiles_in_window.docqa", "decode_batch_occupancy.docqa",
        "kv_window_pages_peak_share.mixdoc16",
        "window_tail_hit_share.mixdoc16"}


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu --trace 2`` at the configuration's tiny widths (8
    layers, a window of two pages): set-up (the two documents' full
    pages and their tails), a window of follow-ups that each resume at a
    tail, the reference check over 12 served tokens, and the
    ``program_counter`` metrics that list the cell in the line."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    mix = spec.load_cell(CELL, root).traffic
    over = json.dumps({"rehearsal": dict(mix["rehearsal"],
                                         max_rounds_per_s=100.0)})
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "5", "--trace", "2",
         "--rehearse-cpu", "--limit", "8", "--override", over],
        cwd=root, env=ENV, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    cmp_ = out["compared"]
    assert cmp_["served_tokens_compared"] == {"value": 12, "limit": 12}
    m = out["metrics"]
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    # every follow-up resumed at its document's tail, and 384 of 412,
    # 640 of 668 tokens of a prompt of document + 104-192 came from the
    # cache
    assert m["window_tail_hit_share.mixdoc16"]["value"] == 100.0
    assert 0 < m["kv_window_pages_peak_share.mixdoc16"]["value"] < 100
    assert 65 < m["prefix_hit_token_share.docqa"]["value"] < 90
