"""The configuration ``ouro-2.6b`` and its cell ``ouro-2.6b-preamble8``:
the config is the published one with nothing reduced, the plain reference
(every pass over the whole sequence, no cache) agrees with the program's
prefill and decode through the pools at the rehearsal widths, the
generator gives passes 2-4 the leaves of pass 1, a served token that was
altered fails the check, the cost files count a slot a layer a PASS, the
new readers read what they say, and the cell walks ``run.py
--rehearse-cpu`` in a copied root."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import fixture_root            # beside this file (pytest prepends its directory)
from chipbench import check, spec, weights
from test_chipbench_rehearsal import (EVERY_CELL_REPORTS,
                                      rehearsal_counters)

CONFIG = os.path.join(spec.ROOT, "chipbench", "configs", "ouro-2.6b")
CELL = "ouro-2.6b-preamble8"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": spec.ROOT + os.pathsep
       + os.environ.get("PYTHONPATH", "")}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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


def test_the_configuration_is_the_published_one_and_nothing_is_reduced():
    cfg, m = published(), meta()
    assert m["reduced"] == [] and m["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json")
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["early_exit_threshold"],
            cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["max_position_embeddings"], cfg["tie_word_embeddings"]) \
        == (48, 2048, 5632, 16, 16, 128, 49152, 4, 1, 1000000, 1e-6, 65536,
            False)
    assert m["published"] == {"num_hidden_layers": 48, "total_ut_steps": 4,
                              "early_exit_threshold": 1,
                              "vocab_size": 49152}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
        assert row["source_url"] == m["source"]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert set(m["assumed"]) == {"attention_bias", "exit gate bias",
                                 "torch_dtype", "no early exit"}
    assert "whole" in m["deployment"] and "no cut" in m["deployment"]
    assert m["step_programs_from_cache"] in (True, False)
    # a dense model held to quantiles of the gaps beside the widest: 192
    # layer-passes of bfloat16 over a seeded model whose best two logits
    # lie 0.04-0.14 apart (meta.json limit_note)
    assert set(m["check"]["served_token_gap_quantile_limits"]) \
        == {"0.5", "0.9"}
    assert 0 < m["check"]["served_token_gap_quantile_limits"]["0.5"] \
        < m["check"]["served_token_gap_quantile_limits"]["0.9"] \
        < m["check"]["served_token_gap_limit"]
    tiny = tiny_config()
    assert tiny["total_ut_steps"] == 2 and tiny["num_hidden_layers"] == 2
    for file in ("reference.py", "weights.py",
                 "../../reference/looped_decoder.py",
                 "../../weight_families/looped_decoder.py"):
        assert "xllm_service_tpu" not in open(
            os.path.join(CONFIG, file)).read()


def test_the_weights_and_the_pool_are_what_the_files_say():
    """5.34 GB of weights held once, 1,572,864 B of cache a token, 40
    pages of 201.3 MB: the arithmetic of ``meta.json`` and the mix."""
    import jax
    cfg = published()
    wts = spec.load_weights(CONFIG)
    tree = jax.eval_shape(lambda: wts.program_tree(cfg, 1))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert tree["layers"]["q_proj"].shape == (48, 2048, 2048)   # ONCE
    assert 5.33e9 < 2 * n < 5.35e9
    mix = spec.load_cell(CELL).traffic
    token = 2 * 192 * 16 * 128 * 2
    page = token * mix["engine"]["page_size"]
    assert (token, page) == (1572864, 201326592)
    assert 8.05e9 < page * mix["engine"]["num_pages"] < 8.06e9


def test_the_generator_gives_every_pass_the_leaves_of_the_first():
    import jax
    wts = spec.load_weights(CONFIG)
    kinds = wts.layer_kinds(published())
    assert len(kinds) == 192
    assert [i for i, k in enumerate(kinds) if k == "pass_end"] \
        == [47, 95, 143]
    assert set(kinds) == {"layer", "pass_end"}
    cfg = tiny_config(total_ut_steps=3)
    kinds = wts.layer_kinds(cfg)
    assert kinds == ["layer", "pass_end", "layer", "pass_end", "layer",
                     "layer"]
    key = weights.root_key(2**31 + 3)
    head = wts.head_params(cfg, key)
    layers = [wts.layer_params(cfg, key, i, k) for i, k in enumerate(kinds)]
    for i, lp in enumerate(layers):
        first = layers[i % 2]
        for name in set(lp) - {"final_norm"}:
            np.testing.assert_array_equal(np.asarray(lp[name], np.float32),
                                          np.asarray(first[name],
                                                     np.float32))
        assert ("final_norm" in lp) == (kinds[i] == "pass_end")
        if "final_norm" in lp:
            np.testing.assert_array_equal(
                np.asarray(lp["final_norm"], np.float32),
                np.asarray(head["final_norm"], np.float32))
    assert not np.array_equal(np.asarray(layers[0]["q_proj"], np.float32),
                              np.asarray(layers[1]["q_proj"], np.float32))
    # traceable in the layer's index, as check.py makes them
    traced = jax.jit(lambda k, i: wts.layer_params(cfg, k, i, "layer"))(
        key, 4)
    np.testing.assert_array_equal(
        np.asarray(traced["down_proj"], np.float32),
        np.asarray(layers[0]["down_proj"], np.float32))
    # the program's tree: the stack ONCE, under its names, with the gate
    tree = wts.program_tree(cfg, 2**31 + 3)
    assert tree["layers"]["post_norm"].shape == (2, 64)
    np.testing.assert_array_equal(
        np.asarray(tree["layers"]["post_norm"][1], np.float32),
        np.asarray(layers[1]["input_layernorm_2"], np.float32))
    np.testing.assert_array_equal(
        np.asarray(tree["layers"]["pre_ff_norm"][0], np.float32),
        np.asarray(layers[0]["post_attention_layernorm"], np.float32))
    assert abs(float(tree["exit_gate"]["b"])) > 1e-3


@pytest.mark.parametrize("seed, over", [
    (11, {}),
    (2**31 + 9, {"total_ut_steps": 4, "num_hidden_layers": 3}),
])
def test_reference_agrees_with_the_programs_prefill_and_decode(seed, over):
    """Logits at the rehearsal widths, float32 both sides: the program's
    ``forward_prefill`` in two windows (24 tokens, then 16 on the first
    window's pages), then 16 decode steps, against the plain reference's
    walk of all passes over all 56. 2e-5 of the largest logit: float32
    sums in another order; bfloat16 reads 1e-2 (``tests/
    test_looped_layers.py``)."""
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
    T, ps, passes = 56, 16, cfg["total_ut_steps"]
    toks = np.random.default_rng(seed).integers(3, cfg["vocab_size"], size=T)
    want, q = ref.forward_with_exits(leaves, toks, cfg)
    want, q = np.asarray(want), np.asarray(q)
    scale = np.abs(want).max()
    n_pages = (T + ps - 1) // ps + 1
    kv = transformer.init_kv_cache(mc, n_pages + 1, ps, jnp.float32)
    assert kv[0].shape[0] == passes * cfg["num_hidden_layers"]
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None, :]
    for start, n in ((0, 24), (24, 16)):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = toks[start:start + n]
        out = transformer.forward_prefill(
            tree, mc, jnp.asarray(padded), jnp.asarray([start], jnp.int32),
            jnp.asarray([n], jnp.int32), kv, table, return_all_logits=True,
            return_stats=True)
        assert np.abs(np.asarray(out[1][0, :n])
                      - want[start:start + n]).max() < 2e-5 * scale
        kv = out[2]
    for p in range(40, T):
        lg, kv, st = transformer.forward_decode(
            tree, mc, jnp.asarray(toks[p:p + 1], jnp.int32),
            jnp.asarray([p], jnp.int32), jnp.asarray([True]), kv, table,
            return_stats=True)
        assert np.abs(np.asarray(lg[0]) - want[p]).max() < 2e-5 * scale, p
        assert np.abs(np.asarray(st["exit_pdf"][0]) - q[p]).max() < 1e-5
        assert int(st["loop"][1]) == passes


@pytest.mark.parametrize("what", [
    "the second norm on a sublayer's output", "the norm between passes",
    "the later passes", "the gate's bias"])
def test_the_reference_sees_what_the_program_must_not_lose(what):
    """The reference changes when a mechanism is taken out of it: each is
    therefore something the check on the chip would catch in the program
    (the gate's bias moves the exit probabilities alone: at a threshold
    of 1 it shapes no logit)."""
    import jax.numpy as jnp
    cfg = tiny_config()
    ref = spec.load_reference(CONFIG)
    leaves = stored(cfg, 5)
    toks = np.random.default_rng(5).integers(3, cfg["vocab_size"], size=24)
    want, q = (np.asarray(a) for a in
               ref.forward_with_exits(leaves, toks, cfg))
    broken = dict(leaves, layers=[dict(lp) for lp in leaves["layers"]])
    run_cfg = cfg
    if what.startswith("the second norm"):
        for lp in broken["layers"]:
            lp["input_layernorm_2"] = jnp.ones_like(lp["input_layernorm_2"])
    elif what == "the norm between passes":
        for lp in broken["layers"]:
            if "final_norm" in lp:
                lp["final_norm"] = jnp.ones_like(lp["final_norm"])
    elif what == "the later passes":
        broken["layers"] = broken["layers"][-cfg["num_hidden_layers"]:]
        run_cfg = dict(cfg, total_ut_steps=1)
    else:
        broken["exit_gate_b"] = jnp.zeros_like(broken["exit_gate_b"])
    got, q2 = (np.asarray(a) for a in
               ref.forward_with_exits(broken, toks, run_cfg))
    if what == "the gate's bias":
        assert np.abs(got - want).max() == 0
        assert np.abs(q2 - q).max() > 1e-2
    else:
        assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ref.forward(leaves, toks, dict(cfg, early_exit_threshold=0.9))


def test_an_altered_served_token_fails_the_check():
    """``check.compare`` over this configuration's reference and weights
    (its walk over ``layer_kinds``, ``pass_end`` layers and all): the
    reference's own greedy continuation reads a gap of 0 at every served
    token, and one token swapped for another reads a gap."""
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


@pytest.mark.parametrize("cfg, slots", [
    (published, 192),                                # 48 layers x 4 passes
    (lambda: spec.load_json(os.path.join(
        spec.ROOT, "chipbench", "configs", "mistral-7b-v01",
        "config.json")), 16),                        # no key: run once
])
def test_the_cost_files_count_a_slot_a_layer_a_pass(cfg, slots):
    cfg = cfg()
    new = spec.load_kernel_cost("decode_attention_by_pass")
    old = spec.load_kernel_cost("decode_attention")
    assert new.layer_slots(cfg) == slots
    flops, bytes_ = new.cost(699, cfg)
    if "total_ut_steps" not in cfg:
        assert (flops, bytes_) == old.cost(699, cfg)
        return
    # 700 positions, 16 key-value heads of 128, keys and values
    assert flops == 192 * 4 * 700 * 16 * 128
    assert bytes_ == 192 * (2 * 700 * 16 * 128 + 2 * 16 * 128) * 2
    step = spec.load_kernel_cost("decode_step_by_pass")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert step.layer_weights(cfg) == layer == 51380224
    # a step walks the 48 layers once a PASS and the head once
    assert step.step_cost(cfg) == (0.0, (4 * 48 * layer + 2048 * 49152)
                                   * 2.0)
    ops, bytes_ = step.cost(3, [699] * 8, cfg)
    assert bytes_ == 3 * step.step_cost(cfg)[1] + 8 * (
        new.cost(699, cfg)[1] + 2048 * 2)
    assert ops == 8 * (2.0 * (4 * 48 * layer + 2048 * 49152)
                       + new.cost(699, cfg)[0])
    # bound by bytes at 8 rows: 19.9 GB of weights a step
    assert 19.9e9 < step.step_cost(cfg)[1] < 20.0e9


def test_the_mix_is_the_issues():
    mix = spec.load_json(os.path.join(spec.ROOT, "chipbench", "traffic",
                                      "preamble8.json"))
    assert (mix["loop"], mix["clients"], mix["stagger_s"], mix["ramp_s"],
            mix["tail_s"], mix["max_rounds_per_s"]) \
        == ("closed", 8, 0.6, 9, 2, 0.3)
    eng = mix["engine"]
    assert (eng["page_size"], eng["max_model_len"],
            eng["max_batch_size"]) == (128, 1024, 8)
    assert 36 <= eng["num_pages"] <= 40
    sp = mix["shared_prefix"]
    assert sp["lengths"] == [k * 128 + 28 for k in (2, 3, 4)]
    assert sp["choose"] == "round_robin" and sp["prefill_in_setup"]
    assert (mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"],
            mix["output_tokens"]["min"], mix["output_tokens"]["max"]) \
        == (104, 192, 96, 160)
    assert mix["sampling"] == {"temperature": 0.0, "ignore_eos": True}
    assert mix["check"]["served_tokens"] == 288
    # the longest request stays under max_model_len, the peak in the pool
    assert 540 + 192 + 160 < eng["max_model_len"]
    shared = sum(n // 128 for n in sp["lengths"])
    own = max(-(-(n + 192 + 160) // 128) - n // 128 for n in sp["lengths"])
    assert (shared, own) == (9, 3)
    assert shared + 8 * own <= eng["num_pages"] - 1
    # a window holds under two cycles of 8 rounds: a permutation a round
    assert 51 * mix["max_rounds_per_s"] < 2 * mix["clients"]
    from chipbench import traffic
    sched = traffic.build(mix, 2**31 + 1, 51.0, 49152)
    assert len(sched["docs"]) == 3 and len(sched["requests"]) == 8 * 20
    shapes = traffic.warmup_shapes(mix, 128)
    assert shapes["decode_widths"] == [4, 8]
    assert {(b, 256, mp) for b in (1, 2, 4, 8) for mp in (4, 8)} \
        <= set(shapes["prefill"])


def test_the_new_readers_on_hand_made_steps():
    lo = 50.0
    steps = [
        {"t_wall": 100.5, "kind": "mixed", "passes": 8, "exit_cdf": [
            0.2, 0.4, 0.5], "decode_tokens": 2},
        {"t_wall": 100.6, "kind": "decode", "passes": 4, "exit_cdf": [
            0.1, 0.3, 0.8], "decode_tokens": 6},
        {"t_wall": 100.7, "kind": "decode", "passes": 4, "exit_cdf": None,
         "decode_tokens": 0},
        {"t_wall": 101.5, "kind": "prefill", "passes": 4, "exit_cdf": None,
         "decode_tokens": 0},
        {"t_wall": 300.0, "kind": "decode", "passes": 2, "exit_cdf": [
            0.9, 0.9, 0.9], "decode_tokens": 8}]
    ctx = {"steps": steps, "config": published(), "open_t": lo,
           "close_t": lo + 2.0, "wall_minus_mono": 50.0,
           "cell": spec.load_cell(CELL), "root": spec.ROOT}

    def read(metric, ctx=ctx):
        info = spec.layer_metric_file(metric)
        return spec.load_reader(info["reader"]).read(ctx, info)

    assert read("layer_passes_per_step.preamble8") == 4.0
    assert read("exit_cdf_before_last_pass.preamble8") == pytest.approx(
        (0.5 * 2 + 0.8 * 6) / 8)
    # a step that left a pass out shows
    short = dict(ctx, steps=steps[:1] + [dict(steps[1], passes=3)])
    assert read("layer_passes_per_step.preamble8", short) == 3.0
    # a program without the records (the parent) gives the readers nothing
    old = dict(ctx, steps=[{"t_wall": 100.5, "kind": "decode",
                            "decode_tokens": 8}])
    assert read("layer_passes_per_step.preamble8", old) is None
    assert read("exit_cdf_before_last_pass.preamble8", old) is None
    # and neither roofline without a trace
    assert read("decode_step_roofline.preamble8") is None
    assert read("decode_attn_roofline.preamble8") is None


def test_the_step_roofline_on_a_hand_made_trace():
    """Two executions of a decode program of 30 ms each, eight tokens
    that arrived inside the traced seconds (one of them a request's
    first, which a prefill sampled): the bytes bound the step, and the
    share is least time over device time."""
    dev = "/device:TPU:0"
    hlo = "%while.7 = (s32[], bf16[8,1,2048], bf16[192,40,128,16,128])"
    events = []
    for i in range(2):
        t0 = 1_000_000 + i * 40_000_000
        events += [
            {"plane": dev, "line": "XLA Modules", "name": "jit__unknown(1)",
             "start": t0, "dur": 30_000_000},
            {"plane": dev, "line": "XLA Ops", "name": hlo, "start": t0,
             "dur": 29_000_000}]
    cfg = published()
    records = [{"n_prompt": 500, "frames": [[10.0, 1], [10.1, 4]]},
               {"n_prompt": 600, "frames": [[9.0, 1], [10.2, 3]]}]
    ctx = {"trace": {"events": events, "wall0": 59.5, "wall1": 61.0},
           "records": records, "config": cfg, "wall_minus_mono": 50.0,
           "device_kind": "TPU v5 lite", "root": spec.ROOT}
    info = spec.layer_metric_file("decode_step_roofline.preamble8")
    got = spec.load_reader(info["reader"]).read(ctx, info)
    cost = spec.load_kernel_cost("decode_step_by_pass")
    contexts = [501, 502, 503, 504, 601, 602, 603]
    flops, bytes_ = cost.cost(2, contexts, cfg)
    peaks = spec.peaks_for("TPU v5 lite")
    assert bytes_ / peaks["hbm_bytes_s"] > flops / peaks["bf16_flops"]
    assert got == pytest.approx(
        100.0 * (bytes_ / peaks["hbm_bytes_s"]) / 0.060)
    assert 75 < got < 100


def test_every_metric_of_the_cell_has_its_file_and_its_reader(root):
    cell = spec.load_cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    own = {f"{n}.preamble8" for n in (
        "decode_attn_roofline", "decode_step_roofline",
        "layer_passes_per_step", "exit_cdf_before_last_pass")}
    # entries that other cells list too (PR 52 folded the twins into lists)
    shared = EVERY_CELL_REPORTS | {
        "decode_batch_occupancy.docqa",
        "attn_share_of_decode_step.docqa64"}
    assert own | shared == names
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
               if m["name"] in own)
    assert rehearsal_counters(CELL, root) == {
        "prefix_hit_token_share.docqa", "kv_pages_peak_share.docqa",
        "compiles_in_window.docqa", "decode_batch_occupancy.docqa",
        "layer_passes_per_step.preamble8",
        "exit_cdf_before_last_pass.preamble8"}


def test_the_cell_walks_the_whole_command_in_a_copied_root(tmp_path):
    """``--rehearse-cpu --trace 2`` at the configuration's tiny widths (2
    layers, 2 passes): set-up (the preambles' pages, every pass's slots
    of them), a window of follow-ups that each begin from cached pages,
    the reference check over 12 served tokens, and the
    ``program_counter`` metrics that list the cell in the line."""
    root = str(tmp_path / "copy")
    fixture_root.copy_benchmark(root)
    mix = spec.load_cell(CELL, root).traffic
    over = json.dumps({"rehearsal": dict(mix["rehearsal"],
                                         max_rounds_per_s=100.0)})
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "5", "--trace", "2",
         "--rehearse-cpu", "--limit", "0.05", "--override", over],
        cwd=root, env=ENV, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    cmp_ = out["compared"]
    assert cmp_["served_tokens_compared"] == {"value": 12, "limit": 12}
    assert cmp_["served_token_gap_max"]["value"] < 0.05
    m = out["metrics"]
    assert set(m) == {"setup_s"} | rehearsal_counters(CELL, root)
    assert m["layer_passes_per_step.preamble8"]["value"] == 2.0
    assert 0 < m["exit_cdf_before_last_pass.preamble8"]["value"] < 1
    # 256 of a 284-token preamble, 384 of 412, 512 of 540: about two
    # thirds of a prompt of preamble + 104-192
    assert 55 < m["prefix_hit_token_share.docqa"]["value"] < 80
